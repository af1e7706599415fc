"""``sdar-30b-a3b-chat``: the arithmetic of its flops file, what its file
states, a whole rehearsal run of its cell, runs with the mask rule or the
objective's weights broken underneath, and the fp8 control."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "sdar-30b-a3b-chat"
CELL = "sdar_blockdiff_seq4096"


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    # q 2048x4096 + k, v 2048x512 + o 4096x2048; one expert 3 x 2048 x 768
    assert flops.attention_params(c) == 18_874_368 and flops.expert_params(c) == 4_718_592
    outside = 18_874_368 + 2 * 128 + 2 * 2048 + 2048 * 128
    assert flops.layer_params(c, 16) == outside + 16 * 4_718_592 == 94_638_336
    assert flops.total_params(c, published=True) \
        == 48 * (outside + 128 * 4_718_592) + 2 * 151_936 * 2048 + 2048 == 30_532_122_624
    shapes = correct.load_by_name("reference", NAME).param_shapes(c)
    import jax

    leaves = jax.tree.leaves(shapes, is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == flops.total_params(c) == 456_346_624
    # follow_reference's six float32 copies: four layers fit the chip, five do not
    assert flops.total_params(c) * 24 / 2**30 < 10.3
    assert flops.total_params({**c, "num_hidden_layers": 5}) * 24 / 2**30 > 12.3


def test_required_operations_count_the_masks_area_and_the_expected_expert_share():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t, b = c["seq_len"], c["block_length"]
    assert flops.mask_area(c) == t * t + t * b == t * b + t * (t - b) // 2 + t * (t + b) // 2
    per_position = 18_874_368 + 2048 * 128 + (8 * 16 / 128) * 4_718_592
    attention = 2 * 32 * 128 * (t * t + t * b)
    want = 4 * (2 * t * per_position + attention) + t * 2048 * 18_992
    assert flops.forward_macs_per_sample(c) == pytest.approx(want)
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * want)
    assert 8.9e12 < flops.train_flops_per_sample(c) < 9.0e12
    assert 0.36 < 6 * 4 * attention / flops.train_flops_per_sample(c) < 0.38
    costs = flops.kernel_costs(c, 1)
    product = 2 * 32 * 128 * (t * t + t * b)
    assert costs["tpuframe_flash_fwd"]["flops"] == 2 * product
    assert costs["tpuframe_flash_bwd"]["flops"] == 5 * product
    # q and the output at 32 heads, k and v at 4, bfloat16
    assert costs["tpuframe_flash_fwd"]["bytes"] == 2 * 2 * t * 128 * 2 * (32 + 4)
    # the MXU bounds both: operations over the peak take longer than the bytes
    for c_ in costs.values():
        assert c_["flops"] / 197e12 > c_["bytes"] / 819e9


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (4, 16, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"], c["intermediate_size"],
            c["rope_theta"], c["norm_topk_prob"]) == (2048, 32, 4, 128, 768, 8, 6144, 1000000, True)
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "BlockDiffusionLM"
    assert kw["moe_experts"] == 128 and kw["moe_top_k"] == 8 and kw["moe_kwargs"]["held"] == [0, 16]
    assert kw["moe_kwargs"]["capacity_factor"] is None and kw["moe_kwargs"]["shared_dim"] == 0
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"], kw["rope_dim"], kw["d_model"]) \
        == (32, 4, 128, 128, 2048)
    assert kw["moe_kwargs"]["expert_dim"] == 768 and kw["qk_norm"] and kw["rope_theta"] == 1000000
    # the floors: four layers, at least 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert c["mask_token_id"] == c["vocab_size"] - 1 and c["block_length"] == 4
    assert "8 chips share each layer" in c["deployment"]
    r = c["rehearsal"]
    assert r["num_experts_published"] == 8 and r["num_experts"] == 4 and r["mask_token_id"] == 255


def test_the_traffic_carries_the_draws():
    from chipbench.traffic import generator

    c = cfg()
    data = generator.make_dataset(generator.load_mix("blockdiff-seq4096"), c, 2**31 + 5, 1)
    x, y = data.first_batches(1, 1)[0]
    assert x.shape == (1, 4096, 3) and x.dtype == "int32" and y.shape == (1,)
    assert 0 <= x.min() and x.max() < c["mask_token_id"]   # the mask token is never data


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 77, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    assert m["blockdiff.tiles_visited_over_needed"]["value"] >= 1
    assert 0 <= m["blockdiff.moe_padded_rows_pct"]["value"] < 100
    assert m["blockdiff.expert_load_max_over_mean"]["value"] >= 1
    # the device-trace readers find nothing on the CPU, and say nothing
    assert "blockdiff.flash_ms" not in m and "blockdiff.flash_roofline" not in m
    assert "moe.padded_rows_pct" not in m and "attention.flash_ms" not in m


@pytest.mark.parametrize("fault", ["causal_over_the_row", "weights_left_out"])
def test_a_run_with_the_rule_or_the_weights_broken_underneath(monkeypatch, tmp_path, fault):
    """The mask rule replaced by ``causal`` over the 2 L row, or the ``1/t``
    weights left out of the objective: ``correct`` comes out false."""
    import jax.numpy as jnp
    import optax

    from tpuframe.models import block_diffusion as bd
    from tpuframe.models import transformer as tr

    if fault == "causal_over_the_row":
        real = tr._attend
        monkeypatch.setattr(tr, "_attend", lambda *a, mask=None, **kw: real(
            *a, **{**kw, "causal": True}))
    else:
        real = bd.forward_process
        # the row is noised as it should be; the objective divides by 1
        monkeypatch.setattr(bd, "block_diffusion_losses", lambda logits, inputs, **kw: jnp.mean(
            jnp.where(real(inputs, **kw)[1], optax.softmax_cross_entropy_with_integer_labels(
                logits, inputs[..., 0]), 0.0), axis=-1))
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
    assert not next(r for r in out["extras"]["rows"] if r["number"] == "loss_gap")["ok"]


def test_the_control_fails_where_the_configurations_precision_passes():
    """The reference with every matmul on operands rounded to fp8 against
    itself in float32, at the rehearsal sizes: outside ``loss_gap`` and every
    ``grad_diff`` leaf of limits that the same reference in bfloat16 keeps."""
    import numpy as np

    from chipbench.traffic import generator

    full = cfg()
    c = {**full, **{k: v for k, v in full["rehearsal"].items() if not isinstance(v, dict)},
         "probe_leaves": full["rehearsal"]["probe_leaves"]}
    ref = correct.load_by_name("reference", NAME)
    data = generator.make_dataset(generator.load_mix("blockdiff-seq4096"), c, 2**31 + 7, 2)
    batches = data.first_batches(3, 2)
    sound = correct.follow_reference(ref, c, 7, batches)
    kept = sound.pop("_kept")
    gaps = {}
    for name, wrap in (("bf16", correct.bf16_wrap), ("fp8", correct.control_wrap)):
        got = correct.follow_reference(ref, c, 7, batches, wrap)
        got["grad_diff"] = correct.rel_diff(got.pop("_kept"), kept)
        gaps[name] = got
    # bf16 reads 0.00011 and at most 0.0092, fp8 0.00079 and at least 0.031
    limits = {"loss_gap": 3e-4, "grad_gap": 1.0, "update_gap": 1.0, "grad_diff": 0.02}
    ok, rows = correct.compare(gaps["bf16"], sound, limits)
    assert ok, rows
    ok, rows = correct.compare(gaps["fp8"], sound, limits)
    assert not ok
    assert all(not r["ok"] for r in rows if r["number"] in ("loss_gap", "grad_diff")), rows
    assert np.isfinite([r["value"] for r in rows]).all()
