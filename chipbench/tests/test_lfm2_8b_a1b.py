"""``lfm2-8b-a1b``: the arithmetic of its flops file, what its file states,
a whole rehearsal run of its cell, runs with the conv taps reversed or the
selection bias added to the weights underneath, and the fp8 control."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-8b-a1b"
CELL = "lfm2moe_seq4096"


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")


def test_parameters_whole_and_as_cut():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    # q, o 2048x2048 + k, v 2048x512; in_proj 2048x6144 + out_proj 2048x2048; one expert 3 x 2048 x 1792
    assert flops.attention_params(c) == 10_485_760 and flops.conv_params(c) == 16_777_216
    assert flops.expert_params(c) == 11_010_048
    assert flops.layer_params(c, "conv", False, 0) == 16_783_360 + 44_040_192 + 4_096 == 60_827_648
    assert flops.layer_params(c, "full_attention", True, 8) \
        == 10_485_888 + 65_536 + 32 + 8 * 11_010_048 + 4_096 == 98_635_936
    assert flops.layer_params(c, "conv", True, 8) == 104_933_408
    assert flops.total_params(c, published=True) == 8_474_148_288
    # with a tied head, the catalog's "8.3B"
    assert flops.total_params(c, published=True) - 65_536 * 2048 == 8_339_930_560
    shapes = correct.load_by_name("reference", NAME).param_shapes(c)
    import jax

    leaves = jax.tree.leaves(shapes, is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == flops.total_params(c) == c["parameters"] \
        == 60_827_648 + 98_635_936 + 3 * 104_933_408 + 67_110_912 == 541_374_720
    assert c["parameters_published_untied"] == 8_474_148_288
    # follow_reference's six float32 copies fit the chip's 15.75 GiB; eight (AdamW) do not
    assert 12.0 < flops.total_params(c) * 24 / 2**30 < 12.2
    assert flops.total_params(c) * 32 / 2**30 > 15.75


def test_required_operations_against_the_parameter_count():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t, d = c["seq_len"], 2048
    # every matmul parameter a token passes through: all but the embedding, the
    # norms, the taps and the biases, with 1 of the 8 held experts a sparse layer
    matmul = (flops.total_params(c) - c["vocab_size"] * d - d - 5 * 2 * d - 2 * 64
              - 4 * 3 * d - 4 * 32 - 4 * 7 * flops.expert_params(c))
    taps_and_gates = 4 * (3 + 2) * d
    attention = 2 * 32 * 64 * (t * (t + 1) // 2)
    want = t * (matmul + taps_and_gates) + attention
    assert flops.forward_macs_per_sample(c) == pytest.approx(want)
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * want)
    # 8192 tokens a step: 10.2 TFLOP, attention's scores under a tenth of them
    assert 10.1e12 < 2 * flops.train_flops_per_sample(c) < 10.3e12
    assert 6 * attention / flops.train_flops_per_sample(c) < 0.1
    costs = flops.kernel_costs(c, 2)
    plane = 2 * t * d * 2
    assert costs["tpuframe_short_conv_fwd"]["bytes"] == 4 * plane
    assert costs["tpuframe_short_conv_bwd"]["bytes"] == 7 * plane
    for name, c_ in costs.items():
        by_bytes, by_flops = c_["bytes"] / 819e9, c_["flops"] / 197e12
        # bandwidth bounds the short-conv pair, the MXU the flash pair
        assert (by_bytes > by_flops) == name.startswith("tpuframe_short_conv")


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "num_dense_layers"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"], c["num_dense_layers"]) \
        == (5, 8, 16384, 1)
    assert c["published"] == {"num_hidden_layers": 24, "num_experts": 32, "vocab_size": 65536,
                              "num_dense_layers": 2}
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["conv_L_cache"], c["norm_eps"], c["rope_theta"], c["routed_scaling_factor"]) \
        == (2048, 32, 8, 7168, 1792, 4, 3, 1e-5, 1000000, 1)
    # every key of the catalog's config but the four reduced, as published
    published = catalog_row()["config"]
    assert {k for k, v in published.items() if c[k] != v} == set(c["reduced"])
    # the published layers this chip runs: the leading dense layers once, one whole period
    here = [c["layer_types"][i] for i in c["layers_held"]]
    assert c["layers_held"] == [0, 2, 3, 4, 5] and len(here) == c["num_hidden_layers"]
    assert here == ["conv", "full_attention", "conv", "conv", "conv"]
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "TransformerLM" and kw["layer_types"] == here
    assert kw["moe_experts"] == 32 and kw["moe_top_k"] == 4 and kw["moe_first_dense"] == 1
    mk = kw["moe_kwargs"]
    assert mk["held"] == [0, 8] and mk["capacity_factor"] is None and mk["shared_dim"] == 0
    assert mk["scoring"] == "sigmoid" and mk["select_bias"] and mk["aux_loss_weight"] == 0
    assert mk["expert_dim"] == 1792 and mk["renormalize"] and mk["routed_scale"] == 1
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"], kw["rope_dim"], kw["d_model"],
            kw["mlp_dim"], kw["conv_taps"]) == (32, 8, 64, 64, 2048, 7168, 3)
    assert kw["qk_norm"] and kw["norm"] == "rms" and kw["norm_eps"] == 1e-5 and not kw["remat"]
    # the floors: a whole period and four layers after the dense one, 8 experts, an eighth... a quarter here
    assert c["num_hidden_layers"] - c["num_dense_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "4 chips share each layer" in c["deployment"]
    assert any("selection bias" in a for a in c["assumed"])
    assert any("not tied" in a for a in c["assumed"])
    assert set(c["tolerance"]["grad_diff"]) == set(c["probe_leaves"])


def _bias_in_the_weights(self, logits, k):
    """The fault: a chosen expert weighed by score + bias, not by score."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    bias = self.param("expert_bias", nn.initializers.zeros, (logits.shape[-1],), jnp.float32)
    vals, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
    return vals / (jnp.sum(vals, -1, keepdims=True) + 1e-6), idx


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 77, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    assert 0 < m["lfm2moe.bias_moved_choices_pct"]["value"] < 100
    assert m["lfm2moe.expert_load_max_over_mean"]["value"] >= 1
    assert m["lfm2moe.slot_rows_over_routed"]["value"] >= 1
    # the device-trace readers find nothing on the CPU, and say nothing
    assert "shortconv.kernel_ms" not in m and "shortconv.roofline" not in m
    assert "moe.slot_rows_over_routed" not in m and "blockdiff.flash_ms" not in m


@pytest.mark.parametrize("fault", ["taps_reversed", "bias_added_to_the_weights"])
def test_a_run_with_the_new_layers_broken_underneath(monkeypatch, tmp_path, fault):
    """The short convolution under its taps in reverse order, or the selection
    bias added to the chosen experts' weights: ``correct`` comes out false (the
    rehearsal sizes probe a router and an expert leaf, and seed the bias at 0.3)."""
    from tpuframe.models import moe, transformer

    if fault == "taps_reversed":
        real = transformer.short_conv
        monkeypatch.setattr(transformer, "short_conv",
                            lambda bch, w, **kw: real(bch, w[::-1], **kw))
    else:
        monkeypatch.setattr(moe.MoEMLP, "_sigmoid_choices", _bias_in_the_weights)
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
    outside = {(r["number"], r["at"]) for r in out["extras"]["rows"] if not r["ok"]}
    if fault == "taps_reversed":
        assert ("loss_gap", "max over steps") in outside
    else:
        # random experts re-weighed leave a seeded model's loss where it was:
        # the router's and the experts' own gradients show it
        assert {("grad_diff", "block1/moe/router/kernel"), ("grad_diff", "block2/moe/w_in")} <= outside


def test_the_control_fails_where_the_configurations_precision_passes():
    """The reference with every matmul and the taps' sum on operands rounded
    to fp8 against itself in float32, at the rehearsal sizes: outside
    ``loss_gap`` and every ``grad_diff`` leaf of limits that the same
    reference in bfloat16 keeps."""
    import numpy as np

    from chipbench import run
    from chipbench.traffic import generator

    full = cfg()
    c = run._merge(full, full["rehearsal"])
    ref = correct.load_by_name("reference", NAME)
    data = generator.make_dataset(generator.load_mix("tokens-seq4096"), c, 2**31 + 7, 2)
    batches = data.first_batches(3, 2)
    sound = correct.follow_reference(ref, c, 7, batches)
    kept = sound.pop("_kept")
    gaps = {}
    for name, wrap in (("bf16", correct.bf16_wrap), ("fp8", correct.control_wrap)):
        got = correct.follow_reference(ref, c, 7, batches, wrap)
        got["grad_diff"] = correct.rel_diff(got.pop("_kept"), kept)
        gaps[name] = got
    print({n: (max(abs(a - b) for a, b in zip(g["loss"], sound["loss"])), g["grad_diff"])
           for n, g in gaps.items()})
    limits = {"loss_gap": 3e-4, "grad_gap": 1.0, "update_gap": 1.0, "grad_diff": 0.025}
    ok, rows = correct.compare(gaps["bf16"], sound, limits)
    assert ok, rows
    ok, rows = correct.compare(gaps["fp8"], sound, limits)
    assert not ok
    assert all(not r["ok"] for r in rows if r["number"] in ("loss_gap", "grad_diff")), rows
    assert np.isfinite([r["value"] for r in rows]).all()
