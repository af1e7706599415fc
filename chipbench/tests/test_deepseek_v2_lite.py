"""``deepseek-v2-lite``: the arithmetic of its flops file, a whole rehearsal
run of its cell, and the run with an expert left out underneath."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "deepseek-v2-lite"


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    assert flops.total_params(c, published=True) == 15_706_484_224
    assert flops.attention_params(c) == 13_762_560 and flops.expert_params(c) == 8_650_752
    shapes = correct.load_by_name("reference", NAME).param_shapes(c)
    import jax

    leaves = jax.tree.leaves(shapes, is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == flops.total_params(c) == 535_060_992
    # ISSUE 27's cut, one sparse layer more: 635.5M
    assert flops.total_params({**c, "num_hidden_layers": 6}) == 635_466_752


def test_required_operations_count_the_routed_experts_at_their_expected_share():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    d, t = c["hidden_size"], c["seq_len"]
    per_layer = 13_762_560 + t * 16 * (192 + 128)
    sparse = d * 64 + 2 * 8_650_752 + (6 * 8 / 64) * 8_650_752
    want = 5 * per_layer + 3 * d * 10944 + 4 * sparse + d * 12800
    assert flops.forward_macs_per_token(c, t) == pytest.approx(want)
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * want * t)
    assert 8.5e12 < flops.train_flops_per_sample(c) < 9.5e12
    assert flops.kernel_costs(c, 2) == {}
    assert flops.grouped_matmul_flops(c, 6144) == 6 * 6144 * 8_650_752


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (5, 8, 12800)
    assert c["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 102400}
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["num_experts_per_tok"]) \
        == (2048, 10944, 1408, 512, 128, 64, 128, 6)
    kw = c["model"]["kwargs"]
    assert kw["moe_experts"] == 64 and kw["moe_top_k"] == 6 and kw["moe_kwargs"]["held"] == [0, 8]
    assert kw["moe_kwargs"]["capacity_factor"] is None      # no token dropped
    # the floors: four sparse layers after the dense one, 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    r = c["rehearsal"]
    assert r["num_hidden_layers"] == 3 and r["n_routed_experts_published"] == 8 and r["n_routed_experts"] == 4


@pytest.mark.parametrize("broken", [False, True])
def test_a_rehearsal_run_and_one_with_an_expert_left_out(monkeypatch, tmp_path, broken):
    """The whole run at the rehearsal sizes on the CPU.  Sound, ``correct`` is
    true and the expert layer's metrics report; with the second held expert's
    rows zeroed behind the grouped product, it comes out false."""
    import jax.numpy as jnp

    from chipbench import run
    from tpuframe.models import moe

    if broken:
        real = moe.grouped_matmul

        def without_expert_1(rows, weights, sizes):
            ends = jnp.cumsum(sizes)
            pos = jnp.arange(rows.shape[0])
            gone = (pos >= ends[1] - sizes[1]) & (pos < ends[1])
            return jnp.where(gone[:, None], 0, real(rows, weights, sizes))

        monkeypatch.setattr(moe, "grouped_matmul", without_expert_1)
    out = run.run_cell("dsv2lite_seq4096", 2**31 + 77, 0.5, True, rehearsal=True,
                       out_dir=str(tmp_path))
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (not broken)
    padded = out["metrics"]["moe.padded_rows_pct"]["value"]
    assert 0 <= padded < 100 and out["metrics"]["moe.expert_load_max_over_mean"]["value"] >= 1
    if broken:
        leaf = next(r for r in out["extras"]["rows"] if r["at"] == "block1/moe/w_in")
        assert not leaf["ok"] and leaf["value"] > 0.3
