"""``mellum2-12b-a2.5b-instruct``: the arithmetic of its flops file, what its
file states, a whole rehearsal run of its cell, and runs with the window rule
or the full layer's rotary tables broken underneath."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-instruct"
CELL = "mellum2_seq8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    # q 2304x4096 + k, v 2304x512 + o 4096x2304; one expert 3 x 2304 x 896
    assert flops.attention_params(c) == 21_233_664 and flops.expert_params(c) == 6_193_152
    outside = 21_233_664 + 2 * 128 + 2 * 2304 + 2304 * 64
    assert flops.layer_params(c, 8) == outside + 8 * 6_193_152 == 70_931_200
    assert flops.layer_params(c, 64) == 417_747_712
    assert flops.total_params(c, published=True) \
        == 28 * 417_747_712 + 2 * 98_304 * 2304 + 2304 == 12_149_923_072 == c["parameters_published"]
    shapes = correct.load_by_name("reference", NAME).param_shapes(c)
    import jax

    leaves = jax.tree.leaves(shapes, is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == flops.total_params(c) == 340_350_208 \
        == c["parameters"]
    published = {**c, "num_hidden_layers": 28, "layers_held": list(range(28)),
                 "num_experts": 64, "vocab_size": 98_304}
    whole = correct.load_by_name("reference", NAME).param_shapes(published)
    assert sum(math.prod(s[0]) for s in jax.tree.leaves(whole, is_leaf=correct._is_spec)) \
        == 12_149_923_072
    # follow_reference's eight float32 copies under AdamW fit the chip; the cuts
    # the file names as too large do not fit even six
    assert flops.total_params(c) * 32 / 2**30 < 10.2
    assert flops.total_params({**c, "num_experts": 16, "vocab_size": 24_576}) == 595_154_176
    assert flops.total_params({**c, "num_hidden_layers": 8}) == 624_075_008


def test_required_operations_count_each_kind_of_layer_at_its_own_area():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t, w = c["seq_len"], c["sliding_window"]
    assert flops.mask_area(c, "full_attention") == t * (t + 1) // 2 == 33_558_528
    assert flops.mask_area(c, "sliding_attention") == t * w - w * (w - 1) // 2 == 7_864_832
    assert flops.mask_area({**c, "seq_len": 512}, "sliding_attention") == 512 * 513 // 2
    per_token = 21_233_664 + 2304 * 64 + (8 * 8 / 64) * 6_193_152
    attention = 2 * 32 * 128 * (3 * 7_864_832 + 33_558_528)
    want = 4 * t * per_token + attention + t * 2304 * 12_288
    assert flops.forward_macs_per_sample(c) == pytest.approx(want)
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * want)
    assert 9.0e12 < flops.train_flops_per_sample(c) < 9.8e12
    # against the parameter count: 6 FLOP a parameter a token passes through, plus attention
    through = (flops.total_params(c) - c["vocab_size"] * 2304        # the embedding is a gather
               - 4 * 7 * 6_193_152                                   # 1 expert pass of the 8 held
               - 4 * (2 * 128 + 2 * 2304) - 2304)                    # norm scales multiply no matrix
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * t * through + 6 * attention)
    # the three window layers together cost less than the one full layer
    assert 3 * 7_864_832 < 33_558_528
    costs = flops.kernel_costs(c, 1)
    assert set(costs) == {"tpuframe_flash_fwd", "tpuframe_flash_bwd",
                          "tpuframe_flash_fwd_window", "tpuframe_flash_bwd_window"}
    assert costs["tpuframe_flash_fwd_window"]["flops"] == 2 * 2 * 32 * 128 * 7_864_832
    assert costs["tpuframe_flash_bwd"]["flops"] == 5 * 2 * 32 * 128 * 33_558_528
    assert costs["tpuframe_flash_fwd"]["bytes"] == 2 * t * 128 * 2 * (32 + 4)
    # the MXU bounds all four: operations over the peak take longer than the bytes
    for c_ in costs.values():
        assert c_["flops"] / 197e12 > c_["bytes"] / 819e9


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (4, 8, 12288)
    assert c["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value or key in c["reduced"], key
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"], c["sliding_window"],
            c["norm_topk_prob"]) == (2304, 32, 4, 128, 896, 8, 1024, True)
    assert c["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    assert c["rope_parameters"]["sliding_attention"] == {"rope_type": "default",
                                                         "rope_theta": 500000}
    assert [c["layer_types"][i] for i in c["layers_held"]] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "TransformerLM"
    assert kw["layer_types"] == [c["layer_types"][i] for i in c["layers_held"]]
    assert kw["rope_parameters"] == c["rope_parameters"] and kw["sliding_window"] == 1024
    assert kw["moe_experts"] == 64 and kw["moe_top_k"] == 8 and kw["moe_kwargs"]["held"] == [0, 8]
    assert kw["moe_kwargs"]["capacity_factor"] is None and kw["moe_kwargs"]["shared_dim"] == 0
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"], kw["rope_dim"], kw["d_model"]) \
        == (32, 4, 128, 128, 2304)
    assert kw["moe_kwargs"]["expert_dim"] == 896 and kw["qk_norm"]
    # the floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "8 chips share each layer" in c["deployment"]
    assert c["trainer"]["optimizer"] == c["optimizer"]["name"]
    assert any("head" in a and "norm" in a.lower() for a in c["assumed"])
    assert any(c["optimizer"]["name"] in a.lower() for a in c["assumed"])
    r = c["rehearsal"]
    assert r["sliding_window"] < r["seq_len"]      # the band is narrower than the row
    assert [c["layer_types"][i] for i in r["layers_held"]] == r["model"]["kwargs"]["layer_types"]


def test_the_traffic_is_rows_of_the_slice():
    from chipbench.traffic import generator

    c = cfg()
    data = generator.make_dataset(generator.load_mix("tokens-seq8192"), c, 2**31 + 5, 1)
    x, y = data.first_batches(1, 1)[0]
    assert x.shape == y.shape == (1, 8192) and x.dtype == "int32"
    assert (x[:, 1:] == y[:, :-1]).all() and 0 <= x.min() and x.max() < c["vocab_size"]


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 41, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    assert m["swa.tiles_visited_over_needed"]["value"] >= 1
    assert m["mellum2.expert_load_max_over_mean"]["value"] >= 1
    assert m["mellum2.slot_rows_over_routed"]["value"] >= 1
    # the device-trace readers find nothing on the CPU, and say nothing
    for name in ("swa.flash_ms", "swa.flash_roofline", "mellum2.flash_ms", "mellum2.flash_roofline",
                 "blockdiff.tiles_visited_over_needed", "moe.slot_rows_over_routed"):
        assert name not in m


@pytest.mark.parametrize("fault", ["window_is_causal", "full_layer_with_the_windows_tables"])
def test_a_run_with_the_rule_or_the_tables_broken_underneath(monkeypatch, tmp_path, fault):
    """The window rule replaced by ``causal`` in the program, or the full
    layer given the window layers' rotary tables: ``correct`` comes out
    false."""
    from tpuframe.models import transformer as tr

    if fault == "window_is_causal":
        real = tr._attend
        monkeypatch.setattr(tr, "_attend", lambda *a, mask=None, **kw: real(*a, **kw))
    else:
        plain = tr.TransformerLM._rope_of
        monkeypatch.setattr(tr.TransformerLM, "_rope_of",
                            lambda self, kind: plain(self, "sliding_attention"))
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
    assert not next(r for r in out["extras"]["rows"] if r["number"] == "loss_gap")["ok"]


def test_the_readers_of_the_window_kernels_tell_them_from_the_full_layers():
    """On a hand-made reduced trace: the ``swa.*`` readers take the calls
    whose names carry the band rule's suffix, ``mellum2.*`` all eight, each
    priced by its own name."""
    c = cfg()
    kernels = {"tpuframe_flash_fwd_window": {"seconds": 16 * 3 * 1.0e-3, "calls": 48},
               "tpuframe_flash_bwd_window": {"seconds": 16 * 3 * 2.0e-3, "calls": 48},
               "tpuframe_flash_fwd": {"seconds": 16 * 4.0e-3, "calls": 16},
               "tpuframe_flash_bwd": {"seconds": 16 * 8.0e-3, "calls": 16},
               "tpuframe_head_norm_rope_fwd": {"seconds": 1.0, "calls": 128}}
    ctx = {"trace": {"steps": 16, "kernels": kernels}, "cfg": c, "global_batch": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: correct.load_by_name("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("swa.flash_ms") == pytest.approx(9.0)
    assert read("mellum2.flash_ms") == pytest.approx(21.0)
    costs = correct.load_by_name("flops", NAME).kernel_costs(c, 1)
    least = lambda n: costs[n]["flops"] / 197e12  # noqa: E731
    window = 3 * (least("tpuframe_flash_fwd_window") + least("tpuframe_flash_bwd_window"))
    full = least("tpuframe_flash_fwd") + least("tpuframe_flash_bwd")
    assert read("swa.flash_roofline") == pytest.approx(100 * window / 9.0e-3)
    assert read("mellum2.flash_roofline") == pytest.approx(100 * (window + full) / 21.0e-3)
    assert 0 < read("swa.flash_roofline") < 100 and 0 < read("mellum2.flash_roofline") < 100
    # a program without such kernels (the parent: no band rule) reads as nothing
    ctx["trace"]["kernels"] = {"tpuframe_flash_fwd": kernels["tpuframe_flash_fwd"]}
    assert read("swa.flash_ms") is None and read("swa.flash_roofline") is None
