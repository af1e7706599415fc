"""``keye-vl-2.0-30b-a3b``: the arithmetic of its flops file, what its file
states, a whole rehearsal run of its cell, runs with the choice broken
underneath, the readers of the new kernels on a hand-made trace, and
``BENCHMARK.json`` holding the cell and its entries (by membership)."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "keye-vl-2.0-30b-a3b"
CELL = "keyevl2_seq8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("dsa.index_ms", "dsa.index_roofline", "dsa.flash_ms", "dsa.flash_roofline",
           "dsa.selected_over_causal", "keyevl2.experts_ms", "keyevl2.expert_load_max_over_mean")


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    import jax

    flops, c = correct.load_by_name("flops", NAME), cfg()
    # q 2048x4096 + k, v 2048x512 + o 4096x2048; one expert 3 x 2048 x 768
    assert flops.attention_params(c) == 18_874_368 and flops.expert_params(c) == 4_718_592
    # the index: 2048 x (16 x 64) + 2048 x 64 + 2048 x 16, and its key norm's 128
    assert flops.index_params(c) == 2_097_152 + 131_072 + 32_768
    outside = 18_874_368 + 256 + 4_096 + 2_261_120 + 262_144
    assert flops.layer_params(c, 8) == outside + 8 * 4_718_592 == 59_150_720
    assert flops.total_params(c) == 4 * 59_150_720 + 77_793_280 == 314_396_160 == c["parameters"]
    assert flops.total_params(c, published=True) == c["parameters_published"] == 30_640_656_384
    ref = correct.load_by_name("reference", NAME)
    count = lambda shapes: sum(math.prod(s[0]) for s in jax.tree.leaves(  # noqa: E731
        shapes, is_leaf=correct._is_spec))
    assert count(ref.param_shapes(c)) == 314_396_160
    published = {**c, "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151_936}
    assert count(ref.param_shapes(published)) == 30_640_656_384
    # follow_reference's eight float32 copies under AdamW fit the chip beside the
    # reference's activations; the other cut the issue names is six copies of more
    assert flops.total_params(c) * 32 / 2**30 < 9.4
    assert flops.total_params({**c, "num_experts": 16}) == 465_391_104


def test_required_operations_count_the_chosen_pairs_and_the_index_once():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t, k = c["seq_len"], c["sa_config"]["topk"]
    assert flops.causal_pairs(c) == t * (t + 1) // 2 == 33_558_528
    assert flops.chosen_pairs(c) == sum(min(i + 1, k) for i in range(t)) == 14_681_088
    assert flops.chosen_pairs({**c, "seq_len": 2048}) == 2048 * 2049 // 2     # a plain row
    per_token = 18_874_368 + 2048 * 128 + (8 * 8 / 128) * 4_718_592
    attention = 2 * 32 * 128 * 14_681_088
    forward = 4 * (t * per_token + attention) + t * 2048 * 18_992
    assert flops.forward_macs_per_sample(c) == pytest.approx(forward)
    index = t * 2_260_992 + 16 * 64 * 33_558_528
    assert flops.index_macs_per_sample(c) == index
    assert flops.index_macs_per_sample({**c, "seq_len": 2048}) == 0
    assert flops.train_flops_per_sample(c) == pytest.approx(6 * forward + 2 * 4 * index)
    assert 9.2e12 < flops.train_flops_per_sample(c) < 9.7e12
    # against the parameter count: 6 FLOP a parameter a token passes through with a
    # backward pass, 2 a parameter of the index, plus attention and the index scores
    through = (flops.total_params(c) - c["vocab_size"] * 2048        # the embedding is a gather
               - 4 * 7.5 * 4_718_592                                 # half an expert pass of 8 held
               - 4 * 2_261_120                                       # the index: forward only
               - 4 * (256 + 4_096) - 2048)                           # norm scales multiply no matrix
    assert flops.train_flops_per_sample(c) == pytest.approx(
        6 * t * through + 6 * 4 * attention + 2 * 4 * index)
    costs = flops.kernel_costs(c, 1)
    assert set(costs) == {"tpuframe_flash_fwd_select", "tpuframe_flash_bwd_select",
                          "tpuframe_index_topk"}
    assert costs["tpuframe_flash_fwd_select"]["flops"] == 2 * 2 * 32 * 128 * 14_681_088
    assert costs["tpuframe_flash_bwd_select"]["flops"] == 5 * 2 * 32 * 128 * 14_681_088
    assert costs["tpuframe_flash_fwd_select"]["bytes"] == 2 * t * 128 * 2 * (32 + 4)
    assert costs["tpuframe_index_topk"]["flops"] == 2 * 16 * 64 * 33_558_528
    # the MXU bounds all three: operations over the peak take longer than the bytes
    for c_ in costs.values():
        assert c_["flops"] / 197e12 > c_["bytes"] / 819e9


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (4, 8, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value or key in c["reduced"], key
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"], c["norm_topk_prob"],
            c["rope_theta"]) == (2048, 32, 4, 128, 768, 8, True, 10000000)
    assert c["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                              "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                              "q_chunk_size": 512, "topk": 2048}
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "TransformerLM"
    assert kw["sparse_index"] == {"num_heads": 16, "head_dim": 64, "topk": 2048}
    assert kw["moe_experts"] == 128 and kw["moe_top_k"] == 8 and kw["moe_kwargs"]["held"] == [0, 8]
    assert kw["moe_kwargs"]["capacity_factor"] is None and kw["moe_kwargs"]["shared_dim"] == 0
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"], kw["rope_dim"], kw["d_model"],
            kw["rope_theta"]) == (32, 4, 128, 128, 2048, 10000000)
    assert kw["moe_kwargs"]["expert_dim"] == 768 and kw["qk_norm"] and not kw["remat"]
    # the floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "16 chips share each layer" in c["deployment"] and "314,396,160" in c["deployment"]
    assert c["trainer"]["optimizer"] == c["optimizer"]["name"] == "adamw"
    for word in ("head", "LayerNorm", "no rotary turn", "q_chunk_size", "frozen", "text rows"):
        assert any(word in a for a in c["assumed"]), word
    r = c["rehearsal"]
    assert r["sa_config"]["topk"] < r["seq_len"]       # the choice is real at rehearsal sizes
    assert r["model"]["kwargs"]["sparse_index"]["topk"] == r["sa_config"]["topk"]


def test_the_traffic_is_rows_of_the_slice():
    from chipbench.traffic import generator

    c = cfg()
    data = generator.make_dataset(generator.load_mix("tokens-seq8192"), c, 2**31 + 5, 1)
    x, y = data.first_batches(1, 1)[0]
    assert x.shape == y.shape == (1, 8192) and x.dtype == "int32"
    assert (x[:, 1:] == y[:, :-1]).all() and 0 <= x.min() and x.max() < c["vocab_size"]


def test_benchmark_json_holds_the_cell_and_its_entries():
    """By membership, not by position: a later PR appends behind them."""
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = next(x for x in bench["configs"] if x["name"] == NAME)
    assert conf["file"] == f"chipbench/configs/{NAME}.json" and conf["source"] == cfg()["source"]
    assert conf["reduced"] == cfg()["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "tokens-seq8192", 1)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "samples_per_s_chip"
        assert os.path.exists(os.path.join(ROOT, "layer_metrics", f"{name}.py")), name
    assert by_name["dsa.index_roofline"]["unit"] == by_name["dsa.flash_roofline"]["unit"] == "%"
    # no entry the benchmark had names the cell: its lists are other PRs'
    assert [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())] == list(METRICS)


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 47, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    c = cfg()["rehearsal"]
    t, k = c["seq_len"], c["sa_config"]["topk"]
    chosen = k * (k + 1) // 2 + (t - k) * k
    assert m["dsa.selected_over_causal"]["value"] == pytest.approx(chosen / (t * (t + 1) // 2))
    assert m["keyevl2.expert_load_max_over_mean"]["value"] >= 1
    # the device-trace readers find nothing on the CPU, and say nothing
    for name in ("dsa.index_ms", "dsa.index_roofline", "dsa.flash_ms", "dsa.flash_roofline",
                 "keyevl2.experts_ms", "moe.experts_ms", "swa.tiles_visited_over_needed"):
        assert name not in m


@pytest.mark.parametrize("fault", ["a_choice_that_ignores_w", "attention_over_all_causal_keys",
                                   "half_the_keys"])
def test_a_run_with_the_choice_broken_underneath(monkeypatch, tmp_path, fault):
    """The index's head weights left out of its scores, the rule replaced by
    ``causal`` in the program, or half the keys chosen: ``correct`` comes out
    false."""
    from tpuframe.models import transformer as tr

    if fault == "attention_over_all_causal_keys":
        real = tr._attend
        monkeypatch.setattr(tr, "_attend",
                            lambda *a, mask=None, mask_operands=(), **kw: real(*a, **kw))
    else:
        real = tr.select_keys
        broken = ((lambda qi, ki, w, topk, **kw: real(qi, ki, 0 * w + 1, topk, **kw))
                  if fault == "a_choice_that_ignores_w" else
                  (lambda qi, ki, w, topk, **kw: real(qi, ki, w, topk // 2, **kw)))
        monkeypatch.setattr(tr, "select_keys", broken)
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
    assert not next(r for r in out["extras"]["rows"] if r["number"] == "loss_gap")["ok"]


def test_the_readers_of_the_new_kernels():
    """On a hand-made reduced trace: ``dsa.flash_*`` take the calls whose names
    carry the rule's suffix, ``dsa.index_*`` the index kernels, each priced by
    ``kernel_costs``; a program without them reads as nothing."""
    c = cfg()
    kernels = {"tpuframe_flash_fwd_select": {"seconds": 16 * 4 * 5.0e-3, "calls": 64},
               "tpuframe_flash_bwd_select": {"seconds": 16 * 4 * 10.0e-3, "calls": 64},
               "tpuframe_index_topk": {"seconds": 16 * 4 * 4.0e-3, "calls": 64},
               "tpuframe_flash_fwd": {"seconds": 1.0, "calls": 16},
               "tpuframe_grouped_fwd": {"seconds": 16 * 2.0e-3, "calls": 192}}
    ctx = {"trace": {"steps": 16, "kernels": kernels}, "cfg": c, "global_batch": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: correct.load_by_name("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("dsa.flash_ms") == pytest.approx(60.0)
    assert read("dsa.index_ms") == pytest.approx(16.0)
    assert read("keyevl2.experts_ms") == pytest.approx(2.0)
    costs = correct.load_by_name("flops", NAME).kernel_costs(c, 1)
    least = lambda n: costs[n]["flops"] / 197e12  # noqa: E731
    flash = 4 * (least("tpuframe_flash_fwd_select") + least("tpuframe_flash_bwd_select"))
    assert read("dsa.flash_roofline") == pytest.approx(100 * flash / 60.0e-3)
    assert read("dsa.index_roofline") == pytest.approx(100 * 4 * least("tpuframe_index_topk") / 16e-3)
    assert 0 < read("dsa.flash_roofline") < 100 and 0 < read("dsa.index_roofline") < 100
    # a program without such kernels (the parent: no such rule, no index) reads as nothing
    ctx["trace"]["kernels"] = {"tpuframe_flash_fwd": kernels["tpuframe_flash_fwd"]}
    for name in ("dsa.flash_ms", "dsa.flash_roofline", "dsa.index_ms", "dsa.index_roofline",
                 "keyevl2.experts_ms"):
        assert read(name) is None
    ctx["trace"] = None
    assert read("dsa.index_ms") is None and read("dsa.flash_roofline") is None
