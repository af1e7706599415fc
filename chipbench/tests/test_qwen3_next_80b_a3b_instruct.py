"""``qwen3-next-80b-a3b-instruct``: the arithmetic of its flops file against the
parameter counts of ISSUE 43, ``kernel_costs`` for every kernel name the gated
delta rule can emit, what its file states, the new readers on a hand-made
``ctx``, and a whole rehearsal run of its cell."""

import json
import math
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "qwen3-next-80b-a3b-instruct"
CELL = "qwen3next_seq8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    import jax

    flops, c = correct.load_by_name("flops", NAME), cfg()
    # the two fused projections, the taps, A_log, dt_bias, the gated norm, the output projection
    assert flops.linear_params(c) == 33_718_464
    # q with its gate 2048 x 8192, k and v 2048 x 512, o 4096 x 2048, two head norms of 256
    assert flops.attention_params(c) == 27_263_488
    assert flops.expert_params(c) == 3_145_728
    # outside the mixer: two norms, the router, the shared expert and its gate
    assert 2 * 2048 + flops.moe_shared_params(c) == 4_200_448
    assert flops.layer_params(c, "linear_attention", 0) == 33_718_464 + 4_200_448
    assert flops.layer_params(c, "full_attention", 0) == 27_263_488 + 4_200_448
    assert 2 * 18_992 * 2048 + 2048 == 77_793_280
    assert flops.total_params(c) == 424_340_544 == c["parameters"]
    assert flops.total_params(c, published=True) == 79_674_391_296 == c["parameters_published"]
    ref = correct.load_by_name("reference", NAME)
    leaves = jax.tree.leaves(ref.param_shapes(c), is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == 424_340_544
    published = {**c, "num_hidden_layers": 48, "layers_held": list(range(48)),
                 "num_experts": 512, "vocab_size": 151_936}
    whole = jax.tree.leaves(ref.param_shapes(published), is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in whole) == 79_674_391_296
    # ISSUE 43's table: 16 and 64 chips a layer; the check's six float32 copies
    assert flops.total_params({**c, "num_experts": 32}) == 625_667_136
    assert flops.total_params({**c, "num_experts": 8}) == 323_677_248
    assert 9.4 < flops.total_params(c) * 24 / 2**30 < 9.5
    assert flops.total_params({**c, "num_experts": 32}) * 24 / 2**30 > 13.9


def test_required_operations_count_the_rule_at_the_recurrence():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t = c["seq_len"]
    assert flops.layer_types(c) == ["linear_attention"] * 3 + ["full_attention"]
    assert flops.causal_area(c) == t * (t + 1) // 2 == 33_558_528
    # a token's multiply-adds, forward: ISSUE 43's 188M, 101M of them the fused projections
    linear = 2048 * 12_288 + 2048 * 64 + 4096 * 2048
    assert flops.linear_matmul_params(c) == linear == 33_685_504 and 100e6 < 3 * linear < 102e6
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert flops.attention_matmul_params(c) == attention
    every = 2048 * 512 + 3 * 2048 * 512 + 2048 + (10 * 16 / 512) * 3_145_728
    per_token = 3 * linear + attention + 4 * every + 2048 * 18_992
    assert 187e6 < per_token < 189e6
    scores = 2 * 16 * 256 * 33_558_528
    assert flops.forward_macs_per_sample(c) == pytest.approx(t * per_token + scores)
    # the rule: 7 FLOP an element of a 128 x 128 state, a position and value head
    assert flops.rule_flops_forward(c) == 7 * 128 * 128 * 32 * t
    want = 3 * (2 * (t * per_token + scores) + 3 * flops.rule_flops_forward(c))
    assert flops.train_flops_per_sample(c) == pytest.approx(want)
    assert 9.2e12 < 6 * t * per_token < 9.4e12           # the matmuls, ~9.3 TFLOP
    assert 1.6e12 < 6 * scores < 1.7e12                  # the scores' two products
    assert 0.26e12 < 9 * flops.rule_flops_forward(c) < 0.3e12
    assert 11.0e12 < flops.train_flops_per_sample(c) < 11.4e12


def test_kernel_costs_for_every_kernel_the_cell_runs():
    import importlib

    flops, c = correct.load_by_name("flops", NAME), cfg()
    costs = flops.kernel_costs(c, 1)
    # every kernel name the op can emit: read from the op's own source
    source = open(importlib.import_module("tpuframe.ops.gated_delta").__file__).read()
    import re

    emitted = set(re.findall(r'name="(tpuframe_gated_delta_\w+)"', source))
    assert emitted == {"tpuframe_gated_delta_fwd", "tpuframe_gated_delta_bwd"}
    assert emitted | {"tpuframe_flash_fwd", "tpuframe_flash_bwd"} == set(costs)
    t = c["seq_len"]
    rule = 7 * 128 * 128 * 32 * t
    fwd, bwd = costs["tpuframe_gated_delta_fwd"], costs["tpuframe_gated_delta_bwd"]
    assert fwd["flops"] == rule and bwd["flops"] == 2 * rule
    states = (t // 64) * 32 * 128 * 128 * 4
    assert states == 268_435_456            # ISSUE 43: 268 MB a layer
    io = 2 * t * 16 * 128 * 2 + t * 32 * 128 * 2 + 2 * t * 32 * 4
    assert fwd["bytes"] == io + t * 32 * 128 * 2 + states
    assert bwd["bytes"] == 2 * io + 2 * t * 32 * 128 * 2 + states
    # HBM bounds the rule's kernels, the MXU the flash kernels'
    for k in (fwd, bwd):
        assert k["bytes"] / 819e9 > k["flops"] / 197e12
    assert costs["tpuframe_flash_fwd"]["flops"] == 2 * 2 * 16 * 256 * 33_558_528
    assert costs["tpuframe_flash_bwd"]["flops"] == 5 * 2 * 16 * 256 * 33_558_528
    assert costs["tpuframe_flash_fwd"]["bytes"] == 2 * t * 256 * 2 * (16 + 2)
    for name in ("tpuframe_flash_fwd", "tpuframe_flash_bwd"):
        assert costs[name]["flops"] / 197e12 > costs[name]["bytes"] / 819e9


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (4, 16, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value or key in c["reduced"], key
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "TransformerLM"
    ref = correct.load_by_name("reference", NAME)
    assert kw["layer_types"] == ref.layer_types(c) == ["linear_attention"] * 3 + ["full_attention"]
    assert c["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 12
    assert kw["linear_attention"] == {"num_key_heads": 16, "num_value_heads": 32, "key_dim": 128,
                                      "value_dim": 128, "conv_taps": 4}
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"], kw["d_model"]) == (16, 2, 256, 2048)
    assert kw["rope_dim"] == int(c["partial_rotary_factor"] * c["head_dim"]) == 64
    assert kw["rope_theta"] == c["rope_theta"] and kw["attn_gated"] and kw["norm_unit_offset"]
    assert kw["moe_experts"] == 512 and kw["moe_top_k"] == 10
    moe = kw["moe_kwargs"]
    assert moe["held"] == [0, 16] and moe["expert_dim"] == 512 and moe["shared_dim"] == 512
    assert moe["shared_token_gate"] and moe["capacity_factor"] is None and moe["renormalize"]
    assert moe["aux_loss_weight"] == c["router_aux_loss_coef"]
    # the floors: a whole period, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "32 chips share each layer" in c["deployment"] and "16 held" in c["deployment"]
    assert c["trainer"]["optimizer"] == c["optimizer"]["name"] == "sgd"
    for said in ("MTP", "router_aux_loss_coef", "AdamW", "A_log", "seq_len 8192"):
        assert any(said in a for a in c["assumed"]), said
    assert set(c["probe_leaves"]) == {"block0/deltanet/in_proj_ba/kernel",
                                      "block3/attn/key/kernel", "lm_head/kernel"}
    assert set(c["tolerance"]["grad_diff"]) == set(c["probe_leaves"])
    r = c["rehearsal"]
    assert ref.layer_types({**c, **r}) == r["model"]["kwargs"]["layer_types"]


def test_the_traffic_is_rows_of_the_slice():
    from chipbench.traffic import generator

    c = cfg()
    data = generator.make_dataset(generator.load_mix("tokens-seq8192"), c, 2**31 + 5, 1)
    x, y = data.first_batches(1, 1)[0]
    assert x.shape == y.shape == (1, 8192) and x.dtype == "int32"
    assert (x[:, 1:] == y[:, :-1]).all() and 0 <= x.min() and x.max() < c["vocab_size"]


def test_the_new_readers_on_a_hand_made_trace():
    """The ``deltanet.*`` readers take the rule's calls alone, price each by
    its own name and divide by the chunk steps the program counted; the
    pass-throughs read what their accepted twins read."""
    from tpuframe.track.telemetry import get_telemetry

    c = cfg()
    kernels = {"tpuframe_gated_delta_fwd": {"seconds": 16 * 3 * 5.0e-3, "calls": 48},
               "tpuframe_gated_delta_bwd": {"seconds": 16 * 3 * 15.0e-3, "calls": 48},
               "tpuframe_flash_fwd": {"seconds": 16 * 4.0e-3, "calls": 16},
               "tpuframe_flash_bwd": {"seconds": 16 * 8.0e-3, "calls": 16},
               "tpuframe_grouped_fwd": {"seconds": 16 * 2.0e-3, "calls": 192},
               "tpuframe_head_norm_rope_fwd": {"seconds": 1.0, "calls": 32}}
    ctx = {"trace": {"steps": 16, "kernels": kernels}, "cfg": c, "global_batch": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: correct.load_by_name("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("deltanet.kernel_ms") == pytest.approx(60.0)
    costs = correct.load_by_name("flops", NAME).kernel_costs(c, 1)
    least = 3 * sum(costs[n]["bytes"] / 819e9
                    for n in ("tpuframe_gated_delta_fwd", "tpuframe_gated_delta_bwd"))
    assert read("deltanet.roofline") == pytest.approx(100 * least / 60.0e-3)
    assert 0 < read("deltanet.roofline") < 100
    registry = get_telemetry().registry
    chunks, calls = registry.counter("deltanet/chunks"), registry.counter("deltanet/calls")
    if not calls.value:
        assert read("deltanet.us_per_chunk") is None
    # the model traced twice: three layers each time, 2 x 32 x 64 chunk steps a layer
    chunks.inc(2 * 3 * 4096 - chunks.value)
    calls.inc(2 * 3 - calls.value)
    assert read("deltanet.us_per_chunk") == pytest.approx(60.0e3 / (3 * 4096))
    assert read("qwen3next.flash_ms") == pytest.approx(12.0)
    full = (costs["tpuframe_flash_fwd"]["flops"] + costs["tpuframe_flash_bwd"]["flops"]) / 197e12
    assert read("qwen3next.flash_roofline") == pytest.approx(100 * full / 12.0e-3)
    assert read("qwen3next.experts_ms") == pytest.approx(2.0)
    # a program without such kernels (the parent) reads as nothing
    ctx["trace"]["kernels"] = {"tpuframe_flash_fwd": kernels["tpuframe_flash_fwd"]}
    for name in ("deltanet.kernel_ms", "deltanet.roofline", "deltanet.us_per_chunk",
                 "qwen3next.experts_ms"):
        assert read(name) is None
    ctx["trace"] = None
    for name in ("deltanet.kernel_ms", "deltanet.roofline", "deltanet.us_per_chunk",
                 "qwen3next.flash_ms", "qwen3next.flash_roofline", "qwen3next.experts_ms"):
        assert read(name) is None


def test_benchmark_json_lists_the_cell_and_its_eight_metrics():
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == NAME and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "deltanet.kernel_ms", "deltanet.roofline", "deltanet.us_per_chunk", "qwen3next.flash_ms",
        "qwen3next.flash_roofline", "qwen3next.experts_ms", "qwen3next.slot_rows_over_routed",
        "qwen3next.expert_load_max_over_mean"]
    assert bench["per_layer"][-8:] == mine
    for m in mine:
        assert os.path.exists(os.path.join(ROOT, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] == "samples_per_s_chip"
    assert not any(CELL in m.get("workloads", []) for m in bench["per_layer"] if m not in mine)


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 43, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    before = (registry.counter("deltanet/chunks").value, registry.counter("deltanet/calls").value)
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    assert m["qwen3next.expert_load_max_over_mean"]["value"] >= 1
    assert m["qwen3next.slot_rows_over_routed"]["value"] >= 1
    # the device-trace readers find nothing on the CPU, and say nothing
    for name in ("deltanet.kernel_ms", "deltanet.roofline", "deltanet.us_per_chunk",
                 "qwen3next.flash_ms", "qwen3next.flash_roofline", "qwen3next.experts_ms",
                 "moe.slot_rows_over_routed", "mellum2.flash_ms"):
        assert name not in m
    chunks = registry.counter("deltanet/chunks").value - before[0]
    calls = registry.counter("deltanet/calls").value - before[1]
    # one linear-attention layer at rehearsal sizes: 2 rows x 2 value heads x 1 chunk, each way
    assert calls >= 1 and chunks / calls == 2 * 2 * 2


@pytest.mark.parametrize("fault", ["no_decay", "rotary_over_the_whole_head"])
def test_a_run_with_the_rule_or_the_rotary_width_broken_underneath(monkeypatch, tmp_path, fault):
    """The decay left out of the rule in the program, or the full layer's
    heads turned over their whole width: ``correct`` comes out false."""
    from tpuframe.models import transformer as tr

    if fault == "no_decay":
        real = tr.gated_delta
        monkeypatch.setattr(tr, "gated_delta",
                            lambda q, k, v, g, beta, **kw: real(q, k, v, 0 * g, beta, **kw))
    else:
        real = tr.rope_tables
        monkeypatch.setattr(tr, "rope_tables",
                            lambda length, dim, *a, **kw: real(length, 16, *a, **kw))
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
