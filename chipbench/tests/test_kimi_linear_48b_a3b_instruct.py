"""``kimi-linear-48b-a3b-instruct``: the arithmetic of its flops file against the
parameter counts of ISSUE 50, ``kernel_costs`` for every kernel name the
vector-decay delta rule can emit, what its file states, the new readers on a
hand-made ``ctx``, and a whole rehearsal run of its cell."""

import json
import math
import os
import re

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b-instruct"
CELL = "kimilinear_seq4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["kda", "kda", "kda", "full_attention", "kda"]


def cfg():
    with open(os.path.join(ROOT, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_parameters_whole_and_as_cut():
    import jax

    flops, c = correct.load_by_name("flops", NAME), cfg()
    # ISSUE 50's table, row by row
    assert flops.kda_params(c) == 39_514_272
    assert flops.latent_params(c) == 29_114_880
    assert flops.layer_params(c, 1, "kda", 8) == 103_219_872
    assert flops.layer_params(c, 2, "kda", 8) == 103_809_952
    assert flops.layer_params(c, 4, "full_attention", 8) == 93_410_560
    assert 2 * 20_480 * 2304 + 2304 == 94_374_144
    assert flops.total_params(c) == 602_434_432 == c["parameters"]
    ref = correct.load_by_name("reference", NAME)
    leaves = jax.tree.leaves(ref.param_shapes(c), is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in leaves) == 602_434_432
    published = {**c, "num_hidden_layers": 27, "layers_held": list(range(1, 28)),
                 "num_experts": 256, "vocab_size": 163_840}
    whole = jax.tree.leaves(ref.param_shapes(published), is_leaf=correct._is_spec)
    assert sum(math.prod(s[0]) for s in whole) == flops.total_params(c, published=True)
    assert 48e9 < flops.total_params(c, published=True) < 50e9          # "48B"
    # the cuts that do not fit: 16 held experts; AdamW's eight copies of this one
    assert flops.total_params({**c, "num_experts": 16}) == 828_926_848
    assert 13.4 < flops.total_params(c) * 24 / 2**30 < 13.5
    assert flops.total_params(c) * 32 / 2**30 > 17.9
    assert flops.total_params({**c, "num_experts": 16}) * 24 / 2**30 > 18.5


def test_required_operations_count_the_rule_at_the_recurrence():
    flops, c = correct.load_by_name("flops", NAME), cfg()
    t = c["seq_len"]
    assert flops.layer_types(c) == KINDS
    assert flops.causal_area(c) == t * (t + 1) // 2
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert flops.kda_matmul_params(c) == kda
    latent = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert flops.latent_matmul_params(c) == latent
    sparse = 2304 * 256 + 3 * 2304 * 1024 + (8 * 8 / 256) * 3 * 2304 * 1024
    per_token = 4 * kda + latent + 3 * 2304 * 9216 + 4 * sparse + 2304 * 20_480
    assert 330e6 < per_token < 340e6                       # ISSUE 50: 336M a token
    scores = 32 * (192 + 128) * flops.causal_area(c)
    assert flops.forward_macs_per_sample(c) == pytest.approx(t * per_token + scores)
    # the rule: 8 FLOP an element of a 128 x 128 state, a position and head
    assert flops.rule_flops_forward(c) == 8 * 128 * 128 * 32 * t
    want = 3 * (2 * (t * per_token + scores) + 4 * flops.rule_flops_forward(c))
    assert flops.train_flops_per_sample(c) == pytest.approx(want)
    assert 8.8e12 < flops.train_flops_per_sample(c) < 9.2e12


def test_kernel_costs_for_every_kernel_the_op_can_emit():
    import importlib

    flops, c = correct.load_by_name("flops", NAME), cfg()
    costs = flops.kernel_costs(c, 1)
    source = open(importlib.import_module("tpuframe.ops.kda").__file__).read()
    emitted = set(re.findall(r'name="(tpuframe_\w+)"', source))
    assert emitted == {"tpuframe_kda_fwd", "tpuframe_kda_bwd"} == set(costs)
    t = c["seq_len"]
    rule = 8 * 128 * 128 * 32 * t
    fwd, bwd = costs["tpuframe_kda_fwd"], costs["tpuframe_kda_bwd"]
    assert fwd["flops"] == rule and bwd["flops"] == 2 * rule
    head = t * 32 * 128 * 2
    g, beta, states = t * 32 * 128 * 4, t * 32 * 4, (t // 64) * 32 * 128 * 128 * 4
    assert fwd["bytes"] == 4 * head + g + beta + states
    assert bwd["bytes"] == 2 * (3 * head + g + beta) + 2 * head + states
    for k in (fwd, bwd):                                   # HBM bounds both
        assert k["bytes"] / 819e9 > k["flops"] / 197e12


def test_the_file_holds_the_catalogs_numbers_and_states_the_cut():
    c = cfg()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (5, 8, 20480)
    assert c["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value or key in c["reduced"], key
    kw = c["model"]["kwargs"]
    assert c["model"]["class"] == "TransformerLM"
    ref = correct.load_by_name("reference", NAME)
    assert kw["layer_types"] == ref.layer_types(c) == KINDS and c["layers_held"] == [1, 2, 3, 4, 5]
    assert [ref.is_dense(c, layer) for layer in c["layers_held"]] == [True] + [False] * 4
    la = c["linear_attn_config"]
    assert kw["kda"] == {"num_heads": la["num_heads"], "head_dim": la["head_dim"],
                         "conv_taps": la["short_conv_kernel_size"], "rank": c["kda_low_rank"]}
    assert (kw["num_heads"], kw["head_dim"], kw["rope_dim"], kw["v_head_dim"], kw["kv_lora_rank"],
            kw["d_model"]) == (32, 128, 64, 128, 512, 2304)
    assert kw["nope"] is c["mla_use_nope"] is True and "rope_theta" not in kw
    assert kw["mlp_dim"] == c["intermediate_size"] and kw["moe_first_dense"] == 1
    assert kw["moe_experts"] == 256 and kw["moe_top_k"] == 8
    moe = kw["moe_kwargs"]
    assert moe["held"] == [0, 8] and moe["expert_dim"] == 1024 and moe["shared_dim"] == 1024
    assert moe["scoring"] == "sigmoid" and moe["select_bias"] and moe["renormalize"]
    assert moe["routed_scale"] == c["routed_scaling_factor"] == 2.446
    assert moe["aux_loss_weight"] == 0 and moe["capacity_factor"] is None
    # the floors: the dense layer and four behind it with a whole period, 8 experts, an eighth
    assert KINDS[1:].count("full_attention") == 1 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "32 chips share each layer" in c["deployment"] and "floor" in c["deployment"]
    assert c["trainer"]["optimizer"] == c["optimizer"]["name"] == "sgd"
    for said in ("rank of the two low-rank pairs", "A_log", "dt_bias", "selection bias", "AdamW",
                 "seq_len 4096", "bfloat16", "attn_out_init_std", "1e-20"):
        assert any(said in a for a in c["assumed"]), said
    assert c["probe_leaves"] == ["block0/kda/f_b/kernel", "block3/attn/kv_b/kernel",
                                 "lm_head/kernel"]
    assert set(c["tolerance"]["grad_diff"]) == set(c["probe_leaves"])
    assert not any("moe" in leaf for leaf in c["probe_leaves"])
    r = c["rehearsal"]
    merged = {**c, **r, "linear_attn_config": {**la, **r["linear_attn_config"]}}
    assert ref.layer_types(merged) == r["model"]["kwargs"]["layer_types"]


def test_the_traffic_is_rows_of_the_slice():
    from chipbench.traffic import generator

    c = cfg()
    data = generator.make_dataset(generator.load_mix("tokens-seq4096"), c, 2**31 + 5, 1)
    x, y = data.first_batches(1, 1)[0]
    assert x.shape == y.shape == (1, 4096) and x.dtype == "int32"
    assert (x[:, 1:] == y[:, :-1]).all() and 0 <= x.min() and x.max() < c["vocab_size"]


def test_the_new_readers_on_a_hand_made_trace():
    """The ``kda.*`` readers take the rule's calls alone, price each by its own
    name and divide by the chunk steps the program counted; ``kda.inputs_ms``
    takes the ``tpuframe_conv_silu_*`` calls; none of them reads the scalar
    rule's kernels, and a program without the kernels reads as nothing."""
    from tpuframe.track.telemetry import get_telemetry

    c = cfg()
    kernels = {"tpuframe_kda_fwd": {"seconds": 16 * 4 * 1.0e-3, "calls": 64},
               "tpuframe_kda_bwd": {"seconds": 16 * 4 * 3.0e-3, "calls": 64},
               "tpuframe_conv_silu_fwd": {"seconds": 16 * 4 * 0.5e-3, "calls": 64},
               "tpuframe_conv_silu_bwd": {"seconds": 16 * 4 * 1.5e-3, "calls": 64},
               "tpuframe_gated_delta_fwd": {"seconds": 9.0, "calls": 48},
               "tpuframe_flash_fwd": {"seconds": 16 * 2.0e-3, "calls": 16}}
    ctx = {"trace": {"steps": 16, "kernels": kernels}, "cfg": c, "global_batch": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: correct.load_by_name("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("kda.kernel_ms") == pytest.approx(16.0)
    assert read("kda.inputs_ms") == pytest.approx(8.0)
    costs = correct.load_by_name("flops", NAME).kernel_costs(c, 1)
    least = 4 * sum(costs[n]["bytes"] / 819e9 for n in ("tpuframe_kda_fwd", "tpuframe_kda_bwd"))
    assert read("kda.roofline") == pytest.approx(100 * least / 16.0e-3)
    assert 0 < read("kda.roofline") < 100
    registry = get_telemetry().registry
    chunks, calls = registry.counter("kda/chunks"), registry.counter("kda/calls")
    if not calls.value:
        assert read("kda.us_per_chunk") is None
    # the model traced twice: four layers each time, 2 x 32 x 32 chunk steps a layer
    chunks.inc(2 * 4 * 2048 - chunks.value)
    calls.inc(2 * 4 - calls.value)
    assert read("kda.us_per_chunk") == pytest.approx(16.0e3 / (4 * 2048))
    # a configuration whose flops file prices no such kernel (the older cells')
    with open(os.path.join(ROOT, "configs", "lfm2-8b-a1b.json")) as f:
        older = {**ctx, "cfg": json.load(f)}
    assert correct.load_by_name("layer_metrics", "kda.roofline").read(older) is None
    # a program without such kernels (the parent) reads as nothing
    ctx["trace"]["kernels"] = {"tpuframe_flash_fwd": kernels["tpuframe_flash_fwd"],
                               "tpuframe_gated_delta_fwd": kernels["tpuframe_gated_delta_fwd"]}
    for name in ("kda.kernel_ms", "kda.roofline", "kda.us_per_chunk", "kda.inputs_ms"):
        assert read(name) is None
    ctx["trace"] = None
    for name in ("kda.kernel_ms", "kda.roofline", "kda.us_per_chunk", "kda.inputs_ms"):
        assert read(name) is None


def test_benchmark_json_lists_the_cell_and_its_four_metrics():
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == NAME and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "tokens-seq4096"
    assert bench["configs"][-1]["reduced"] == cfg()["reduced"]
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == ["kda.kernel_ms", "kda.roofline", "kda.us_per_chunk",
                                         "kda.inputs_ms"]
    assert bench["per_layer"][-4:] == mine
    for m in mine:
        assert os.path.exists(os.path.join(ROOT, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] == "samples_per_s_chip" and m["layer"] == "kernels"
    assert not any(CELL in m.get("workloads", []) for m in bench["per_layer"] if m not in mine)


def _run(tmp_path, **kw):
    from chipbench import run

    return run.run_cell(CELL, 2**31 + 50, 0.5, True, rehearsal=True, out_dir=str(tmp_path), **kw)


def test_a_rehearsal_run_comes_out_correct_and_reports_its_metrics(tmp_path):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    before = (registry.counter("kda/chunks").value, registry.counter("kda/calls").value)
    out = _run(tmp_path)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"] is True
    m = out["metrics"]
    # the device-trace readers find nothing on the CPU, and say nothing
    for name in ("kda.kernel_ms", "kda.roofline", "kda.us_per_chunk", "kda.inputs_ms",
                 "deltanet.kernel_ms", "moe.experts_ms"):
        assert name not in m
    chunks = registry.counter("kda/chunks").value - before[0]
    calls = registry.counter("kda/calls").value - before[1]
    # a KDA layer at rehearsal sizes: 2 rows x 2 heads x 2 chunks, each way
    assert calls >= 2 and chunks / calls == 2 * 2 * 2 * 2


@pytest.mark.parametrize("fault", ["one_decay_a_head", "rotary_on_the_shared_key"])
def test_a_run_with_the_decay_or_the_positions_broken_underneath(monkeypatch, tmp_path, fault):
    """One decay a head put in the vector's place in the program, or the
    latent layer given rotary tables: ``correct`` comes out false."""
    import jax.numpy as jnp

    from tpuframe.models import transformer as tr

    if fault == "one_decay_a_head":
        real = tr.kda
        monkeypatch.setattr(tr, "kda", lambda q, k, v, g, beta, **kw: real(
            q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta, **kw))
    else:
        real = tr.TransformerLM.__init__
        monkeypatch.setattr(tr.TransformerLM, "__init__",
                            lambda self, *a, **kw: real(self, *a, **{**kw, "nope": False}))
    out = _run(tmp_path)
    assert out["failed"] == 0 and out["correct"] is False
