"""Qwen3-Next's kinds of layer: the gated-attention and partial-rotary paths
against plain ``jax.numpy``, the linear-attention mixer and the gated shared
expert, the model with ``layer_types`` against the plain reference of
``chipbench/reference/qwen3-next-80b-a3b-instruct.py`` (loss, gradients, three
steps), the share test for its expert layer, the sharding rules for the new
leaves, and the older configurations' StableHLO digests.  Small sizes, on the
CPU."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe import models
from tpuframe.models import TransformerLM, moe_rules, transformer_tp_rules
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP
from tpuframe.ops.head_norm_rope import head_norm_rope, head_norm_rope_reference
from tpuframe.ops.short_conv import causal_taps

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "qwen3-next-80b-a3b-instruct"


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def _config(name, rehearsal=True):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        full = json.load(f)
    return _merge(full, full["rehearsal"]) if rehearsal else full


def _leaf_names(tree):
    return ["/".join(k.key for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree, is_leaf=correct._is_spec)[0]]


CFG = _config(NAME)
REF = correct.load_by_name("reference", NAME)
LEAVES = _leaf_names(REF.param_shapes(CFG))
_PLAIN = lambda f: f  # noqa: E731


# -- partial rotary ---------------------------------------------------------------
def _turned_by_hand(y, cos, sin):
    """Dimension ``i`` of the first ``R`` with ``i + R / 2``, the rest alone."""
    r = cos.shape[-1]
    out = np.array(y, np.float64)
    a, b = np.array(y[..., :r // 2], np.float64), np.array(y[..., r // 2:r], np.float64)
    c, s = np.asarray(cos, np.float64)[None, :, None, :], np.asarray(sin, np.float64)[None, :, None, :]
    out[..., :r // 2] = a * c[..., :r // 2] - b * s[..., :r // 2]
    out[..., r // 2:r] = b * c[..., r // 2:] + a * s[..., r // 2:]
    return out


class TestPartialRotary:
    @pytest.mark.parametrize("d, r", [(256, 64), (16, 4), (128, 128)])
    def test_apply_rope_turns_the_first_dimensions_in_pairs(self, d, r):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, d))
        cos, sin = tr.rope_tables(12, r, 1e7)
        got = np.asarray(tr.apply_rope(x, cos, sin))
        np.testing.assert_allclose(got, _turned_by_hand(np.asarray(x), cos, sin),
                                   rtol=1e-5, atol=1e-6)
        if r < d:
            np.testing.assert_array_equal(got[..., r:], np.asarray(x)[..., r:])

    @pytest.mark.parametrize("part", ["out", "dx", "dscale"])
    @pytest.mark.parametrize("shape", [(48, 2, 256, 64), (40, 4, 128, 32), (32, 2, 64, 16),
                                       (300, 2, 256, 64)])
    def test_the_kernels_match_the_oracle_and_the_oracle_the_formula(self, shape, part):
        l, h, d, r = shape
        keys = jax.random.split(jax.random.PRNGKey(l), 3)
        x = jax.random.normal(keys[0], (2, l, h * d))
        scale = 1 + 0.2 * jax.random.normal(keys[1], (d,))
        ct = jax.random.normal(keys[2], (2, l, h * d))
        cos, sin = tr.rope_tables(l, r, 1e7)

        def both(op):
            y, vjp = jax.vjp(lambda x, s: op(x, s, cos, sin, num_heads=h, eps=1e-6), x, scale)
            return dict(zip(("out", "dx", "dscale"), (y, *vjp(ct))))

        got = both(lambda *a, **kw: head_norm_rope(*a, interpret=True, **kw))[part]
        want = both(head_norm_rope_reference)[part]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))
        if part == "out":
            y = np.asarray(x, np.float64).reshape(2, l, h, d)
            y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * np.asarray(scale, np.float64)
            np.testing.assert_allclose(np.asarray(want).reshape(2, l, h, d),
                                       _turned_by_hand(y, cos, sin), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("r", [258, 63])
    def test_tables_wider_than_the_head_or_odd_are_refused(self, r):
        with pytest.raises(ValueError, match="even R <= 256"):
            head_norm_rope(jnp.zeros((1, 16, 512)), jnp.ones((256,)), jnp.zeros((16, r)),
                           jnp.zeros((16, r)), num_heads=2, eps=1e-6)


# -- the layers against plain jax.numpy ---------------------------------------------
@pytest.fixture(scope="module")
def seeded():
    params = correct.init_params(REF.param_shapes(CFG), 4300001)
    # norm scales off their seeds, so that the (1 + w) form shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.3 if path[-1].key == "scale" else a, params)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, CFG["seq_len"], CFG["hidden_size"]))
    return {"params": params, "x": x}


class TestLayersAgainstPlainJnp:
    @pytest.mark.parametrize("impl", ["full", "blockwise"])
    def test_gated_attention_with_partial_rotary(self, seeded, impl, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        kw = CFG["model"]["kwargs"]
        layer = tr.SelfAttention(kw["num_heads"], kw["head_dim"], attn_impl=impl,
                                 num_kv_heads=kw["num_kv_heads"], qk_norm=True, gated=True,
                                 norm_unit_offset=True)
        p = seeded["params"]["block1"]["attn"]
        rope = tr.rope_tables(CFG["seq_len"], kw["rope_dim"], kw["rope_theta"])
        got = layer.apply({"params": p}, seeded["x"], rope=rope)
        want = REF._attn(p, seeded["x"], CFG, _PLAIN, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_the_gate_and_the_unit_offset_are_not_nothing(self, seeded):
        kw = CFG["model"]["kwargs"]
        p = seeded["params"]["block1"]["attn"]
        rope = tr.rope_tables(CFG["seq_len"], kw["rope_dim"], kw["rope_theta"])
        want = np.asarray(REF._attn(p, seeded["x"], CFG, _PLAIN, False))
        features = kw["num_heads"] * kw["head_dim"]
        for change in ({"gated": False}, {"norm_unit_offset": False}):
            flags = {"gated": True, "norm_unit_offset": True, **change}
            layer = tr.SelfAttention(kw["num_heads"], kw["head_dim"], attn_impl="full",
                                     num_kv_heads=kw["num_kv_heads"], qk_norm=True, **flags)
            q = p if flags["gated"] else {
                **p, "query": {"kernel": p["query"]["kernel"][:, :features]}}
            got = np.asarray(layer.apply({"params": q}, seeded["x"], rope=rope))
            assert np.abs(got - want).max() > 1e-3, change

    def test_the_linear_attention_mixer(self, seeded, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        layer = tr.GatedDeltaNet(**CFG["model"]["kwargs"]["linear_attention"])
        p = seeded["params"]["block0"]["deltanet"]
        got = layer.apply({"params": p}, seeded["x"])
        want = REF._linear(p, seeded["x"], CFG, _PLAIN, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)

    def test_causal_taps_are_the_references_and_causal(self):
        u = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 6))
        w = jax.random.normal(jax.random.PRNGKey(4), (4, 6))
        np.testing.assert_allclose(np.asarray(causal_taps(u, w)), np.asarray(REF._taps(u, w)),
                                   rtol=1e-6, atol=1e-6)
        moved = np.asarray(causal_taps(u.at[0, 7].add(1.0), w) - causal_taps(u, w))
        assert not moved[0, :7].any() and moved[0, 7:11].all() and not moved[0, 11:].any()

    def test_the_expert_layer_with_the_gated_shared_expert(self, seeded):
        kw = CFG["model"]["kwargs"]
        layer = MoEMLP(num_experts=kw["moe_experts"], top_k=kw["moe_top_k"],
                       **{**kw["moe_kwargs"], "held": tuple(kw["moe_kwargs"]["held"])})
        p = seeded["params"]["block1"]["moe"]
        got, upd = layer.apply({"params": p}, seeded["x"], mutable=["aux_loss", "counters", "gauges"])
        want, aux = REF._moe(p, seeded["x"], CFG, _PLAIN, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)
        np.testing.assert_allclose(float(jax.tree.leaves(upd["aux_loss"])[0]), float(aux), rtol=1e-5)
        # without the gate the shared expert counts whole
        ungated = MoEMLP(num_experts=kw["moe_experts"], top_k=kw["moe_top_k"],
                         **{**kw["moe_kwargs"], "held": tuple(kw["moe_kwargs"]["held"]),
                            "shared_token_gate": False})
        q = {k: v for k, v in p.items() if k != "shared_expert_gate"}
        assert np.abs(np.asarray(ungated.apply({"params": q}, seeded["x"],
                                               mutable=["aux_loss", "counters", "gauges"])[0])
                      - np.asarray(want)).max() > 1e-3

    @pytest.mark.parametrize("unit_offset", [False, True])
    def test_rms_norm_in_both_forms(self, unit_offset):
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 8))
        w = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (8,))
        norm = tr.RMSNorm(eps=1e-6, unit_offset=unit_offset)
        seed = norm.init(jax.random.PRNGKey(0), x)["params"]["scale"]
        np.testing.assert_array_equal(np.asarray(seed), np.full(8, 0.0 if unit_offset else 1.0))
        want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (w + unit_offset)
        np.testing.assert_allclose(np.asarray(norm.apply({"params": {"scale": w}}, x)),
                                   np.asarray(want), rtol=1e-6, atol=1e-6)


# -- the model against the plain reference ---------------------------------------
def _program_loss(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
    data = -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
    return data + (aux - jax.lax.stop_gradient(aux)), upd


def _rows(rng):
    rows = rng.integers(0, CFG["vocab_size"], (2, CFG["seq_len"] + 1))
    return jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes (a linear-attention layer and a
    gated full-attention layer, each with experts of which 4 of 8 are held and
    the gated shared expert), seeded weights, a batch, and loss and gradients
    both ways, the kernels in interpret mode."""
    params = correct.init_params(REF.param_shapes(CFG), 2147483999)
    x, y = _rows(np.random.default_rng(5))
    model = TransformerLM(**CFG["model"]["kwargs"])
    os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"
    try:
        got = jax.value_and_grad(lambda p: _program_loss(model, p, x, y)[0])(params)
    finally:
        del os.environ["TPUFRAME_PALLAS_INTERPRET"]
    want = jax.value_and_grad(REF.loss)(params, x, y, CFG)
    return {"params": params, "x": x, "y": y, "model": model, "got": got, "want": want}


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])
        assert LEAVES == _leaf_names(small["params"])

    def test_the_programs_own_seeds(self, small):
        p = small["model"].init(jax.random.PRNGKey(0), small["x"])["params"]
        net = p["block0"]["deltanet"]
        assert float(jnp.exp(net["A_log"]).max()) <= 16 and float(jnp.exp(net["A_log"]).min()) > 0
        np.testing.assert_array_equal(np.asarray(net["dt_bias"]), 1.0)
        np.testing.assert_array_equal(np.asarray(net["norm"]), 1.0)
        for leaf in (p["block0"]["ln1"], p["block1"]["attn"]["q_norm"], p["ln_f"]):
            np.testing.assert_array_equal(np.asarray(leaf["scale"]), 0.0)

    def test_loss(self, small):
        assert abs(float(small["got"][0]) - float(small["want"][0])) < 1e-5

    @pytest.mark.parametrize("leaf", LEAVES)
    def test_gradient_leaf(self, small, leaf):
        g, w = (correct.leaf_paths(small[side][1])[leaf] for side in ("got", "want"))
        assert float(jnp.linalg.norm(w)) > 0, leaf
        # (A_log and dt_bias: two numbers a leaf here, sums of ~1e-6 that cancel)
        assert float(jnp.linalg.norm(g - w)) < 3e-4 * float(jnp.linalg.norm(w)) + 2e-8, leaf

    def test_three_sgd_steps(self, small, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        m, p = small["model"], small["params"]
        r = p
        rng = np.random.default_rng(9)
        for _ in range(3):
            x, y = _rows(rng)
            g = jax.grad(lambda q: _program_loss(m, q, x, y)[0])(p)
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            gr = jax.grad(REF.loss)(r, x, y, CFG)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, gr)
        start = correct.leaf_paths(small["params"])
        for (name, a), b in zip(correct.leaf_paths(p).items(), jax.tree.leaves(r)):
            moved = float(jnp.linalg.norm(b - start[name]))
            assert moved > 0, name
            assert float(jnp.linalg.norm(a - b)) < 5e-4 * moved, name

    def test_counters_and_scopes(self, small, monkeypatch):
        from tpuframe.track.telemetry import get_telemetry

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        registry = get_telemetry().registry
        before = (registry.counter("deltanet/chunks").value, registry.counter("deltanet/calls").value)
        text = jax.jit(lambda p: small["model"].apply(
            {"params": p}, small["x"], mutable=["aux_loss", "counters", "gauges"])).lower(
            small["params"]).as_text(debug_info=True)
        # one linear-attention layer: 2 rows x 2 value heads x 1 chunk of 128, each way
        assert registry.counter("deltanet/chunks").value - before[0] == 2 * 2 * 2
        assert registry.counter("deltanet/calls").value - before[1] == 1
        for scope in ("tpuframe/deltanet", "tpuframe/deltanet/rule", "tpuframe/attn",
                      "tpuframe/moe/shared"):
            assert scope in text, scope
        _, upd = _program_loss(small["model"], small["params"], small["x"], small["y"])
        assert float(upd["counters"]["block0"]["moe"]["moe/assignments_here"]) > 0

    @pytest.mark.parametrize("fault", ["no_decay", "rotary_over_the_whole_head", "gate_left_out",
                                       "shared_expert_ungated", "plain_norms"])
    def test_a_fault_in_the_new_layers_is_seen(self, small, monkeypatch, fault):
        kw = dict(CFG["model"]["kwargs"])
        params = small["params"]
        if fault == "no_decay":
            real = tr.gated_delta
            monkeypatch.setattr(tr, "gated_delta",
                                lambda q, k, v, g, beta, **kws: real(q, k, v, 0 * g, beta, **kws))
        elif fault == "rotary_over_the_whole_head":
            kw["rope_dim"] = kw["head_dim"]
        elif fault == "gate_left_out":
            kw["attn_gated"] = False
            features = kw["num_heads"] * kw["head_dim"]
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: a[:, :features] if [k.key for k in path][-2:] == ["query", "kernel"]
                else a, params)
        elif fault == "shared_expert_ungated":
            kw["moe_kwargs"] = {**kw["moe_kwargs"], "shared_token_gate": False}
            params = {k: ({**v, "moe": {n: a for n, a in v["moe"].items()
                                        if n != "shared_expert_gate"}} if "moe" in v else v)
                      for k, v in params.items()}
        else:
            kw["norm_unit_offset"] = False
        got = float(_program_loss(TransformerLM(**kw), params, small["x"], small["y"])[0])
        assert abs(got - float(small["want"][0])) > 1e-4

    @pytest.mark.parametrize("kw, match", [
        ({"linear_attention": {}}, "takes its sizes"),
        ({"rope_dim": 18}, "odd or over"),
        ({"rope_dim": 3}, "odd or over"),
        ({"layer_types": ["linear_attention"]}, "names 1 layers of 2"),
    ])
    def test_what_it_cannot_build_is_refused(self, small, kw, match):
        model = TransformerLM(**{**CFG["model"]["kwargs"], **kw})
        with pytest.raises(ValueError, match=match):
            model.init(jax.random.PRNGKey(0), small["x"])


class TestSharesAddUpToTheUncutLayer:
    def test_the_four_shares_of_sixteen_experts_and_the_shared_expert_once(self):
        """Qwen3-Next's expert layer at a small width: 16 experts, 5 a token,
        softmax gates renormalised, a gated shared expert: the routed parts the
        four chips' 4 experts give, and the gated shared expert counted ONCE,
        add up to what the uncut reference gives; every chip computes the
        shared expert on its own rows, and a sum of the shares' whole outputs
        would count it four times."""
        d, e, h, k, held = 32, 16, 16, 5, 4
        uncut = {**CFG, "hidden_size": d, "moe_intermediate_size": h,
                 "shared_expert_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_tok": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(3), 9)
        n = lambda kk, *s: 0.3 * jax.random.normal(kk, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(key[0], d, e)},
             "w_gate": n(key[1], e, d, h), "w_in": n(key[2], e, d, h), "w_out": n(key[3], e, h, d),
             "shared_gate": {"kernel": n(key[4], d, h)}, "shared_in": {"kernel": n(key[5], d, h)},
             "shared_out": {"kernel": n(key[6], h, d)},
             "shared_expert_gate": {"kernel": n(key[7], d, 1)}}
        x = jax.random.normal(key[8], (2, 24, d), jnp.float32)
        want, _ = REF._moe(p, x, uncut, _PLAIN, False)
        shared = REF.shared_part(p, x, _PLAIN)
        routed, whole = jnp.zeros_like(x), jnp.zeros_like(x)
        for first in range(0, e, held):
            layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(first, held), gated=True,
                           shared_dim=h, shared_token_gate=True, aux_loss_weight=0.001,
                           capacity_factor=None)
            share = {**p, **{w: p[w][first:first + held] for w in ("w_gate", "w_in", "w_out")}}
            part = layer.apply({"params": share}, x, mutable=["aux_loss", "counters", "gauges"])[0]
            # the reference's own share is the program's
            ref_share = {**uncut, "num_experts": held, "held_first": first}
            np.testing.assert_allclose(np.asarray(part), np.asarray(
                REF._moe(share, x, ref_share, _PLAIN, False)[0]), rtol=2e-5, atol=2e-6)
            routed = routed + (part - shared)
            whole = whole + part
        np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                                   rtol=2e-5, atol=3e-6)
        assert np.abs(np.asarray(whole) - np.asarray(want)).max() > 1e-2


# -- sharding rules ----------------------------------------------------------------
@pytest.fixture(scope="module")
def placed():
    from tpuframe.parallel.compose import compose

    plan = compose(dp=2, fsdp=2, tp=2, zero_stage=3, min_shard_elems=1,
                   rules=transformer_tp_rules() + moe_rules())
    params = correct.init_params(REF.param_shapes(CFG), 7)
    shardings = plan.param_shardings(params)
    return {"plan": plan, "params": params,
            "specs": {k: s.spec for k, s in correct.leaf_paths(shardings).items()}}


class TestShardingRulesPlaceTheNewLeaves:
    @pytest.mark.parametrize("leaf", LEAVES)
    def test_every_leaf_has_a_spec_that_divides_it(self, placed, leaf):
        spec, shape = placed["specs"][leaf], correct.leaf_paths(placed["params"])[leaf].shape
        assert len(spec) <= len(shape)
        for size, entry in zip(shape, spec):
            names = () if entry is None else entry if isinstance(entry, tuple) else (entry,)
            assert size % int(np.prod([placed["plan"].axis_size(a) for a in names] or [1])) == 0

    @pytest.mark.parametrize("leaf, axis, dim", [
        ("block0/deltanet/out_proj/kernel", "model", 1),
        ("block1/attn/query/kernel", "model", 1),
        ("block1/attn/attn_out/kernel", "model", 0),
        ("block0/moe/w_gate", "expert", 0),
    ])
    def test_a_rule_names_the_leaf(self, placed, leaf, axis, dim):
        assert placed["specs"][leaf][dim] == axis

    @pytest.mark.parametrize("leaf", [
        "block0/deltanet/in_proj_qkvz/kernel", "block0/deltanet/in_proj_ba/kernel",
        "block0/deltanet/conv", "block0/deltanet/A_log", "block0/moe/router/kernel",
        "block0/moe/shared_expert_gate/kernel"])
    def test_what_a_head_reads_whole_stays_off_the_model_axis(self, placed, leaf):
        assert "model" not in jax.tree.leaves(tuple(placed["specs"][leaf]))


# -- the older configurations ------------------------------------------------------
@pytest.mark.parametrize("name, digest", [
    ("deepseek-v2-lite", "9ef30ed59827517aa2315c8e75be0c17f7f6f5cd4032f09b37db003e27ff2962"),
    ("sdar-30b-a3b-chat", "b2e5bddd8e0a687f4322a5b8a1cc3fdb827518dec58ab48555100aab35b03ad0"),
    ("lfm2-8b-a1b", "4f0e9ae9ad79049bcbb547f82be78402e9955df5ce96601a2462ee8c7f98024b"),
    ("mellum2-12b-a2.5b-instruct", "7269ae3fcccacf7193dab9f0f055033f0afc7bfbe5883d042d047c55e89778b5"),
    ("gpt2-medium", "5b88065afea35c114fa6b9f4aff4c17c9297d6b6aa62d2ec8f607b9cb1fad14c"),
])
def test_the_older_configurations_lower_to_the_parents_program(name, digest):
    """Every transformer configuration the benchmark had before this one, at
    its rehearsal sizes, loss and every gradient, lowers to the StableHLO that
    PR 42's commit lowers it to, byte for byte: the new mixer, gate, rotary
    width, norm form and shared expert's gate default to what those models
    are.  The digests were taken with this very function under jax 0.9.0 on
    PR 42's commit (the first two are `tests/test_lfm2.py`'s, which have held
    since PRs 32 and 35).  A change that means to alter those models' program
    takes them anew."""
    cfg = _config(name)
    model = getattr(models, cfg["model"]["class"])(**cfg["model"]["kwargs"])
    shape = (2, cfg["seq_len"], 3) if cfg["sample"] == "blockdiff" else (2, cfg["seq_len"])
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(shape, jnp.int32)))["params"]

    def objective(p, x):
        out, upd = model.apply({"params": p}, x, train=True,
                               mutable=["aux_loss", "counters", "gauges"])
        aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd.get("aux_loss", {})))
        return jnp.sum(out.astype(jnp.float32)) + aux, upd

    text = jax.jit(jax.value_and_grad(objective, has_aux=True)).lower(
        params, jax.ShapeDtypeStruct(shape, jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest
