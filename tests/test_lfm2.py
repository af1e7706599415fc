"""LFM2's kinds of layer: the short-convolution op (kernels in interpret mode
against the oracle, causality), the sigmoid router with a selection bias, the
model with ``layer_types`` against the plain reference of
``chipbench/reference/lfm2-8b-a1b.py``, the share test for its expert layer,
and the sharding rules for the new leaves.  Small sizes, on the CPU."""

import functools
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
from tpuframe.models.moe import MoEMLP
from tpuframe.ops.short_conv import short_conv, short_conv_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-8b-a1b"


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


# -- the op ---------------------------------------------------------------------
#: (rows, length, width, taps, dtype): a tile multiple, two lengths that are
#: none (one under a tile, one over several), four taps, bfloat16
SHAPES = {
    "one_tile": (2, 64, 128, 3, jnp.float32),
    "ragged_short": (1, 200, 128, 3, jnp.float32),
    "ragged_tiles": (2, 600, 256, 3, jnp.float32),
    "four_taps": (1, 528, 128, 4, jnp.float32),
    "bf16": (2, 528, 128, 3, jnp.bfloat16),
}


@functools.lru_cache(maxsize=None)
def _both_forms(shape):
    """{form: (out, dB, dC, dh, dw)} of the op under one cotangent."""
    b, l, d, k, dtype = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(len(shape)), 3)
    x = jax.random.normal(keys[0], (b, l, 3 * d), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[1], (k, d), jnp.float32)
    g = jax.random.normal(keys[2], (b, l, d), jnp.float32).astype(dtype)
    out = {}
    for form, op in (("oracle", short_conv_reference),
                     ("kernels", lambda x, w: short_conv(x, w, interpret=True))):
        y, vjp = jax.vjp(op, x, w)
        dx, dw = vjp(g)
        out[form] = (y, dx[..., :d], dx[..., d:2 * d], dx[..., 2 * d:], dw)
    return out


class TestShortConvOp:
    @pytest.mark.parametrize("part", ["out", "dB", "dC", "dh", "dw"])
    @pytest.mark.parametrize("shape", list(SHAPES))
    def test_kernels_match_the_oracle(self, shape, part):
        forms = _both_forms(shape)
        i = ["out", "dB", "dC", "dh", "dw"].index(part)
        got, want = (np.asarray(forms[f][i], np.float32) for f in ("kernels", "oracle"))
        tol = 2e-2 if SHAPES[shape][4] == jnp.bfloat16 else 2e-6
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)

    def test_the_oracle_is_the_equation_by_hand(self):
        rng = np.random.default_rng(0)
        x, w = rng.standard_normal((2, 9, 12)), rng.standard_normal((3, 4))
        b, c, h = x[..., :4], x[..., 4:8], x[..., 8:]
        z = b * h
        want = np.zeros_like(z)
        for t in range(9):
            for j in range(3):
                if t - 2 + j >= 0:
                    want[:, t] += w[j] * z[:, t - 2 + j]
        got = short_conv_reference(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), c * want, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("t", [0, 1, 255, 256, 257, 599])
    @pytest.mark.parametrize("form", ["oracle", "kernels"])
    def test_changing_a_position_moves_no_output_before_it(self, form, t):
        op = short_conv_reference if form == "oracle" else (
            lambda x, w: short_conv(x, w, interpret=True))
        keys = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jax.random.normal(keys[0], (1, 600, 384), jnp.float32)
        w = jax.random.normal(keys[1], (3, 128), jnp.float32)
        moved = np.asarray(op(x.at[0, t].add(1.0), w) - op(x, w))[0]
        assert not moved[:t].any()
        # and the taps reach K - 1 positions on, no further; a row keeps to itself
        assert moved[t].any() and not moved[t + 3:].any()

    def test_rows_of_the_batch_keep_to_themselves(self):
        keys = jax.random.split(jax.random.PRNGKey(2), 2)
        x = jax.random.normal(keys[0], (2, 48, 384), jnp.float32)
        w = jax.random.normal(keys[1], (3, 128), jnp.float32)
        moved = np.asarray(short_conv(x.at[0].add(1.0), w, interpret=True)
                           - short_conv(x, w, interpret=True))
        assert moved[0].any() and not moved[1].any()

    @pytest.mark.parametrize("width, taps", [(96, 3), (128, 18)])
    def test_shapes_the_kernels_do_not_take_run_the_oracle(self, width, taps, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        x = jax.random.normal(keys[0], (1, 32, 3 * width), jnp.float32)
        w = jax.random.normal(keys[1], (taps, width), jnp.float32)
        text = jax.jit(short_conv).lower(x, w).as_text()
        assert "tpuframe_short_conv" not in text
        np.testing.assert_array_equal(np.asarray(short_conv(x, w)),
                                      np.asarray(short_conv_reference(x, w)))

    def test_a_wrong_width_is_refused(self):
        with pytest.raises(ValueError, match="3 \\* 128"):
            short_conv(jnp.zeros((1, 16, 256)), jnp.zeros((3, 128)))

    def test_per_shard_on_a_mesh(self, monkeypatch):
        from tpuframe.core import MeshSpec

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        mesh = MeshSpec(data=4, fsdp=2).build()
        keys = jax.random.split(jax.random.PRNGKey(4), 3)
        x = jax.random.normal(keys[0], (8, 32, 384), jnp.float32)
        w = jax.random.normal(keys[1], (3, 128), jnp.float32)
        g = jax.random.normal(keys[2], (8, 32, 128), jnp.float32)
        loss = lambda op: lambda x, w: jnp.sum(op(x, w) * g)  # noqa: E731
        got = jax.jit(jax.grad(loss(lambda x, w: short_conv(x, w, mesh=mesh)), (0, 1)))(x, w)
        want = jax.grad(loss(short_conv_reference), (0, 1))(x, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# -- the router -----------------------------------------------------------------
def _layer(**kw):
    base = dict(num_experts=8, top_k=3, capacity_factor=None, expert_dim=16, gated=True,
                held=(0, 4), scoring="sigmoid", select_bias=True, aux_loss_weight=0.0)
    return MoEMLP(**{**base, **kw})


@pytest.fixture(scope="module")
def routed():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    return {"layer": layer, "x": x, "params": {**params, "expert_bias": bias}}


def _by_hand(p, x, k, held, bias=True):
    """The source's router and a dense evaluation of the held experts."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, sel = jax.lax.top_k(s + p["expert_bias"] if bias else s, k)
    g = jnp.take_along_axis(s, sel, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    out = 0.0
    for i in range(held):
        gate = jnp.sum(jnp.where(sel == i, g, 0.0), -1, keepdims=True)
        out = out + gate * ((jax.nn.silu(x @ p["w_gate"][i]) * (x @ p["w_in"][i])) @ p["w_out"][i])
    return out, sel, g


class TestSigmoidRouter:
    def test_the_layer_is_the_source_router_by_hand(self, routed):
        got = routed["layer"].apply({"params": routed["params"]}, routed["x"])
        want, _, _ = _by_hand(routed["params"], routed["x"], 3, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_the_bias_changes_which_experts_are_chosen(self, routed):
        p, x = routed["params"], routed["x"]
        _, with_bias, _ = _by_hand(p, x, 3, 4)
        _, without, _ = _by_hand(p, x, 3, 4, bias=False)
        assert (np.sort(np.asarray(with_bias), -1) != np.sort(np.asarray(without), -1)).any()
        zero = {**p, "expert_bias": jnp.zeros(8)}
        assert not np.allclose(np.asarray(routed["layer"].apply({"params": p}, x)),
                               np.asarray(routed["layer"].apply({"params": zero}, x)))

    def test_and_never_the_weights_given_to_them(self, routed):
        # a bias that keeps every choice (the same for all experts) changes nothing
        p, x = routed["params"], routed["x"]
        lifted = {**p, "expert_bias": p["expert_bias"] + 0.37}
        np.testing.assert_array_equal(np.asarray(routed["layer"].apply({"params": p}, x)),
                                      np.asarray(routed["layer"].apply({"params": lifted}, x)))
        # and a chosen expert's weight is its score over the chosen scores' sum
        _, sel, g = _by_hand(p, x, 3, 4)
        s = jax.nn.sigmoid(x @ p["router"]["kernel"])
        picked = jnp.take_along_axis(s, sel, -1)
        np.testing.assert_allclose(np.asarray(g), np.asarray(
            picked / (picked.sum(-1, keepdims=True) + 1e-6)), rtol=1e-6)

    def test_its_gradient_is_exactly_zero(self, routed):
        layer, x = routed["layer"], routed["x"]
        g = jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x) ** 2))(routed["params"])
        assert not np.asarray(g["expert_bias"]).any()
        assert np.asarray(g["router"]["kernel"]).any() and np.asarray(g["w_in"]).any()

    def test_counters_say_how_many_choices_it_moved(self, routed):
        p, x = routed["params"], routed["x"]
        _, upd = routed["layer"].apply({"params": p}, x, mutable=["counters", "gauges"])
        _, with_bias, _ = _by_hand(p, x, 3, 4)
        _, without, _ = _by_hand(p, x, 3, 4, bias=False)
        moved = sum(int(e not in set(b)) for a, b in zip(
            np.asarray(with_bias).reshape(-1, 3), np.asarray(without).reshape(-1, 3)) for e in a)
        assert moved > 0
        assert float(upd["counters"]["moe/bias_moved_choices"]) == moved
        assert float(upd["counters"]["moe/bias_choices"]) == 2 * 24 * 3
        _, upd = routed["layer"].apply({"params": {**p, "expert_bias": jnp.zeros(8)}}, x,
                                       mutable=["counters", "gauges"])
        assert float(upd["counters"]["moe/bias_moved_choices"]) == 0

    def test_without_a_bias_there_is_no_leaf_and_no_counter(self, routed):
        layer = _layer(select_bias=False)
        variables = layer.init(jax.random.PRNGKey(1), routed["x"])
        assert "expert_bias" not in variables["params"]
        out, upd = layer.apply({"params": variables["params"]}, routed["x"],
                               mutable=["counters", "gauges", "aux_loss"])
        assert "moe/bias_moved_choices" not in upd["counters"] and "aux_loss" not in upd
        want, _, _ = _by_hand({**variables["params"], "expert_bias": 0.0}, routed["x"], 3, 4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_a_scale_on_the_routed_part(self, routed):
        p, x = routed["params"], routed["x"]
        np.testing.assert_allclose(
            np.asarray(_layer(routed_scale=2.5).apply({"params": p}, x)),
            2.5 * np.asarray(routed["layer"].apply({"params": p}, x)), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("kw, match", [
        ({"aux_loss_weight": 0.01}, "aux_loss_weight=0"),
        ({"scoring": "tanh"}, "softmax or sigmoid"),
        ({"scoring": "softmax"}, "select_bias is sigmoid"),
    ])
    def test_what_it_does_not_compute_is_refused(self, routed, kw, match):
        with pytest.raises(ValueError, match=match):
            _layer(**kw).init(jax.random.PRNGKey(0), routed["x"])

    @pytest.mark.parametrize("name, digest", [
        ("deepseek-v2-lite", "9ef30ed59827517aa2315c8e75be0c17f7f6f5cd4032f09b37db003e27ff2962"),
        ("sdar-30b-a3b-chat", "b2e5bddd8e0a687f4322a5b8a1cc3fdb827518dec58ab48555100aab35b03ad0"),
    ])
    def test_softmax_scoring_lowers_to_the_program_before_the_sigmoid_router(self, name, digest):
        """The two softmax configurations' models at their rehearsal sizes,
        loss and every gradient, lower to the StableHLO that the commit before
        the sigmoid router (PR 32's) lowers them to, byte for byte: the new
        scoring, bias and mixers touch nothing of theirs.  The digests were
        taken with this very function under jax 0.9.0 and this directory's
        conftest: ``deepseek-v2-lite``'s on PR 32's commit, and it has held
        since (PR 34 touched neither latent attention nor the expert layer);
        ``sdar-30b-a3b-chat``'s on PR 34's commit, whose attention calls
        `ops.head_norm_rope`'s oracle where it ran ``RMSNorm`` and
        ``apply_rope`` (on PR 32's commit it read ``697480dc...74e6``), and anew
        on PR 35's, which took four counters nobody read out of the model's
        outputs (``5a066652...5386`` before; loss and gradients alone lower to
        the same text on both commits).  A change that means to alter those
        models' program takes them anew."""
        cfg = _config(name)
        model = getattr(models, cfg["model"]["class"])(**cfg["model"]["kwargs"])
        shape = (2, cfg["seq_len"], 3) if cfg["sample"] == "blockdiff" else (2, cfg["seq_len"])
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(shape, jnp.int32)))["params"]

        def objective(p, x):
            out, upd = model.apply({"params": p}, x, train=True,
                                   mutable=["aux_loss", "counters", "gauges"])
            aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
            return jnp.sum(out.astype(jnp.float32)) + aux, upd

        text = jax.jit(jax.value_and_grad(objective, has_aux=True)).lower(
            params, jax.ShapeDtypeStruct(shape, jnp.int32)).as_text()
        assert "logistic" not in text
        assert hashlib.sha256(text.encode()).hexdigest() == digest


# -- the model against the plain reference ---------------------------------------
@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes (conv + dense MLP, attention +
    experts, conv + experts; 8 experts of which 4 held), seeded weights, a
    batch, and loss and gradients both ways, the kernels in interpret mode."""
    params = correct.init_params(REF.param_shapes(CFG), 2147483999)
    rng = np.random.default_rng(5)
    rows = rng.integers(0, CFG["vocab_size"], (2, CFG["seq_len"] + 1))
    x, y = jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)
    model = TransformerLM(**CFG["model"]["kwargs"])
    os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"
    try:
        got = jax.value_and_grad(lambda p: _program_loss(model, p, x, y)[0])(params)
    finally:
        del os.environ["TPUFRAME_PALLAS_INTERPRET"]
    want = jax.value_and_grad(REF.loss)(params, x, y, CFG)
    return {"params": params, "x": x, "y": y, "model": model, "got": got, "want": want}


def _program_loss(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1)), upd


def _bias_in_the_weights(self, logits, k):
    """The fault: a chosen expert weighed by score + bias, not by score."""
    import flax.linen as nn

    bias = self.param("expert_bias", nn.initializers.zeros, (logits.shape[-1],), jnp.float32)
    vals, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
    return vals / (jnp.sum(vals, -1, keepdims=True) + 1e-6), idx


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])
        assert LEAVES == _leaf_names(small["params"])

    def test_loss(self, small):
        assert abs(float(small["got"][0]) - float(small["want"][0])) < 1e-5

    @pytest.mark.parametrize("leaf", LEAVES)
    def test_gradient_leaf(self, small, leaf):
        g, w = (correct.leaf_paths(small[side][1])[leaf] for side in ("got", "want"))
        if leaf.endswith("expert_bias"):
            assert not np.asarray(g).any() and not np.asarray(w).any()
            return
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * float(jnp.linalg.norm(w)), leaf

    def test_three_sgd_steps_leave_the_bias_alone(self, small):
        m, p = small["model"], small["params"]
        r = p
        rng = np.random.default_rng(9)
        for _ in range(3):
            rows = rng.integers(0, CFG["vocab_size"], (2, CFG["seq_len"] + 1))
            x, y = jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)
            g = jax.grad(lambda q: _program_loss(m, q, x, y)[0])(p)
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            gr = jax.grad(REF.loss)(r, x, y, CFG)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, gr)
        start = correct.leaf_paths(small["params"])
        for (name, a), b in zip(correct.leaf_paths(p).items(), jax.tree.leaves(r)):
            moved = float(jnp.linalg.norm(b - start[name]))
            if name.endswith("expert_bias"):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(start[name]))
                continue
            assert moved > 0, name
            assert float(jnp.linalg.norm(a - b)) < 3e-4 * moved, name

    def test_counters_and_scope(self, small):
        _, upd = _program_loss(small["model"], small["params"], small["x"], small["y"])
        c = upd["counters"]
        assert float(c["block1"]["moe"]["moe/assignments_here"]) > 0
        assert float(c["block2"]["moe"]["moe/bias_choices"]) == 2 * CFG["seq_len"] * 3
        assert float(c["block1"]["moe"]["moe/bias_moved_choices"]) > 0
        assert "aux_loss" not in upd
        text = jax.jit(lambda p: small["model"].apply({"params": p}, small["x"])).lower(
            small["params"]).as_text(debug_info=True)
        assert "tpuframe/shortconv" in text

    @pytest.mark.parametrize("fault", ["taps_reversed", "bias_added_to_the_weights"])
    def test_a_fault_in_the_new_layers_is_seen(self, small, monkeypatch, fault):
        from tpuframe.models import moe, transformer

        if fault == "taps_reversed":
            real = transformer.short_conv
            monkeypatch.setattr(transformer, "short_conv",
                                lambda bch, w, **kw: real(bch, w[::-1], **kw))
        else:
            monkeypatch.setattr(moe.MoEMLP, "_sigmoid_choices", _bias_in_the_weights)
        got = float(_program_loss(small["model"], small["params"], small["x"], small["y"])[0])
        assert abs(got - float(small["want"][0])) > 1e-4

    @pytest.mark.parametrize("kw, match", [
        ({"layer_types": ["conv"]}, "names 1 layers of 3"),
        ({"layer_types": ["conv", "mamba", "conv"]}, "unknown mixer"),
    ])
    def test_layer_types_are_checked(self, small, kw, match):
        model = TransformerLM(**{**CFG["model"]["kwargs"], **kw})
        with pytest.raises(ValueError, match=match):
            model.init(jax.random.PRNGKey(0), small["x"])

    def test_no_layer_types_is_attention_in_every_layer(self, small):
        kw = {k: v for k, v in CFG["model"]["kwargs"].items() if k != "layer_types"}
        params = TransformerLM(**kw).init(jax.random.PRNGKey(0), small["x"])["params"]
        assert all("attn" in params[f"block{i}"] for i in range(3))


class TestSharesAddUpToTheUncutLayer:
    @pytest.mark.parametrize("bias", [0.0, 0.1])
    def test_the_four_shares_of_eight_experts(self, bias):
        """32 experts, 4 a token, sigmoid scores renormalised, a selection
        bias, no shared expert (nothing is counted once): the parts the four
        chips' 8 experts give add up to the uncut layer."""
        d, e, h, k = 32, 32, 16, 4
        uncut = {**CFG, "hidden_size": d, "moe_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_tok": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(3), 6)
        n = lambda kk, *s: 0.3 * jax.random.normal(kk, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(key[0], d, e)}, "expert_bias": bias / 0.3 * n(key[5], e),
             "w_gate": n(key[1], e, d, h), "w_in": n(key[2], e, d, h), "w_out": n(key[3], e, h, d)}
        x = jax.random.normal(key[4], (2, 24, d), jnp.float32)
        want = REF._moe(p, x, uncut, lambda f: f, False)
        total = jnp.zeros_like(x)
        for first in range(0, e, 8):
            layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(first, 8), gated=True,
                           scoring="sigmoid", select_bias=True, aux_loss_weight=0.0,
                           capacity_factor=None)
            share = {**p, **{w: p[w][first:first + 8] for w in ("w_gate", "w_in", "w_out")}}
            part = layer.apply({"params": share}, x)
            total = total + part
            # and the reference's own share is the program's
            held = {**uncut, "num_experts": 8, "held_first": first}
            np.testing.assert_allclose(np.asarray(part), np.asarray(
                REF._moe(share, x, held, lambda f: f, False)), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-5, atol=2e-6)


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
        ("block0/conv/out_proj/kernel", "model", 1),
        ("block2/conv/out_proj/kernel", "model", 1),
        ("block1/attn/query/kernel", "model", 1),
        ("block1/moe/w_gate", "expert", 0),
        ("block2/moe/w_in", "expert", 0),
    ])
    def test_a_rule_names_the_leaf(self, placed, leaf, axis, dim):
        assert placed["specs"][leaf][dim] == axis

    @pytest.mark.parametrize("leaf", ["block0/conv/in_proj/kernel", "block0/conv/w",
                                      "block1/moe/expert_bias", "block1/moe/router/kernel"])
    def test_what_scores_or_gates_whole_columns_stays_off_the_model_axis(self, placed, leaf):
        assert "model" not in str(placed["specs"][leaf]) and "expert" not in str(placed["specs"][leaf])

    def test_the_sharded_model_computes_the_same(self, placed):
        model = TransformerLM(**CFG["model"]["kwargs"])
        rows = np.random.default_rng(3).integers(0, CFG["vocab_size"], (4, CFG["seq_len"]))
        x = jnp.asarray(rows, jnp.int32)
        want = model.apply({"params": placed["params"]}, x)
        sharded = placed["plan"].shard_params(placed["params"])
        got = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
