"""Kimi-Linear's kinds of layer: the delta rule whose decay is a vector a head
(`tpuframe.ops.kda`) against the recurrence position by position, forward and
every gradient, at decays to -20 a position and mixed inside one head, and
against `ops.gated_delta` where the channels share one decay; the KDA mixer,
position-free latent attention and the sigmoid router's layer against plain
``jax.numpy``; the model with ``layer_types`` against the plain reference of
``chipbench/reference/kimi-linear-48b-a3b-instruct.py`` (loss, gradients,
three steps); the faults the rehearsal's ``loss_gap`` must refuse; the share
test for its expert layer; the sharding rules for the new leaves.  Small
sizes, on the CPU."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe.models import TransformerLM, moe_rules, transformer_tp_rules
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP
from tpuframe.ops.gated_delta import gated_delta_chunked

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b-instruct"
# by module path: tpuframe.ops re-exports the function under this name
kda_op = importlib.import_module("tpuframe.ops.kda")


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
PARTS = ("out", "dq", "dk", "dv", "dg", "dbeta")


# -- the op against the recurrence -------------------------------------------------
def _inputs(seed, decays, b=1, l=256, h=2, dk=128, dv=128):
    """q, k unit vectors a head (q scaled), v, ``g`` by regime, beta."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, l, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, l, h, dk)))
    v = jax.random.normal(ks[2], (b, l, h, dv))
    u = jax.random.uniform(ks[3], (b, l, h, dk))
    if decays == "mild":
        g = -0.5 * u
    elif decays == "to_minus_20":
        g = -20.0 * u ** 3
    else:
        # mixed inside one head: a channel in four forgets at once, one hardly at all
        rate = jnp.asarray([20.0, 1.0, 0.05, 0.001])[jnp.arange(dk) % 4]
        g = -rate * (0.5 + u)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, h)))
    return q, k, v, g, beta


def _both(fn, args, weight):
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4))(*args)
    return dict(zip(PARTS, (out, *grads)))


@pytest.fixture(scope="module", params=["mild", "to_minus_20", "mixed"])
def op_runs(request):
    args = _inputs(7, request.param)
    weight = jax.random.normal(jax.random.PRNGKey(11), args[2].shape)
    forms = {"recurrence": kda_op.kda_reference, "schedule": kda_op.kda_chunked,
             "kernels": lambda *a: kda_op.kda(*a, interpret=True)}
    return {name: _both(fn, args, weight) for name, fn in forms.items()}


LOCAL_PARTS = ("u", "w", "qe", "kd", "m", "gamma", "T")


@pytest.fixture(scope="module", params=["mild", "to_minus_20", "mixed"])
def local_runs(request):
    """The chunk-local part both ways: XLA's `_prepare` and its autodiff
    transpose, and the kernels (interpret mode) handed the same inputs,
    ``T`` and cotangents."""
    args = _inputs(7, request.param)
    want, t = kda_op._prepare(*args)
    got, t_got = kda_op._pallas_local_fwd(*args, True)
    d_parts = tuple(jax.random.normal(jax.random.PRNGKey(20 + i), a.shape)
                    for i, a in enumerate(want))
    transpose = jax.vjp(lambda *a: kda_op._prepare(*a, t=t)[0], *args)[1]
    return {"decays": request.param,
            "fwd": dict(zip(LOCAL_PARTS, (*got, t_got))),
            "again": dict(zip(LOCAL_PARTS, kda_op._pallas_local_again(*args, t_got, True))),
            "xla": dict(zip(LOCAL_PARTS, (*want, t))),
            "bwd": dict(zip(PARTS[1:], kda_op._pallas_local_bwd(*args, t, d_parts, True))),
            "xla_bwd": dict(zip(PARTS[1:], transpose(d_parts)))}


class TestTheChunkLocalKernelsAgainstPrepare:
    @pytest.mark.parametrize("part", LOCAL_PARTS)
    def test_the_six_parts_and_the_solve(self, local_runs, part):
        got, want = local_runs["fwd"][part], local_runs["xla"][part]
        assert got.shape == want.shape and got.dtype == want.dtype
        assert bool(jnp.isfinite(got).all())
        # (every gamma is 0 at the strongest decays: no quotient of two zeros)
        assert float(jnp.linalg.norm(got - want)) <= 2e-6 * float(jnp.linalg.norm(want)), part

    @pytest.mark.parametrize("part", LOCAL_PARTS[:-1])
    def test_handed_the_solve_it_makes_the_same_parts(self, local_runs, part):
        np.testing.assert_array_equal(np.asarray(local_runs["again"][part]),
                                      np.asarray(local_runs["fwd"][part]))

    @pytest.mark.parametrize("part", PARTS[1:])
    def test_the_transpose(self, local_runs, part):
        got, want = local_runs["bwd"][part], local_runs["xla_bwd"][part]
        assert got.shape == want.shape and got.dtype == want.dtype
        assert bool(jnp.isfinite(got).all())
        assert float(jnp.linalg.norm(got - want)) < 5e-6 * float(jnp.linalg.norm(want)), part


class TestTheOpAgainstTheRecurrence:
    @pytest.mark.parametrize("form", ["schedule", "kernels"])
    @pytest.mark.parametrize("part", PARTS)
    def test_outputs_and_all_five_gradients(self, op_runs, form, part):
        got, want = op_runs[form][part], op_runs["recurrence"][part]
        if part == "out":
            # a weighted sum of every output: relative to the outputs' size
            assert abs(float(got - want)) < 1e-4 * float(jnp.sqrt(want.size + 0.0)) + 1e-3
            return
        assert float(jnp.linalg.norm(got - want)) < 2e-4 * float(jnp.linalg.norm(want)), part

    def test_the_strongest_decays_reach_minus_20(self):
        assert float(_inputs(7, "to_minus_20")[3].min()) < -19.0
        g = _inputs(7, "mixed")[3]
        assert float(g[..., 0].min()) < -19.0 and float(g[..., 3].max()) > -0.002

    @pytest.mark.parametrize("decays", ["to_minus_20", "mixed"])
    def test_the_strongest_decays_stay_finite_inside_the_kernels_in_bfloat16(self, decays):
        """What the chip runs: bfloat16 rows, float32 ``g``; parts, ``T`` and
        the five cotangents finite and beside XLA's to bfloat16's rounding."""
        args = _inputs(7, decays, h=1)
        args = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
        want, t = kda_op._prepare(*args)
        got, t_got = kda_op._pallas_local_fwd(*args, True)
        d_parts = jax.tree.map(jnp.ones_like, want)
        grads = kda_op._pallas_local_bwd(*args, t, d_parts, True)
        xla = jax.vjp(lambda *a: kda_op._prepare(*a, t=t)[0], *args)[1](d_parts)
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        for a, b in zip((*got, t_got, *grads), (*want, t, *xla)):
            assert a.dtype == b.dtype and bool(jnp.isfinite(f32(a)).all())
            assert float(jnp.linalg.norm(f32(a) - f32(b))) <= 2e-2 * float(jnp.linalg.norm(f32(b)))

    @pytest.mark.parametrize("length", [200, 128, 384, 37])
    def test_a_ragged_row_is_padded_behind(self, length):
        args = _inputs(3, "mixed", l=length)
        want = kda_op.kda_reference(*args)
        for fn in (kda_op.kda_chunked, lambda *a: kda_op.kda(*a, interpret=True)):
            np.testing.assert_allclose(np.asarray(fn(*args)), np.asarray(want),
                                       rtol=2e-4, atol=2e-5)

    def test_bfloat16_inputs_keep_their_dtype_and_lie_near_float32(self):
        args = _inputs(5, "mild")
        narrow = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
        out = kda_op.kda(*narrow, interpret=True)
        assert out.dtype == jnp.bfloat16
        want = kda_op.kda_reference(*args)
        assert float(jnp.linalg.norm(out.astype(jnp.float32) - want)) < 3e-2 * float(
            jnp.linalg.norm(want))

    def test_heads_that_are_no_whole_lanes_take_the_scan_schedule(self):
        args = _inputs(9, "mild", l=128, dk=32, dv=16)
        text = jax.jit(kda_op.kda).lower(*args).as_text()
        assert "tpuframe_kda" not in text
        np.testing.assert_allclose(np.asarray(kda_op.kda(*args)),
                                   np.asarray(kda_op.kda_reference(*args)), rtol=2e-4, atol=2e-5)
        with pytest.raises(ValueError, match="are not"):
            kda_op.kda(*args[:3], args[3][..., 0], args[4])

    @pytest.mark.parametrize("name, which", [
        ("tpuframe_kda_fwd", 0), ("tpuframe_kda_bwd", 1), ("tpuframe_kdachunk_fwd", 2),
        ("tpuframe_kdachunk_again", 3), ("tpuframe_kdachunk_bwd", 4)])
    def test_tpu_lowering_carries_the_stable_kernel_name(self, name, which):
        args = _inputs(1, "mild", l=256, h=1)
        parts, t = kda_op._prepare(*args)
        if which == 0:
            text = jax.jit(lambda p: kda_op._pallas_fwd(p, False)).trace(parts).lower(
                lowering_platforms=("tpu",)).as_text()
        elif which >= 2:
            fn = (lambda *a: kda_op._pallas_local_fwd(*a, False),
                  lambda *a: kda_op._pallas_local_again(*a, t, False),
                  lambda *a: kda_op._pallas_local_bwd(*a, t, parts, False))[which - 2]
            text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
            # the pass's readers sum the names that start with tpuframe_kda_
            assert "tpuframe_kda_" not in text
        else:
            states = jnp.zeros((1, 1, 2, 128, 128), jnp.float32)
            text = jax.jit(lambda p, s, d: kda_op._pallas_bwd(p, s, d, False)).trace(
                parts, states, parts[0]).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text and f'kernel_name = "{name}"' in text

    def test_no_array_a_chunk_pair_and_channel_is_made(self):
        """The schedule's jaxpr holds no (.., C, C, dk) array, forward or
        backward: 8.6 GB a layer at the published sizes."""
        args = _inputs(2, "mild", l=256)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_op.kda_chunked(*a)),
                                        argnums=(0, 1, 2, 3, 4)))(*args)
        shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars}

        def walk(j):
            for eqn in j.eqns:
                for v in eqn.outvars:
                    shapes.add(tuple(v.aval.shape))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert not [s for s in shapes if s[-3:] == (128, 128, 128)]
        assert [s for s in shapes if s[-3:] == (16, 16, 128)]      # the diagonal sub-blocks are


class TestOneDecayAHeadIsTheGatedDeltaRule:
    @pytest.fixture(scope="class")
    def one_decay(self):
        q, k, v, g, beta = _inputs(4, "to_minus_20")
        args = (q, k, v, g[..., 0], beta)
        weight = jax.random.normal(jax.random.PRNGKey(12), v.shape)
        forms = {"schedule": kda_op.kda_chunked,
                 "kernels": lambda *a: kda_op.kda(*a, interpret=True)}
        got = {form: _both(lambda q, k, v, g1, beta, fn=fn: fn(
            q, k, v, jnp.broadcast_to(g1[..., None], q.shape), beta), args, weight)
            for form, fn in forms.items()}
        return got, _both(gated_delta_chunked, args, weight)

    @pytest.mark.parametrize("form", ["schedule", "kernels"])
    @pytest.mark.parametrize("part", PARTS)
    def test_equal_to_gated_delta_to_rounding(self, one_decay, part, form):
        """With ``g`` the same in every channel the rule is the one the
        benchmark holds (`ops.gated_delta`): outputs and gradients, the
        decay's summed over the channels; the scan schedule and the kernels."""
        got, want = one_decay[0][form], one_decay[1]
        if part == "out":
            assert abs(float(got[part] - want[part])) < 1e-3
            return
        assert float(jnp.linalg.norm(got[part] - want[part])) < 2e-4 * float(
            jnp.linalg.norm(want[part]))


# -- the layers against plain jax.numpy ---------------------------------------------
@pytest.fixture(scope="module")
def seeded():
    params = correct.init_params(REF.param_shapes(CFG), 5000001)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, CFG["seq_len"], CFG["hidden_size"]))
    return {"params": params, "x": x}


def _latent(impl="full"):
    kw = CFG["model"]["kwargs"]
    return tr.LatentAttention(kw["num_heads"], kw["head_dim"], kw["rope_dim"], kw["v_head_dim"],
                              kw["kv_lora_rank"], scale=(kw["head_dim"] + kw["rope_dim"]) ** -0.5,
                              norm_eps=kw["norm_eps"], attn_impl=impl)


class TestLayersAgainstPlainJnp:
    def test_the_kda_mixer(self, seeded, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        kw = CFG["model"]["kwargs"]
        layer = tr.KimiDeltaAttention(norm_eps=kw["norm_eps"], **kw["kda"])
        p = seeded["params"]["block0"]["kda"]
        got = layer.apply({"params": p}, seeded["x"])
        want = REF._kda(p, seeded["x"], CFG, _PLAIN, False)
        # (outputs of ~0.5: float32 roundings of the two schedules, 4e-6 at two of 32768)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_the_decay_is_a_number_a_channel_in_float32(self, seeded):
        p = seeded["params"]["block0"]["kda"]
        g = REF.decay(p, seeded["x"], CFG)
        assert g.shape == (2, CFG["seq_len"], 2, 128) and g.dtype == jnp.float32
        assert float(g.max()) < 0 and float(jnp.std(g[0, 0, 0])) > 0

    @pytest.mark.parametrize("impl", ["full", "blockwise"])
    def test_latent_attention_without_positions(self, seeded, impl, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        p = seeded["params"]["block1"]["attn"]
        got = _latent(impl).apply({"params": p}, seeded["x"])
        want = REF._mla(p, seeded["x"], CFG, _PLAIN, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)

    def test_no_positions_is_cosine_one_and_sine_zero(self, seeded):
        kw = CFG["model"]["kwargs"]
        p = seeded["params"]["block1"]["attn"]
        ones = jnp.ones((CFG["seq_len"], kw["rope_dim"]), jnp.float32)
        turned = _latent().apply({"params": p}, seeded["x"], (ones, 0 * ones))
        np.testing.assert_allclose(np.asarray(_latent().apply({"params": p}, seeded["x"])),
                                   np.asarray(turned), rtol=1e-6, atol=1e-7)
        # and real tables are another function: the layer has positions or it has none
        real = tr.rope_tables(CFG["seq_len"], kw["rope_dim"], 10000.0)
        assert np.abs(np.asarray(_latent().apply({"params": p}, seeded["x"], real))
                      - np.asarray(turned)).max() > 1e-4

    def test_the_expert_layer_sigmoid_bias_scale_shared_and_held_together(self, seeded):
        kw = CFG["model"]["kwargs"]
        layer = MoEMLP(num_experts=kw["moe_experts"], top_k=kw["moe_top_k"],
                       **{**kw["moe_kwargs"], "held": tuple(kw["moe_kwargs"]["held"])})
        p = seeded["params"]["block1"]["moe"]
        got, upd = layer.apply({"params": p}, seeded["x"], mutable=["aux_loss", "counters", "gauges"])
        want = REF._moe(p, seeded["x"], CFG, _PLAIN, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)
        assert float(upd["counters"]["moe/bias_moved_choices"]) > 0     # the bias chose


# -- the model against the plain reference ---------------------------------------
def _program_loss(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1)), upd


def _rows(rng):
    rows = rng.integers(0, CFG["vocab_size"], (2, CFG["seq_len"] + 1))
    return jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes (a KDA layer with the dense MLP, a
    position-free latent layer and a second KDA layer, each of the two with
    experts of which 4 of 8 are held and the shared expert), seeded weights, a
    batch, and loss and gradients both ways, the kernels in interpret mode."""
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


#: the selection bias enters ``top_k``'s argument alone: its gradient is zero both ways
FROZEN = [leaf for leaf in LEAVES if leaf.endswith("expert_bias")]


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])
        assert LEAVES == _leaf_names(small["params"])
        assert "pos_embed" not in got                     # no position table either

    def test_the_programs_own_seeds(self, small):
        p = small["model"].init(jax.random.PRNGKey(0), small["x"])["params"]
        net = p["block0"]["kda"]
        assert float(jnp.exp(net["A_log"]).max()) <= 16 and float(jnp.exp(net["A_log"]).min()) > 0
        np.testing.assert_array_equal(np.asarray(net["dt_bias"]), 1.0)
        np.testing.assert_array_equal(np.asarray(net["norm"]), 1.0)

    def test_loss(self, small):
        assert abs(float(small["got"][0]) - float(small["want"][0])) < 1e-5

    @pytest.mark.parametrize("leaf", [leaf for leaf in LEAVES if leaf not in FROZEN])
    def test_gradient_leaf(self, small, leaf):
        g, w = (correct.leaf_paths(small[side][1])[leaf] for side in ("got", "want"))
        assert float(jnp.linalg.norm(w)) > 0, leaf
        assert float(jnp.linalg.norm(g - w)) < 3e-4 * float(jnp.linalg.norm(w)) + 2e-8, leaf

    @pytest.mark.parametrize("leaf", FROZEN)
    def test_the_selection_bias_is_frozen(self, small, leaf):
        for side in ("got", "want"):
            assert not np.asarray(correct.leaf_paths(small[side][1])[leaf]).any()

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
            if name in FROZEN:
                continue
            moved = float(jnp.linalg.norm(b - start[name]))
            assert moved > 0, name
            assert float(jnp.linalg.norm(a - b)) < 5e-4 * moved, name

    def test_counters_scopes_and_the_verdict(self, small, monkeypatch):
        from tpuframe.track.telemetry import get_telemetry

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        registry = get_telemetry().registry
        before = (registry.counter("kda/chunks").value, registry.counter("kda/calls").value)
        text = jax.jit(lambda p: small["model"].apply(
            {"params": p}, small["x"], mutable=["aux_loss", "counters", "gauges"])).lower(
            small["params"]).as_text(debug_info=True)
        # two KDA layers: 2 rows x 2 heads x 2 chunks of 128, each way, a layer
        assert registry.counter("kda/chunks").value - before[0] == 2 * (2 * 2 * 2 * 2)
        assert registry.counter("kda/calls").value - before[1] == 2
        for scope in ("tpuframe/kda", "tpuframe/kda/rule", "tpuframe/kda/decay",
                      "tpuframe/kda/gate", "tpuframe/mla", "tpuframe/moe/shared"):
            assert scope in text, scope
        assert "tpuframe_kda_fwd" in text
        _, upd = _program_loss(small["model"], small["params"], small["x"], small["y"])
        assert float(upd["counters"]["block1"]["moe"]["moe/assignments_here"]) > 0

    @pytest.mark.parametrize("fault", ["one_decay_a_head", "silu_for_the_gates_sigmoid",
                                       "rotary_on_the_shared_key", "bias_in_the_weights",
                                       "routed_scale_left_out"])
    def test_a_fault_in_the_new_layers_is_refused_by_the_rehearsals_loss_gap(
            self, small, monkeypatch, fault):
        kw = dict(CFG["model"]["kwargs"])
        if fault == "one_decay_a_head":
            real = tr.kda
            monkeypatch.setattr(tr, "kda", lambda q, k, v, g, beta, **kws: real(
                q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta, **kws))
        elif fault == "silu_for_the_gates_sigmoid":
            def silu_gate(o, gate, scale, eps, dtype):
                o32 = o.astype(jnp.float32)
                o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
                return (o32 * scale * jax.nn.silu(gate.reshape(o.shape))).astype(dtype)

            monkeypatch.setattr(tr, "_kda_gate", silu_gate)
        elif fault == "rotary_on_the_shared_key":
            kw["nope"] = False
        elif fault == "bias_in_the_weights":
            monkeypatch.setattr(
                jnp, "take_along_axis",
                _biased_take(jnp.take_along_axis, small["params"]["block1"]["moe"]["expert_bias"]))
        else:
            kw["moe_kwargs"] = {**kw["moe_kwargs"], "routed_scale": 1.0}
        got = float(_program_loss(TransformerLM(**kw), small["params"], small["x"], small["y"])[0])
        limit = _config(NAME, rehearsal=False)["tolerance_rehearsal"]["loss_gap"]
        assert abs(got - float(small["want"][0])) > limit, fault

    @pytest.mark.parametrize("kw, match", [
        ({"kda": {}}, "takes its sizes"),
        ({"layer_types": ["kda"]}, "names 1 layers of 3"),
        ({"layer_types": ["kda", "mamba", "kda"]}, "known: .*linear_attention, kda"),
    ])
    def test_what_it_cannot_build_is_refused(self, small, kw, match):
        model = TransformerLM(**{**CFG["model"]["kwargs"], **kw})
        with pytest.raises(ValueError, match=match):
            model.init(jax.random.PRNGKey(0), small["x"])


def _biased_take(real, bias):
    """``take_along_axis`` that hands the sigmoid router's layer scores + bias
    where it asks for the scores of the chosen experts."""
    def take(arr, idx, axis=-1, **kw):
        if arr.ndim == 2 and arr.shape[-1] == bias.shape[0] and idx.shape[-1] == CFG[
                "num_experts_per_token"]:
            arr = arr + bias
        return real(arr, idx, axis=axis, **kw)

    return take


class TestSharesAddUpToTheUncutLayer:
    def test_the_32_shares_of_256_experts_and_the_shared_expert_once(self):
        """Kimi-Linear's expert layer at a small width: 256 experts, 8 a token,
        sigmoid scores chosen with a bias, renormalised and scaled by 2.446,
        one shared expert: the routed parts the 32 chips' 8 experts give, and
        the shared expert counted ONCE, add up to what the uncut reference
        gives; every chip computes the shared expert on its own rows, and a sum
        of the shares' whole outputs would count it 32 times."""
        d, e, h, k, held = 16, 256, 8, 8, 8
        uncut = {**CFG, "hidden_size": d, "moe_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_token": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(3), 9)
        n = lambda kk, *s: 0.3 * jax.random.normal(kk, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(key[0], d, e)}, "expert_bias": 0.1 * n(key[7], e),
             "w_gate": n(key[1], e, d, h), "w_in": n(key[2], e, d, h), "w_out": n(key[3], e, h, d),
             "shared_gate": {"kernel": n(key[4], d, h)}, "shared_in": {"kernel": n(key[5], d, h)},
             "shared_out": {"kernel": n(key[6], h, d)}}
        x = jax.random.normal(key[8], (2, 24, d), jnp.float32)
        want = REF._moe(p, x, uncut, _PLAIN, False)
        shared = REF.shared_part(p, x, _PLAIN)
        routed, whole = jnp.zeros_like(x), jnp.zeros_like(x)
        for first in range(0, e, held):
            layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(first, held), gated=True,
                           shared_dim=h, scoring="sigmoid", select_bias=True, routed_scale=2.446,
                           aux_loss_weight=0.0, capacity_factor=None)
            share = {**p, **{w: p[w][first:first + held] for w in ("w_gate", "w_in", "w_out")}}
            part = layer.apply({"params": share}, x, mutable=["aux_loss", "counters", "gauges"])[0]
            if first in (0, 128, 248):
                # the reference's own share is the program's
                ref_share = {**uncut, "num_experts": held, "held_first": first}
                np.testing.assert_allclose(np.asarray(part), np.asarray(
                    REF._moe(share, x, ref_share, _PLAIN, False)), rtol=2e-5, atol=2e-6)
            routed = routed + (part - shared)
            whole = whole + part
        np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                                   rtol=2e-5, atol=1e-5)
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
        ("block0/kda/out_proj/kernel", "model", 1),
        ("block1/attn/query/kernel", "model", 1),
        ("block1/attn/attn_out/kernel", "model", 0),
        ("block1/moe/w_gate", "expert", 0),
    ])
    def test_a_rule_names_the_leaf(self, placed, leaf, axis, dim):
        assert placed["specs"][leaf][dim] == axis

    @pytest.mark.parametrize("leaf", [
        "block0/kda/in_proj_qkv/kernel", "block0/kda/f_a/kernel", "block0/kda/f_b/kernel",
        "block0/kda/g_a/kernel", "block0/kda/g_b/kernel", "block0/kda/b_proj/kernel",
        "block0/kda/conv", "block0/kda/A_log", "block0/kda/dt_bias", "block1/moe/router/kernel",
        "block1/moe/expert_bias"])
    def test_what_a_head_reads_whole_stays_off_the_model_axis(self, placed, leaf):
        assert "model" not in jax.tree.leaves(tuple(placed["specs"][leaf]))
