"""The gated delta rule (`tpuframe.ops.gated_delta`): the chunked schedule and
the kernels in interpret mode against the recurrence position by position,
outputs and all five gradients, at lengths that are and are not whole chunks
and grid steps, with decays as strong as the published initialisation makes
them; the solve inside a chunk; the chunk-local kernels against `_prepare`
and its transpose; the dispatch.  Small sizes, on the CPU."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.ops.gated_delta import (
    chunks_walked,
    gated_delta,
    gated_delta_chunked,
    gated_delta_reference,
)

# the module, by path: ``tpuframe.ops`` rebinds the name to the function
gd = importlib.import_module("tpuframe.ops.gated_delta")

#: (rows, length, key heads, value heads, dk, dv, strongest decay rate)
SHAPES = {
    "one_chunk": (2, 128, 1, 2, 128, 128, 16.0),
    "under_a_chunk": (1, 40, 1, 1, 128, 128, 16.0),
    "ragged_chunks": (2, 200, 1, 2, 128, 128, 16.0),
    "one_grid_step": (1, 256, 2, 2, 128, 128, 1.0),
    "ragged_grid_steps": (1, 300, 1, 2, 128, 128, 16.0),
    "two_grid_steps_mild": (1, 512, 1, 1, 128, 128, 0.05),
}
FORMS = {"chunked": gated_delta_chunked,
         "kernels": functools.partial(gated_delta, interpret=True)}
INPUTS = ("q", "k", "v", "g", "beta")


def _inputs(seed, b, l, hk, h, dk, dv, rate, dtype=jnp.float32):
    """Inputs as `GatedDeltaNet` makes them: unit keys, queries scaled by
    ``dk^-1/2``, ``g = -A softplus(a + 1)`` with ``A`` up to ``rate`` a head
    (16: the published ``A_log`` at its strongest, ``g`` near -20 a position),
    ``beta`` a sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, l, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, l, hk, dk)))
    v = jax.random.normal(ks[2], (b, l, h, dv))
    a = jnp.linspace(rate / h, rate, h)
    g = -a * jax.nn.softplus(jax.random.normal(ks[3], (b, l, h)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, h)))
    ct = jax.random.normal(ks[5], (b, l, h, dv))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta), ct


@functools.lru_cache(maxsize=None)
def _all_forms(shape):
    args, ct = _inputs(len(shape), *SHAPES[shape])
    out = {}
    for name, op in {"oracle": gated_delta_reference, **FORMS}.items():
        fn = jax.value_and_grad(lambda *a, op=op: jnp.sum(op(*a) * ct), tuple(range(5)))
        out[name] = (op(*args), fn(*args)[1])
    return out


class TestAgainstTheRecurrence:
    @pytest.mark.parametrize("part", ["out", *INPUTS])
    @pytest.mark.parametrize("form", list(FORMS))
    @pytest.mark.parametrize("shape", list(SHAPES))
    def test_outputs_and_all_five_gradients(self, shape, form, part):
        forms = _all_forms(shape)
        pick = lambda f: f[0] if part == "out" else f[1][INPUTS.index(part)]  # noqa: E731
        got, want = np.asarray(pick(forms[form])), np.asarray(pick(forms["oracle"]))
        assert np.isfinite(got).all()
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * max(scale, 1e-6))

    @pytest.mark.parametrize("form", list(FORMS))
    def test_the_strongest_decays_neither_overflow_nor_lose_the_state(self, form):
        """``g`` of -20 a position is -2600 a chunk: a quotient of two
        exponentials would be inf / inf or 0 / 0; a difference taken before the
        exponential is a number.  And a head that hardly decays beside it
        still carries its state through every chunk."""
        (q, k, v, g, beta), ct = _inputs(7, 1, 320, 1, 2, 128, 128, 16.0)
        g = g.at[..., 0].set(-25.0).at[..., 1].set(-1e-4)
        op = FORMS[form]
        out, grads = jax.value_and_grad(
            lambda *a: jnp.sum(op(*a) * ct), tuple(range(5)))(q, k, v, g, beta)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in (out, *grads))
        want = gated_delta_reference(q, k, v, g, beta)
        np.testing.assert_allclose(np.asarray(op(q, k, v, g, beta)), np.asarray(want),
                                   rtol=2e-4, atol=2e-6)
        # the slow head's output at the last position still depends on the first value
        moved = op(q, k, v.at[0, 0].add(1.0), g, beta) - op(q, k, v, g, beta)
        assert float(jnp.abs(moved[0, -1, 1]).max()) > 1e-6
        assert float(jnp.abs(moved[0, -1, 0]).max()) == 0.0

    @pytest.mark.parametrize("form", ["oracle", *FORMS])
    @pytest.mark.parametrize("t", [0, 127, 128, 299])
    def test_changing_a_position_moves_no_output_before_it(self, form, t):
        op = {"oracle": gated_delta_reference, **FORMS}[form]
        (q, k, v, g, beta), _ = _inputs(3, 1, 300, 1, 1, 128, 128, 1.0)
        moved = np.asarray(op(q, k, v.at[0, t].add(1.0), g, beta) - op(q, k, v, g, beta))[0]
        assert not moved[:t].any() and moved[t].any()

    @pytest.mark.parametrize("form", list(FORMS))
    def test_rows_and_heads_keep_to_themselves(self, form):
        op = FORMS[form]
        (q, k, v, g, beta), _ = _inputs(4, 2, 160, 1, 2, 128, 128, 1.0)
        moved = np.asarray(op(q, k, v.at[0, :, 0].add(1.0), g, beta) - op(q, k, v, g, beta))
        assert moved[0, :, 0].any() and not moved[1].any() and not moved[0, :, 1].any()

    @pytest.mark.parametrize("form", list(FORMS))
    def test_a_key_head_serves_its_value_heads_in_a_row(self, form):
        """Key head ``h // 2`` serves value head ``h`` (repeat-interleave)."""
        (q, k, v, g, beta), _ = _inputs(5, 1, 80, 2, 4, 128, 128, 1.0)
        wide = FORMS[form](jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta)
        np.testing.assert_allclose(np.asarray(FORMS[form](q, k, v, g, beta)), np.asarray(wide),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("form", list(FORMS))
    def test_bfloat16_operands_stay_near_the_float32_recurrence(self, form):
        (q, k, v, g, beta), ct = _inputs(6, 1, 256, 1, 2, 128, 128, 2.0)
        want = np.asarray(gated_delta_reference(q, k, v, g, beta))
        narrow = [x.astype(jnp.bfloat16) for x in (q, k, v)]
        got = FORMS[form](*narrow, g, beta)
        assert got.dtype == jnp.bfloat16
        err = np.linalg.norm(np.asarray(got, np.float32) - want) / np.linalg.norm(want)
        assert err < 2e-2
        grads = jax.grad(lambda *a: jnp.sum(FORMS[form](*a).astype(jnp.float32) * ct),
                         (0, 1, 2, 3, 4))(*narrow, g, beta)
        wants = jax.grad(lambda *a: jnp.sum(gated_delta_reference(*a) * ct),
                         (0, 1, 2, 3, 4))(q, k, v, g, beta)
        for a, b in zip(grads, wants):
            a, b = np.asarray(a, np.float32), np.asarray(b)
            assert np.linalg.norm(a - b) / np.linalg.norm(b) < 5e-2


class TestTheSolveInsideAChunk:
    @pytest.mark.parametrize("keys", ["random", "all_alike"])
    def test_block_substitution_inverts_the_unit_lower_triangle(self, keys):
        """``all_alike``: every key of the chunk the same, ``beta`` 1, no
        decay: ``A`` is all ones under the diagonal, its powers grow to
        1e18, and the true inverse is the bidiagonal ``I - shift``."""
        n = gd._CHUNK
        if keys == "random":
            a = np.tril(np.random.default_rng(0).normal(0, 0.3, (3, n, n)), -1)
        else:
            a = np.tril(np.ones((1, n, n)), -1)
        got = np.asarray(gd._inv_unit_lower(jnp.asarray(a, jnp.float32)))
        want = np.linalg.inv(np.eye(n) + a)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        assert not np.triu(got, 1).any()

    def test_the_kept_solve_transposes_like_the_substitution(self):
        """``_solved`` hands the backward pass the forward's ``T`` and the
        inverse's own transpose, ``-T^T dT T^T``: what differentiating the
        substitution's levels gives."""
        n = 16
        a = jnp.asarray(np.tril(np.random.default_rng(1).normal(0, 0.4, (2, n, n)), -1), jnp.float32)
        ct = jax.random.normal(jax.random.PRNGKey(0), (2, n, n))
        want = jax.grad(lambda a: jnp.sum(gd._inv_unit_lower(a) * ct))(a)
        t = gd._inv_unit_lower(a)
        got = jax.grad(lambda a: jnp.sum(gd._solved(a, t) * ct))(a)
        # the substitution reads the strict lower triangle alone
        np.testing.assert_allclose(np.tril(np.asarray(got), -1), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_the_chunk_local_arrays_are_finite_at_the_strongest_decays(self):
        (q, k, v, g, beta), _ = _inputs(8, 1, 256, 1, 2, 128, 128, 16.0)
        parts, t = gd._prepare(q, k, v, g - 5.0, beta)
        assert all(bool(jnp.all(jnp.isfinite(p))) for p in (*parts, t))
        u, w, qe, kd, m, gamma = parts
        # a head's rows together: (B, H, L, width)
        assert u.shape == w.shape == qe.shape == kd.shape == (1, 2, 256, 128)
        assert m.shape == (1, 2, 256, gd._CHUNK) and gamma.shape == (1, 2, 2)
        assert t.shape == (1, 2, 2, gd._CHUNK, gd._CHUNK) and t.dtype == jnp.float32
        # every decay is at most 1: an exponential of a sum or difference <= 0
        assert float(jnp.max(gamma)) <= 1.0
        assert float(jnp.abs(m).max()) <= float(jnp.abs(q).max()) * 128 ** 0.5 + 1e-6


#: the chunk-local kernels' cases: (rows, length, key heads, value heads, dk, dv, rate)
CHUNK_CASES = {
    "one_chunk": (1, 128, 1, 1, 128, 128, 2.0),
    "three_grid_steps": (1, 1536, 1, 1, 128, 128, 1.0),
    "two_value_heads_a_key_head": (2, 256, 2, 4, 128, 128, 4.0),
    "keys_all_alike": (1, 256, 1, 2, 128, 128, 1.0),
    "strongest_decays": (1, 256, 1, 2, 128, 128, 16.0),
}
PARTS = ("u", "w", "qe", "kd", "m", "gamma", "T")


def _chunk_inputs(case, dtype=jnp.float32):
    (q, k, v, g, beta), _ = _inputs(21, *CHUNK_CASES[case], dtype=dtype)
    if case == "keys_all_alike":
        # every key of a chunk the same, beta 1, no decay: A is all ones under
        # the diagonal (what made the product-of-factors solve cancel)
        k, beta, g = jnp.broadcast_to(k[:, :1], k.shape), jnp.ones_like(beta), jnp.zeros_like(g)
    elif case == "strongest_decays":
        g = g - 5.0  # -20 and under a position for the fastest head
    return q, k, v, g, beta


def _cotangents(parts, seed=22):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(parts))
    return tuple(jax.random.normal(key, p.shape).astype(p.dtype) for key, p in zip(keys, parts))


def _xla_chunk_local(args, d_parts=None):
    """`_prepare`'s parts and ``T``, and its transpose with ``T`` handed back."""
    parts, t = gd._prepare(*args)
    d_parts = _cotangents(parts) if d_parts is None else d_parts
    grads = jax.vjp(lambda *a: gd._prepare(*a, t=t)[0], *args)[1](d_parts)
    return parts, t, d_parts, grads


@functools.lru_cache(maxsize=None)
def _chunk_local(case):
    args = _chunk_inputs(case)
    parts, t, d_parts, grads = _xla_chunk_local(args)
    got, got_t = gd._pallas_chunk_fwd(*args, True)
    return {"fwd": ((*got, got_t), (*parts, t)),
            "again": (gd._pallas_chunk_again(*args, t, True), parts),
            "bwd": (gd._pallas_chunk_bwd(*args, t, d_parts, True), grads)}


class TestTheChunkLocalKernels:
    """``tpuframe_delta_chunk_fwd`` / ``_again`` / ``_bwd`` in interpret mode
    against `_prepare` and ``jax.vjp(_prepare)``."""

    @staticmethod
    def _close(got, want, rtol, atol, floor=1e-6):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=rtol,
                                   atol=atol * max(float(np.abs(want).max()), floor))

    @pytest.mark.parametrize("part", PARTS)
    @pytest.mark.parametrize("case", list(CHUNK_CASES))
    def test_every_part_and_the_solve(self, case, part):
        got, want = (x[PARTS.index(part)] for x in _chunk_local(case)["fwd"])
        assert got.shape == want.shape and got.dtype == want.dtype
        self._close(got, want, 1e-5, 1e-6)

    @pytest.mark.parametrize("part", INPUTS)
    @pytest.mark.parametrize("case", list(CHUNK_CASES))
    def test_all_five_cotangents(self, case, part):
        got, want = (x[INPUTS.index(part)] for x in _chunk_local(case)["bwd"])
        assert got.shape == want.shape and got.dtype == want.dtype
        # dg is a difference of sums of terms the size of dq's (at the strongest
        # decays it is 1e-3 of them): its rounding goes with theirs
        floor = float(jnp.abs(_chunk_local(case)["bwd"][1][0]).max()) if part == "g" else 0.0
        self._close(got, want, 2e-4, 1e-4, floor)

    @pytest.mark.parametrize("case", list(CHUNK_CASES))
    def test_the_entry_that_is_handed_t_equals_the_one_that_solves(self, case):
        results = _chunk_local(case)
        for again, solved in zip(results["again"][0], results["fwd"][0]):
            np.testing.assert_array_equal(np.asarray(again), np.asarray(solved))

    def test_the_solve_of_keys_all_alike_is_the_bidiagonal(self):
        t = np.asarray(_chunk_local("keys_all_alike")["fwd"][0][-1])[0, 0, 0]
        want = np.eye(gd._CHUNK) - np.eye(gd._CHUNK, k=-1)
        np.testing.assert_allclose(t, want, atol=1e-4)

    @pytest.mark.parametrize("which", ["fwd", "bwd"])
    def test_bfloat16_inputs_stay_near_the_float32_form(self, which):
        wide = _chunk_inputs("two_value_heads_a_key_head")
        narrow = _chunk_inputs("two_value_heads_a_key_head", jnp.bfloat16)
        parts, t, d_parts, grads = _xla_chunk_local(wide)
        if which == "fwd":
            got, got_t = gd._pallas_chunk_fwd(*narrow, True)
            assert all(a.dtype == jnp.bfloat16 for a in got[:5]) and got_t.dtype == jnp.float32
            pairs = zip((*got, got_t), (*parts, t))
        else:
            d_narrow = tuple(d.astype(jnp.bfloat16) for d in d_parts[:5]) + d_parts[5:]
            got = gd._pallas_chunk_bwd(*narrow, t, d_narrow, True)
            assert [a.dtype for a in got] == [a.dtype for a in narrow]
            pairs = zip(got, grads)
        for a, b in pairs:
            a, b = np.asarray(a, np.float32), np.asarray(b)
            assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b)

    def test_the_numbers_run_along_the_lanes(self):
        """A number a position is (.., C) in HBM, never (.., C, 1): a key
        head's ``c`` rows, then its ``beta`` rows, a chunk."""
        _, _, _, g, beta = _chunk_inputs("two_value_heads_a_key_head")
        numbers, gamma = gd._numbers(g, beta, 2)
        assert numbers.shape == (2, 2, 2, 4, gd._CHUNK) and gamma.shape == (2, 4, 2)
        c = np.cumsum(np.asarray(g).reshape(2, 2, gd._CHUNK, 4), axis=2)
        np.testing.assert_allclose(np.asarray(numbers)[1, 1, 0, 1], c[1, 0, :, 3], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(numbers)[0, 1, 1, 2],
                                      np.asarray(beta)[0, gd._CHUNK:, 2])
        np.testing.assert_allclose(np.asarray(gamma)[1, 3, 0], np.exp(c[1, 0, -1, 3]), rtol=1e-6)


class TestDispatch:
    def test_the_chunk_local_kernels_are_in_the_lowered_step_once_for_three_layers(
            self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        (q, k, v, g, beta), _ = _inputs(9, 1, 256, 1, 2, 128, 128, 1.0)

        def three(q, k, v, g, beta):
            for _ in range(3):
                v = gated_delta(q, k, v, g, beta)
            return jnp.sum(v)

        text = jax.jit(jax.grad(three, (0, 1, 2, 3, 4))).lower(q, k, v, g, beta).as_text()
        for caller, most in (("_pallas_chunk_fwd", 2), ("_pallas_chunk_again", 1),
                             ("_pallas_chunk_bwd", 1)):
            assert len(re.findall(rf"func.func private @{caller}(_\d+)?\(", text)) <= most
            assert len(re.findall(rf"call @{caller}(_\d+)?\(", text)) == 3

    def test_the_kernels_are_in_the_lowered_step_once_for_three_layers(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        (q, k, v, g, beta), _ = _inputs(9, 1, 256, 1, 2, 128, 128, 1.0)

        def three(q, k, v, g, beta):
            for _ in range(3):
                v = gated_delta(q, k, v, g, beta)
            return jnp.sum(v)

        text = jax.jit(jax.grad(three, (0, 1, 2, 3, 4))).lower(q, k, v, g, beta).as_text()
        # the kernels' callers are jitted: a function in the module (one
        # trace, one lowering of the kernel inside it) called a layer, not a
        # copy a layer (the first layer's forward, whose operands are the
        # step's own arguments, may get a second)
        for caller, most in (("_pallas_fwd", 2), ("_pallas_bwd", 1)):
            assert len(re.findall(rf"func.func private @{caller}(_\d+)?\(", text)) <= most
            assert len(re.findall(rf"call @{caller}(_\d+)?\(", text)) == 3

    @pytest.mark.parametrize("dk, dv", [(64, 128), (128, 96)])
    def test_heads_that_are_no_whole_lanes_run_the_scan_schedule(self, dk, dv, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        (q, k, v, g, beta), _ = _inputs(10, 1, 96, 1, 2, dk, dv, 4.0)
        text = jax.jit(gated_delta).lower(q, k, v, g, beta).as_text()
        assert "_pallas_fwd" not in text
        np.testing.assert_allclose(np.asarray(gated_delta(q, k, v, g, beta)),
                                   np.asarray(gated_delta_reference(q, k, v, g, beta)),
                                   rtol=2e-4, atol=2e-6)

    def test_a_cpu_runs_the_scan_schedule(self):
        (q, k, v, g, beta), _ = _inputs(11, 1, 70, 1, 1, 128, 128, 4.0)
        text = jax.jit(gated_delta).lower(q, k, v, g, beta).as_text()
        assert "_pallas_fwd" not in text and "while" in text

    @pytest.mark.parametrize("bad", ["heads", "g", "length"])
    def test_shapes_that_do_not_go_together_are_refused(self, bad):
        (q, k, v, g, beta), _ = _inputs(12, 1, 64, 2, 4, 128, 128, 1.0)
        if bad == "heads":
            v, g, beta = v[:, :, :3], g[..., :3], beta[..., :3]
        elif bad == "g":
            g = g[..., :2]
        else:
            q = q[:, :32]
        with pytest.raises(ValueError, match="Hk dividing H"):
            gated_delta(q, k, v, g, beta)

    def test_one_verdict_event_a_shape_class_with_the_chunk_length(self, monkeypatch):
        from tpuframe.ops import dispatch
        from tpuframe.track import telemetry

        events = []
        monkeypatch.setattr(dispatch, "_VERDICT_EMITTED", set())
        monkeypatch.setattr(telemetry.get_telemetry(), "event",
                            lambda name, **attrs: events.append((name, attrs)))
        (q, k, v, g, beta), _ = _inputs(13, 1, 100, 1, 2, 128, 128, 1.0)
        for _ in range(2):
            gated_delta(q, k, v, g, beta, interpret=True)
        mine = [a for n, a in events if n == "ops/kernel_verdict" and a["op"] == "gated_delta"]
        assert len(mine) == 1
        assert mine[0]["shape_class"] == "c128_h2_l128" and mine[0]["enable"] is True

    def test_per_shard_on_a_mesh(self, monkeypatch):
        from tpuframe.core import MeshSpec

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        mesh = MeshSpec(data=4, fsdp=2).build()
        (q, k, v, g, beta), ct = _inputs(14, 8, 140, 1, 1, 128, 128, 2.0)
        loss = lambda op: lambda *a: jnp.sum(op(*a) * ct)  # noqa: E731
        sharded = lambda *a: gated_delta(*a, mesh=mesh)  # noqa: E731
        assert "call @_pallas_fwd(" in jax.jit(sharded).lower(q, k, v, g, beta).as_text()
        got = jax.jit(jax.grad(loss(sharded), (0, 1, 2, 3, 4)))(q, k, v, g, beta)
        want = jax.grad(loss(gated_delta_reference), (0, 1, 2, 3, 4))(q, k, v, g, beta)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                       atol=2e-5 * float(jnp.abs(b).max()))

    def test_the_chunk_steps_a_call_walks(self):
        # forward and backward, a chunk of 128 a head a row; a ragged row is padded
        assert chunks_walked(1, 8192, 32) == 2 * 32 * 64
        assert chunks_walked(2, 200, 4) == 2 * 2 * 4 * 2
