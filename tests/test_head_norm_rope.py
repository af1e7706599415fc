"""Head norms and rotary positions in one op (`tpuframe.ops.head_norm_rope`):
the oracle against the composition it replaces (``RMSNorm`` on a
(B, L, H, D) view, then ``apply_rope``), the kernel pair in interpret mode
against the oracle, ``SelfAttention`` against the lines it ran before, and
the engage rule.  Small sizes, on the CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models.transformer import RMSNorm, SelfAttention, apply_rope, rope_tables
from tpuframe.ops import dispatch
from tpuframe.ops.head_norm_rope import head_norm_rope, head_norm_rope_reference
from tpuframe.ops.ring_attention import attention_reference

#: (rows, length, heads, head width, dtype, eps): the two cells' head shapes
#: (32 query heads over 4 or 8 key/value heads, 128 and 64 wide) at a few
#: positions, lengths that are no multiple of the row block (under one, over
#: several) or of a sublane tile, both epsilons, a head of two vregs
SHAPES = {
    "q128": (1, 64, 32, 128, jnp.float32, 1e-6),
    "k128": (2, 48, 4, 128, jnp.float32, 1e-6),
    "q64": (2, 32, 32, 64, jnp.float32, 1e-5),
    "k64": (1, 64, 8, 64, jnp.float32, 1e-5),
    "ragged_tiles_128": (1, 600, 4, 128, jnp.float32, 1e-6),
    "ragged_tiles_64": (2, 300, 8, 64, jnp.float32, 1e-5),
    "odd_length": (1, 37, 4, 128, jnp.float32, 1e-6),
    "bf16_128": (1, 528, 4, 128, jnp.bfloat16, 1e-6),
    "bf16_64": (2, 272, 8, 64, jnp.bfloat16, 1e-5),
    "wide_head": (1, 32, 2, 256, jnp.float32, 1e-6),
}
PARTS = ("out", "dx", "dscale", "dcos", "dsin")


def _kernels_in(fn, *args):
    """Whether ``fn`` calls the kernel pair (an interpret-mode kernel leaves
    no custom call in the lowered text; the jaxpr keeps its name)."""
    return "tpuframe_head_norm_rope" in str(jax.make_jaxpr(fn)(*args))


def _inputs(shape):
    b, l, h, d, dtype, eps = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(len(shape)), 3)
    x = jax.random.normal(keys[0], (b, l, h * d), jnp.float32).astype(dtype)
    scale = 1 + 0.3 * jax.random.normal(keys[1], (d,), jnp.float32)
    g = jax.random.normal(keys[2], (b, l, h * d), jnp.float32).astype(dtype)
    # the block-diffusion row: every position id twice
    cos, sin = rope_tables(l, d, 1e6, None, np.arange(l) // 2)
    return (x, scale, cos, sin), g, dict(num_heads=h, eps=eps)


def _composition(x, scale, cos, sin, *, num_heads, eps):
    """The lines the op replaces: the head norm rounds to the storage
    dtype, the rotation takes it up to float32 again and rounds again."""
    b, l, width = x.shape
    heads = x.reshape(b, l, num_heads, width // num_heads)
    normed = RMSNorm(eps=eps, dtype=x.dtype).apply({"params": {"scale": scale}}, heads)
    return apply_rope(normed, cos, sin).reshape(b, l, width)


@functools.lru_cache(maxsize=None)
def _forms(shape):
    """{form: (out, dx, dscale, dcos, dsin)} under one cotangent."""
    args, g, kw = _inputs(shape)
    out = {}
    for form, op in (("oracle", head_norm_rope_reference),
                     ("kernels", functools.partial(head_norm_rope, interpret=True)),
                     ("composition", _composition)):
        y, vjp = jax.vjp(functools.partial(op, **kw), *args)
        out[form] = (y,) + vjp(g)
    return out


class TestOracle:
    @pytest.mark.parametrize("part", ["out", "dx", "dscale"])
    @pytest.mark.parametrize("shape", ["q128", "k64", "ragged_tiles_64", "wide_head"])
    def test_float32_is_the_composition_it_replaces(self, shape, part):
        forms = _forms(shape)
        i = PARTS.index(part)
        got, want = (np.asarray(forms[f][i]) for f in ("oracle", "composition"))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())

    @pytest.mark.parametrize("shape", ["bf16_128", "bf16_64"])
    def test_bfloat16_is_one_rounding_from_the_float32_result(self, shape):
        """... and the composition's two roundings lie further from it."""
        args, _, kw = _inputs(shape)
        exact = np.asarray(head_norm_rope_reference(
            args[0].astype(jnp.float32), *args[1:], **kw))
        got = np.asarray(_forms(shape)["oracle"][0], np.float32)
        np.testing.assert_array_equal(
            got, np.asarray(jnp.asarray(exact).astype(jnp.bfloat16), np.float32))
        twice = np.asarray(_forms(shape)["composition"][0], np.float32)
        assert np.linalg.norm(got - exact) < np.linalg.norm(twice - exact)

    def test_it_is_the_equation_by_hand(self):
        rng = np.random.default_rng(0)
        x, scale = rng.standard_normal((2, 5, 3, 8)), rng.standard_normal(8)
        ang = rng.standard_normal((5, 4))
        cos, sin = (np.concatenate([f(ang), f(ang)], axis=-1) for f in (np.cos, np.sin))
        y = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * scale
        y1, y2 = y[..., :4], y[..., 4:]
        c, s = cos[None, :, None, :4], sin[None, :, None, :4]
        want = np.concatenate([y1 * c - y2 * s, y2 * c + y1 * s], axis=-1)
        got = head_norm_rope_reference(
            jnp.asarray(x.reshape(2, 5, 24), jnp.float32), jnp.asarray(scale, jnp.float32),
            jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32), num_heads=3, eps=1e-5)
        np.testing.assert_allclose(np.asarray(got).reshape(x.shape), want, rtol=2e-5, atol=2e-6)


class TestKernels:
    @pytest.mark.parametrize("part", ["out", "dx", "dscale"])
    @pytest.mark.parametrize("shape", list(SHAPES))
    def test_kernels_match_the_oracle(self, shape, part):
        forms = _forms(shape)
        i = PARTS.index(part)
        got, want = (np.asarray(forms[f][i], np.float32) for f in ("kernels", "oracle"))
        tol = 2e-2 if SHAPES[shape][4] == jnp.bfloat16 and part != "dscale" else 2e-6
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)

    @pytest.mark.parametrize("shape", ["k128", "bf16_64"])
    def test_the_tables_get_no_gradient(self, shape):
        for table in _forms(shape)["kernels"][3:]:
            assert table.dtype == jnp.float32 and not np.asarray(table).any()

    def test_a_bfloat16_scale_keeps_its_dtype(self):
        (x, scale, cos, sin), g, kw = _inputs("bf16_128")
        op = functools.partial(head_norm_rope, interpret=True, **kw)
        _, vjp = jax.vjp(lambda x, s: op(x, s, cos, sin), x, scale.astype(jnp.bfloat16))
        assert vjp(g)[1].dtype == jnp.bfloat16

    def test_rows_and_heads_keep_to_themselves(self):
        (x, scale, cos, sin), _, kw = _inputs("k64")
        op = functools.partial(head_norm_rope, interpret=True, **kw)
        moved = np.array(op(x.at[0, 9, 64:128].add(1.0), scale, cos, sin)
                         - op(x, scale, cos, sin))[0]
        assert moved[9, 64:128].any()
        moved[9, 64:128] = 0
        assert not moved.any()

    @pytest.mark.parametrize("heads, width", [(3, 64), (4, 32), (4, 96), (1, 192)])
    def test_shapes_the_kernels_do_not_take_run_the_oracle(self, heads, width, monkeypatch):
        """Rows that are no whole lanes, heads under 64 wide, heads that
        neither fill nor divide 128 lanes."""
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        x = jax.random.normal(keys[0], (1, 32, heads * width), jnp.float32)
        scale = jax.random.normal(keys[1], (width,), jnp.float32)
        cos, sin = rope_tables(32, width, 1e4)
        op = functools.partial(head_norm_rope, num_heads=heads, eps=1e-6)
        assert not _kernels_in(op, x, scale, cos, sin)
        np.testing.assert_array_equal(
            np.asarray(op(x, scale, cos, sin)),
            np.asarray(head_norm_rope_reference(x, scale, cos, sin, num_heads=heads, eps=1e-6)))

    @pytest.mark.parametrize("bad", ["heads", "scale", "tables"])
    def test_wrong_shapes_are_refused(self, bad):
        x, scale, table = jnp.zeros((1, 16, 256)), jnp.zeros(128), jnp.zeros((16, 128))
        if bad == "heads":
            args, heads = (x, scale, table, table), 3
        elif bad == "scale":
            args, heads = (x, jnp.zeros(64), table, table), 2
        else:
            args, heads = (x, scale, table, jnp.zeros((8, 128))), 2
        with pytest.raises(ValueError, match="x \\(1, 16, 256\\)"):
            head_norm_rope(*args, num_heads=heads, eps=1e-6)

    def test_per_shard_on_a_mesh(self, monkeypatch):
        from tpuframe.core import MeshSpec

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        mesh = MeshSpec(data=4, fsdp=2).build()
        keys = jax.random.split(jax.random.PRNGKey(4), 3)
        x = jax.random.normal(keys[0], (8, 32, 256), jnp.float32)
        scale = jax.random.normal(keys[1], (64,), jnp.float32)
        g = jax.random.normal(keys[2], (8, 32, 256), jnp.float32)
        cos, sin = rope_tables(32, 64, 1e4)
        kw = dict(num_heads=4, eps=1e-6)
        loss = lambda op: lambda x, s: jnp.sum(op(x, s, cos, sin, **kw) * g)  # noqa: E731
        fused = jax.jit(jax.grad(loss(functools.partial(head_norm_rope, mesh=mesh)), (0, 1)))
        assert _kernels_in(fused, x, scale)
        for a, b in zip(fused(x, scale), jax.grad(loss(head_norm_rope_reference), (0, 1))(x, scale)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# -- the module -----------------------------------------------------------------
def _attention(dtype=jnp.float32, **kw):
    base = dict(num_heads=4, head_dim=64, num_kv_heads=2, qk_norm=True, norm_eps=1e-5,
                attn_impl="full", dtype=dtype)
    return SelfAttention(**{**base, **kw})


def _parents_lines(module, params, x, rope):
    """``SelfAttention.__call__`` as it stood before the op: the
    projections on a (B, L, H, D) view, a norm module a projection, then
    ``apply_rope``."""
    b, l, _ = x.shape
    h, kv, d = module.num_heads, module.num_kv_heads, module.head_dim
    dense = lambda name, heads: (  # noqa: E731
        x.astype(module.dtype) @ params[name]["kernel"].astype(module.dtype)
    ).reshape(b, l, heads, d)
    q, k, v = dense("query", h), dense("key", kv), dense("value", kv)
    norm = RMSNorm(eps=module.norm_eps, dtype=module.dtype)
    q = apply_rope(norm.apply({"params": params["q_norm"]}, q), *rope)
    k = apply_rope(norm.apply({"params": params["k_norm"]}, k), *rope)
    out = attention_reference(q, k, v, causal=True).reshape(b, l, h * d)
    return out @ params["attn_out"]["kernel"].astype(module.dtype)


@pytest.fixture(scope="module")
def attention():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 96), jnp.float32)
    rope = rope_tables(32, 64, 1e6, None, np.arange(32) // 2)
    module = _attention()
    params = module.init(jax.random.PRNGKey(1), x, rope=rope)["params"]
    params = jax.tree.map(lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(2), p.shape),
                          params)
    return module, params, x, rope


class TestSelfAttention:
    def test_the_parameter_tree_is_the_one_before(self, attention):
        _, params, _, _ = attention
        shapes = jax.tree.map(lambda p: p.shape, params)
        assert shapes == {
            "query": {"kernel": (96, 256)}, "key": {"kernel": (96, 128)},
            "value": {"kernel": (96, 128)}, "attn_out": {"kernel": (256, 96)},
            "q_norm": {"scale": (64,)}, "k_norm": {"scale": (64,)}}
        unfused = _attention().init(jax.random.PRNGKey(1), attention[2])["params"]
        assert jax.tree.structure(unfused) == jax.tree.structure(params)

    @pytest.mark.parametrize("interpret", [False, True], ids=["oracle", "kernels"])
    def test_output_and_gradients_are_the_parents(self, attention, interpret, monkeypatch):
        module, params, x, rope = attention
        if interpret:
            monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        co = jax.random.normal(jax.random.PRNGKey(3), x.shape)
        got = jax.value_and_grad(
            lambda p: jnp.sum(module.apply({"params": p}, x, rope=rope) * co))(params)
        assert _kernels_in(lambda p: module.apply({"params": p}, x, rope=rope), params) is interpret
        want = jax.value_and_grad(
            lambda p: jnp.sum(_parents_lines(module, p, x, rope) * co))(params)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                       atol=2e-5 * float(jnp.abs(b).max()))

    def test_bfloat16_is_within_a_rounding_of_the_parents(self, attention):
        _, params, x, rope = attention
        module = _attention(jnp.bfloat16)
        got = np.asarray(module.apply({"params": params}, x, rope=rope), np.float32)
        want = np.asarray(_parents_lines(module, params, x, rope), np.float32)
        assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want)

    @pytest.mark.parametrize("qk_norm, with_rope", [(False, True), (True, False), (False, False)])
    def test_one_of_the_two_alone_keeps_its_lines(self, attention, qk_norm, with_rope,
                                                  monkeypatch):
        """No head norms, or no rotary positions: the module never asks the
        op, kernels or not."""
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        dispatch._VERDICT_EMITTED.clear()
        _, _, x, rope = attention
        module = _attention(qk_norm=qk_norm)
        rope = rope if with_rope else None
        params = module.init(jax.random.PRNGKey(1), x, rope=rope)["params"]
        assert ("q_norm" in params) is qk_norm
        assert not _kernels_in(lambda p: module.apply({"params": p}, x, rope=rope), params)
        assert not [k for k in dispatch._VERDICT_EMITTED if k[0] == "head_norm_rope"]


# -- the engage rule --------------------------------------------------------------
@pytest.mark.parametrize("env, kernels, source", [
    ({}, False, "default"),                                    # a CPU
    ({"TPUFRAME_PALLAS_INTERPRET": "1"}, True, "default"),
    ({"TPUFRAME_PALLAS_INTERPRET": "1", "TPUFRAME_DISABLE_PALLAS": "1"}, False, "forced"),
])
def test_one_verdict_event_a_decision(env, kernels, source, monkeypatch, tmp_path):
    from tpuframe.track import telemetry as T

    for knob in ("TPUFRAME_PALLAS_INTERPRET", "TPUFRAME_DISABLE_PALLAS"):
        monkeypatch.delenv(knob, raising=False)
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    (x, scale, cos, sin), _, kw = _inputs("k64")
    dispatch._VERDICT_EMITTED.clear()
    tele = T.configure(str(tmp_path / "events.jsonl"))
    try:
        op = functools.partial(head_norm_rope, **kw)
        for _ in range(3):
            assert _kernels_in(op, x, scale, cos, sin) is kernels
        (event,) = [e for e in tele.recent_events(50) if e["name"] == "ops/kernel_verdict"]
        assert (event["op"], event["shape_class"]) == ("head_norm_rope", "d64_h8_l64")
        assert event["enable"] is kernels and event["source"] == source
        np.testing.assert_allclose(
            np.asarray(op(x, scale, cos, sin)),
            np.asarray(head_norm_rope_reference(x, scale, cos, sin, **kw)), rtol=1e-5, atol=1e-6)
    finally:
        T.reset()
        dispatch._VERDICT_EMITTED.clear()
