"""Blockwise (flash-style) single-device attention: exactness against
the full-softmax oracle for outputs AND gradients, block-size edge
cases, numerical stability at large logits, and TransformerLM wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.ops import blockwise_attention
from tpuframe.ops.ring_attention import attention_reference


def _qkv(b=2, l=64, h=4, d=8, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, l, h, d)) * scale, jnp.float32
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [16, 64, 512])
def test_matches_full_attention(causal, block_size):
    q, k, v = _qkv()
    want = attention_reference(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_size=block_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_full(causal):
    q, k, v = _qkv(l=32)

    def loss_full(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    def loss_blk(q, k, v):
        return jnp.sum(
            blockwise_attention(q, k, v, causal=causal, block_size=8) ** 2
        )

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_full, g_blk):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("l", [48, 13, 100])
@pytest.mark.parametrize("causal", [False, True])
def test_indivisible_lengths_pad_and_mask(l, causal):
    """Non-multiple (incl. prime) lengths pad up to the block size —
    padded keys masked, padded query rows sliced — and stay exact."""
    q, k, v = _qkv(l=l)
    got = blockwise_attention(q, k, v, causal=causal, block_size=16)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("l", [13, 100])
@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_on_padded_lengths(l, causal):
    """The hand-written backward must honor the kv_len padding mask: its
    _tile_grads recomputes probabilities itself (unlike the former
    autodiff backward, correct by construction), so padded-key columns
    and sliced-off query rows need explicit gradient coverage."""
    q, k, v = _qkv(l=l, seed=3)

    def loss_full(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 3)

    def loss_blk(q, k, v):
        return jnp.sum(
            blockwise_attention(q, k, v, causal=causal, block_size=16) ** 3
        )

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_full, g_blk):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), atol=5e-4)


def test_gradients_bf16_close_to_f32_oracle():
    """bf16 inputs flow through the backward's p/ds downcasts; gradients
    must track the f32 oracle within bf16 resolution."""
    qf, kf, vf = _qkv(l=40, seed=4, scale=0.5)
    q, k, v = (a.astype(jnp.bfloat16) for a in (qf, kf, vf))

    def loss_blk(q, k, v):
        out = blockwise_attention(q, k, v, causal=True, block_size=16)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(qf, kf, vf)
    for got, want in zip(g_blk, g_full):
        assert got.dtype == jnp.bfloat16  # grads come back in storage dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), atol=0.05, rtol=0.05
        )


def test_bf16_inputs_stay_bf16_out():
    q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(l=32))
    got = blockwise_attention(q, k, v, causal=True, block_size=8)
    assert got.dtype == jnp.bfloat16
    want = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.05
    )


def test_large_logits_no_overflow():
    # logits ~ +-200: exp() would overflow f32 (max ~exp(88)) without the
    # running-max subtraction; larger scales make softmax a knife-edge
    # argmax where fp tie-breaks differ legitimately between schedules
    q, k, v = _qkv(l=32, scale=8.0)
    got = blockwise_attention(q, k, v, causal=True, block_size=8)
    assert np.isfinite(np.asarray(got)).all()
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3
    )


def test_mismatched_shapes_rejected():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="must match"):
        blockwise_attention(q, k[:, :32], v)


def test_transformer_lm_blockwise_trains():
    import optax

    from tpuframe.models import TransformerLM
    from tpuframe.train import create_train_state, make_train_step

    model = TransformerLM(
        vocab_size=32, num_layers=2, num_heads=4, head_dim=8, max_len=64,
        attn_impl="blockwise",
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 32, (8, 64)).astype(np.int32)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.asarray(toks[:1]), optax.adam(1e-3)
    )
    step = make_train_step()
    losses = []
    for _ in range(5):
        state, m = step(
            state,
            {"input": jnp.asarray(toks), "label": jnp.asarray(np.roll(toks, -1, 1))},
        )
        losses.append(float(m["loss_sum"] / m["count"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_unknown_attn_impl_rejected():
    from tpuframe.models import TransformerLM

    model = TransformerLM(
        vocab_size=16, num_layers=1, num_heads=2, head_dim=4, max_len=8,
        attn_impl="flashy",
    )
    toks = jnp.zeros((1, 8), jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, toks, train=False)
    with pytest.raises(ValueError, match="unknown attn_impl"):
        model.apply(variables, toks, train=False)


def test_auto_picks_blockwise_for_long_unsharded_seq(monkeypatch):
    """attn_impl='auto' must route long single-shard sequences through the
    linear-memory path instead of materializing (B,H,L,L)."""
    from tpuframe.core import runtime as rt

    rt.reset_runtime()  # a leaked seq-sharded mesh would dispatch to ring
    import tpuframe.models.transformer as tr

    calls = []
    real = tr.attention_reference

    def spy_full(q, k, v, causal=False):
        calls.append("full")
        return real(q, k, v, causal=causal)

    # `tpuframe.ops.blockwise_attention` the attribute is the FUNCTION
    # (ops/__init__ rebinds the name); fetch the module itself
    import importlib

    bw = importlib.import_module("tpuframe.ops.blockwise_attention")
    real_blk = bw.blockwise_attention

    def spy_blk(q, k, v, **kw):
        calls.append("blockwise")
        return real_blk(q, k, v, **kw)

    monkeypatch.setattr(tr, "attention_reference", spy_full)
    monkeypatch.setattr(bw, "blockwise_attention", spy_blk)
    monkeypatch.setattr(tr, "_BLOCKWISE_AUTO_LEN", 64)  # keep the test small

    from tpuframe.models import TransformerLM

    model = TransformerLM(
        vocab_size=16, num_layers=1, num_heads=2, head_dim=4, max_len=128,
        attn_impl="auto",
    )
    long_toks = jnp.zeros((1, 128), jnp.int32)
    short_toks = jnp.zeros((1, 16), jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, short_toks,
                           train=False)
    calls.clear()
    model.apply(variables, long_toks, train=False)
    assert "blockwise" in calls and "full" not in calls
    calls.clear()
    model.apply(variables, short_toks, train=False)
    assert "full" in calls and "blockwise" not in calls


# -- the Pallas flash kernels (interpret mode: the kernels' own code on CPU) --

import importlib  # noqa: E402

bw = importlib.import_module("tpuframe.ops.blockwise_attention")


def _wide_qkv(l, d, dv, dtype, b=1, h=2, seed=0, scale=0.5):
    rng = np.random.default_rng(seed)
    mk = lambda w: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, l, h, w)) * scale, dtype
    )
    return mk(d), mk(d), mk(dv)


def _f32(a):
    return np.asarray(a, np.float32)


def _kernel(causal, **kw):
    return lambda q, k, v: blockwise_attention(
        q, k, v, causal=causal, block_size=128, interpret=True, **kw
    )


def _grads(fn, q, k, v):
    return jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2)
    )(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("l", [256, 300], ids=["blocks", "indivisible"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("widths", [(192, 128), (128, 128), (64, 64)],
                         ids=["192x128", "128x128", "64x64"])
def test_kernel_matches_full_attention(widths, causal, l, dtype):
    """Forward and all three gradients of the kernels against the dense
    oracle in float32, at the head widths the kernels must take."""
    d, dv = widths
    q, k, v = _wide_qkv(l, d, dv, dtype)
    exact = [a.astype(jnp.float32) for a in (q, k, v)]
    ref = lambda q, k, v: attention_reference(q, k, v, causal=causal)  # noqa: E731
    tol = dict(atol=2e-5, rtol=1e-5) if dtype == jnp.float32 else dict(atol=0.03, rtol=0.03)
    got = _kernel(causal)(q, k, v)
    assert got.dtype == dtype and got.shape == (1, l, 2, dv)
    np.testing.assert_allclose(_f32(got), _f32(ref(*exact)), **tol)
    gtol = dict(atol=5e-4) if dtype == jnp.float32 else dict(atol=0.06, rtol=0.06)
    for a, b_, x in zip(_grads(_kernel(causal), q, k, v), _grads(ref, *exact), (q, k, v)):
        assert a.dtype == x.dtype and a.shape == x.shape
        np.testing.assert_allclose(_f32(a), _f32(b_), **gtol)


@pytest.mark.parametrize("shape, how, layout", [
    ((1, 512, 8, 2, 128, 128), "mask", (4, 11)),    # sdar's: four heads a block, one K/V head
    ((2, 256, 4, 4, 64, 64), "causal", (2, 11)),    # gpt2's: two heads a block of lanes
    ((1, 256, 8, 2, 64, 64), "causal", (2, 11)),    # lfm2's: a group shares half a K/V block
    ((1, 256, 4, 4, 32, 32), "bidir", (4, 11)),     # four heads a block
    ((1, 256, 2, 2, 192, 128), "causal", (1, 5)),   # deepseek's: rows and copies in one call
    ((1, 256, 3, 3, 64, 64), "causal", (1, 0)),     # heads in no whole blocks: copies
    ((1, 256, 6, 6, 128, 128), "causal", (2, 11)),  # whole-lane heads two a block, each its K/V
    ((2, 768, 8, 2, 128, 128), "causal", (4, 11)),  # a length that pads (tiles of 512)
    ((1, 768, 4, 2, 64, 64), "mask", (2, 11)),      # ... in blocks of two heads, a group of two
], ids=["rows", "two_heads", "grouped_half", "four_heads", "mixed", "odd_heads",
        "rows_pairs", "rows_padded", "two_heads_padded"])
def test_kernel_layouts_match_scan_schedule(shape, how, layout):
    """The layouts the chunk rule makes, each against the scan schedule on
    the same float32 inputs: output and all three gradients, and the rule's
    own answer for the shape (heads a block of lanes, arrays in place)."""
    from tpuframe.ops import BlockDiffusionMask

    b, l, h, kv_heads, d, dv = shape
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.standard_normal((b, l, heads, w)) * 0.5, jnp.float32)
               for heads, w in ((h, d), (kv_heads, d), (kv_heads, dv)))
    assert (bw._chunk_heads(h, kv_heads, d, dv, l),
            bw.layout_counts(h, kv_heads, d, dv)[0]) == layout
    kw = {"mask": BlockDiffusionMask(l // 2, 4)} if how == "mask" else {"causal": how == "causal"}
    block = None if l == 768 else 128
    fn = lambda q, k, v: blockwise_attention(  # noqa: E731
        q, k, v, block_size=block, interpret=True, **kw)
    sched = lambda q, k, v: bw.blockwise_attention_reference(  # noqa: E731
        q, k, v, block_size=128, **kw)
    got = fn(q, k, v)
    assert got.shape == (b, l, h, dv)
    np.testing.assert_allclose(_f32(got), _f32(sched(q, k, v)), atol=5e-6)
    for a, c, x in zip(_grads(fn, q, k, v), _grads(sched, q, k, v), (q, k, v)):
        assert a.shape == x.shape
        np.testing.assert_allclose(_f32(a), _f32(c), atol=5e-5)


@pytest.mark.parametrize("l", [256, 300], ids=["blocks", "indivisible"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_kernel_matches_scan_schedule(causal, l):
    """The kernels and the scan schedule are two schedules of one
    arithmetic: float32 round-off apart on the same inputs, forward and
    backward (the registry's parity test)."""
    q, k, v = _wide_qkv(l, 192, 128, jnp.float32, seed=1)
    sched = lambda q, k, v: bw.blockwise_attention_reference(  # noqa: E731
        q, k, v, causal=causal, block_size=128)
    np.testing.assert_allclose(
        _f32(_kernel(causal)(q, k, v)), _f32(sched(q, k, v)), atol=2e-6)
    for a, b_ in zip(_grads(_kernel(causal), q, k, v), _grads(sched, q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(b_), atol=2e-5)


def test_kernel_bf16_follows_scan_schedule_at_equal_tiles():
    """Same tiles, same order of the same operations: in bfloat16 the two
    schedules agree within one rounding of the storage dtype, output and
    gradients."""
    q, k, v = _wide_qkv(384, 192, 128, jnp.bfloat16, seed=2)
    sched = lambda q, k, v: bw.blockwise_attention_reference(  # noqa: E731
        q, k, v, causal=True, block_size=128)
    np.testing.assert_allclose(
        _f32(_kernel(True)(q, k, v)), _f32(sched(q, k, v)), atol=2e-3, rtol=2**-7)
    for a, b_ in zip(_grads(_kernel(True), q, k, v), _grads(sched, q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(b_), atol=2e-3, rtol=2**-7)


@pytest.mark.parametrize("l", [300, 512, 1000], ids=["padded", "blocks", "long"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_kernel_forward_tile_twice_the_backwards(monkeypatch, causal, l):
    """The forward's tile may be twice the backward's (as 1024 and 512
    are at 4096 positions), and the padding may reach over more than one
    of the backward's K blocks (300 positions padded to 512, in tiles of
    128): the diagonal, the clamped fetches and the padding mask follow."""
    monkeypatch.setattr(bw, "_tiles", lambda l, block: (256, 128))
    q, k, v = _wide_qkv(l, 64, 64, jnp.float32, seed=3)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=causal)  # noqa: E731
    np.testing.assert_allclose(
        _f32(_kernel(causal)(q, k, v)), _f32(ref(q, k, v)), atol=2e-5)
    for a, b_ in zip(_grads(_kernel(causal), q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(b_), atol=5e-4)


@pytest.mark.parametrize("l, block, want", [
    (4096, None, (1024, 512)),   # the forward doubles: it pads no further
    (1536, None, (512, 512)),
    (8000, None, (1024, 512)),   # 8000 pads to 8192 either way
    (300, None, (384, 384)),     # one tile of whole lanes
    (300, 128, (128, 128)),      # an explicit block: every tile's side
    (4096, 768, (768, 768)),
    (4096, 100, (128, 128)),     # in whole lanes
    (8192, 4096, (1024, 1024)),  # no side passes 1024
])
def test_kernel_tile_rule(l, block, want):
    """The kernels' (forward, backward) tile sides follow L alone, or an
    explicit ``block_size``; both divide L padded to the forward's."""
    assert bw._tiles(l, block) == want
    assert want[0] % want[1] == 0


def test_kernel_tiles_ignore_the_schedules_block(monkeypatch):
    jax.clear_caches()  # the kernels are jitted: trace this call anew
    seen = []
    real = bw._flash_call
    monkeypatch.setattr(
        bw, "_flash_call",
        lambda *a, side, **kw: seen.append(side) or real(*a, side=side, **kw))
    monkeypatch.setattr(bw, "_SCAN_BLOCK", 128)
    q, k, v = _wide_qkv(300, 64, 64, jnp.float32)
    blockwise_attention(q, k, v, causal=True, interpret=True)
    assert seen == [384]  # not _SCAN_BLOCK: that is the scan schedule's


@pytest.mark.parametrize("blocks", [1, 2], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("block", [640, 768, 896])
def test_kernel_gradients_at_blocks_no_power_of_two(block, blocks):
    """Every tile side divides the padded length, so every grid covers
    it: block sizes the knob's domain allows and 512 does not divide."""
    q, k, v = _wide_qkv(block * blocks, 64, 64, jnp.float32, h=1, seed=6)
    fn = lambda q, k, v: blockwise_attention(  # noqa: E731
        q, k, v, causal=True, block_size=block, interpret=True)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True)  # noqa: E731
    np.testing.assert_allclose(_f32(fn(q, k, v)), _f32(ref(q, k, v)), atol=2e-5)
    for a, b_ in zip(_grads(fn, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(b_), atol=5e-4)


def test_tiles_that_do_not_divide_are_refused():
    """A grid is never floored: 768 positions unpadded in tiles of 512."""
    q, k, v = _wide_qkv(768, 64, 64, jnp.float32, h=1)
    with pytest.raises(ValueError, match="do not divide"):
        bw._flash_fwd(q, k, v, True, 1.0, 512, 768, True)


def test_backward_is_one_kernel_and_long_sequences_take_the_schedule(monkeypatch):
    """One backward kernel, a head's float32 dQ resident in VMEM; auto
    dispatch leaves a sequence whose dQ would not fit to the scan
    schedule, whatever the backend would run."""
    import re

    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")

    def kernels(l, d, interpret=None):
        q = jax.ShapeDtypeStruct((1, l, 1, d), jnp.bfloat16)
        jaxpr = jax.jit(jax.grad(lambda *a: jnp.sum(
            blockwise_attention(*a, causal=True, interpret=interpret).astype(jnp.float32)
        ), (0, 1, 2))).trace(q, q, q).jaxpr
        return set(re.findall(r"name=(tpuframe_\w+)", str(jaxpr)))

    both = {"tpuframe_flash_fwd", "tpuframe_flash_bwd"}
    assert kernels(4096, 192) == both
    assert kernels(32768, 192) == both  # 96 of 100 MiB
    assert kernels(40960, 192) == set()
    assert kernels(40960, 192, interpret=True) == both  # an explicit choice wins
    assert kernels(65536, 64) == both


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_kernel_custom_scale_and_value_width(causal):
    q, k, v = _wide_qkv(300, 192, 128, jnp.float32, seed=4)
    scale = 192 ** -0.5 * 1.26 ** 2  # latent attention's: rotary temperature folded in
    want = attention_reference(q, k, v, causal=causal, scale=scale)
    np.testing.assert_allclose(
        _f32(_kernel(causal, scale=scale)(q, k, v)), _f32(want), atol=2e-5)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=causal, scale=scale)  # noqa: E731
    for a, b_ in zip(_grads(_kernel(causal, scale=scale), q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(b_), atol=5e-4)


def test_kernel_large_logits_no_overflow():
    q, k, v = _wide_qkv(256, 64, 64, jnp.float32, seed=5, scale=3.0)  # logits ~ +-200
    got = _kernel(True)(q, k, v)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_allclose(
        _f32(got), _f32(attention_reference(q, k, v, causal=True)), rtol=1e-4, atol=1e-3)
    assert all(np.isfinite(_f32(g)).all() for g in _grads(_kernel(True), q, k, v))


def test_kernel_verdict_event_and_auto_dispatch(monkeypatch, tmp_path):
    """Auto dispatch asks the one rule every op asks: on this backend the
    scan schedule, under TPUFRAME_PALLAS_INTERPRET the kernels, and the
    decision is announced once a shape class."""
    from tpuframe.ops import dispatch
    from tpuframe.track import telemetry as T

    calls = []
    real = bw._flash_fwd
    monkeypatch.setattr(bw, "_flash_fwd", lambda *a: calls.append(1) or real(*a))
    dispatch._VERDICT_EMITTED.clear()
    tele = T.configure(str(tmp_path / "events.jsonl"))
    try:
        q, k, v = _wide_qkv(256, 64, 64, jnp.float32)
        want = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            _f32(blockwise_attention(q, k, v, causal=True)), _f32(want), atol=2e-5)
        assert not calls  # CPU, no interpret knob: the scan schedule
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        for _ in range(2):
            np.testing.assert_allclose(
                _f32(blockwise_attention(q, k, v, causal=True)), _f32(want), atol=2e-5)
        assert len(calls) == 2
        monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
        blockwise_attention(q, k, v, causal=True)
        assert len(calls) == 2
        events = [e for e in tele.recent_events(50) if e["name"] == "ops/kernel_verdict"]
        assert [(e["op"], e["shape_class"], e["enable"], e["source"]) for e in events] == [
            ("blockwise_attention", "d64_l256", False, "default"),
            ("blockwise_attention", "d64_l256", True, "default"),
            ("blockwise_attention", "d64_l256", False, "forced")]
        # how many of the kernels' eleven arrays stay in the model's rows:
        # said where the kernels engage, and nowhere else
        assert [(e.get("operands_in_place"), e.get("operands_copied")) for e in events] == [
            (None, None), (11, 0), (None, None)]  # two 64-wide heads a block of lanes
    finally:
        T.reset()
        dispatch._VERDICT_EMITTED.clear()


def test_lowered_latent_step_holds_the_flash_kernels(compiled_backend):
    """A latent-attention TransformerLM's gradient, lowered for the TPU
    with the backend's answer pinned to "compiled": the attention core is
    the flash kernels and no loop is left under ``tpuframe/mla``."""
    from tpuframe.models import TransformerLM

    model = TransformerLM(
        vocab_size=64, num_layers=1, num_heads=2, d_model=64, head_dim=128,
        rope_dim=64, kv_lora_rank=32, v_head_dim=128, max_len=256,
        norm="rms", attn_impl="blockwise",
        dtype=jnp.bfloat16,
    )
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 16), jnp.int32), train=False))

    def loss(params, toks):
        return jnp.sum(model.apply({"params": params}, toks, train=False)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).trace(variables["params"], toks).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    for name in ("tpuframe_flash_fwd", "tpuframe_flash_bwd"):
        assert f'kernel_name = "{name}"' in text
    assert "tpuframe/mla" in text
    loops = [line for line in text.splitlines() if "stablehlo.while" in line]
    assert not [line for line in loops if "tpuframe/mla" in line], loops[:2]


# -- per shard on a mesh: the placement attn_impl="auto" gives the kernels ----


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("mesh_spec", [dict(data=-1), dict(data=4, model=2)],
                         ids=["data", "data_model"])
def test_attend_per_shard_matches_full_attention(mesh_spec, causal, monkeypatch, tmp_path):
    """``_attend`` on a mesh of virtual devices, the kernels in interpret
    mode under the ``shard_map`` the rule gives them (rows over the batch
    axes, heads over the model axis), against full attention in float32:
    the output and all three gradients, at a length that pads (200 -> 256),
    and the verdict event of the placement, once."""
    from tpuframe.core import MeshSpec
    from tpuframe.core import runtime as rt
    from tpuframe.models import transformer
    from tpuframe.ops import dispatch
    from tpuframe.track import telemetry as T

    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(transformer, "_FLASH_AUTO_LEN", 128)  # keep the test small
    placed = []
    real = bw._flash_fwd
    monkeypatch.setattr(
        bw, "_flash_fwd",
        lambda *a: placed.append((dispatch.inside_shard_map(), a[0].shape)) or real(*a))
    b, l, h, d = 8, 200, 4, 16
    rng = np.random.default_rng(7)
    q, k, v, w = (jnp.asarray(rng.standard_normal((b, l, h, d)) * 0.5, jnp.float32)
                  for _ in range(4))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * w)

    def per_shard(q, k, v):
        return transformer._attend(q, k, v, impl="auto", causal=causal,
                                   num_heads=h, initializing=False)

    def full(q, k, v):
        return attention_reference(q, k, v, causal=causal)

    dispatch._VERDICT_EMITTED.clear()
    tele = T.configure(str(tmp_path / "events.jsonl"))
    rt.reset_runtime()
    try:
        runtime = rt.initialize(MeshSpec(**mesh_spec))
        got = jax.jit(per_shard)(q, k, v)
        grads = jax.jit(jax.grad(loss(per_shard), (0, 1, 2)))(q, k, v)
        events = [e for e in tele.recent_events(50) if e["name"] == "ops/kernel_verdict"]
    finally:
        rt.reset_runtime()
        T.reset()
        dispatch._VERDICT_EMITTED.clear()
    # every kernel call saw one shard's rows and heads, in the model's layout
    shards = runtime.mesh.shape
    local = (b // shards["data"], 256, h // shards["model"], d)
    assert placed and set(placed) == {(True, local)}
    # announced by the op's own call, which knows the values' width: the
    # rule's question with q alone leaves kernels that engage to it
    assert [(e["op"], e["shape_class"], e["enable"], e["source"],
             e["operands_in_place"], e["operands_copied"]) for e in events] == [
        ("blockwise_attention", "d16_l256", True, "default", 0, 11)]
    np.testing.assert_allclose(_f32(got), _f32(full(q, k, v)), atol=2e-5)
    for a, c in zip(grads, jax.grad(loss(full), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(_f32(a), _f32(c), atol=5e-5)
