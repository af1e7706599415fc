"""Pallas op tests: kernel code (interpret mode on CPU) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe.ops import (
    cross_entropy_reference,
    fused_adamw,
    fused_adamw_update,
    fused_cross_entropy,
    normalize_images,
    normalize_images_reference,
)

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def test_normalize_matches_reference_uint8():
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (4, 17, 17, 3), dtype=np.uint8)
    got = normalize_images(jnp.asarray(imgs), MEAN, STD, interpret=True)
    want = normalize_images_reference(jnp.asarray(imgs), MEAN, STD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_normalize_grayscale_and_dtype():
    rng = np.random.default_rng(1)
    imgs = rng.random((2, 28, 28, 1), dtype=np.float32)
    got = normalize_images(
        jnp.asarray(imgs), (0.5,), (0.5,), scale=1.0,
        out_dtype=jnp.bfloat16, interpret=True,
    )
    want = normalize_images_reference(
        jnp.asarray(imgs), (0.5,), (0.5,), scale=1.0, out_dtype=jnp.bfloat16
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=1e-2
    )


def test_normalize_auto_dispatch_matches_reference(monkeypatch):
    # pin the dispatch to the reference path so the assert is meaningful
    # (and tolerance-free) on any backend, TPU runners included
    monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
    imgs = jnp.ones((2, 4, 4, 3), jnp.uint8) * 128
    got = normalize_images(imgs, MEAN, STD)
    want = normalize_images_reference(imgs, MEAN, STD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def _ulp_ceiling(dtype) -> float:
    """One unit in the last place of ``dtype`` at the largest magnitude a
    normalized ImageNet pixel takes (|x| < 4: spacing of [2, 4))."""
    return float(jnp.finfo(dtype).eps) * 2.0


class TestNormalizeLayoutRule:
    """Where the kernel would engage, auto dispatch keeps an image batch in
    its own layout (PR 25): on the chip the kernel's flat ``(rows, 128)``
    view of a channels-last array with 3 or 1 in the minor dimension is a
    physical re-layout.  The backend's answer is pinned to "compiled" (what
    a TPU says); the decision is read off the traced program, and the jnp
    form runs anywhere."""

    @pytest.fixture(autouse=True)
    def _pinned(self, compiled_backend):
        pass

    @staticmethod
    def _traces_kernel(shape, **kw) -> bool:
        per_channel = (0.5,) * shape[-1]  # "channels" are the last axis
        text = str(jax.make_jaxpr(
            lambda x: normalize_images(x, per_channel, per_channel, **kw)
        )(jax.ShapeDtypeStruct(shape, jnp.uint8)))
        return "pallas_call" in text

    @pytest.mark.parametrize("shape,kernel", [
        ((4, 17, 17, 3), False),    # NHWC, 3 channels
        ((2, 28, 28, 1), False),    # NHWC, grayscale
        ((128,), True),             # 1-D: already the flat stream
        ((100,), True),             # 1-D, ragged: padded, still no re-layout
        ((2, 3, 128), True),        # whole 128-lane rows
    ])
    def test_shape_decides_the_path(self, shape, kernel):
        assert self._traces_kernel(shape) is kernel

    @pytest.mark.parametrize("interpret", [True, False])
    def test_explicit_interpret_runs_the_kernel_on_an_image(self, interpret):
        assert self._traces_kernel((4, 17, 17, 3), interpret=interpret)

    @pytest.mark.parametrize("channels", [3, 1])
    @pytest.mark.parametrize("in_dtype", [np.uint8, np.float32])
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_in_layout_matches_reference(self, channels, in_dtype, out_dtype):
        rng = np.random.default_rng(channels)
        raw = rng.integers(0, 256, (4, 17, 17, channels), dtype=np.uint8)
        imgs = jnp.asarray(raw.astype(in_dtype))  # 0-255 floats: MixUp's
        mean, std = MEAN[:channels], STD[:channels]
        got = normalize_images(imgs, mean, std, out_dtype=out_dtype)
        want = normalize_images_reference(imgs, mean, std, out_dtype=out_dtype)
        assert got.dtype == out_dtype and got.shape == imgs.shape
        # the folded constants round differently from the three-op chain:
        # a few f32 ulps before the one rounding to out_dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=max(_ulp_ceiling(out_dtype), 4 * _ulp_ceiling(jnp.float32)),
            rtol=0)

    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_in_layout_agrees_with_kernel_to_one_ulp(self, out_dtype):
        rng = np.random.default_rng(7)
        imgs = jnp.asarray(rng.integers(0, 256, (4, 17, 17, 3), dtype=np.uint8))
        got = normalize_images(imgs, MEAN, STD, out_dtype=out_dtype)
        kern = normalize_images(imgs, MEAN, STD, out_dtype=out_dtype,
                                interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(kern, np.float32),
            atol=_ulp_ceiling(out_dtype), rtol=0)

    def test_sharded_image_needs_no_shard_map(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(13)
        raw = rng.integers(0, 256, (8, 5, 5, 3), dtype=np.uint8)
        sharded = jax.device_put(
            raw, NamedSharding(mesh8, P(mesh8.axis_names[0])))
        fn = jax.jit(lambda x: normalize_images(x, MEAN, STD, mesh=mesh8))
        assert "shard_map" not in str(jax.make_jaxpr(fn)(sharded))
        got = fn(sharded)
        assert got.sharding.is_equivalent_to(sharded.sharding, raw.ndim)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(normalize_images_reference(jnp.asarray(raw), MEAN, STD)),
            atol=4 * _ulp_ceiling(jnp.float32))

    def test_one_layout_verdict_per_decision(self, tmp_path):
        from tpuframe.track import telemetry as T

        tele = T.configure(str(tmp_path / "events.jsonl"))
        try:
            img = jnp.zeros((4, 17, 17, 3), jnp.uint8)
            for _ in range(3):
                normalize_images(img, MEAN, STD)
                normalize_images(img, MEAN, STD, interpret=True)
            events = [e for e in tele.recent_events(50)
                      if e["name"] == "ops/kernel_verdict"]
            assert len(events) == 1
            (e,) = events
            assert (e["op"], e["enable"], e["source"]) == (
                "normalize", False, "layout")
            # the ledger was not asked: neither coverage counter moves
            assert tele.registry.counter("ops/ledger_hit").value == 0
            assert tele.registry.counter("ops/ledger_miss").value == 0
            # another shape class is another decision
            normalize_images(jnp.zeros((2, 28, 28, 1), jnp.uint8), (0.5,), (0.5,))
            assert len([e for e in tele.recent_events(50)
                        if e["name"] == "ops/kernel_verdict"]) == 2
        finally:
            T.reset()


def test_disable_flag_is_strict():
    from tpuframe.ops import use_pallas
    import os

    old = os.environ.get("TPUFRAME_DISABLE_PALLAS")
    try:
        os.environ["TPUFRAME_DISABLE_PALLAS"] = "0"
        # "0" must NOT disable the kernels (strict truthy parsing); the
        # result then depends only on backend/device-count.
        import jax

        expected = jax.default_backend() == "tpu" and jax.device_count() == 1
        assert use_pallas() == expected
    finally:
        if old is None:
            os.environ.pop("TPUFRAME_DISABLE_PALLAS", None)
        else:
            os.environ["TPUFRAME_DISABLE_PALLAS"] = old


@pytest.mark.parametrize("b,k", [(8, 10), (13, 1000), (16, 128)])
def test_fused_cross_entropy_forward(b, k):
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32)) * 3
    labels = jnp.asarray(rng.integers(0, k, (b,)).astype(np.int32))
    got = fused_cross_entropy(logits, labels, interpret=True)
    want = cross_entropy_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    also = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(also), rtol=1e-4, atol=1e-5)


def test_fused_cross_entropy_gradient():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((12, 37)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 37, (12,)).astype(np.int32))

    def loss_fused(lg):
        return jnp.mean(fused_cross_entropy(lg, labels, interpret=True))

    def loss_ref(lg):
        return jnp.mean(cross_entropy_reference(lg, labels))

    g_got = jax.grad(loss_fused)(logits)
    g_want = jax.grad(loss_ref)(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), atol=1e-5)


def _reference_adamw(p, g, m, v, step, monkeypatch, **kw):
    """The jnp oracle, pinned even on a TPU-backend runner."""
    monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
    try:
        return fused_adamw_update(p, g, m, v, step, interpret=None, **kw)
    finally:
        monkeypatch.delenv("TPUFRAME_DISABLE_PALLAS")


def test_fused_adamw_update_non_tile_multiple(monkeypatch):
    # 257x130 leaves a partial 128-lane row AND a partial row-tile: the
    # grid must still cover every element (regression: floor-divided grid
    # skipped the tail tile).
    rng = np.random.default_rng(7)
    p = jnp.asarray(rng.standard_normal((257, 130)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((257, 130)).astype(np.float32))
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    step = jnp.ones((), jnp.int32)
    kw = dict(lr=1e-2, weight_decay=0.01)
    p_k, m_k, v_k = fused_adamw_update(p, g, m, v, step, interpret=True, **kw)
    p_r, m_r, v_r = _reference_adamw(p, g, m, v, step, monkeypatch, **kw)
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), atol=1e-6)


def test_fused_adamw_update_matches_math(monkeypatch):
    rng = np.random.default_rng(4)
    p = jnp.asarray(rng.standard_normal((33, 7)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((33, 7)).astype(np.float32))
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    step = jnp.ones((), jnp.int32)
    kw = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    p_k, m_k, v_k = fused_adamw_update(p, g, m, v, step, interpret=True, **kw)
    p_r, m_r, v_r = _reference_adamw(p, g, m, v, step, monkeypatch, **kw)
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), atol=1e-6)


def test_fused_adamw_momentum_free_and_dtype(monkeypatch):
    # b1=0 (momentum-free Adam) is valid in optax and must not crash; the
    # reference path must keep the param dtype like the kernel path does.
    p = jnp.ones((4, 4), jnp.bfloat16)
    g = jnp.ones((4, 4), jnp.bfloat16) * 0.5
    m = jnp.zeros((4, 4), jnp.float32)
    v = jnp.zeros((4, 4), jnp.float32)
    step = jnp.ones((), jnp.int32)
    p_r, m_r, v_r = _reference_adamw(
        p, g, m, v, step, monkeypatch, lr=1e-2, b1=0.0
    )
    assert p_r.dtype == jnp.bfloat16 and m_r.dtype == jnp.float32
    p_k, _, _ = fused_adamw_update(p, g, m, v, step, interpret=True, lr=1e-2, b1=0.0)
    assert p_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(p_k, np.float32), np.asarray(p_r, np.float32), atol=1e-2
    )


def test_fused_adamw_tuple_pytree():
    # params as a raw tuple pytree: the optax contract must survive
    # containers that are themselves tuples.
    params = (jnp.ones((3, 3)), jnp.ones((3,)))
    grads = (jnp.full((3, 3), 0.1), jnp.full((3,), 0.1))
    tx = fused_adamw(1e-3)
    state = tx.init(params)
    updates, state = tx.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    assert isinstance(new_params, tuple) and new_params[0].shape == (3, 3)
    assert float(jnp.max(jnp.abs(updates[0]))) > 0


def test_cross_entropy_rank2_labels_keep_optax_path():
    from tpuframe.train import cross_entropy

    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.standard_normal((2, 5, 7)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 7, (2, 5)).astype(np.int32))
    got = cross_entropy(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    assert got.shape == (2, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_fused_adamw_transform_matches_optax():
    rng = np.random.default_rng(5)
    params = {
        "w": jnp.asarray(rng.standard_normal((5, 9)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((9,)).astype(np.float32)),
    }
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1, params)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    fused = fused_adamw(1e-3, **kw)
    ref = optax.adamw(1e-3, **kw)
    fs, rs = fused.init(params), ref.init(params)
    fp, rp = params, params
    for _ in range(3):
        fu, fs = fused.update(grads, fs, fp)
        fp = optax.apply_updates(fp, fu)
        ru, rs = ref.update(grads, rs, rp)
        rp = optax.apply_updates(rp, ru)
    for key in params:
        np.testing.assert_allclose(
            np.asarray(fp[key]), np.asarray(rp[key]), atol=1e-6
        )


def test_fused_cross_entropy_sharded_matches_unsharded(mesh8):
    # mesh8 = data 2 x fsdp 2 x model 2: batch rows split 4-ways under
    # shard_map; per-shard kernel results must concatenate to the exact
    # global answer, forward and backward.
    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.standard_normal((16, 37)).astype(np.float32)) * 3
    labels = jnp.asarray(rng.integers(0, 37, (16,)).astype(np.int32))
    got = fused_cross_entropy(logits, labels, interpret=True, mesh=mesh8)
    want = cross_entropy_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    g_got = jax.grad(
        lambda lg: jnp.mean(
            fused_cross_entropy(lg, labels, interpret=True, mesh=mesh8)
        )
    )(logits)
    g_want = jax.grad(lambda lg: jnp.mean(cross_entropy_reference(lg, labels)))(
        logits
    )
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), atol=1e-5)


def test_fused_cross_entropy_indivisible_batch_unsharded_kernel(mesh8):
    # 13 rows don't divide the 4-way batch sharding: the op must fall back
    # to the single-shard kernel (explicit interpret) and stay correct.
    rng = np.random.default_rng(10)
    logits = jnp.asarray(rng.standard_normal((13, 10)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, (13,)).astype(np.int32))
    got = fused_cross_entropy(logits, labels, interpret=True, mesh=mesh8)
    want = cross_entropy_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_adamw_update_sharded_matches_unsharded(mesh8):
    # 64 rows of 128 lanes, fsdp=2: each device updates 32 rows of the
    # moments — the ZeRO placement — and results match the unsharded kernel.
    rng = np.random.default_rng(11)
    p = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    step = jnp.ones((), jnp.int32)
    kw = dict(lr=1e-2, weight_decay=0.01)
    with_mesh = fused_adamw_update(
        p, g, m, v, step, interpret=True, mesh=mesh8, shard_axis="fsdp", **kw
    )
    without = fused_adamw_update(p, g, m, v, step, interpret=True, **kw)
    for a, b in zip(with_mesh, without):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_adamw_transform_sharded_auto_path(mesh8, monkeypatch):
    # The full auto path: TPUFRAME_PALLAS_INTERPRET engages the kernels on
    # CPU; mesh routes divisible leaves through shard_map, ragged leaves
    # through the plain kernel; results track optax.adamw.
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(12)
    params = {
        "w": jnp.asarray(rng.standard_normal((32, 128)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((9,)).astype(np.float32)),
    }
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1, params)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    fused = fused_adamw(1e-3, mesh=mesh8, **kw)
    ref = optax.adamw(1e-3, **kw)
    fs, rs = fused.init(params), ref.init(params)
    fp, rp = params, params
    for _ in range(2):
        fu, fs = fused.update(grads, fs, fp)
        fp = optax.apply_updates(fp, fu)
        ru, rs = ref.update(grads, rs, rp)
        rp = optax.apply_updates(rp, ru)
    for key in params:
        np.testing.assert_allclose(
            np.asarray(fp[key]), np.asarray(rp[key]), atol=1e-6
        )


def test_normalize_sharded_matches_reference(mesh8):
    rng = np.random.default_rng(13)
    imgs = rng.integers(0, 256, (8, 5, 5, 3), dtype=np.uint8)
    got = normalize_images(jnp.asarray(imgs), MEAN, STD, interpret=True, mesh=mesh8)
    want = normalize_images_reference(jnp.asarray(imgs), MEAN, STD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_adamw_trains_under_jit():
    # end-to-end: the transform works as the Trainer's tx under jit, and
    # tracks optax.adamw step for step
    from tpuframe.train import create_train_state, make_train_step
    from tpuframe.models import MnistNet

    rng = np.random.default_rng(6)
    batch = {
        "image": jnp.asarray(rng.random((8, 28, 28, 1), np.float32)),
        "label": jnp.asarray(rng.integers(0, 10, (8,)).astype(np.int32)),
    }
    finals = []
    for tx in (fused_adamw(1e-2), optax.adamw(1e-2)):
        state = create_train_state(
            MnistNet(num_classes=10), jax.random.PRNGKey(0),
            jnp.ones((1, 28, 28, 1)), tx,
        )
        step_fn = make_train_step(donate=False)
        for _ in range(3):
            state, _ = step_fn(state, batch)
        finals.append(state.params)
    fused_leaves = jax.tree.leaves(finals[0])
    optax_leaves = jax.tree.leaves(finals[1])
    for a, b in zip(fused_leaves, optax_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
