"""Pallas op tests: kernel code (interpret mode on CPU) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe.ops import (
    cross_entropy_reference,
    fused_cross_entropy,
    normalize_images,
    normalize_images_reference,
)

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def _ulp_ceiling(dtype) -> float:
    """One unit in the last place of ``dtype`` at the largest magnitude a
    normalized ImageNet pixel takes (|x| < 4: spacing of [2, 4))."""
    return float(jnp.finfo(dtype).eps) * 2.0


def _assert_normalized_like_reference(got, imgs, mean, std, scale, out_dtype):
    want = normalize_images_reference(imgs, mean, std, scale, out_dtype)
    assert got.dtype == out_dtype and got.shape == imgs.shape
    # the folded constants round differently from the three-op chain: a
    # few f32 ulps before the one rounding to out_dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=max(_ulp_ceiling(out_dtype), 4 * _ulp_ceiling(jnp.float32)),
        rtol=0)


def _spread(n: int, lo: float, hi: float) -> tuple:
    """``n`` per-channel constants that all differ."""
    return tuple(float(x) for x in np.linspace(lo, hi, n))


# (shape, input dtype, mean, std, scale, out dtype, rows sharded over mesh8):
# every shape the one form must take, the ones only the kernel's tests
# used to cover among them
_NORMALIZE_CASES = {
    "nhwc_uint8": ((4, 17, 17, 3), np.uint8, MEAN, STD, 1 / 255, jnp.float32, None),
    "nhwc_224_bf16_out": ((2, 224, 224, 3), np.uint8, MEAN, STD, 1 / 255, jnp.bfloat16, None),
    "size_no_multiple_of_128": ((3, 5, 7, 3), np.uint8, MEAN, STD, 1 / 255, jnp.float32, None),
    "grayscale": ((2, 28, 28, 1), np.uint8, (0.1307,), (0.3081,), 1 / 255, jnp.float32, None),
    "grayscale_unit_floats_bf16_out": (
        (2, 28, 28, 1), "unit", (0.5,), (0.5,), 1.0, jnp.bfloat16, None),
    "float_input_0_255": ((4, 17, 17, 3), np.float32, MEAN, STD, 1 / 255, jnp.float32, None),
    "one_dimension": ((100,), np.uint8, _spread(100, 0.2, 0.6), _spread(100, 0.2, 0.3),
                      1 / 255, jnp.float32, None),
    "rows_of_128_lanes": ((2, 3, 128), np.uint8, _spread(128, 0.3, 0.5),
                          _spread(128, 0.25, 0.5), 1 / 255, jnp.float32, None),
    "constants_that_differ": ((4, 9, 9, 4), np.uint8, (0.1, 0.4, 0.5, 0.9),
                              (0.3, 0.25, 0.5, 1.0), 1 / 255, jnp.float32, None),
    "sharded_batch_divides": ((8, 5, 5, 3), np.uint8, MEAN, STD, 1 / 255, jnp.float32, True),
    "sharded_batch_does_not_divide": (
        (6, 5, 5, 3), np.uint8, MEAN, STD, 1 / 255, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", sorted(_NORMALIZE_CASES))
def test_normalize_matches_reference(case, mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape, in_dtype, mean, std, scale, out_dtype, divides = _NORMALIZE_CASES[case]
    rng = np.random.default_rng(len(case))
    if in_dtype == "unit":
        raw = rng.random(shape, dtype=np.float32)
    else:
        raw = rng.integers(0, 256, shape, dtype=np.uint8).astype(in_dtype)
    imgs = jnp.asarray(raw)
    fn = jax.jit(lambda x: normalize_images(x, mean, std, scale, out_dtype))
    if divides is not None:
        # rows over data x fsdp (4 ways) where they divide, else replicated
        # on the mesh: GSPMD partitions the elementwise form either way
        spec = P(mesh8.axis_names[:2]) if divides else P()
        imgs = jax.device_put(raw, NamedSharding(mesh8, spec))
        assert "shard_map" not in str(jax.make_jaxpr(fn)(imgs))
    got = fn(imgs)
    if divides:
        assert got.sharding.is_equivalent_to(imgs.sharding, raw.ndim)
    _assert_normalized_like_reference(
        got, jnp.asarray(raw), mean, std, scale, out_dtype)


def test_normalize_rejects_constants_of_another_width():
    with pytest.raises(ValueError, match="channels"):
        normalize_images(jnp.zeros((2, 4, 4, 3), jnp.uint8), (0.5,), (0.5,))


def test_normalize_is_one_form_whatever_the_kernel_switches(monkeypatch):
    """No switch of the dispatch plane reaches the normalize: the traced
    program is the same with the kernels off, interpreted and as on the
    chip, and holds no custom call."""
    from tpuframe.ops import dispatch

    def traced():
        return str(jax.make_jaxpr(lambda x: normalize_images(x, MEAN, STD))(
            jax.ShapeDtypeStruct((4, 17, 17, 3), jnp.uint8)))

    plain = traced()
    assert "pallas_call" not in plain and "shard_map" not in plain
    monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
    assert traced() == plain
    monkeypatch.delenv("TPUFRAME_DISABLE_PALLAS")
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    assert traced() == plain
    monkeypatch.setattr(dispatch, "pallas_mode", lambda: "compiled")
    assert traced() == plain


class TestNormalizeInLayout:
    """The arithmetic the chip has run since PR 25, now on every backend:
    float32 ``x * w[c] + b[c]``, one rounding."""

    @pytest.mark.parametrize("channels", [3, 1])
    @pytest.mark.parametrize("in_dtype", [np.uint8, np.float32])
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_in_layout_matches_reference(self, channels, in_dtype, out_dtype):
        rng = np.random.default_rng(channels)
        raw = rng.integers(0, 256, (4, 17, 17, channels), dtype=np.uint8)
        imgs = jnp.asarray(raw.astype(in_dtype))  # 0-255 floats: MixUp's
        mean, std = MEAN[:channels], STD[:channels]
        got = normalize_images(imgs, mean, std, out_dtype=out_dtype)
        _assert_normalized_like_reference(
            got, imgs, mean, std, 1 / 255, out_dtype)

    def test_sharded_image_needs_no_shard_map(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(13)
        raw = rng.integers(0, 256, (8, 5, 5, 3), dtype=np.uint8)
        sharded = jax.device_put(
            raw, NamedSharding(mesh8, P(mesh8.axis_names[0])))
        fn = jax.jit(lambda x: normalize_images(x, MEAN, STD))
        assert "shard_map" not in str(jax.make_jaxpr(fn)(sharded))
        got = fn(sharded)
        assert got.sharding.is_equivalent_to(sharded.sharding, raw.ndim)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(normalize_images_reference(jnp.asarray(raw), MEAN, STD)),
            atol=4 * _ulp_ceiling(jnp.float32))


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


def test_cross_entropy_rank2_labels_keep_optax_path():
    from tpuframe.train import cross_entropy

    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.standard_normal((2, 5, 7)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 7, (2, 5)).astype(np.int32))
    got = cross_entropy(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    assert got.shape == (2, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


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
