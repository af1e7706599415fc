"""Every Pallas kernel lowers for the TPU under a stable name.

The name is what finds a kernel again after a refactor: ``chip_smoke.py``
looks for the Mosaic custom calls of the train step by it, and a profiler
trace names the kernel's device events by it.  Lowering for
``platforms=("tpu",)`` needs no chip (Pallas -> Mosaic MLIR happens at
lowering; only libtpu's Mosaic *compiler* needs one)."""

import importlib

import jax
import jax.numpy as jnp
import pytest

# by module path: tpuframe.ops re-exports functions under some of these names
ce, ln, qw, sc, us = (
    importlib.import_module(f"tpuframe.ops.{m}")
    for m in ("cross_entropy", "layer_norm", "quant_wire", "short_conv", "unsort")
)

_F32 = jnp.float32


def _x(*shape, dtype=_F32):
    return jnp.ones(shape, dtype)


KERNELS = {
    "tpuframe_ce_fwd": (
        lambda lg, lb: ce._fwd_pallas(lg, lb, False),
        (_x(32, 1000), _x(32, dtype=jnp.int32)),
    ),
    "tpuframe_ce_bwd": (
        lambda lg, lb, g: ce._bwd_pallas(lg, lb, g, False),
        (_x(32, 1000), _x(32, dtype=jnp.int32), _x(32)),
    ),
    "tpuframe_layer_norm_fwd": (
        lambda x, s, b: ln._fwd_pallas(x, s, b, 1e-6, False),
        (_x(64, 768), _x(768), _x(768)),
    ),
    "tpuframe_layer_norm_bwd": (
        lambda x, s, g: ln._bwd_pallas(x, s, g, 1e-6, False),
        (_x(64, 768), _x(768), _x(64, 768)),
    ),
    "tpuframe_quant_amax": (
        lambda v: qw._pallas_bucket_abs_max(v, False), (_x(8, 2048),),
    ),
    "tpuframe_quant_encode": (
        lambda v, a: qw._pallas_encode(v, a, "int8", None, False),
        (_x(8, 2048), _x(8, 1)),
    ),
    "tpuframe_quant_decode": (
        lambda t, a: qw._pallas_decode(t, a, "int8", 8, False),
        (_x(8, 2048, dtype=jnp.int32), _x(8, 1)),
    ),
    # one key head and two value heads of 128, 256 columns behind them
    "tpuframe_conv_silu_fwd": (
        lambda x, w: sc._conv_silu_fwd_pallas(x, w, 1, 128, False),
        (_x(1, 64, 768), _x(4, 512)),
    ),
    "tpuframe_conv_silu_bwd": (
        lambda x, w, *gs: sc._conv_silu_bwd_pallas(x, w, gs, 1, 128, False),
        (_x(1, 64, 768), _x(4, 512), _x(1, 64, 128), _x(1, 64, 128), _x(1, 64, 256)),
    ),
    # 256 slots of 8 experts' rows for 256 tokens
    "tpuframe_unsort": (
        lambda r, t, s: us._unsort(r, t, s, n=256, window=32, interpret=False),
        (_x(256, 128), _x(256, dtype=jnp.int32), _x(8, dtype=jnp.int32)),
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_tpu_lowering_carries_the_stable_kernel_name(name):
    fn, args = KERNELS[name]
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{name}"' in text
