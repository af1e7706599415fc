"""The flash-attention kernels compiled for a described v5e: no chip, the
TPU's own compiler (Mosaic refuses here what it would refuse there: a
tile that does not fit VMEM, a block it cannot lay out, a precision it
does not take).  Nothing runs, so this says nothing of results or times.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library.  Keep every such
compile in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpuframe.ops import blockwise_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out, and the run silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape, dtype, precision, block", [
    ((2, 4096, 16, 192, 128), jnp.bfloat16, "default", None),  # deepseek-v2-lite's latent attention
    ((2, 4096, 16, 192, 128), jnp.bfloat16, "highest", None),  # narrow operands under an ambient highest
    ((1, 4096, 2, 192, 128), jnp.float32, "highest", None),    # float32 rows: 12 MiB of dQ a head
    ((1, 32768, 1, 192, 128), jnp.bfloat16, "default", None),  # the longest auto dispatch hands over: 96 MiB
    ((1, 4096, 2, 192, 128), jnp.float32, "default", 1024),    # the largest explicit tile
    ((1, 1536, 2, 192, 128), jnp.bfloat16, "default", 768),    # an explicit tile no power of two
    ((2, 4096, 8, 128, 128), jnp.bfloat16, "default", None),
    ((4, 1024, 16, 64, 64), jnp.bfloat16, "default", None),    # gpt2-medium's heads
    ((2, 300, 4, 32, 32), jnp.float32, "highest", 128),        # check_kernels_tpu's: padded, float32
], ids=["latent", "latent_highest", "latent_f32", "long", "tile_1024", "tile_768",
        "128x128", "gpt2m", "f32_padded"])
def test_flash_kernels_compile_for_v5e(one_chip, shape, dtype, precision, block):
    b, l, h, d, dv = shape
    q = jax.ShapeDtypeStruct((b, l, h, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, l, h, dv), dtype, sharding=one_chip)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, causal=True, block_size=block, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, v).compile()
    text = compiled.as_text()
    assert "tpuframe_flash_fwd" in text and "tpuframe_flash_bwd" in text
    assert " while(" not in text
    # linear in L: nothing the size of a (B, H, L, L) score matrix
    scores = b * h * l * l * jnp.dtype(dtype).itemsize
    if l >= 1024:
        assert compiled.memory_analysis().temp_size_in_bytes < scores / 2
