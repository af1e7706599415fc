"""The flash-attention kernels, the placements ``attn_impl="auto"``
gives them, the short-convolution pair and the pair of what the gated delta
rule reads, the head-norm-and-rotary pair, and the
expert layer's grouped products, compiled for a described v5e: no chip, the
TPU's own compiler (Mosaic refuses here what it would refuse there: a
tile that does not fit VMEM, a block it cannot lay out, a precision it
does not take).  Nothing runs, so this says nothing of results or times.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library.  Keep every such
compile in this one file."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tpuframe.ops import blockwise_attention


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def v5e_runtime(topo, monkeypatch):
    """``runtime(chips)``: the described v5e as the process's runtime, a
    data mesh over 1 or 4 of its chips, with the dispatch plane's view of
    the backend pinned to what a TPU process of that many devices says."""
    from tpuframe.core import MeshSpec
    from tpuframe.core import runtime as rt
    from tpuframe.ops import dispatch

    def runtime(chips):
        spec = MeshSpec(data=-1)
        mesh = spec.build(list(topo.devices)[:chips])
        monkeypatch.setattr(rt, "_CURRENT", rt.Runtime(
            mesh=mesh, spec=spec, process_index=0, process_count=1, platform="tpu"))
        monkeypatch.setattr(dispatch, "pallas_mode", lambda: "compiled")
        monkeypatch.setattr(jax, "device_count", lambda *a: chips)
        return mesh

    return runtime


def _kernel_calls(text, name):
    return [line for line in text.splitlines()
            if "custom-call(" in line and name in line]


_COLLECTIVE = re.compile(
    r" (all-gather|all-to-all|collective-permute|reduce-scatter|all-reduce)(-start)?\(")


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


@pytest.mark.parametrize("shape, rule, tile", [
    ((1, 8192, 32, 4, 128), ("block", 4096, 4), None),   # sdar-30b-a3b-chat's row: 3/4 of the tile grid dead
    ((2, 600, 8, 2, 128), ("block", 300, 4), None),      # a tile straddles the two copies; padded keys
    ((1, 768, 8, 8, 64), ("block", 384, 3), 256),        # a block that is no power of two (a vector division)
    ((1, 8192, 32, 4, 128), ("window", 1024), None),     # mellum2-12b-a2.5b-instruct's window layers
    ((2, 1000, 8, 2, 128), ("window", 300), None),       # a band no tile multiple over a padded row
], ids=["sdar", "straddle_padded", "block_of_3", "mellum2_window", "window_padded"])
def test_masked_grouped_kernels_compile_for_v5e(one_chip, shape, rule, tile):
    """The flash kernels under a mask rule (the block-diffusion rule, the
    sliding-window band), their plan in scalar memory and a grid of the
    live tiles alone, K and V at one head a group: Mosaic takes the
    scalar-prefetch index maps, the rule's element-wise mask (column and
    row codes, a shift or a division; two compares and a clamp) and the
    grouped K/V blocks."""
    from tpuframe.ops import BlockDiffusionMask, SlidingWindowMask

    b, l, h, kv_heads, d = shape
    q = jax.ShapeDtypeStruct((b, l, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, l, kv_heads, d), jnp.bfloat16, sharding=one_chip)
    rule = {"block": BlockDiffusionMask, "window": SlidingWindowMask}[rule[0]](*rule[1:])

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, mask=rule, block_size=tile, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "tpuframe_flash_fwd" + rule.suffix)) == 1
    assert len(_kernel_calls(text, "tpuframe_flash_bwd" + rule.suffix)) == 1
    assert " while(" not in text
    if l >= 1024:  # nothing the size of one head's (L, L) scores, let alone all heads'
        assert compiled.memory_analysis().temp_size_in_bytes < b * h * l * l * 2 / 8


def _materialized(text):
    """(name, opcode, dtype, elements) of every array the compiled step
    writes: the instructions of its entry computation (what a fusion
    computes inside stays in registers)."""
    found = []
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if m:
            dims = [int(x) for x in m.group(3).split(",") if x]
            found.append((m.group(1), m.group(4), m.group(2),
                          math.prod(dims)))
    return found


@pytest.mark.parametrize("shape, mask", [
    ((1, 8192, 32, 4, 128), (4096, 4)),   # sdar-30b-a3b-chat's call: four heads a block of lanes
    ((4, 1024, 16, 16, 64), None),        # gpt2-medium's: two heads a block
    ((2, 4096, 32, 8, 64), None),         # lfm2-8b-a1b's: a group of four over half a K/V block
    ((1, 8192, 16, 2, 256), None),        # qwen3-next's: 256-wide heads, two lane blocks a head
], ids=["sdar", "gpt2m", "lfm2", "qwen3next"])
def test_flash_kernels_take_the_models_rows(one_chip, shape, mask):
    """The call as the model makes it (the projections' (B, L, H*D) rows
    in, the rows of the output out): the kernels read and write those rows,
    so the compiled text holds one forward and one backward kernel, no copy
    or transpose of an array the size of q, the output, k or v, and no
    float32 array that size (``delta`` is taken from the rows as they lie)."""
    from tpuframe.ops import BlockDiffusionMask

    b, l, h, kv_heads, d = shape
    rows = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (b, l, heads * d), jnp.bfloat16, sharding=one_chip)
    how = {"causal": True} if mask is None else {"mask": BlockDiffusionMask(*mask)}

    def loss(q, k, v, w):
        out = blockwise_attention(
            q.reshape(b, l, h, d), k.reshape(b, l, kv_heads, d), v.reshape(b, l, kv_heads, d),
            interpret=False, **how)
        return jnp.sum((out.reshape(b, l, h * d) * w).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        rows(h), rows(kv_heads), rows(kv_heads), rows(h)).compile().as_text()
    assert len(_kernel_calls(text, "tpuframe_flash_fwd")) == 1
    assert len(_kernel_calls(text, "tpuframe_flash_bwd")) == 1
    arrays = _materialized(text)
    assert len(arrays) > 4
    sizes = {b * l * h * d, b * l * kv_heads * d}
    # a layout change is a copy or a transpose, alone or as the fusion XLA
    # names after it; copy-start / copy-done move an array as it lies
    moved = [a for a in arrays if a[3] in sizes and a[1] not in ("copy-start", "copy-done")
             and re.search("copy|transpose", a[0] + " " + a[1])]
    assert not moved, moved
    wide = [a for a in arrays if a[2] == "f32" and a[3] >= b * l * h * d]
    assert not wide, wide


@pytest.mark.parametrize("shape, dtype", [
    ((2, 4096, 2048, 3), jnp.bfloat16),   # lfm2-8b-a1b's conv layers: 8192 x 2048 a call
    ((2, 600, 256, 3), jnp.float32),      # check_kernels_tpu's: a length that is no tile multiple
    ((1, 4096, 1024, 4), jnp.bfloat16),   # four taps, another width
], ids=["lfm2", "f32_ragged", "four_taps"])
def test_short_conv_kernels_compile_for_v5e(one_chip, shape, dtype):
    """The short-convolution pair: full-width blocks of 256 rows, the
    16-row neighbour views, sublane rolls and the taps' gradient resident
    across the grid, inside the VMEM the kernels ask for."""
    from tpuframe.ops.short_conv import short_conv

    b, l, d, k = shape
    x = jax.ShapeDtypeStruct((b, l, 3 * d), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, d), dtype, sharding=one_chip)

    def loss(x, w):
        return jnp.sum(short_conv(x, w, interpret=False).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(x, w).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "tpuframe_short_conv_fwd")) == 1
    assert len(_kernel_calls(text, "tpuframe_short_conv_bwd")) == 1
    # nothing but the result, its square's gradient and the partial sums of the taps
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * b * l * d * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("shape, dtype", [
    ((1, 8192, 12288, 16, 32), jnp.bfloat16),   # qwen3-next's: the first 8192 of the fused 12288 columns
    ((2, 600, 1536, 2, 4), jnp.float32),        # check_kernels_tpu's: a length that is no tile multiple
    ((1, 1024, 16384, 32, 64), jnp.float32),    # float32 rows twice as wide: a tile of 128 rows
], ids=["qwen3next", "f32_ragged", "f32_wide"])
def test_conv_silu_kernels_compile_for_v5e(one_chip, shape, dtype):
    """What the gated delta rule reads: a block of the fused array's first
    columns (what lies behind them never fetched), three outputs of the
    model's rows, a lane reduction a key head, the 16-row views of a tile's
    neighbours in both directions and the taps' gradient resident across the
    grid; one kernel each way, and nothing kept for the backward pass but the
    input."""
    from tpuframe.ops.short_conv import conv_silu

    b, l, width, hk, hv = shape
    channels = (2 * hk + hv) * 128
    x = jax.ShapeDtypeStruct((b, l, width), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32, sharding=one_chip)

    def loss(x, w):
        return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                   for o in conv_silu(x, w, key_heads=hk, key_dim=128, interpret=False))

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(x, w).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "tpuframe_conv_silu_fwd")) == 1
    assert len(_kernel_calls(text, "tpuframe_conv_silu_bwd")) == 1
    # the three outputs, their squares' gradients and the 8192 columns' cotangent
    assert compiled.memory_analysis().temp_size_in_bytes < 3.1 * b * l * channels * jnp.dtype(
        dtype).itemsize


@pytest.mark.parametrize("shape, dtype", [
    ((1, 8192, 32, 128), jnp.bfloat16),   # sdar-30b-a3b-chat's query projection: a head a vreg column
    ((1, 8192, 4, 128), jnp.bfloat16),    # ... and its key projection
    ((2, 4096, 32, 64), jnp.bfloat16),    # lfm2-8b-a1b's: two heads side by side in 128 lanes
    ((2, 4096, 8, 64), jnp.bfloat16),
    ((2, 600, 4, 128), jnp.float32),      # check_kernels_tpu's: a length that is no tile multiple
    ((1, 8192, 16, 256, 64), jnp.bfloat16),  # qwen3-next's query: 256-wide heads, 64 of them turned
    ((1, 8192, 2, 256, 64), jnp.bfloat16),   # ... and its key projection
    ((2, 600, 4, 128, 32), jnp.float32),
], ids=["sdar_q", "sdar_k", "lfm2_q", "lfm2_k", "f32_ragged", "qwen3next_q_partial",
        "qwen3next_k_partial", "f32_ragged_partial"])
def test_head_norm_rope_kernels_compile_for_v5e(one_chip, shape, dtype):
    """The head-norm-and-rotary pair: whole-row blocks of 256 positions, a
    head (or two) a chunk of whole lanes, lane rolls and lane sums within a
    head, the scale's gradient resident across the grid; and nothing kept
    for the backward pass but the input in its own dtype."""
    from tpuframe.ops.head_norm_rope import head_norm_rope

    # a fifth number: the rotary tables' width where it is under the head's
    b, l, h, d = shape[:4]
    x = jax.ShapeDtypeStruct((b, l, h * d), dtype, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((l, shape[4] if len(shape) > 4 else d), jnp.float32,
                                 sharding=one_chip)

    def loss(x, scale, cos, sin):
        out = head_norm_rope(x, scale, cos, sin, num_heads=h, eps=1e-6, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(x, scale, table, table).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "tpuframe_head_norm_rope_fwd")) == 1
    assert len(_kernel_calls(text, "tpuframe_head_norm_rope_bwd")) == 1
    # no float32 array of the input's size: less than the input in its own dtype
    assert compiled.memory_analysis().temp_size_in_bytes < b * l * h * d * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("shape, dtype", [
    ((1, 8192, 16, 32, 128, 128), jnp.bfloat16),   # qwen3-next's linear-attention layers
    ((2, 300, 2, 4, 128, 128), jnp.float32),       # check_kernels_tpu's: a ragged length, float32
    ((1, 1024, 4, 4, 256, 128), jnp.bfloat16),     # keys twice as wide as values
], ids=["qwen3next", "f32_ragged", "wide_keys"])
def test_gated_delta_kernels_compile_for_v5e(one_chip, shape, dtype):
    """The gated delta rule's pass over the chunks: a head's rows of 256
    positions a grid step, the float32 state and its gradient resident in
    VMEM along the chunks' axis, the products with a transposed left operand
    (``Kd^T V'``, ``M^T dO``), float32 operands whole; one forward and one
    backward kernel, and nothing kept for the backward pass the size of a
    state a position.  And the chunk-local part: the kernel that solves (its
    float32 products whole, through Mosaic), the entry that is handed ``T``
    and the transpose, once each, and no float32 (.., 128, 128) array of a
    chunk left to an XLA product or fusion."""
    from tpuframe.ops.gated_delta import gated_delta

    b, l, hk, h, dk, dv = shape
    arr = lambda *s, t=dtype: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731
    args = (arr(b, l, hk, dk), arr(b, l, hk, dk), arr(b, l, h, dv),
            arr(b, l, h, t=jnp.float32), arr(b, l, h, t=jnp.float32))

    def loss(*a):
        return jnp.sum(gated_delta(*a, interpret=False).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    for kernel in ("tpuframe_gated_delta_fwd", "tpuframe_gated_delta_bwd", "tpuframe_delta_chunk_fwd",
                   "tpuframe_delta_chunk_again", "tpuframe_delta_chunk_bwd"):
        assert len(_kernel_calls(text, kernel)) == 1, kernel
    assert not re.findall(r"= \(?f32\[[\d,]*128,128\]\S* (?:fusion|convolution|dot)\(", text)
    # a state a chunk and the chunk-local arrays, never a state a position
    assert compiled.memory_analysis().temp_size_in_bytes < b * l * h * dk * dv * 4 / 8


@pytest.mark.parametrize("shape, dtype", [
    ((1, 4096, 32, 128, 128), jnp.bfloat16),   # kimi-linear's KDA layers
    ((2, 300, 2, 128, 128), jnp.float32),      # a ragged length, float32
], ids=["kimilinear", "f32_ragged"])
def test_kda_kernels_compile_for_v5e(one_chip, shape, dtype):
    """The vector-decay delta rule's pass over the chunks: `ops.gated_delta`'s
    with the state transposed in VMEM, ``gamma`` a row of lanes a chunk; one
    forward and one backward kernel.  And the chunk-local part: the kernel
    that solves (its float32 products whole and its rolls down the sublanes,
    through Mosaic), the entry that is handed ``T`` and the transpose, once
    each, and no float32 (.., 128, 128) array of a chunk left to an XLA
    product or fusion."""
    from tpuframe.ops.kda import kda

    b, l, h, dk, dv = shape
    arr = lambda *s, t=dtype: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731
    args = (arr(b, l, h, dk), arr(b, l, h, dk), arr(b, l, h, dv),
            arr(b, l, h, dk, t=jnp.float32), arr(b, l, h, t=jnp.float32))

    def loss(*a):
        return jnp.sum(kda(*a, interpret=False).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    for kernel in ("tpuframe_kda_fwd", "tpuframe_kda_bwd", "tpuframe_kdachunk_fwd",
                   "tpuframe_kdachunk_again", "tpuframe_kdachunk_bwd"):
        assert len(_kernel_calls(text, kernel)) == 1, kernel
    assert not re.findall(r"= \(?f32\[[\d,]*128,128\]\S* (?:fusion|convolution|dot)\(", text)
    # T, a state a chunk and the parts with their cotangents (0.39 GiB at the
    # first shape): under a quarter of what XLA's chunk-local part needed
    # for the operands scaled a sub-block (1.14 GiB)
    assert compiled.memory_analysis().temp_size_in_bytes < b * l * h * 16 * dk * 4 / 2


def test_qwen3_next_period_holds_its_kernels_once_a_kind(v5e_runtime):
    """A linear-attention layer twice and a gated full-attention layer of
    ``TransformerLM`` at qwen3-next's widths, the gradient of a loss over
    its logits: the rule's five kernels (the pass's two, the chunk-local
    part's three) and the pair that makes what it reads from the fused
    projection's output lowered once and called a layer, the flash and the
    head-norm-and-rotary pairs at 256-wide heads with 64 of them turned, and
    what the backward pass of a linear-attention layer keeps stays under a
    float32 copy of its fused projection."""
    from tpuframe.models import TransformerLM

    mesh = v5e_runtime(1)
    model = TransformerLM(
        vocab_size=1024, num_layers=3, num_heads=16, head_dim=256, d_model=2048, max_len=8192,
        attn_impl="auto", dtype=jnp.bfloat16, norm="rms", norm_unit_offset=True, rope_dim=64,
        rope_theta=1e7, num_kv_heads=2, qk_norm=True, attn_gated=True, mlp_gated=True,
        mlp_dim=512, layer_types=["linear_attention", "linear_attention", "full_attention"],
        linear_attention={"num_key_heads": 16, "num_value_heads": 32, "key_dim": 128,
                          "value_dim": 128, "conv_taps": 4})
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 16), jnp.int32), train=False))["params"]
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
        params)
    toks = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P()))

    def loss(params, toks):
        logits = model.apply({"params": params}, toks, train=True)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    lowered = jax.jit(jax.grad(loss)).lower(params, toks)
    rule = ("tpuframe_gated_delta_fwd", "tpuframe_gated_delta_bwd", "tpuframe_delta_chunk_fwd",
            "tpuframe_delta_chunk_again", "tpuframe_delta_chunk_bwd",
            "tpuframe_conv_silu_fwd", "tpuframe_conv_silu_bwd")
    for kernel in (*rule, "tpuframe_flash_fwd", "tpuframe_flash_bwd"):
        assert lowered.as_text().count(f'kernel_name = "{kernel}"') == 1, kernel
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel, calls in (*((kernel, 2) for kernel in rule),
                          ("tpuframe_flash_fwd", 1), ("tpuframe_flash_bwd", 1),
                          ("tpuframe_head_norm_rope_fwd", 2), ("tpuframe_head_norm_rope_bwd", 2)):
        # the instruction's own line: a kernel that reads another's output names it too
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == calls, kernel
    # nothing of the convolution's float32 (8192, 8192) arrays, nor of q and k
    # sliced out of one and brought to heads for the norm, is left to XLA
    # (the gated attention layer's fused query projection is 8192 wide too)
    assert not [line for line in text.splitlines() if "/deltanet/" in line
                and ("f32[1,8192,8192]" in line or "f32[1024,8,16,128]" in line)]
    # two linear-attention layers' residuals and one's transients: with every
    # chunk-local array of every layer kept (no barrier before the backward
    # pass computes them again) this read 7.5 GiB for two layers
    assert compiled.memory_analysis().temp_size_in_bytes < 5 << 30


@pytest.mark.parametrize("width, tokens, layer", [
    (2048, (2, 4096), dict(num_experts=64, top_k=6, expert_dim=1408, held=(0, 8), renormalize=False)),
    (2048, (1, 8192), dict(num_experts=128, top_k=8, expert_dim=768, held=(0, 16))),
    (2048, (2, 4096), dict(num_experts=32, top_k=4, expert_dim=1792, held=(0, 8),
                           scoring="sigmoid", select_bias=True, aux_loss_weight=0.0)),
    (2304, (1, 8192), dict(num_experts=64, top_k=8, expert_dim=896, held=(0, 8))),
    (2048, (1, 8192), dict(num_experts=512, top_k=10, expert_dim=512, held=(0, 16))),
    (2048, (1, 8192), dict(num_experts=128, top_k=8, expert_dim=768, held=(0, 8))),
], ids=["dsv2lite_12288x2048x1408x8", "sdar_16384x2048x768x16",
        "lfm2_16384x2048x1792x8", "mellum2_16384x2304x896x8",
        "qwen3next_5120x2048x512x16", "keyevl2_8192x2048x768x8"])
def test_expert_layer_holds_the_grouped_kernels(v5e_runtime, one_chip, width, tokens, layer):
    """One no-drop expert layer as each expert cell runs it (bfloat16
    products over float32 master weights, the slot buffers `slot_bound`
    gives), its value, gradient and a plain update in one program: the
    nine grouped products of the pass that runs are this repo's kernels
    and none is XLA's ragged-dot kernel (which the further windows' loops,
    for traffic that overflows the buffers, keep); Mosaic takes their blocks (a whole (K, N) weight a
    group, a float32 (K, N) accumulator) within the VMEM they ask for; a
    (G, K, N) bfloat16 array is written once a leaf by the cast and once
    by the weight gradient's kernel, the kernels read the casts as they lie
    (the row gradient contracts over the weights' last axis in place; the
    one layout copy left, of w_in and w_gate, feeds the further windows'
    loop) and none passes through a select of its own (the kernels write
    the zeros).  The un-sort is this repo's kernel too (PR 48), once each
    way: Mosaic takes its windows' DMAs at the dtype's sublane tile and its
    product contracted over the slots, and outside the further windows'
    loops no array has a row a (token, choice) pair."""
    from tpuframe.models.moe import MoEMLP

    v5e_runtime(1)
    moe = MoEMLP(capacity_factor=None, gated=True, dtype=jnp.bfloat16, **layer)
    x = jax.ShapeDtypeStruct((*tokens, width), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(lambda: moe.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16, width), jnp.bfloat16)))["params"]
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)

    def loss(p, x):
        out, _ = moe.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def step(p, x):
        value, (grads, d_tokens) = jax.value_and_grad(loss, (0, 1))(p, x)
        return value, jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), d_tokens

    lowered = jax.jit(step, donate_argnums=0).lower(params, x)
    # the jitted kernels are lowered once a shape (w_in and w_gate share
    # theirs), in the first window's pass alone: the further windows' loop
    # bodies, lowered for themselves, keep `ragged_dot`
    for kernel in ("fwd", "drows", "dweights"):
        assert lowered.as_text().count(f'kernel_name = "tpuframe_grouped_{kernel}"') == 2
    # the un-sort once for each jitted body that calls it (the forward's, the backward's)
    assert lowered.as_text().count('kernel_name = "tpuframe_unsort"') == 2
    text = lowered.compile().as_text()
    entry = text[text.index("\nENTRY "):]
    for kernel in ("fwd", "drows", "dweights"):
        assert len(_kernel_calls(text, f"tpuframe_grouped_{kernel}")) == 3
        assert len(_kernel_calls(entry, f"tpuframe_grouped_{kernel}")) == 3
    assert "ragged" not in entry
    assert len(_kernel_calls(text, "tpuframe_unsort")) == len(_kernel_calls(entry, "tpuframe_unsort")) == 2
    assert f"[{layer['top_k']},8192,{width}]" not in entry
    # the route is made by counting (PR 49): under its scope nothing as long
    # as the pairs is sorted, gathered from a number at a time or scattered
    # into, in either pass or in the further windows' loops.  What is left of
    # that kind is the expert choice's: `top_k`'s sort over a token's experts
    # and, at 10 choices of 512, its transpose, which XLA makes a sort of the
    # pairs and a scatter into the (tokens x experts) scores, under no scope
    pairs = tokens[0] * tokens[1] * layer["top_k"]
    routes = [line for line in text.splitlines()
              if "tpuframe/moe/route" in line and re.search(r" (sort|gather|scatter)\(", line)]
    assert [line for line in routes if "top_k" in line]
    assert not [line for line in routes if f"[{pairs}]" in line]
    g, k, n = params["w_in"].shape
    # every array of a weight leaf's shape the program writes (as the entry's
    # text has them: `name = dtype[G,K,N]{layout} opcode(`)
    leaves = [m.groups() for m in re.finditer(
        rf"%?([\w.\-]+) = (\w+)\[(?:{g},{k},{n}|{g},{n},{k})\]\S* ([\w\-]+)\(", entry)]
    narrow = [(name, op) for name, dtype, op in leaves
              if dtype == "bf16" and op not in ("get-tuple-element", "copy-start", "copy-done")]
    written = [name for name, op in narrow if op != "custom-call"]
    casts = [name for name in written if "convert" in name]
    assert len(casts) == 3, narrow
    # what else is written of a leaf's shape is the layout `ragged_dot`'s row
    # gradient wants in the further windows' loop (w_in, w_gate): no kernel reads it
    copies = [name for name in written if name not in casts]
    assert len(copies) <= 2 and all(name.startswith("copy") for name in copies), narrow
    calls = " ".join(_kernel_calls(entry, "tpuframe_grouped"))
    assert not [name for name in copies if f"%{name}," in calls or f"%{name})" in calls]
    moved = [(name, op) for name, _, op in leaves if re.search("transpose|select", name + " " + op)]
    assert not moved, moved


@pytest.mark.parametrize("tokens, k, held, experts", [
    (8192, 6, 8, 64), (8192, 8, 16, 128), (8192, 4, 8, 32), (8192, 8, 8, 64), (8192, 10, 16, 512),
    (8192, 8, 8, 128),
], ids=["dsv2lite", "sdar", "lfm2", "mellum2", "qwen3next", "keyevl2"])
def test_the_counting_route_compiles_for_v5e(one_chip, tokens, k, held, experts):
    """The expert layer's plan of a window of slots at each expert cell's
    shape, with its transpose: fusions, three products and the scans of the
    `count x nb` edges; no sort, no gather, no scatter, no loop, and no
    array of `cap x count x nb` compares is stored."""
    from tpuframe.models import moe

    pairs = tokens * k
    cap = moe.slot_bound(pairs, held, experts)
    assert cap < pairs

    def plan(idx, vals, d_weight):
        key = jnp.where(idx.reshape(-1) < held, idx.reshape(-1), held)
        sizes, edges = moe._count_blocks(key, held)
        tok, weight, pair = moe._window_plan(key, edges, vals, jnp.int32(0), cap, k)
        return sizes, tok, weight, moe._spread_weights(d_weight, pair, pairs)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in (
        ((tokens, k), jnp.int32), ((pairs,), jnp.float32), ((cap,), jnp.float32))]
    compiled = jax.jit(plan).lower(*args).compile()
    text = compiled.as_text()
    assert not re.search(r" (sort|gather|scatter|while)\(", text)
    assert len(re.findall(r" convolution\(", text)) == 3
    nb = pairs // moe._BLOCK
    # the largest array stored is the rows of the one-hot product, 5 byte planes
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 5 * cap * moe._BLOCK * 2
    assert f"[{cap},{held * nb}]" not in text[text.index("\nENTRY "):]


def test_gpt2_heads_per_shard_compile_for_v5e_2x2(v5e_runtime):
    """gpt2_dp4's attention call: 16 rows of 1024 positions over the
    2x2 host's data axis, ``attn_impl="auto"``.  The rule places the
    kernels per shard: every device runs one forward and one backward
    kernel on its four rows, nothing is gathered, and no score matrix
    is left."""
    from tpuframe.models import transformer

    mesh = v5e_runtime(4)
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    q = jax.ShapeDtypeStruct((16, 1024, 16, 64), jnp.bfloat16, sharding=rows)

    def loss(q, k, v):
        out = transformer._attend(q, k, v, impl="auto", causal=True,
                                  num_heads=16, initializing=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).compile()
    text = compiled.as_text()
    assert len(_kernel_calls(text, "tpuframe_flash_fwd")) == 1
    assert len(_kernel_calls(text, "tpuframe_flash_bwd")) == 1
    assert not _COLLECTIVE.findall(text)
    assert "[4,16,1024,1024]" not in text
    # a device's share of the scores alone would be 128 MiB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("chips", [1, 4], ids=["gpt2m_seq1024", "gpt2m_dp4"])
def test_gpt2_medium_step_holds_the_flash_kernels(v5e_runtime, chips):
    """Two layers of ``TransformerLM`` at gpt2-medium's widths, batch 4 a
    chip, the gradient of a loss over its logits: a forward and a
    backward flash kernel a layer on every device, no (4, 16, 1024, 1024)
    tensor, and on four chips the gradients' all-reduces and no other
    collective."""
    from tpuframe.models import TransformerLM

    mesh = v5e_runtime(chips)
    model = TransformerLM(vocab_size=50257, num_layers=2, num_heads=16, head_dim=64,
                          max_len=1024, attn_impl="auto", dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 16), jnp.int32), train=False))["params"]
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
        params)
    toks = jax.ShapeDtypeStruct((4 * chips, 1024), jnp.int32,
                                sharding=NamedSharding(mesh, P(("data", "fsdp"))))

    def loss(params, toks):
        logits = model.apply({"params": params}, toks, train=True)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    lowered = jax.jit(jax.grad(loss)).lower(params, toks)
    # the layers call one jitted per-shard region, and on one chip the jitted
    # kernels themselves: lowered once, not once a layer (48 regions cost the
    # 24-layer step 20 s to lower; 48 kernels of two heads each 14 s, PR 40)
    for kernel in ("tpuframe_flash_fwd", "tpuframe_flash_bwd"):
        assert lowered.as_text().count(f'kernel_name = "{kernel}"') == 1
    text = lowered.compile().as_text()
    assert len(_kernel_calls(text, "tpuframe_flash_fwd")) == 2
    assert len(_kernel_calls(text, "tpuframe_flash_bwd")) == 2
    assert "[4,16,1024,1024]" not in text
    kinds = {kind for kind, _ in _COLLECTIVE.findall(text)}
    assert kinds == ({"all-reduce"} if chips > 1 else set())
