#!/usr/bin/env python
"""On-chip kernel acceptance: every Pallas/custom-vjp op vs its oracle.

CPU/interpret tests prove the math; this script proves the *hardware*
path — Mosaic compilation, tile minimums, VMEM limits, real bf16 matmul
precision.  Run it on TPU whenever a kernel, its block specs, or its
dispatch changes; with any other backend it exits non-zero (the kernels
would dispatch to their own oracles and "pass" vacuously).  One JSON
line per check: {"check", "max_abs_diff", "pass"}; a section the
compiler refuses records one failed ``<section>_compile`` check with
the error and the run moves on, so one call shows every refusal.

Covers: fused LayerNorm (fwd+grads), fused cross-entropy (fwd+grad),
the quant_wire trio
(amax/encode/decode vs the staged jnp expressions — the in-collective
wire's arithmetic contract), blockwise attention's flash kernels
(fwd+grads, causal and not, deepseek-v2-lite's latent shape and
gpt2-medium's heads in bf16), ring and ulysses attention oracle parity on one device,
the no-drop expert layer's bounded slot buffers (one window, and a router that
overflows the bound into several: XLA's ragged-dot kernel leaves the tiles it
does not visit unwritten on the chip, which no CPU run shows), the short
convolution, and the head norms with rotary positions (both timed beside
their oracles' XLA fusions), and the grouped products of the expert layer
(the three kernels against the oracle, and each timed beside
``jax.lax.ragged_dot`` at the four expert cells' shapes), and the index of
sparse attention (exact top-k a query) with the flash kernels under the rule
that reads its choice, and the expert layer's un-sort (the kernel against XLA's
gather a pair, timed beside it at the six expert cells' shapes).

Usage: python benchmarks/check_kernels_tpu.py [--only a,b,...]
(exits 1 on any failure).  ``--only`` runs a named subset — sections:
layer_norm, cross_entropy, quant_wire, blockwise, flash_layout, window, sparse_index, ring,
ulysses, moe_windows, short_conv, conv_silu, head_norm_rope, grouped, unsort, gated_delta, kda
(``--grouped-tiles 128,256,512`` prices other row tiles beside the default).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

RESULTS = []


def record(check: str, diff: float, tol: float) -> None:
    ok = bool(diff < tol)
    RESULTS.append(ok)
    print(json.dumps({"check": check, "max_abs_diff": float(diff),
                      "tol": tol, "pass": ok}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list of sections to run ({','.join(SECTIONS)})")
    ap.add_argument("--grouped-tiles", default="",
                    help="comma list of row tiles the grouped section prices beside the default")
    cli = ap.parse_args()
    _GROUPED_TILES[:] = [int(t) for t in cli.grouped_tiles.split(",") if t]
    chosen = set(cli.only.split(",")) if cli.only else set(SECTIONS)
    unknown = chosen - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections {sorted(unknown)}; "
                         f"known: {list(SECTIONS)}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"check_kernels_tpu needs a TPU: jax.default_backend() is "
            f"{jax.default_backend()!r}"
        )

    from tpuframe.compile import cache as compile_cache

    compile_cache.enable_from_env()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "count": len(jax.devices())}), flush=True)
    rng = np.random.default_rng(0)

    for name, (run_section, _) in SECTIONS.items():
        if name not in chosen:
            continue
        try:
            run_section(jax, jnp, np, rng)
        except Exception as e:  # a Mosaic/XLA refusal fails the section, not the run
            RESULTS.append(False)
            print(json.dumps({"check": f"{name}_compile", "pass": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)

    raise SystemExit(0 if RESULTS and all(RESULTS) else 1)


def _check_layer_norm(jax, jnp, np, rng) -> None:
    from tpuframe.ops.layer_norm import fused_layer_norm, layer_norm_reference

    x = jnp.asarray(rng.standard_normal((1024, 768)), jnp.float32)
    s = jnp.asarray(rng.standard_normal((768,)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((768,)), jnp.float32)
    record(
        "layer_norm_fwd",
        float(jnp.max(jnp.abs(
            jax.jit(fused_layer_norm)(x, s, b) - layer_norm_reference(x, s, b)
        ))),
        1e-4,
    )
    gf = jax.jit(jax.grad(lambda *a: jnp.sum(fused_layer_norm(*a) * jnp.cos(a[0])),
                          (0, 1, 2)))(x, s, b)
    gr = jax.jit(jax.grad(lambda *a: jnp.sum(layer_norm_reference(*a) * jnp.cos(a[0])),
                          (0, 1, 2)))(x, s, b)
    for name, a, c in zip(("dx", "dscale", "dbias"), gf, gr):
        record(f"layer_norm_{name}", float(jnp.max(jnp.abs(a - c))), 5e-4)
    # the LM's activation shape (batch 8 x seq 1024, d_model 768), bf16
    xb = jnp.asarray(rng.standard_normal((8192, 768)), jnp.bfloat16)
    record(
        "layer_norm_fwd_8192x768_bf16",
        float(jnp.max(jnp.abs(
            jax.jit(fused_layer_norm)(xb, s, b).astype(jnp.float32)
            - layer_norm_reference(xb, s, b).astype(jnp.float32)
        ))),
        5e-2,
    )


def _check_cross_entropy(jax, jnp, np, rng) -> None:
    from tpuframe.ops.cross_entropy import (
        cross_entropy_reference,
        fused_cross_entropy,
    )

    logits = jnp.asarray(rng.standard_normal((130, 1000)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 1000, (130,)), jnp.int32)
    (vf, gf2) = jax.jit(jax.value_and_grad(
        lambda lg: jnp.sum(fused_cross_entropy(lg, labels))))(logits)
    (vr, gr2) = jax.jit(jax.value_and_grad(
        lambda lg: jnp.sum(cross_entropy_reference(lg, labels))))(logits)
    record("cross_entropy_value", abs(float(vf - vr)), 1e-2)
    record("cross_entropy_grad", float(jnp.max(jnp.abs(gf2 - gr2))), 1e-4)
    # published widths: the ImageNet head at the trainer's batch (f32
    # and bf16 logits) and the LM head (8 x 1024 tokens, vocab 32768)
    for b, k, dtype, gtol in ((128, 1000, jnp.float32, 1e-5),
                              (128, 1000, jnp.bfloat16, 1e-2),
                              (8192, 32768, jnp.float32, 1e-5)):
        lg = jnp.asarray(rng.standard_normal((b, k)) * 3, dtype)
        lb = jnp.asarray(rng.integers(0, k, (b,)), jnp.int32)
        vf, gf2 = jax.jit(jax.value_and_grad(
            lambda x: jnp.mean(fused_cross_entropy(x, lb))))(lg)
        vr, gr2 = jax.jit(jax.value_and_grad(
            lambda x: jnp.mean(cross_entropy_reference(x, lb))))(lg)
        tag = f"{b}x{k}_{jnp.dtype(dtype).name}"
        record(f"cross_entropy_value_{tag}", abs(float(vf - vr)), 1e-3)
        # grads of the mean are softmax-minus-onehot over b: compare per row
        record(
            f"cross_entropy_grad_{tag}",
            float(jnp.max(jnp.abs(gf2.astype(jnp.float32)
                                  - gr2.astype(jnp.float32)))) * b,
            gtol,
        )


def _check_quant_wire(jax, jnp, np, rng) -> None:
    from tpuframe.ops.quant_wire import (
        bucket_abs_max,
        bucket_abs_max_reference,
        quant_decode,
        quant_decode_reference,
        quant_encode,
        quant_encode_reference,
    )

    # ragged shapes exercise the padded-tile mask and the column-block
    # accumulation; the aligned one is the fast path
    for shape in ((8, 2048), (17, 4096), (3, 130)):
        vv = jnp.asarray(rng.standard_normal(shape) * 7, jnp.float32)
        record(
            f"quant_wire_amax_{shape[0]}x{shape[1]}",
            float(jnp.max(jnp.abs(
                jax.jit(bucket_abs_max)(vv) - bucket_abs_max_reference(vv)
            ))),
            1e-6,
        )
    vv = jnp.asarray(rng.standard_normal((17, 4096)) * 5, jnp.float32)
    amax = bucket_abs_max_reference(vv)
    noise = jnp.asarray(rng.uniform(0, 1, vv.shape), jnp.float32)
    cases = [("int8", "rtn", None), ("int8", "sr", noise), ("fp8", "rtn", None)]
    for mode, tag, nz in cases:
        qk, dk = jax.jit(
            lambda v, a, m=mode, n=nz: quant_encode(v, a, m, noise=n)
        )(vv, amax)
        qr, dr = quant_encode_reference(vv, amax, mode, noise=nz)
        record(
            f"quant_wire_encode_{mode}_{tag}",
            max(
                float(jnp.max(jnp.abs(
                    qk.astype(jnp.float32) - qr.astype(jnp.float32)))),
                float(jnp.max(jnp.abs(dk - dr))),
            ),
            1e-6,
        )
    for mode in ("int8", "fp8"):
        q, _ = quant_encode_reference(vv, amax, mode)
        total = q.astype(jnp.float32) * 8
        total = total.astype(jnp.int32) if mode == "int8" else total
        record(
            f"quant_wire_decode_{mode}",
            float(jnp.max(jnp.abs(
                jax.jit(lambda t, a, m=mode: quant_decode(t, a, m, 8))(
                    total, amax)
                - quant_decode_reference(total, amax, mode, 8)
            ))),
            1e-4,
        )


def _attention_parity(jax, jnp, name: str, fn, qkv, *, causal: bool,
                      scale: float | None = None,
                      tols=(("highest", 2e-4, 2e-3), ("default", 1e-2, 2e-2))) -> None:
    """fwd + grads of ``fn(q, k, v)`` vs the dense oracle, twice: at
    ``highest`` matmul precision (the algorithm — tight tolerances) and at
    the backend default, where a TPU runs f32 matmuls as bf16 passes and
    rows that attend to few keys do not average the rounding away (first
    chip run: 1.6e-3 forward on the causal variants) — that tolerance is
    the precision's, not the kernel's.  The oracle always takes float32
    copies of the inputs; differences are taken in float32."""
    from tpuframe.ops.ring_attention import attention_reference

    def ref(q, k, v):
        # a batch row at a time: at 4096 keys one row's float32 scores
        # are 1 GiB, and the backward holds four such
        return jax.lax.map(
            lambda row: attention_reference(
                *(a[None] for a in row), causal=causal, scale=scale)[0],
            (q, k, v))

    def sq(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)

    def gap(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))

    exact = tuple(a.astype(jnp.float32) for a in qkv)
    for precision, ftol, gtol in tols:
        with jax.default_matmul_precision(precision):
            got, want = jax.jit(fn)(*qkv), jax.jit(ref)(*exact)
            gk = jax.jit(jax.grad(sq(fn), (0, 1, 2)))(*qkv)
            go = jax.jit(jax.grad(sq(ref), (0, 1, 2)))(*exact)
        record(f"{name}_fwd_{precision}", gap(got, want), ftol)
        record(f"{name}_grads_{precision}",
               max(gap(a, c) for a, c in zip(gk, go)), gtol)


def _schedule_parity(jax, jnp, name: str, qkv, *, ftol: float, gtol: float,
                     **how) -> None:
    """The flash kernels against the scan schedule on the SAME inputs,
    forward and gradients, as a share of each value's size (of 1 for
    the smaller ones): two schedules of one arithmetic differ by the
    order of their float32 sums and then by a rounding of the storage
    dtype, 2^-8 of the value in bf16.  A tile-level mistake that the
    float32 oracle's looser bound lets through does not pass here."""
    from tpuframe.ops.blockwise_attention import (
        blockwise_attention,
        blockwise_attention_reference,
    )

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b) / jnp.maximum(jnp.abs(b), 1.0)))

    outs, grads = [], []
    for fn in (blockwise_attention, blockwise_attention_reference):
        def f(q, k, v, fn=fn):
            return fn(q, k, v, **{"causal": True, **how})

        outs.append(jax.jit(f)(*qkv))
        grads.append(jax.jit(jax.grad(
            lambda *a, f=f: jnp.sum(f(*a).astype(jnp.float32) ** 2), (0, 1, 2)))(*qkv))
    record(f"{name}_fwd_vs_schedule", gap(*outs), ftol)
    record(f"{name}_grads_vs_schedule",
           max(gap(a, c) for a, c in zip(*grads)), gtol)


def _qkv(jnp, rng):
    return tuple(jnp.asarray(rng.standard_normal((2, 300, 4, 32)) * 0.3,
                             jnp.float32) for _ in range(3))


def _one_device_seq_mesh(jax, np):
    # One chip means a 1-device seq axis (ring: single hop, no rotation;
    # ulysses: identity all-to-alls) — still the real shard_map lowering
    # and the hand-written backward on-device.
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def _check_blockwise(jax, jnp, np, rng) -> None:
    from tpuframe.ops.blockwise_attention import blockwise_attention

    qkv = _qkv(jnp, rng)
    for causal in (False, True):
        _attention_parity(
            jax, jnp, f"blockwise_{'causal' if causal else 'bidir'}",
            lambda q, k, v, c=causal: blockwise_attention(
                q, k, v, causal=c, block_size=128),
            qkv, causal=causal,
        )
    # the latent-attention shape of deepseek-v2-lite as the benchmark runs
    # it: 192-wide queries and keys, 128-wide values, bf16, causal, its
    # softmax scale (192^-0.5 x YaRN's temperature squared), the kernels'
    # own tiles.  Against the float32 oracle bf16 operands bound the
    # agreement (a bf16 output rounds at 2^-9 of its size, a gradient
    # likewise; gradients here reach 7): read on the v5e 0.0068 forward
    # and 0.027 gradients at most (PR 28), held to twice that.
    def latent(b, l, h):
        return tuple(
            jnp.asarray(rng.standard_normal((b, l, h, w)) * 0.5, jnp.bfloat16)
            for w in (192, 192, 128))

    scale = 192 ** -0.5 * 1.260804 ** 2
    _attention_parity(
        jax, jnp, "blockwise_latent_bf16",
        lambda q, k, v: blockwise_attention(q, k, v, causal=True, scale=scale),
        latent(2, 4096, 16), causal=True, scale=scale,
        tols=(("highest", 1.4e-2, 5.5e-2), ("default", 1.4e-2, 5.5e-2)),
    )
    # read on the v5e: 2^-11 forward, 0.0076 gradients (0.0039 at the
    # long shape): two and one rounding of bf16 at a value's size
    _schedule_parity(jax, jnp, "blockwise_latent_bf16", latent(2, 4096, 16),
                     scale=scale, ftol=2 ** -9, gtol=2 ** -6)
    # gpt2-medium's heads as both GPT-2 cells run them since PR 30 (batch
    # 4 a chip, 1024 positions, 16 heads of 64, bf16, causal), against
    # the float32 oracle; beside them what those cells ran before,
    # `attention_reference` on the same bf16 inputs (scores and softmax
    # in bf16), so the record shows which of the two stands nearer.  Its
    # limits are wide: it is here to be read, not held.
    from tpuframe.ops.ring_attention import attention_reference

    gpt2m = tuple(jnp.asarray(rng.standard_normal((4, 1024, 16, 64)) * 0.5,
                              jnp.bfloat16) for _ in range(3))
    for name, fn, tol in (
        ("blockwise_gpt2m_bf16", blockwise_attention, (1.4e-2, 5.5e-2)),
        ("full_gpt2m_bf16", attention_reference, (0.1, 0.5)),
    ):
        _attention_parity(
            jax, jnp, name, lambda q, k, v, fn=fn: fn(q, k, v, causal=True),
            gpt2m, causal=True, tols=(("default", *tol),),
        )
    # the longest sequence auto dispatch hands the kernels, an indivisible
    # length under it: a head's dQ takes 96 of the 100 MiB of VMEM the
    # backward kernel may ask for
    _schedule_parity(jax, jnp, "blockwise_long_bf16", latent(1, 32768 - 200, 2),
                     scale=scale, ftol=2 ** -9, gtol=2 ** -6)


def _check_flash_layout(jax, jnp, np, rng) -> None:
    """The layouts the flash kernels' chunk rule makes, each at the shape
    of the cell that runs it, against the scan schedule on the same bf16
    inputs: every array in the model's rows (sdar-30b-a3b-chat: 32 heads
    over 4 of 128 under its block mask), 64-wide heads (gpt2-medium's 16;
    lfm2-8b-a1b's 32 over 8), and a call that mixes rows and heads-first
    copies (deepseek-v2-lite: 192-wide q and k, 128-wide v)."""
    from tpuframe.ops import BlockDiffusionMask

    def qkv(b, l, h, kv_heads, d, dv):
        return tuple(
            jnp.asarray(rng.standard_normal((b, l, heads, w)) * 0.5, jnp.bfloat16)
            for heads, w in ((h, d), (kv_heads, d), (kv_heads, dv)))

    for name, shape, how in (
        ("flash_layout_rows_sdar", (1, 8192, 32, 4, 128, 128),
         {"causal": False, "mask": BlockDiffusionMask(4096, 4)}),
        ("flash_layout_d64_gpt2m", (4, 1024, 16, 16, 64, 64), {}),
        ("flash_layout_d64_grouped_lfm2", (2, 4096, 32, 8, 64, 64), {}),
        ("flash_layout_mixed_latent", (2, 4096, 16, 16, 192, 128), {}),
        ("flash_layout_rows_padded", (2, 1000, 8, 2, 128, 128), {}),
    ):
        _schedule_parity(jax, jnp, name, qkv(*shape), ftol=2 ** -9, gtol=2 ** -6, **how)


def _check_window(jax, jnp, np, rng) -> None:
    """The two rules of a model whose layers are sliding-window and full
    attention, alone at mellum2-12b-a2.5b-instruct's call shape (8192
    positions, 32 heads over 4 of 128): the band of 1024 keys and plain
    causal, kernels against the scan schedule on the same bf16 inputs; the
    band against the float32 oracle at one key/value group of that shape
    (four heads a block of lanes, as the cell runs them; all 32 heads'
    scores would not fit); a row that pads up to a tile under a band no
    multiple of it."""
    from tpuframe.ops import SlidingWindowMask, attention_reference, blockwise_attention

    def qkv(b, l, h, kv_heads, d):
        return tuple(
            jnp.asarray(rng.standard_normal((b, l, heads, d)) * 0.5, jnp.bfloat16)
            for heads in (h, kv_heads, kv_heads))

    cell = (1, 8192, 32, 4, 128)
    band = {"causal": False, "mask": SlidingWindowMask(1024)}
    _schedule_parity(jax, jnp, "window_band_cell", qkv(*cell), ftol=2 ** -9, gtol=2 ** -6, **band)
    _schedule_parity(jax, jnp, "window_causal_cell", qkv(*cell), ftol=2 ** -9, gtol=2 ** -6)
    _schedule_parity(jax, jnp, "window_band_padded", qkv(2, 1000, 8, 2, 128),
                     ftol=2 ** -9, gtol=2 ** -6, causal=False, mask=SlidingWindowMask(300))
    group = qkv(1, 8192, 4, 1, 128)
    f32 = tuple(a.astype(jnp.float32) for a in group)
    loss = lambda fn, args: jnp.sum(fn(*args, **band).astype(jnp.float32) ** 2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: attention_reference(*a, **band))(*f32)
        want_g = jax.jit(jax.grad(lambda *a: loss(attention_reference, a), (0, 1, 2)))(*f32)
    got = jax.jit(lambda *a: blockwise_attention(*a, **band))(*group)
    got_g = jax.jit(jax.grad(lambda *a: loss(blockwise_attention, a), (0, 1, 2)))(*group)
    gap = lambda a, b: float(jnp.max(  # noqa: E731
        jnp.abs(a.astype(jnp.float32) - b) / jnp.maximum(jnp.abs(b), 1.0)))
    record("window_band_group_fwd_vs_oracle", gap(got, want), 2 ** -7)
    record("window_band_group_grads_vs_oracle",
           max(gap(a, b) for a, b in zip(got_g, want_g)), 2 ** -5)


def _check_sparse_index(jax, jnp, np, rng) -> None:
    """The index of sparse attention and the flash kernels under the rule that
    reads its choice, at keye-vl-2.0-30b-a3b's call shapes (8192 positions, 16
    index heads of 64 over one index key head, 2048 keys a query; 32 heads
    over 4 of 128).  The index kernel against its oracle bit for bit on
    inputs whose products and sums are exact (values on a coarse grid: plenty
    of ties, so the cut at the earlier key is held too), at the cell's shape
    and at a padded one; on normal bfloat16 inputs, where the kernel's float32
    sums and XLA's differ in their order, every row's count exactly, and every
    pair on which the two choices differ within 2^-16 of the row's threshold
    score.  The flash kernels under the rule against the scan schedule on the
    same choice, forward and gradients, at the cell's shape and a padded one,
    and against the float32 oracle on one key/value group.  Times: the index
    kernel a call beside its oracle (XLA's sort of every row's scores), and the
    flash kernels forward + backward under the rule and under plain causal."""
    from tpuframe.ops import SelectedKeysMask, attention_reference, blockwise_attention
    from tpuframe.ops.sparse_index import (
        index_scores_reference,
        select_keys,
        select_keys_reference,
    )

    def grid(b, l, h, d, dtype):
        draw = lambda *shape: rng.integers(-2, 3, shape) / 4  # noqa: E731
        return (jnp.asarray(draw(b, l, h, d), dtype), jnp.asarray(draw(b, l, d), dtype),
                jnp.asarray(draw(b, l, h) * 2, jnp.float32))

    for name, shape, topk, dtype in (("cell", (1, 8192, 16, 64), 2048, jnp.bfloat16),
                                     ("padded", (2, 1000, 4, 64), 300, jnp.float32),
                                     ("wide_heads", (1, 1536, 2, 128), 512, jnp.bfloat16)):
        args = grid(*shape, dtype)
        got, counts = jax.jit(lambda *a, k=topk: select_keys(*a, k))(*args)
        want, _ = jax.jit(lambda *a, k=topk: select_keys_reference(*a, k))(*args)
        record(f"sparse_index_{name}_pairs_that_differ",
               float(jnp.sum(got != want, dtype=jnp.float32)), 0.5)
        exact = jnp.minimum(jnp.arange(shape[1]) + 1, topk).astype(jnp.float32)
        record(f"sparse_index_{name}_count_gap", float(jnp.max(jnp.abs(counts - exact))), 0.5)

    b, l, hi, di, topk = 1, 8192, 16, 64, 2048
    qi = jnp.asarray(rng.standard_normal((b, l, hi, di)), jnp.bfloat16)
    ki = jnp.asarray(rng.standard_normal((b, l, di)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((b, l, hi)), jnp.float32)
    index = jax.jit(lambda *a: select_keys(*a, topk))
    chosen, counts = index(qi, ki, w)
    exact = jnp.minimum(jnp.arange(l) + 1, topk).astype(jnp.float32)
    record("sparse_index_normal_count_gap", float(jnp.max(jnp.abs(counts - exact))), 0.5)
    record("sparse_index_normal_row_sums_gap",
           float(jnp.max(jnp.abs(jnp.sum(chosen, -1, dtype=jnp.float32) - exact))), 0.5)

    @jax.jit
    def flips(rows, chosen_rows, first):
        """(pairs that differ from the oracle's choice, their largest distance
        from the row's threshold score as a share of it) for a block of rows."""
        seen = jnp.arange(l)[None, None, :] <= (first + jnp.arange(rows[0].shape[1]))[None, :, None]
        scores = jnp.where(seen, index_scores_reference(rows[0], ki, rows[1]), -jnp.inf)
        top, _ = jax.lax.top_k(scores, topk)
        edge = top[..., -1:]
        want = seen & (scores >= edge)          # ties aside, the oracle's choice
        differ = (want != (chosen_rows != 0)) & jnp.isfinite(edge)
        far = jnp.where(differ, jnp.abs(scores - edge) / jnp.maximum(jnp.abs(edge), 1.0), 0.0)
        return jnp.sum(differ, dtype=jnp.float32), jnp.max(far)

    n_flips = far = 0.0
    for first in range(0, l, 512):
        rows = slice(first, first + 512)
        n, f = flips((qi[:, rows], w[:, rows]), chosen[:, rows], first)
        n_flips, far = n_flips + float(n), max(far, float(f))
    print(json.dumps({"info": "sparse_index_normal", "pairs_that_differ": n_flips,
                      "of": float(jnp.sum(exact))}), flush=True)
    record("sparse_index_normal_flips_from_threshold", far, 2 ** -16)
    record("sparse_index_normal_flip_share", n_flips / float(jnp.sum(exact)), 1e-4)

    def qkv(b, l, h, kv_heads, d):
        return tuple(
            jnp.asarray(rng.standard_normal((b, l, heads, d)) * 0.5, jnp.bfloat16)
            for heads in (h, kv_heads, kv_heads))

    rule = {"causal": False, "mask": SelectedKeysMask(topk), "mask_operands": (chosen,)}
    cell = qkv(1, 8192, 32, 4, 128)
    _schedule_parity(jax, jnp, "select_cell", cell, ftol=2 ** -9, gtol=2 ** -6, **rule)
    small = grid(2, 1000, 4, 64, jnp.float32)
    small_chosen, _ = select_keys(*small, 300)
    _schedule_parity(jax, jnp, "select_padded", qkv(2, 1000, 8, 2, 128), ftol=2 ** -9,
                     gtol=2 ** -6, causal=False, mask=SelectedKeysMask(300),
                     mask_operands=(small_chosen,))
    group = qkv(1, 8192, 4, 1, 128)
    f32 = tuple(a.astype(jnp.float32) for a in group)
    loss = lambda fn, args: jnp.sum(fn(*args, **rule).astype(jnp.float32) ** 2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: attention_reference(*a, **rule))(*f32)
        want_g = jax.jit(jax.grad(lambda *a: loss(attention_reference, a), (0, 1, 2)))(*f32)
    got = jax.jit(lambda *a: blockwise_attention(*a, **rule))(*group)
    got_g = jax.jit(jax.grad(lambda *a: loss(blockwise_attention, a), (0, 1, 2)))(*group)
    gap = lambda a, b: float(jnp.max(  # noqa: E731
        jnp.abs(a.astype(jnp.float32) - b) / jnp.maximum(jnp.abs(b), 1.0)))
    record("select_group_fwd_vs_oracle", gap(got, want), 2 ** -7)
    record("select_group_grads_vs_oracle",
           max(gap(a, b) for a, b in zip(got_g, want_g)), 2 ** -5)

    both = lambda how: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(blockwise_attention(*a, **how).astype(jnp.float32) ** 2), (0, 1, 2)))
    print(json.dumps({
        "info": "sparse_index_ms_a_call",
        "index_topk": _ms_a_call(jax, index, (qi, ki, w), calls=5),
        "index_oracle_lax_top_k": _ms_a_call(
            jax, jax.jit(lambda *a: select_keys_reference(*a, topk)), (qi, ki, w), calls=3),
        "flash_fwd_bwd_select": _ms_a_call(jax, both(rule), cell, calls=5),
        "flash_fwd_bwd_causal": _ms_a_call(jax, both({"causal": True}), cell, calls=5),
    }), flush=True)


def _check_moe_windows(jax, jnp, np, rng) -> None:
    """4,096 tokens x 4 choices over 32 experts of which 4 are held: 16,384
    pairs through buffers of 4,096 slots, against every token through every
    held expert; a fair router (one window) and one that sends most pairs
    here (several).  Relative error of the output and of every gradient."""
    from tpuframe.models.moe import MoEMLP, slot_bound

    n, k, e, held, d, h = 4096, 4, 32, 4, 256, 128
    cap = slot_bound(n * k, held, e)
    layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(0, held), gated=True,
                   capacity_factor=None)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, co = f32(n, d).at[:, 0].set(1.0), f32(n, d)
    base = {"w_gate": f32(held, d, h) / 16, "w_in": f32(held, d, h) / 16,
            "w_out": f32(held, h, d) / 11}

    def oracle(p, x):
        gates, chosen = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["kernel"], -1), k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        out = 0.0
        for i in range(held):
            w = jnp.sum(jnp.where(chosen == i, gates, 0.0), -1, keepdims=True)
            out = out + w * ((jax.nn.silu(x @ p["w_gate"][i]) * (x @ p["w_in"][i])) @ p["w_out"][i])
        return out

    def program(p, x):
        return layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])

    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))  # noqa: E731
    for name, lift in (("fair", 0.0), ("overflowing", 0.12)):
        # the lift raises the held experts' logits for every token
        router = (f32(d, e) / 16).at[0, :held].add(lift * 16)
        p = {**base, "router": {"kernel": router}}
        out, upd = jax.jit(program)(p, x)
        here, rows = (float(upd["counters"][c]) for c in ("moe/assignments_here", "moe/slot_rows"))
        print(json.dumps({"check": f"moe_windows_{name}", "routed_here": here, "slot_rows": rows,
                          "bound": cap, "overflow_calls": float(upd["counters"]["moe/overflow_calls"])}),
              flush=True)
        record(f"moe_windows_{name}_windows", abs(rows / cap - max(1, -(-here // cap))), 0.5)
        record(f"moe_windows_{name}_fwd", rel(out, jax.jit(oracle)(p, x)), 2e-2)
        got = jax.jit(jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * co), argnums=(0, 1)))(p, x)
        want = jax.jit(jax.grad(lambda p, x: jnp.sum(oracle(p, x) * co), argnums=(0, 1)))(p, x)
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
            leaf = jax.tree_util.keystr(path).replace("'", "")
            record(f"moe_windows_{name}_grad{leaf}", rel(g, w), 3e-2)


def _check_short_conv(jax, jnp, np, rng) -> None:
    """The short-convolution kernel pair against its oracle, output and both
    gradients: float32 at a length that is no tile multiple, and bfloat16 at
    ``lfm2-8b-a1b``'s shape (2 rows of 4096 positions, 2048 wide, 3 taps),
    where both forms are also timed, forward + backward a call (a line of
    its own; the time passes or fails nothing)."""
    from tpuframe.ops.short_conv import short_conv, short_conv_reference

    def both(op):
        def run(x, w, g):
            y, vjp = jax.vjp(op, x, w)
            return (y,) + vjp(g)
        return jax.jit(run)

    kernel = both(lambda x, w: short_conv(x, w, interpret=False))
    oracle = both(short_conv_reference)
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))  # noqa: E731
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    for name, (b, l, d, k), dtype, tol in (("f32_ragged", (2, 600, 256, 3), jnp.float32, 1e-5),
                                           ("bf16_lfm2", (2, 4096, 2048, 3), jnp.bfloat16, 2e-2)):
        x = jnp.asarray(rng.standard_normal((b, l, 3 * d)), dtype)
        w = jnp.asarray(rng.standard_normal((k, d)), dtype)
        g = jnp.asarray(rng.standard_normal((b, l, d)), dtype)
        for part, got, want in zip(("out", "dx", "dw"), kernel(x, w, g), oracle(x, w, g)):
            record(f"short_conv_{name}_{part}", rel(got, want), tol)
    times = {form: _ms_a_call(jax, fn, (x, w, g))
             for form, fn in (("kernels", kernel), ("oracle", oracle))}
    print(json.dumps({"check": "short_conv_ms_a_call_fwd_bwd", "shape": [b, l, d, k],
                      **times}), flush=True)


def _check_conv_silu(jax, jnp, np, rng) -> None:
    """What the gated delta rule reads (`ops.short_conv.conv_silu`: four
    taps, SiLU, unit queries and keys) against its oracle, the three outputs
    and both gradients: float32 at a cut shape whose length is no tile
    multiple, and bfloat16 at ``qwen3-next-80b-a3b-instruct``'s (one row of
    8192 positions, the first 8192 of the fused projection's 12288 columns,
    16 key heads and 32 value heads of 128), where both forms are timed, the
    forward alone and forward + backward a call (lines of their own; a time
    passes or fails nothing)."""
    from tpuframe.ops.short_conv import conv_silu, conv_silu_reference

    def forms(op, hk):
        fwd = functools.partial(op, key_heads=hk, key_dim=128)

        def both(x, w, gs):
            out, vjp = jax.vjp(fwd, x, w)
            return out + vjp(gs)
        return jax.jit(fwd), jax.jit(both)

    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))  # noqa: E731
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    for name, (b, l, width, hk, hv), dtype, tol in (
            ("f32_ragged", (2, 600, 1536, 2, 4), jnp.float32, 1e-5),
            ("bf16_qwen3next", (1, 8192, 12288, 16, 32), jnp.bfloat16, 2e-2)):
        keys, channels = hk * 128, (2 * hk + hv) * 128
        x = jnp.asarray(rng.standard_normal((b, l, width)), dtype)
        w = jnp.asarray(0.5 * rng.standard_normal((4, channels)), jnp.float32)
        gs = tuple(jnp.asarray(rng.standard_normal((b, l, n)), dtype)
                   for n in (keys, keys, channels - 2 * keys))
        kernel = forms(functools.partial(conv_silu, interpret=False), hk)
        oracle = forms(conv_silu_reference, hk)
        for part, got, want in zip(("q", "k", "v", "dx", "dw"),
                                   kernel[1](x, w, gs), oracle[1](x, w, gs)):
            record(f"conv_silu_{name}_{part}", rel(got, want), tol)
    times = {}
    for form, (fwd, both) in (("kernels", kernel), ("oracle", oracle)):
        times[form + "_fwd"] = _ms_a_call(jax, fwd, (x, w))
        times[form + "_fwd_bwd"] = _ms_a_call(jax, both, (x, w, gs))
    print(json.dumps({"check": "conv_silu_ms_a_call", "shape": [b, l, width, hk, hv],
                      **times}), flush=True)


def _ms_a_call(jax, fn, args, calls=20, laps=5) -> float:
    """The median of ``laps`` laps of ``calls`` calls, in ms a call."""
    import time

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(laps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) / calls)
    return 1e3 * sorted(took)[len(took) // 2]


def _check_head_norm_rope(jax, jnp, np, rng) -> None:
    """The head-norm-and-rotary kernel pair against its oracle, output and
    both gradients: float32 at a length that is no tile multiple, and
    bfloat16 at the query and key projections of ``sdar-30b-a3b-chat`` (one
    row of 8192 positions, 32 and 4 heads of 128, each position id twice) and
    of ``lfm2-8b-a1b`` (2 x 4096, 32 and 8 heads of 64), where both forms are
    also timed, forward + backward a call (20 calls chained in one program,
    the median of 5 laps), with the share of the HBM bandwidth the pair's
    five passes reach (a line of its own; the time passes or fails nothing)."""
    import time

    from tpuframe.models.transformer import rope_tables
    from tpuframe.ops.head_norm_rope import head_norm_rope, head_norm_rope_reference

    def both(op, h, eps, chain=1):
        """``chain`` calls forward + backward in one program, each fed by the
        one before (its input gradient as input, its output as cotangent), so
        that a lap is the device's time and not the host's dispatch."""
        def run(x, s, cos, sin, g):
            for _ in range(chain):
                y, vjp = jax.vjp(lambda x, s: op(x, s, cos, sin, num_heads=h, eps=eps), x, s)
                dx, ds = vjp(g)
                x, g, s = dx, y, s + 0 * ds
            return y, dx, ds
        return jax.jit(run)

    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))  # noqa: E731
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    forms = (("kernels", functools.partial(head_norm_rope, interpret=False)),
             ("oracle", head_norm_rope_reference))
    for name, (b, l, h, d), dtype, eps, tol in (
            ("f32_ragged", (2, 600, 4, 128), jnp.float32, 1e-6, 1e-5),
            ("f32_ragged_64", (2, 600, 8, 64), jnp.float32, 1e-5, 1e-5),
            ("sdar_q", (1, 8192, 32, 128), jnp.bfloat16, 1e-6, 2e-2),
            ("sdar_k", (1, 8192, 4, 128), jnp.bfloat16, 1e-6, 2e-2),
            ("lfm2_q", (2, 4096, 32, 64), jnp.bfloat16, 1e-5, 2e-2),
            ("lfm2_k", (2, 4096, 8, 64), jnp.bfloat16, 1e-5, 2e-2)):
        x = jnp.asarray(rng.standard_normal((b, l, h * d)), dtype)
        g = jnp.asarray(rng.standard_normal((b, l, h * d)), dtype)
        s = jnp.asarray(1 + 0.3 * rng.standard_normal((d,)), jnp.float32)
        cos, sin = rope_tables(l, d, 1e6, None, np.arange(l) // 2)
        args = (x, s, cos, sin, g)
        got, want = (both(op, h, eps)(*args) for _, op in forms)
        for part, a, c in zip(("out", "dx", "dscale"), got, want):
            record(f"head_norm_rope_{name}_{part}", rel(a, c), tol)
        if dtype != jnp.bfloat16:
            continue
        times = {}
        for form, op in forms:
            fn = both(op, h, eps, chain=20)
            jax.block_until_ready(fn(*args))
            laps = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                laps.append((time.perf_counter() - t0) / 20)
            times[form] = 1e3 * sorted(laps)[len(laps) // 2]
        # two passes forward, three backward, and the two tables each way
        moved = 5 * x.size * x.dtype.itemsize + 4 * l * max(d, 128) * 4
        print(json.dumps({"check": "head_norm_rope_ms_a_call_fwd_bwd", "name": name,
                          "shape": [b, l, h, d], **times,
                          "kernels_pct_of_819_gb_s": 100 * moved / (times["kernels"] * 1e-3 * 819e9)}),
              flush=True)


#: (M, K, N, G) of the expert products in dsv2lite_seq4096, sdar_blockdiff_seq4096,
#: lfm2moe_seq4096 and mellum2_seq8192
_GROUPED_SHAPES = ((12288, 2048, 1408, 8), (16384, 2048, 768, 16),
                   (16384, 2048, 1792, 8), (16384, 2304, 896, 8))
#: row tiles ``--grouped-tiles`` asks the section to price beside the default
_GROUPED_TILES: list = []


def _check_grouped(jax, jnp, np, rng) -> None:
    """The grouped products' three kernels (rows x weights, cotangent x
    weights transposed, rows transposed x cotangent) against the float32
    oracle: at a cut shape in float32 with a tile two groups share, empty
    groups and a tail of NaN rows that must come out as exact zeros, then in
    bfloat16 at the four expert cells' shapes (M, K, N, G) and their
    transposes, half the buffer routed (as `slot_bound` leaves it), the
    groups as a balanced router makes them and with the fullest 1.4 times
    the mean.  Each product is timed beside ``jax.lax.ragged_dot`` with
    its selects on the same operands (a line of its own a shape: ms a call,
    the median of 5 laps of 10, and the share of the MXU's peak over the
    routed rows; the time passes or fails nothing)."""
    import importlib
    import time

    gm = importlib.import_module("tpuframe.ops.grouped_matmul")
    peak = 197e12

    def parts(op):
        return {
            "fwd": jax.jit(lambda r, w, s, g: op(r, w, s)),
            "drows": jax.jit(lambda r, w, s, g: jax.vjp(lambda r: op(r, w, s), r)[1](g)[0]),
            "dweights": jax.jit(lambda r, w, s, g: jax.vjp(lambda w: op(r, w, s), w)[1](g)[0]),
        }

    def oracle(r, w, s):
        with jax.default_matmul_precision("highest"):
            return gm.grouped_matmul_reference(r.astype(jnp.float32), w.astype(jnp.float32), s)

    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)  # noqa: E731
                             / jnp.maximum(jnp.linalg.norm(b), 1e-30))
    kernels = lambda tile: parts(  # noqa: E731
        lambda r, w, s: gm.grouped_matmul(r, w, s, interpret=False, tile_rows=tile))

    # a cut shape, float32: groups that share tiles of 128, empty first, middle and last
    m, k, n = 1024, 384, 896
    sizes = jnp.asarray([0, 300, 0, 129, 260, 0], jnp.int32)
    total = int(sizes.sum())
    r = jnp.asarray(rng.standard_normal((m, k)), jnp.float32).at[total:].set(jnp.nan)
    w = jnp.asarray(rng.standard_normal((6, k, n)) / 16, jnp.float32).at[0].set(jnp.nan)
    g = jnp.asarray(rng.standard_normal((m, n)), jnp.float32).at[total:].set(jnp.nan)
    clean = lambda a: jnp.nan_to_num(a, nan=0.0)  # noqa: E731
    want = parts(oracle)
    for name, fn in kernels(128).items():
        got = fn(r, w, sizes, g)
        record(f"grouped_f32_cut_{name}", rel(got, want[name](clean(r), clean(w), sizes, clean(g))), 2e-2)
        outside = got[jnp.asarray([0, 2, 5])] if name == "dweights" else got[total:]
        record(f"grouped_f32_cut_{name}_zeros_past_the_groups",
               float(jnp.max(jnp.abs(jnp.nan_to_num(outside, nan=1.0)))), 1e-30)

    def laps(fn, *args):
        jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(10):
                out = fn(*args)
            jax.block_until_ready(out)
            took.append((time.perf_counter() - t0) / 10)
        return 1e3 * sorted(took)[2]

    tiles = [gm._TILE_ROWS] + [t for t in _GROUPED_TILES if t != gm._TILE_ROWS]
    for m, k, n, groups in _GROUPED_SHAPES:
        for k, n in ((k, n), (n, k)):
            r = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            w = jnp.asarray(rng.standard_normal((groups, k, n)) / 32, jnp.bfloat16)
            g = jnp.asarray(rng.standard_normal((m, n)), jnp.bfloat16)
            for load in ("balanced", "fullest_1.4"):
                share = np.full(groups, 1.0)
                if load != "balanced":
                    share[1] = 1.4
                    share[2:] = (groups - 1.4 - 1.0) / (groups - 2)
                sizes = rng.multinomial(m // 2, share / share.sum())
                routed = int(sizes.sum())
                sizes = jnp.asarray(sizes, jnp.int32)
                flops = 2 * routed * k * n
                line = {"check": "grouped_ms_a_call", "shape": [m, k, n, groups], "load": load,
                        "fullest_over_mean": float(sizes.max() * groups / routed)}
                ragged = parts(gm._ragged)
                for name, fn in ragged.items():
                    line[f"ragged_dot_{name}"] = laps(fn, r, w, sizes, g)
                f32 = [a.astype(jnp.float32) for a in (r, w, g)]
                want = {name: fn(f32[0], f32[1], sizes, f32[2])
                        for name, fn in parts(oracle).items()} if load == "balanced" else None
                for tile in tiles:
                    tag = f"tile{tile}"
                    for name, fn in kernels(tile).items():
                        if want is not None:
                            record(f"grouped_{m}x{k}x{n}x{groups}_{tag}_{name}",
                                   rel(fn(r, w, sizes, g), want[name]), 1e-2)
                        ms = laps(fn, r, w, sizes, g)
                        line[f"{tag}_{name}"] = ms
                        line[f"{tag}_{name}_pct_of_peak"] = 100 * flops / (ms * 1e-3 * peak)
                    edge = gm._edge_rows(tile)
                    line[f"{tag}_padded_rows_pct"] = 100 * (1 - routed / float(
                        gm.tiles_visited(sizes, edge) * edge))
                print(json.dumps(line), flush=True)


#: (choices a token, held experts, experts) of the expert layers in dsv2lite_seq4096,
#: sdar_blockdiff_seq4096, lfm2moe_seq4096, mellum2_seq8192, qwen3next_seq8192 and
#: keyevl2_seq8192 (8,192 tokens a step each), and the rows' width
_UNSORT_SHAPES = ((6, 8, 64, 2048), (8, 16, 128, 2048), (4, 8, 32, 2048), (8, 8, 64, 2304),
                  (10, 16, 512, 2048), (8, 8, 128, 2048))


def _check_unsort(jax, jnp, np, rng) -> None:
    """The un-sort kernel (``tpuframe_unsort``) against XLA's form
    (``models.moe._sum_choices_impl``) at the six expert cells' shapes, 8,192
    tokens routed as a seeded router routes them (fair, and with the held
    experts' scores lifted unevenly, so that the fullest holds more than the mean,
    and with one tile's 256 tokens all choosing one expert, a run of several
    rounds of windows),
    on two arrays of rows a shape (the forward's and a cotangent's: the
    same call): equal where a token has at most two rows here, within one
    rounding of bfloat16 elsewhere; NaN in the slots past the groups must
    not reach a sum.  Both forms are timed (a line a shape: ms a call, the
    median of 5 laps of 20, and the kernel's share of the least time HBM
    takes to read the routed rows and write every token's; the time passes
    or fails nothing)."""
    import importlib

    from tpuframe.models.moe import _sum_choices_impl, slot_bound

    unsort = importlib.import_module("tpuframe.ops.unsort")
    n = 8192
    for k, held, experts, d in _UNSORT_SHAPES:
        cap = slot_bound(n * k, held, experts)
        for load, lift in (("fair", 0.0), ("lifted", 0.35), ("bunched", 0.0)):
            scores = rng.standard_normal((n, experts))
            scores[:, :held] += lift * rng.random(held)
            if load == "bunched":  # a tile of tokens all choose one expert: further rounds
                scores[256:512, 0] += 10.0
            key = np.argsort(-scores, axis=1)[:, :k].reshape(-1)
            key = np.where(key < held, key, held)
            order = np.argsort(key, kind="stable")
            sizes = np.bincount(key, minlength=held + 1)[:held]
            routed = int(sizes.sum())
            if routed > cap:  # the layer would run a second window: not this section's
                print(json.dumps({"check": "unsort_skipped", "routed": routed, "cap": cap}), flush=True)
                continue
            tok = jnp.asarray((order // k)[:cap], jnp.int32)
            inv = jnp.asarray(np.argsort(order), jnp.int32)
            sizes = jnp.asarray(sizes, jnp.int32)
            live = (jnp.arange(cap) < routed)[:, None]
            here = np.bincount(np.asarray(tok[:routed]), minlength=n)
            xla = jax.jit(functools.partial(_sum_choices_impl, n=n))
            kernel = jax.jit(functools.partial(unsort.unsort, n=n, interpret=False))
            tag = f"unsort_{k}of{experts}_held{held}_{cap}x{d}_{load}"
            line = {"check": "unsort_ms_a_call", "shape": [k, held, experts, cap, d], "load": load,
                    "routed": routed, "fullest_over_mean": float(sizes.max() * held / routed),
                    "window": unsort.unsort_window(cap, d, held, n, jnp.bfloat16)}
            for rows_of in ("fwd", "cotangent"):
                rows = jnp.asarray(rng.standard_normal((cap, d)), jnp.bfloat16)
                want = xla(jnp.where(live, rows, 0), inv).astype(jnp.float32)
                rows = jnp.where(live, rows, jnp.nan)
                got = kernel(rows, tok, sizes).astype(jnp.float32)
                gap = jnp.abs(got - want)
                record(f"{tag}_{rows_of}_equal_at_two_rows_or_fewer",
                       float(jnp.max(jnp.where((here <= 2)[:, None], gap, 0.0))), 1e-30)
                record(f"{tag}_{rows_of}_within_one_rounding",
                       float(jnp.max(gap / jnp.maximum(jnp.abs(want), 1e-3))), 2.0 ** -7)
                if rows_of == "fwd":
                    line["kernel_ms"] = ms = _ms_a_call(jax, kernel, (rows, tok, sizes))
                    line["kernel_pct_of_hbm_roofline"] = 100 * (
                        (routed + n) * d * 2 / 819e9) / (ms * 1e-3)
                    line["xla_ms"] = _ms_a_call(jax, xla, (jnp.where(live, rows, 0), inv))
            print(json.dumps(line), flush=True)


def _check_gated_delta(jax, jnp, np, rng) -> None:
    """The gated delta rule's kernels against the recurrence position by
    position (float32, a ragged length, decays as strong as the published
    initialisation's) and against the scan schedule at
    ``qwen3-next-80b-a3b-instruct``'s shape in bfloat16 (one row of 8192
    positions, 16 key heads and 32 value heads of 128), output and all five
    gradients; there the kernels, the scan schedule and the chunk-local part
    alone are also timed, forward + backward a call (lines of their own; the
    times pass or fail nothing).  Then the two neighbours the configuration
    runs at a width no other has: the head-norm-and-rotary pair with tables
    of 64 of a head's 256 dimensions, and the flash kernels at 256-wide heads
    (16 over 2), each against its oracle."""
    import importlib
    import time

    from tpuframe.models.transformer import rope_tables
    from tpuframe.ops.head_norm_rope import head_norm_rope, head_norm_rope_reference

    gd = importlib.import_module("tpuframe.ops.gated_delta")
    # (at the strongest decays every gamma is 0: no quotient of two zeros)
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))  # noqa: E731
                             / jnp.maximum(jnp.linalg.norm(b.astype(jnp.float32)), 1e-30))

    def inputs(b, l, hk, h, dk, dv, dtype):
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        q = unit(jnp.asarray(rng.standard_normal((b, l, hk, dk)), jnp.float32)) * dk ** -0.5
        k = unit(jnp.asarray(rng.standard_normal((b, l, hk, dk)), jnp.float32))
        v = jnp.asarray(rng.standard_normal((b, l, h, dv)), jnp.float32)
        rates = jnp.asarray(16.0 * (np.arange(h) + 0.5) / h, jnp.float32)
        g = -rates * jax.nn.softplus(jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32) + 1)
        beta = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32))
        ct = jnp.asarray(rng.standard_normal((b, l, h, dv)), dtype)
        return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), ct

    def both(op):
        def run(args, ct):
            y, vjp = jax.vjp(op, *args)
            return (y,) + vjp(ct)
        return jax.jit(run)

    forms = {"kernels": both(functools.partial(gd.gated_delta, interpret=False)),
             "schedule": both(gd.gated_delta_chunked),
             "recurrence": both(gd.gated_delta_reference)}
    parts = ("out", "dq", "dk", "dv", "dg", "dbeta")
    args, ct = inputs(2, 300, 2, 4, 128, 128, jnp.float32)
    want = forms["recurrence"](args, ct)
    for form in ("kernels", "schedule"):
        for part, a, c in zip(parts, forms[form](args, ct), want):
            record(f"gated_delta_f32_ragged_{form}_{part}", rel(a, c), 2e-3)
    args, ct = inputs(1, 8192, 16, 32, 128, 128, jnp.bfloat16)
    got, want = forms["kernels"](args, ct), forms["schedule"](args, ct)
    for part, a, c in zip(parts, got, want):
        record(f"gated_delta_qwen3next_kernels_vs_schedule_{part}", rel(a, c), 2e-2)
    # against float32: what bfloat16 operands cost, the same for both forms
    exact = forms["schedule"](tuple(a.astype(jnp.float32) for a in args), ct.astype(jnp.float32))
    for part, a, c in zip(parts, got, exact):
        record(f"gated_delta_qwen3next_kernels_vs_float32_{part}", rel(a, c), 5e-2)

    def laps(fn, *a):
        jax.block_until_ready(fn(*a))
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(5):
                r = fn(*a)
            jax.block_until_ready(r)
            out.append((time.perf_counter() - t0) / 5)
        return 1e3 * sorted(out)[len(out) // 2]

    forward = jax.jit(functools.partial(gd.gated_delta, interpret=False))
    prepare = both(lambda *a: gd._prepare(*a)[0])
    d_parts = jax.tree.map(lambda a: jnp.ones(a.shape, a.dtype),
                           jax.eval_shape(gd._prepare, *args)[0])
    times = {"kernels_fwd_bwd": laps(forms["kernels"], args, ct),
             "kernels_fwd": laps(forward, *args),
             "schedule_fwd_bwd": laps(forms["schedule"], args, ct),
             "chunk_local_fwd_bwd": laps(prepare, args, d_parts)}
    steps = gd.chunks_walked(1, 8192, 32)
    print(json.dumps({"check": "gated_delta_ms_a_call", "shape": [1, 8192, 16, 32, 128, 128],
                      **times, "chunk_steps_fwd_and_bwd": steps}), flush=True)
    _check_delta_chunk(jax, jnp, gd, inputs, rel, laps, args)

    # the head norms with partial rotary tables at 256-wide heads
    for name, h in (("q", 16), ("k", 2)):
        x = jnp.asarray(rng.standard_normal((1, 8192, h * 256)), jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((1, 8192, h * 256)), jnp.bfloat16)
        scale = jnp.asarray(1 + 0.3 * rng.standard_normal((256,)), jnp.float32)
        cos, sin = rope_tables(8192, 64, 1e7)

        def pair(op):
            def run(x, scale):
                y, vjp = jax.vjp(lambda x, s: op(x, s, cos, sin, num_heads=h, eps=1e-6), x, scale)
                return (y,) + vjp(g)
            return jax.jit(run)

        got = pair(functools.partial(head_norm_rope, interpret=False))(x, scale)
        want = pair(head_norm_rope_reference)(x, scale)
        for part, a, c in zip(("out", "dx", "dscale"), got, want):
            record(f"head_norm_rope_qwen3next_{name}_partial_rotary_{part}", rel(a, c), 2e-2)

    # plain causal flash kernels at 256-wide heads, 16 over 2, 8192 keys
    qkv = tuple(jnp.asarray(0.5 * rng.standard_normal((1, 8192, heads, 256)), jnp.bfloat16)
                for heads in (16, 2, 2))
    _schedule_parity(jax, jnp, "flash_qwen3next_256_wide", qkv, ftol=2e-2, gtol=3e-2)


def _solve_with(jnp, gd, form, top=0):
    """The kernels' solve in other forms, for their price: ``masked``, every
    level two products of the whole (C, C) array with the level's quarters
    of ``a`` masked in (`_inv_unit_lower` as it stands); ``block``, that with
    the ``top`` upper levels as ``T21 = -T22 A21 T11`` a block, products of
    the level's own block side."""
    hi, side = gd._dot, gd._CHUNK
    levels = side.bit_length() - 1

    def solve(a, row, col):
        t = (row == col).astype(jnp.float32)
        for level in range(levels):
            s = 1 << level
            if form == "masked" or level < levels - top:
                quarter = ((((row ^ col) >> (level + 1)) == 0)
                           & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0))
                t = t - hi(hi(t, jnp.where(quarter, a, 0.0)), t)
                continue
            bands = []
            for r in range(0, side, 2 * s):
                t11, t22 = t[r:r + s, r:r + s], t[r + s:r + 2 * s, r + s:r + 2 * s]
                band = [-hi(hi(t22, a[r + s:r + 2 * s, r:r + s]), t11), t22]
                if r:
                    band.insert(0, jnp.zeros((s, r), jnp.float32))
                if side - r - 2 * s:
                    band.append(jnp.zeros((s, side - r - 2 * s), jnp.float32))
                bands += [t[r:r + s], jnp.concatenate(band, axis=1)]
            t = jnp.concatenate(bands, axis=0)
        return t

    return solve


def _check_kda(jax, jnp, np, rng) -> None:
    """The vector-decay delta rule's kernels (`ops.kda`) against the
    recurrence position by position (float32, a ragged length, a channel's
    decay up to -20 a position and mixed inside a head) and against the scan
    schedule at ``kimi-linear-48b-a3b-instruct``'s shape in bfloat16 (one row
    of 4096 positions, 32 heads of 128), output and all five gradients; the
    chunk-local kernels (``tpuframe_kdachunk_fwd`` / ``_again`` / ``_bwd``)
    against XLA's `_prepare` and its transpose at both shapes, parts and
    cotangents; and the op both ways, `_prepare` and the three kernels timed
    side by side, forward and forward + backward a call (a line of its own;
    the times pass or fail nothing)."""
    import importlib
    import time

    kd = importlib.import_module("tpuframe.ops.kda")
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))  # noqa: E731
                             / jnp.maximum(jnp.linalg.norm(b.astype(jnp.float32)), 1e-30))

    def inputs(b, l, h, dk, dv, dtype):
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        q = unit(jnp.asarray(rng.standard_normal((b, l, h, dk)), jnp.float32)) * dk ** -0.5
        k = unit(jnp.asarray(rng.standard_normal((b, l, h, dk)), jnp.float32))
        v = jnp.asarray(rng.standard_normal((b, l, h, dv)), jnp.float32)
        # a head's rate at the quantiles of uniform(1, 16), a channel in four ten times weaker
        rates = jnp.asarray(1.0 + 15.0 * (np.arange(h) + 0.5) / h, jnp.float32)[:, None]
        rates = rates * jnp.asarray([1.0, 1.0, 1.0, 0.1])[jnp.arange(dk) % 4]
        g = -rates * jax.nn.softplus(
            jnp.asarray(rng.standard_normal((b, l, h, dk)), jnp.float32) * 0.3 + 1)
        beta = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32))
        ct = jnp.asarray(rng.standard_normal((b, l, h, dv)), dtype)
        return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), ct

    def both(op):
        def run(args, ct):
            y, vjp = jax.vjp(op, *args)
            return (y,) + vjp(ct)
        return jax.jit(run)

    forms = {"kernels": both(functools.partial(kd.kda, interpret=False)),
             "schedule": both(kd.kda_chunked), "recurrence": both(kd.kda_reference)}
    parts = ("out", "dq", "dk", "dv", "dg", "dbeta")
    args, ct = inputs(2, 300, 4, 128, 128, jnp.float32)
    want = forms["recurrence"](args, ct)
    for form in ("kernels", "schedule"):
        for part, a, c in zip(parts, forms[form](args, ct), want):
            record(f"kda_f32_ragged_{form}_{part}", rel(a, c), 2e-3)
    args, ct = inputs(1, 4096, 32, 128, 128, jnp.bfloat16)
    got, want = forms["kernels"](args, ct), forms["schedule"](args, ct)
    for part, a, c in zip(parts, got, want):
        record(f"kda_kimilinear_kernels_vs_schedule_{part}", rel(a, c), 2e-2)
    # against float32: what bfloat16 operands cost, the same for both forms
    exact = forms["schedule"](tuple(a.astype(jnp.float32) for a in args), ct.astype(jnp.float32))
    for part, a, c in zip(parts, got, exact):
        record(f"kda_kimilinear_kernels_vs_float32_{part}", rel(a, c), 5e-2)

    def laps(fn, *a):
        jax.block_until_ready(fn(*a))
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(5):
                r = fn(*a)
            jax.block_until_ready(r)
            out.append((time.perf_counter() - t0) / 5)
        return 1e3 * sorted(out)[len(out) // 2]

    # the chunk-local part: the kernels against XLA's `_prepare` and its
    # transpose (every part, the solve's T, the five cotangents), float32 at
    # the ragged shape padded to whole chunks, bfloat16 at the cell's
    names = ("u", "w", "qe", "kd", "m", "gamma", "T")
    xla = jax.jit(kd._prepare)
    xla_t = jax.jit(lambda a, t, d: jax.vjp(lambda *a: kd._prepare(*a, t=t)[0], *a)[1](d))
    local_fwd = lambda a: kd._pallas_local_fwd(*a, False)  # noqa: E731
    local_again = lambda a, t: kd._pallas_local_again(*a, t, False)  # noqa: E731
    local_bwd = lambda a, t, d: kd._pallas_local_bwd(*a, t, d, False)  # noqa: E731
    waves = lambda a: jax.tree.map(  # noqa: E731
        lambda x: jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape).astype(x.dtype),
        jax.eval_shape(kd._prepare, *a)[0])
    small = tuple(jnp.pad(a, ((0, 0), (0, 84)) + ((0, 0),) * (a.ndim - 2))
                  for a in inputs(2, 300, 4, 128, 128, jnp.float32)[0])
    d_parts = waves(args)
    for tag, a, d, tol in (("f32_ragged", small, waves(small), 2e-3),
                           ("kimilinear", args, d_parts, 2e-2)):
        want, t = xla(*a)
        got, t_got = local_fwd(a)
        for name, x, y in zip(names, (*got, t_got), (*want, t)):
            record(f"kda_chunk_{tag}_fwd_{name}", rel(x, y), 1e-4 if name == "T" else tol)
        for name, x, y in zip(names, local_again(a, t), want):
            record(f"kda_chunk_{tag}_again_{name}", rel(x, y), tol)
        for name, x, y in zip(parts[1:], local_bwd(a, t, d), xla_t(a, t, d)):
            record(f"kda_chunk_{tag}_bwd_{name}", rel(x, y), tol)
    # the cell's cotangents against float32 inputs': what bfloat16 operands cost either form
    wide = tuple(a.astype(jnp.float32) for a in args)
    exact = xla_t(wide, xla(*wide)[1], jax.tree.map(lambda x: x.astype(jnp.float32), d_parts))
    for form, fn in (("kernels", local_bwd), ("xla", xla_t)):
        for name, x, y in zip(parts[1:], fn(args, t, d_parts), exact):
            record(f"kda_chunk_kimilinear_{form}_vs_float32_{name}", rel(x, y), 5e-2)

    forward = jax.jit(functools.partial(kd.kda, interpret=False))
    xla_both = both(lambda *a: kd._prepare(*a)[0])
    times = {"kernels_fwd_bwd": laps(forms["kernels"], args, ct),
             "kernels_fwd": laps(forward, *args),
             "schedule_fwd_bwd": laps(forms["schedule"], args, ct),
             "schedule_fwd": laps(jax.jit(kd.kda_chunked), *args),
             "chunk_local_xla_fwd": laps(xla, *args),
             "chunk_local_xla_fwd_bwd": laps(xla_both, args, d_parts),
             "chunk_local_xla_transpose": laps(xla_t, args, t, d_parts),
             "chunk_local_kernel_fwd": laps(local_fwd, args),
             "chunk_local_kernel_again": laps(local_again, args, t),
             "chunk_local_kernel_bwd": laps(local_bwd, args, t, d_parts)}
    times["chunk_local_kernels_fwd_bwd"] = sum(
        times[f"chunk_local_kernel_{k}"] for k in ("fwd", "again", "bwd"))
    print(json.dumps({"check": "kda_ms_a_call", "shape": [1, 4096, 32, 128, 128], **times,
                      "chunk_steps_fwd_and_bwd": kd.chunks_walked(1, 4096, 32)}), flush=True)


def _check_delta_chunk(jax, jnp, gd, inputs, rel, laps, args) -> None:
    """The chunk-local kernels (``tpuframe_delta_chunk_fwd`` / ``_again`` /
    ``_bwd``) against XLA's ``_prepare`` and its transpose: every part, the
    solve's ``T`` and the five cotangents, float32 at a cut shape and
    bfloat16 at the cell's (``args``); then each kernel's time a
    layer beside XLA's, and the price of the solve's levels in other forms
    and of other chunks a grid step (lines of their own; the times pass or
    fail nothing)."""
    names = ("u", "w", "qe", "kd", "m", "gamma", "T")
    grads = ("dq", "dk", "dv", "dg", "dbeta")
    xla = jax.jit(gd._prepare)
    xla_t = jax.jit(lambda a, t, d: jax.vjp(lambda *a: gd._prepare(*a, t=t)[0], *a)[1](d))
    chunk_fwd = lambda a: gd._pallas_chunk_fwd(*a, False)  # noqa: E731
    chunk_again = lambda a, t: gd._pallas_chunk_again(*a, t, False)  # noqa: E731
    chunk_bwd = lambda a, t, d: gd._pallas_chunk_bwd(*a, t, d, False)  # noqa: E731
    small, _ = inputs(2, 512, 2, 4, 128, 128, jnp.float32)
    waves = lambda a: jax.tree.map(  # noqa: E731
        lambda x: jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape).astype(x.dtype),
        jax.eval_shape(gd._prepare, *a)[0])
    d_parts = waves(args)
    for tag, a, d, tol, t_tol in (("f32", small, waves(small), 2e-3, 1e-4),
                                  ("qwen3next", args, d_parts, 2e-2, 1e-4)):
        want, t = xla(*a)
        got, t_got = chunk_fwd(a)
        for name, x, y in zip(names, (*got, t_got), (*want, t)):
            record(f"delta_chunk_{tag}_fwd_{name}", rel(x, y), t_tol if name == "T" else tol)
        for name, x, y in zip(names, chunk_again(a, t), want):
            record(f"delta_chunk_{tag}_again_{name}", rel(x, y), tol)
        for name, x, y in zip(grads, chunk_bwd(a, t, d), xla_t(a, t, d)):
            record(f"delta_chunk_{tag}_bwd_{name}", rel(x, y), tol)
    times = {"xla_prepare": laps(xla, *args), "xla_transpose": laps(xla_t, args, t, d_parts),
             "chunk_fwd": laps(chunk_fwd, args), "chunk_again": laps(chunk_again, args, t),
             "chunk_bwd": laps(chunk_bwd, args, t, d_parts)}
    print(json.dumps({"check": "delta_chunk_ms_a_layer", "shape": [1, 8192, 16, 32, 128, 128],
                      **times}), flush=True)

    # the solve's levels in other forms, and other chunks a grid step
    kept = gd._solve, gd._LOCAL_CHUNKS
    prices = {}
    for tag, solve, chunks in (
            ("kernel", gd._solve, kept[1]), ("masked", _solve_with(jnp, gd, "masked"), kept[1]),
            ("block_top1", _solve_with(jnp, gd, "block", 1), kept[1]),
            ("block_top2", _solve_with(jnp, gd, "block", 2), kept[1]),
            ("kernel_chunks2", gd._solve, 2), ("kernel_chunks8", gd._solve, 8)):
        gd._solve, gd._LOCAL_CHUNKS = solve, chunks
        try:
            fn = jax.jit(lambda *a: gd._chunk_parts(*a, None, False))
            got_t = fn(*args)[1]
            prices[tag] = {"ms": laps(fn, *args), "T_rel": rel(got_t, t)}
        except Exception as e:  # Mosaic refuses a form: say so and price the next
            prices[tag] = {"refused": f"{type(e).__name__}: {e}"[:300]}
        finally:
            gd._solve, gd._LOCAL_CHUNKS = kept
    print(json.dumps({"check": "delta_chunk_solve_levels_ms_a_layer", **prices}), flush=True)


def _check_ring(jax, jnp, np, rng) -> None:
    from tpuframe.ops.ring_attention import ring_attention

    mesh = _one_device_seq_mesh(jax, np)
    _attention_parity(
        jax, jnp, "ring_1dev",
        lambda q, k, v: ring_attention(q, k, v, mesh, causal=True,
                                       batch_axes=("data",)),
        _qkv(jnp, rng), causal=True,
    )


def _check_ulysses(jax, jnp, np, rng) -> None:
    from tpuframe.ops.ulysses import ulysses_attention

    mesh = _one_device_seq_mesh(jax, np)
    _attention_parity(
        jax, jnp, "ulysses_1dev",
        lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=True,
                                          batch_axes=("data",)),
        _qkv(jnp, rng), causal=True,
    )


#: section -> (its check, the rows of ``tpuframe/ops/registry.py::OPS_REGISTRY``
#: it holds to their oracles on the chip).  ``tests/test_chip_tools.py`` holds
#: every registry row to a section here or a reason in ``NO_CHIP_CHECK``.
SECTIONS = {
    "layer_norm": (_check_layer_norm, ("layer_norm",)),
    "cross_entropy": (_check_cross_entropy, ("cross_entropy",)),
    "quant_wire": (_check_quant_wire, ("quant_wire",)),
    "blockwise": (_check_blockwise, ("blockwise_attention",)),
    "flash_layout": (_check_flash_layout, ("blockwise_attention",)),
    "window": (_check_window, ("blockwise_attention",)),
    "sparse_index": (_check_sparse_index, ("sparse_index", "blockwise_attention")),
    "ring": (_check_ring, ("ring_attention",)),
    "ulysses": (_check_ulysses, ("ulysses",)),
    "moe_windows": (_check_moe_windows, ("grouped_matmul",)),
    "short_conv": (_check_short_conv, ("short_conv",)),
    "conv_silu": (_check_conv_silu, ("conv_silu",)),
    "head_norm_rope": (_check_head_norm_rope, ("head_norm_rope",)),
    "grouped": (_check_grouped, ("grouped_matmul",)),
    "unsort": (_check_unsort, ("unsort",)),
    "gated_delta": (_check_gated_delta,
                    ("gated_delta", "head_norm_rope", "blockwise_attention")),
    "kda": (_check_kda, ("kda",)),
}

#: registry rows with no section, each with why none is owed
NO_CHIP_CHECK = (
    ("normalize", "plain jnp on every backend, no kernel to refuse; "
                  "chip_smoke.py holds it to its oracle on the chip (normalize_vs_oracle)"),
    ("moe_gating", "pure XLA scatter and gather, the same program on every backend; "
                   "tests/test_moe.py holds it to the dense oracle"),
)


if __name__ == "__main__":
    main()
