#!/usr/bin/env python
"""Time the attention op alone, on one device, no mesh.

``--standalone B,L,H,D[,Dv]``: causal bf16 attention at that shape
through the flash kernels, the scan schedule and ``attention_reference``,
forward and forward + backward under one ``jax.grad``, with each
compiled program's temporaries — the figures that size moving a shape
onto the kernels (PERF.md section 6, PR 30).  ``--layers N`` chains N
calls in one program, each call's output the next one's queries, and
reports the time a call: one call of a short sequence takes less than
the host takes to dispatch it (0.23 ms forward, 0.5 ms forward +
backward on the v5e's host), so only a chain reads it.  On a non-TPU
host the kernels run in interpret mode (the only way the kernel code
runs here).

Which form ``attn_impl="auto"`` takes is ``models/transformer.py``'s
``_resolve_impl``; a change to it is a PR that a benchmark cell
measures, and this script only sizes the candidate.

Usage: python benchmarks/bench_attention.py --standalone 4,1024,16,64 [--layers 24]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def standalone(shape: tuple[int, ...], calls: int, layers: int = 1) -> int:
    """One JSON line per implementation: ms a call (of ``layers`` chained
    in a program), forward and forward + backward, and the MiB of
    temporaries of each compiled program."""
    import time

    import jax
    import jax.numpy as jnp

    from tpuframe.ops.blockwise_attention import (
        blockwise_attention,
        blockwise_attention_reference,
    )
    from tpuframe.ops.ring_attention import attention_reference

    b, l, h, d = shape[:4]
    dv = shape[4] if len(shape) > 4 else d
    if layers > 1 and dv != d:
        raise SystemExit("--layers chains outputs into queries: one head width")
    interpret = None if jax.default_backend() == "tpu" else True
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    qkv = tuple(jax.random.normal(k, (b, l, h, w), jnp.bfloat16) * 0.5
                for k, w in zip(keys, (d, d, dv)))
    impls = {
        "flash_kernels": lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, interpret=interpret),
        "scan_schedule": lambda q, k, v: blockwise_attention_reference(
            q, k, v, causal=True),
        "attention_reference": lambda q, k, v: attention_reference(
            q, k, v, causal=True),
    }

    def chained(f):
        def run(q, k, v):
            for _ in range(layers):
                q = f(q, k, v)
            return q
        return run

    def ms_and_mib(fn):
        compiled = fn.lower(*qkv).compile()
        jax.block_until_ready(compiled(*qkv))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = compiled(*qkv)
        jax.block_until_ready(out)
        return ((time.perf_counter() - t0) / (calls * layers) * 1e3,
                compiled.memory_analysis().temp_size_in_bytes / 2**20)

    dev = jax.devices()[0]
    for name, f in impls.items():
        f = chained(f)
        rec = {"metric": "attention_standalone", "impl": name,
               "shape": [b, l, h, d, dv], "dtype": "bfloat16", "causal": True,
               "calls": calls, "layers": layers, "backend": dev.platform,
               "device_kind": dev.device_kind,
               "pallas_interpret": bool(interpret)}
        try:
            rec["fwd_ms"], rec["fwd_temp_mib"] = ms_and_mib(jax.jit(f))
            rec["fwd_bwd_ms"], rec["fwd_bwd_temp_mib"] = ms_and_mib(jax.jit(jax.grad(
                lambda *a, f=f: jnp.sum(f(*a).astype(jnp.float32) ** 2), (0, 1, 2))))
        except Exception as e:  # a shape an implementation cannot hold
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(rec), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--standalone", required=True, metavar="B,L,H,D[,Dv]",
                    help="the shape to time the op at (bf16, causal)")
    ap.add_argument("--calls", type=int, default=24,
                    help="timed calls per program")
    ap.add_argument("--layers", type=int, default=1,
                    help="calls of the op chained in one program")
    args = ap.parse_args()
    return standalone(tuple(int(x) for x in args.standalone.split(",")),
                      args.calls, args.layers)


if __name__ == "__main__":
    raise SystemExit(main())
