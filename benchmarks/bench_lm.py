#!/usr/bin/env python
"""Transformer-LM train-step benchmark: tokens/sec + MFU on the chip.

The ResNet50 headline (bench.py) is HBM-bandwidth-bound (PERF.md
roofline); this script measures the MXU-bound side of the framework — a
decoder-only TransformerLM train step — plus the long-context path
(blockwise flash-style attention) that the reference has no counterpart
for.  One JSON line per config:

  gpt_small   GPT-2-small shape (12x12x64, seq 1024; 136M params with
              the untied 32k-vocab head) — the standard MFU yardstick
  long_ctx    same width at seq 8192, batch scaled down, attn_impl
              "auto" takes the blockwise linear-memory path
  long_remat  seq 8192 with block rematerialization (the memory-bound
              recipe: activation memory O(1) blocks for ~1/3 extra FLOPs)

Reuses bench.py's methodology (timing windows, XLA cost analysis,
device-peak table, preflight) so numbers are comparable with the
headline.  Usage: python benchmarks/bench_lm.py [--steps 20] [--configs ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

CONFIGS = {
    "gpt_small": dict(seq=1024, batch=16, remat=False),
    "long_ctx": dict(seq=8192, batch=2, remat=False),
    "long_remat": dict(seq=8192, batch=2, remat=True),
}

VOCAB = 32768
LAYERS, HEADS, HEAD_DIM = 12, 12, 64


def run_config(name: str, cfg: dict, steps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe.core.runtime import MeshSpec
    from tpuframe.models import TransformerLM
    from tpuframe.parallel import ParallelPlan, align_model_dtype, bf16_compute
    from tpuframe.train import create_train_state, make_train_step

    import bench as headline_bench

    policy = bf16_compute()
    model = align_model_dtype(
        TransformerLM(
            vocab_size=VOCAB,
            num_layers=LAYERS,
            num_heads=HEADS,
            head_dim=HEAD_DIM,
            max_len=cfg["seq"],
            attn_impl="auto",
            remat=cfg["remat"],
        ),
        policy,
    )
    plan = ParallelPlan(mesh=MeshSpec(data=-1).build())
    # config batch is per chip; the data mesh spans every local device and
    # shard_batch requires divisibility (bench.py scales the same way)
    batch_size = cfg["batch"] * max(jax.local_device_count(), 1)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (batch_size, cfg["seq"])).astype(np.int32)
    state = create_train_state(
        model,
        jax.random.PRNGKey(0),
        jnp.asarray(tokens[:1]),
        optax.adamw(3e-4),
        plan=plan,
        init_kwargs={"train": False},
    )
    batch = plan.shard_batch(
        {"input": tokens, "label": np.roll(tokens, -1, axis=1)}
    )
    compiled = make_train_step(policy).lower(state, batch).compile()
    flops, bytes_accessed = headline_bench.cost_analysis(compiled)
    img_s, state, _metrics = headline_bench.time_train_step(
        compiled, state, batch, batch=batch_size, steps=steps
    )
    tokens_s = img_s * cfg["seq"]
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    peak = (
        headline_bench.device_peaks(device_kind)["bf16_flops_per_s"]
        if backend != "cpu" else None
    )
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    return {
        "config": name,
        "seq_len": cfg["seq"],
        "batch": batch_size,
        "params_m": round(n_params / 1e6, 1),
        "backend": backend,
        "device_kind": device_kind,
        "tokens_per_sec": round(tokens_s, 0),
        # MFU against XLA's own FLOP count for the compiled step (includes
        # remat recompute, so the long_remat row reports hardware
        # utilization, not "useful-FLOP" MFU)
        "mfu": (
            round(flops * img_s / batch_size / peak, 4)
            if flops and peak
            else None
        ),
        "hbm_gb_per_step": round(bytes_accessed / 1e9, 2) if bytes_accessed else None,
        "step_ms": round(batch_size / img_s * 1000, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--configs", default="gpt_small,long_ctx,long_remat")
    args = ap.parse_args()

    import jax

    import bench as headline_bench

    headline_bench.enable_compile_cache()

    print(f"# backend={jax.default_backend()} devices={jax.devices()}", file=sys.stderr)
    for name in args.configs.split(","):
        name = name.strip()
        out = run_config(name, CONFIGS[name], args.steps)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
