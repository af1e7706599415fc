#!/usr/bin/env python
"""TPU perf experiments: A/B the HBM-traffic levers on the real chip.

Run on TPU hardware (plain `python`, one process).
Measures the ResNet50 224px bf16 train step — the PERF.md headline — in
several configurations and prints one JSON line per config:

  baseline      bf16 policy, BN outputs f32 (r02's 2237.7 img/s shape)
  bn_bf16       norm_dtype=bf16: BN emits bf16, killing the f32
                BN->relu->conv activation traffic (PERF.md headroom item)
  batch_256     baseline at batch 256 (sweep point)
  bn_bf16_b256  both
  bn_bf16_b512  bn_bf16 at batch 512 (r04 sweep point)
  uint8_in      uint8 images + fused on-device normalize to bf16 (raw
                bytes over PCIe; no f32 image tensor ever on chip)
  uint8_in_b256 uint8_in at batch 256

Each record carries img/s, MFU, and XLA cost-analysis bytes so PERF.md's
roofline table can attribute the delta.  Safe to re-run: the persistent
compile cache (JAX_COMPILATION_CACHE_DIR) makes repeats cheap.

Usage: python benchmarks/bench_tpu_experiments.py [--steps 30] [--configs a,b]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

CONFIGS = {
    "baseline": dict(batch=128, norm_bf16=False),
    "bn_bf16": dict(batch=128, norm_bf16=True),
    "batch_256": dict(batch=256, norm_bf16=False),
    "bn_bf16_b256": dict(batch=256, norm_bf16=True),
    # r04 headroom sweep (VERDICT r03 #8): batch scaling beyond 256,
    # uint8 input + fused on-device normalize (cuts the input tensor's
    # HBM write+read from f32 to bytes), and both together.  For the
    # XLA latency-hiding scheduler A/B, re-run any config under
    #   XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true"
    # (must be set before jax initializes — not toggleable in-process).
    "bn_bf16_b512": dict(batch=512, norm_bf16=True),
    "uint8_in": dict(batch=128, norm_bf16=True, uint8_input=True),
    "uint8_in_b256": dict(batch=256, norm_bf16=True, uint8_input=True),
}


def run_config(name: str, cfg: dict, steps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe.core.runtime import MeshSpec
    from tpuframe.models import ResNet50
    from tpuframe.parallel import ParallelPlan, align_model_dtype, bf16_compute
    from tpuframe.train import create_train_state, make_train_step

    policy = bf16_compute()
    model = align_model_dtype(
        ResNet50(
            num_classes=1000,
            norm_dtype=jnp.bfloat16 if cfg["norm_bf16"] else None,
        ),
        policy,
    )
    plan = ParallelPlan(mesh=MeshSpec(data=-1).build())
    state = create_train_state(
        model,
        jax.random.PRNGKey(0),
        jnp.ones((1, 224, 224, 3), jnp.float32),
        optax.sgd(0.1, momentum=0.9),
        plan=plan,
        init_kwargs={"train": False},
    )
    rng = np.random.default_rng(0)
    uint8_input = bool(cfg.get("uint8_input"))
    if uint8_input:
        images = rng.integers(0, 256, (cfg["batch"], 224, 224, 3), dtype=np.uint8)
    else:
        images = rng.standard_normal((cfg["batch"], 224, 224, 3)).astype(np.float32)
    batch = plan.shard_batch(
        {
            "image": images,
            "label": rng.integers(0, 1000, (cfg["batch"],)).astype(np.int32),
        }
    )
    # bench.py owns the measurement methodology (timing windows, cost
    # analysis, device-kind peak table) AND the shared uint8 fused
    # normalize
    import bench as headline_bench

    batch_transform = (
        headline_bench.make_uint8_normalize_transform(
            on_accel=jax.default_backend() != "cpu"
        )
        if uint8_input else None
    )

    compiled = (
        make_train_step(policy, batch_transform=batch_transform)
        .lower(state, batch)
        .compile()
    )
    flops, bytes_accessed = headline_bench.cost_analysis(compiled)
    img_s, state, _metrics = headline_bench.time_train_step(
        compiled, state, batch, batch=cfg["batch"], steps=steps
    )
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    peak = (
        headline_bench.device_peaks(device_kind)["bf16_flops_per_s"]
        if backend != "cpu" else None
    )
    return {
        "config": name,
        "batch": cfg["batch"],
        "backend": backend,
        "device_kind": device_kind,
        "images_per_sec": round(img_s, 1),
        "mfu": (
            round(flops * img_s / cfg["batch"] / peak, 4)
            if flops and peak
            else None
        ),
        "hbm_gb_per_step": round(bytes_accessed / 1e9, 2) if bytes_accessed else None,
        "step_ms": round(cfg["batch"] / img_s * 1000, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--configs", default="baseline,bn_bf16")
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
