#!/usr/bin/env python
"""ResNet50 ImageNet-shape train-step throughput on the accelerator.

Runs in ONE process and prints ONE JSON line naming the device it ran on
(``platform``, ``device_kind``, ``chips``).  With no accelerator it exits
non-zero: there is no CPU arm, and a number from a CPU run is never
written under a device metric's name.  Not yet the benchmark of record —
ROADMAP.md S1 builds the multi-cell benchmark on this base.

Also the helpers the ``benchmarks/`` scripts import:
``enable_compile_cache``, ``time_train_step``, ``cost_analysis``,
``make_uint8_normalize_transform``, ``device_peaks``.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline``
compares against an estimate of the reference hardware's capability:
~400 images/sec for ResNet50 mixed-precision training on one A10G (the
per-GPU rate the reference's 4xA10G DDP examples would sustain, matching
the timing hooks at `/root/reference/01_torch_distributor/
01_basic_torch_distributor.py:376-378`).
"""

from __future__ import annotations

import json
import time

# Reference-hardware estimate (A10G, ResNet50, mixed precision), img/s/GPU.
BASELINE_IMG_PER_SEC = 400.0

#: published per-chip peaks, keyed by the exact ``device_kind`` jax reports.
#: A device that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add a row to bench.PEAKS with its source"
        ) from None


def enable_compile_cache() -> str | None:
    """Enable the compile spine's persistent cache
    (``tpuframe.compile.cache``): the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when set, else the one fixed
    in-checkout path — the same resolution ``core.initialize()`` uses,
    so the scripts and the trainer share one cache."""
    from tpuframe.compile import cache as compile_cache

    return compile_cache.enable_from_env()


def make_uint8_normalize_transform(on_accel: bool):
    """Batch transform for raw-uint8 input: on-device normalize emitting
    the compute dtype directly, the trainer's own normalize (elementwise
    jnp that GSPMD shards with the batch).  Shared by bench_e2e.py and
    bench_tpu_experiments.py so the A/B and the e2e bench can never
    diverge on normalize semantics."""
    import jax.numpy as jnp

    from tpuframe.data.transforms import IMAGENET_MEAN, IMAGENET_STD
    from tpuframe.ops import normalize_images

    def batch_transform(b: dict) -> dict:
        b["image"] = normalize_images(
            b["image"], IMAGENET_MEAN, IMAGENET_STD,
            out_dtype=jnp.bfloat16 if on_accel else jnp.float32,
        )
        return b

    return batch_transform


def cost_analysis(compiled) -> tuple[float | None, float | None]:
    """(flops, bytes accessed) per device-step from XLA cost analysis.
    Positives only — a backend may omit an entry or report the -1
    "unknown" sentinel."""
    ca = compiled.cost_analysis() or {}
    f = float(ca.get("flops", -1.0))
    b = float(ca.get("bytes accessed", -1.0))
    return (f if f > 0 else None, b if b > 0 else None)


def time_train_step(compiled, state, data, *, batch: int, steps: int,
                    rounds: int = 3):
    """Median images/sec over ``rounds`` timed windows of ``steps`` steps.

    Warms up twice, then ends every timed window with a *value readback*
    of the step counter inside the window: the donated state chain paces
    the loop to real execution, and the readback closes it, so the
    recorded rate never counts un-executed dispatches.  Returns
    ``(images_per_sec, final_state, final_metrics)``.  The one timing
    methodology for bench.py and the perf-experiment harness — fixes
    here reach both.
    """
    import jax
    import numpy as np

    for _ in range(2):
        state, metrics = compiled(state, data)
    jax.block_until_ready((state, metrics))
    _ = int(state.step)
    rates = []
    for _ in range(rounds):
        step_before = int(state.step)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = compiled(state, data)
        step_now = int(state.step)
        # INVARIANT the timing depends on: ``state.step`` must be an
        # output of the SAME compiled program as the training math, so the
        # readback above transitively waits for the whole step.  If a
        # refactor ever computes metrics in a separate dispatch, this
        # INSIDE-the-window readiness wait charges that dispatch to the
        # measured time (free when metrics ride the same program — they
        # are already ready), so the window can't silently under-report.
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - t0
        assert step_now == step_before + steps
        rates.append(batch * steps / elapsed)
    assert np.isfinite(float(metrics["loss_sum"]))
    return sorted(rates)[len(rates) // 2], state, metrics


def main() -> None:
    import jax

    if jax.default_backend() == "cpu":
        raise SystemExit(
            "bench.py needs an accelerator: jax.default_backend() is 'cpu' "
            "(a CPU timing is not a device metric; there is no CPU arm)"
        )
    device_kind = jax.devices()[0].device_kind
    peaks = device_peaks(device_kind)  # unknown device: fail before compiling

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe.core.runtime import MeshSpec
    from tpuframe.models import ResNet50
    from tpuframe.parallel import ParallelPlan, align_model_dtype, bf16_compute
    from tpuframe.train import create_train_state, make_train_step

    chips = jax.local_device_count()
    batch, size, steps = 128 * chips, 224, 60

    # Data-parallel over every local device so the per-chip division below
    # reflects work actually placed on each chip.
    plan = ParallelPlan(mesh=MeshSpec(data=-1).build())

    policy = bf16_compute()
    # Model compute dtype must match the policy: an f32 model under a bf16
    # policy silently up-casts inside every layer.  BN outputs in bf16
    # (running stats stay f32) cut the f32 BN→relu→conv activation traffic.
    model = align_model_dtype(
        ResNet50(num_classes=1000, norm_dtype=jnp.bfloat16), policy
    )
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_train_state(
        model,
        jax.random.PRNGKey(0),
        jnp.ones((1, size, size, 3), jnp.float32),
        tx,
        plan=plan,
        init_kwargs={"train": False},
    )
    step_fn = make_train_step(policy)

    rng = np.random.default_rng(0)
    data = plan.shard_batch(
        {
            "image": rng.standard_normal((batch, size, size, 3)).astype(np.float32),
            "label": rng.integers(0, 1000, (batch,)).astype(np.int32),
        }
    )

    # AOT-compile once and reuse the executable for warmup + benchmark
    # (jit's call path would not share the AOT cache — compiling twice
    # costs minutes).  Cost analysis reports the FLOPs of the *per-device*
    # partitioned program, with the analytic ResNet50 count as fallback.
    compiled = step_fn.lower(state, data).compile()
    flops_per_dev_step, bytes_per_dev_step = cost_analysis(compiled)
    # FLOP convention (stated once, used everywhere): 2 FLOP per MAC —
    # the same convention XLA's cost analysis uses.  ResNet50 at 224px is
    # ~4.09 GMAC forward/image => 2*4.09 GFLOP fwd, x3 for fwd+bwd.
    analytic = 3 * 2 * 4.09e9 * batch / chips
    flops_source = "xla_cost_analysis"
    if flops_per_dev_step is None:
        flops_per_dev_step, flops_source = analytic, "analytic_2flop_per_mac"
    else:
        # Both paths should agree (same convention); ~10% slack covers XLA
        # counting non-conv ops.  A disagreement flags the record.
        ratio = flops_per_dev_step / analytic
        if not 0.9 < ratio < 1.1:
            flops_source = f"xla_cost_analysis(conflicts_analytic_{ratio:.2f}x)"

    global_img_s, state, metrics = time_train_step(
        compiled, state, data, batch=batch, steps=steps
    )
    value = global_img_s / chips
    # Per-device FLOP rate vs the chip's peak: the per-device program
    # runs (global images/sec / batch) steps/sec on every chip.
    mfu = round(
        flops_per_dev_step * global_img_s / batch / peaks["bf16_flops_per_s"], 4
    )

    print(
        json.dumps(
            {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": round(value, 2),
                "unit": f"images/sec/chip (batch={batch}, {size}px, bf16)",
                "vs_baseline": round(value / BASELINE_IMG_PER_SEC, 3),
                "platform": jax.devices()[0].platform,
                "device_kind": device_kind,
                "chips": chips,
                "mfu": mfu,
                "flops_source": flops_source,
                # per-device HBM traffic from XLA cost analysis; None when
                # the backend omits it
                "hbm_gb_per_step": (
                    round(bytes_per_dev_step / 1e9, 2) if bytes_per_dev_step else None
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
