#!/usr/bin/env python
"""Compile-spine benchmark: measured time-to-first-step, not assumed.

Four child processes, one JSON line.  Each child runs the same tiny fit
(MnistNet on synthetic data, a simulated per-item decode cost so the
loader has a real warmup to overlap) and reports the wall from
``fit()`` start to the first completed train step:

- **cold**      fresh compilation cache, no AOT — today's baseline:
                loader warmup + trace + backend compile + step, serialized.
- **warm**      same cache dir again (a restart / a new rank on the
                host): the backend compile is a cache retrieval.
- **aot**       fresh cache, ``Trainer.precompile()`` auto-overlap: the
                compile runs in a background thread while the
                DataLoader/ring-buffer spins up, so the first step costs
                ``max(compile, loader warmup)`` instead of their sum.
- **warm_aot**  both — the production steady state for a supervised
                restart: retrieval overlapped with loader warmup.

The committed record carries a ``time_to_first_step`` block, so
``python -m tpuframe.track analyze --baseline benchmarks/results/``
regression-gates compile/startup time exactly like step time (exit 3).

CPU-friendly by design; on a TPU host the same script prices the real
XLA compile (on chip: not measured).

Usage: python benchmarks/bench_compile.py [--steps N] [--batch N]
           [--item-cost-ms F] [--image-size N]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

MODES = ("cold", "warm", "aot", "warm_aot")


class SlowDataset:
    """Synthetic dataset with a fixed per-item cost — the stand-in for
    JPEG decode + augmentation, declared in the committed record so the
    number is honest about what it simulates."""

    def __init__(self, inner, item_cost_ms: float):
        self.inner = inner
        self.item_cost_s = item_cost_ms / 1e3
        self.num_classes = inner.num_classes

    def __len__(self):
        return len(self.inner)

    def __getitem__(self, i):
        time.sleep(self.item_cost_s)
        return self.inner[i]


def run_child(args) -> None:
    """One measured fit; mode semantics live in the env the driver set."""
    from tpuframe.compile import cache as compile_cache
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.models import MnistNet
    from tpuframe.train import Callback, Trainer
    from tpuframe.track.telemetry import get_telemetry

    precompile = bool(int(os.environ.get("BENCH_PRECOMPILE", "0")))
    # enable explicitly (the dir came from the driver) so the listener
    # counters below see every compile of this process
    compile_cache.enable(os.environ["TPUFRAME_COMPILE_CACHE"])

    n = args.batch * args.steps
    ds = SlowDataset(
        SyntheticImageDataset(
            n=n, image_size=args.image_size, channels=1, num_classes=4, seed=0
        ),
        args.item_cost_ms,
    )

    first_step_t: list[float] = []

    class FirstStep(Callback):
        def on_step_end(self, trainer) -> None:
            if not first_step_t:
                first_step_t.append(time.perf_counter())

    tr = Trainer(
        MnistNet(num_classes=4),
        train_dataloader=DataLoader(
            ds, batch_size=args.batch, shuffle=True, seed=3
        ),
        max_duration="1ep",
        eval_interval=0,
        log_interval=0,
        callbacks=[FirstStep()],
        precompile=precompile,
    )
    reg = get_telemetry().registry
    t0 = time.perf_counter()
    tr.fit()
    fit_wall = time.perf_counter() - t0

    import jax

    snap = reg.snapshot()
    print(json.dumps({
        "mode": args.child,
        "ttfs_s": round(first_step_t[0] - t0, 4),
        "fit_wall_s": round(fit_wall, 4),
        "precompile": precompile,
        "precompile_wall_s": (tr._precompile_report or {}).get("wall_s"),
        "cache_hits": snap.get("compile/cache_hits", 0.0),
        "cache_misses": snap.get("compile/cache_misses", 0.0),
        "backend_compiles": snap.get("compile/backend_compiles", 0.0),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }))


def run_driver(args) -> None:
    """Spawn one fresh process per mode (cold really is cold: no live
    jit caches carry over), aggregate, emit the committed record."""
    cache_lazy = tempfile.mkdtemp(prefix="tpuframe_bcompile_lazy_")
    cache_aot = tempfile.mkdtemp(prefix="tpuframe_bcompile_aot_")
    plan = {
        "cold": (cache_lazy, 0),
        "warm": (cache_lazy, 0),
        "aot": (cache_aot, 1),
        "warm_aot": (cache_aot, 1),
    }
    results: dict[str, dict] = {}
    for mode in MODES:
        cache_dir, pre = plan[mode]
        env = dict(os.environ)
        env.update(
            TPUFRAME_COMPILE_CACHE=cache_dir,
            BENCH_PRECOMPILE=str(pre),
            TPUFRAME_PRECOMPILE=str(pre),
        )
        argv = [sys.executable, os.path.abspath(__file__), "--child", mode,
                "--steps", str(args.steps), "--batch", str(args.batch),
                "--item-cost-ms", str(args.item_cost_ms),
                "--image-size", str(args.image_size)]
        proc = subprocess.run(
            argv, env=env, capture_output=True, text=True, timeout=600
        )
        if proc.returncode != 0:
            print(proc.stderr[-2000:], file=sys.stderr)
            raise SystemExit(f"child {mode} failed rc={proc.returncode}")
        results[mode] = json.loads(proc.stdout.strip().splitlines()[-1])

    cold = results["cold"]["ttfs_s"]
    warm = results["warm"]["ttfs_s"]
    aot = results["aot"]["ttfs_s"]
    warm_aot = results["warm_aot"]["ttfs_s"]
    first_batch_s = args.item_cost_ms / 1e3 * args.batch
    print(json.dumps({
        "metric": "time_to_first_step_s",
        # headline: the steady-state restart number (warm cache + AOT
        # overlap) — what a supervised restart or new same-host rank pays
        "value": warm_aot,
        "unit": ("seconds from fit() start to first completed train step "
                 f"(MnistNet {args.image_size}px b{args.batch}, "
                 f"{args.item_cost_ms}ms simulated per-item decode, "
                 f"{results['cold']['backend']})"),
        "backend": results["cold"]["backend"],
        "device_kind": results["cold"]["device_kind"],
        "modes": results,
        "loader_first_batch_s": round(first_batch_s, 4),
        "speedup_warm_vs_cold": round(cold / warm, 3),
        "speedup_aot_vs_cold": round(cold / aot, 3),
        "speedup_warm_aot_vs_cold": round(cold / warm_aot, 3),
        # the baseline-gate block: analyze --baseline diffs measured
        # time-to-first-step against this and exits 3 on regression
        "time_to_first_step": {
            "s": warm_aot,
            "cold_s": cold,
            "warm_s": warm,
            "aot_s": aot,
            "warm_aot_s": warm_aot,
        },
    }))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--item-cost-ms", type=float, default=15.0)
    p.add_argument("--image-size", type=int, default=28)
    p.add_argument("--child", choices=MODES, default=None,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child:
        run_child(args)
    else:
        run_driver(args)


if __name__ == "__main__":
    main()
