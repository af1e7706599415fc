#!/usr/bin/env python
"""Analyzer self-test benchmark: an instrumented fit analyzes itself.

Runs a short CPU-friendly training fit with the telemetry JSONL sink on,
then points ``tpuframe.track.analyze`` at the run's own telemetry dir and
reports:

- ``step_time`` — the fit's per-step dispatch distribution (this block is
  exactly what ``analyze --baseline`` diffs against, so committing this
  record makes every future run regression-checkable);
- ``skew`` — the cross-rank skew aggregates (single-rank on CI: the
  interesting number is that the pipeline runs, not the skew itself);
- ``trace_events`` + ``analyze_wall_s`` — the analyzer's own cost over
  the log it just produced (events parsed per second: the analyzer must
  stay cheap enough to run in a post-job hook).

On a TPU host the same script prices the real step distribution
(on chip: not measured).

Usage: python benchmarks/bench_analyze.py [--steps-per-epoch N]
           [--epochs N] [--keep-dir]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def run_fit(tele_dir: str, args) -> dict:
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.models import MnistNet
    from tpuframe.track import telemetry
    from tpuframe.train import Trainer

    telemetry.configure(jsonl_dir=tele_dir)
    ds = SyntheticImageDataset(
        n=16 * args.steps_per_epoch, image_size=28, channels=1,
        num_classes=4, seed=0,
    )
    trainer = Trainer(
        MnistNet(num_classes=4),
        train_dataloader=DataLoader(ds, batch_size=16, shuffle=True, seed=3),
        max_duration=f"{args.epochs}ep",
        eval_interval=0,
        log_interval=0,
        straggler_sync_steps=8,
    )
    t0 = time.perf_counter()
    trainer.fit()
    fit_wall = time.perf_counter() - t0
    telemetry.reset()  # flush + close the JSONL sink before reading it back
    return {
        "fit_wall_s": round(fit_wall, 3),
        "steps": trainer.batches_seen,
    }


def analyze_dir(tele_dir: str) -> dict:
    from tpuframe.track import analyze

    t0 = time.perf_counter()
    ranks = analyze.load_dir(tele_dir)
    report = analyze.skew_report(ranks)
    trace = analyze.build_trace(ranks)
    wall = time.perf_counter() - t0
    events = sum(len(r.events) for r in ranks)
    return {
        "report": report,
        "events_parsed": events,
        "trace_events": len(trace["traceEvents"]),
        "analyze_wall_s": round(wall, 4),
        "events_per_sec": round(events / max(wall, 1e-9)),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps-per-epoch", type=int, default=24)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--keep-dir", action="store_true",
                    help="print + keep the telemetry dir for inspection")
    args = ap.parse_args()

    import jax

    tele_dir = tempfile.mkdtemp(prefix="tpuframe_bench_analyze_")
    try:
        fit = run_fit(tele_dir, args)
        an = analyze_dir(tele_dir)
    finally:
        if args.keep_dir:
            print(f"telemetry dir kept: {tele_dir}", file=sys.stderr)
        else:
            shutil.rmtree(tele_dir, ignore_errors=True)

    report = an["report"]
    rec = {
        "metric": "analyze_selftest",
        "value": an["events_per_sec"],
        "unit": "telemetry events parsed+analyzed per second "
                "(merge + skew table + Perfetto trace)",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "fit": fit,
        # the regression-diff anchor: `analyze --baseline` compares p50/p95
        "step_time": report["step_time"],
        "skew": {
            "ranks": report["ranks"],
            "steps": report["steps"],
            "total_lost_s": report["total_lost_s"],
            "straggler_lost_s": report["straggler_lost_s"],
            "straggling_steps": report["straggling_steps"],
        },
        "events_parsed": an["events_parsed"],
        "trace_events": an["trace_events"],
        "analyze_wall_s": an["analyze_wall_s"],
    }
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
