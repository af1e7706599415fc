#!/usr/bin/env python
"""Device-time capture self-test benchmark: a sampled capture prices itself.

Four numbers, one instrumented CPU/TPU fit:

- **armed overhead** — A/B p50 step-wall medians of the same fit with the
  cadence ``ProfilerCallback`` absent vs armed-but-out-of-window (the
  "leave ``TPUFRAME_PROFILE_*`` set on a week-long run" claim: steps
  outside a capture window must pay ≤2% — out-of-window the callback is
  one integer compare per step);
- **capture cost** — extra total wall per sampled window (start_trace +
  traced steps + stop_trace serialization), the real price one window
  costs; amortized over ``TPUFRAME_PROFILE_EVERY`` steps by the operator
  (the committed record shows the division for this fit's cadence);
- **parse throughput** — raw trace events per second through the stdlib
  gzip+json parser (``track/device_time.py``) over the capture the fit
  just wrote (the parser must stay cheap enough for a post-job hook /
  the doctor);
- the **device_time block** — the profiled fit's own skew report parsed
  back, committed so ``analyze --baseline benchmarks/results/``
  regression-diffs every future run's exposed-comms and device-step
  seconds against this one (exit 3 on growth past threshold).

On a TPU host the same script prices the real XLA capture (CPU captures
are dominated by host TraceMe serialization — megabytes per window for
a toy fit — which is why capture cost is reported per window, not
buried in a total); on chip: not measured.

Usage: python benchmarks/bench_profile.py [--steps-per-epoch N]
           [--epochs N] [--reps N] [--keep-dir]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def run_fit(tele_dir: str, args, *, mode: str, profile_dir: str | None = None):
    """One fit.  ``mode``: "off" (no profiler callback), "armed" (cadence
    callback attached, first window scheduled past the end of the run —
    prices the steady-state per-step tax), "capture" (real sampled
    windows into ``profile_dir``)."""
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.models import MnistNet
    from tpuframe.track import ProfilerCallback, telemetry
    from tpuframe.train import Trainer

    tele = telemetry.configure(jsonl_dir=tele_dir)
    callbacks = []
    prof = None
    total_steps = args.steps_per_epoch * args.epochs
    if mode == "armed":
        prof = ProfilerCallback(
            logdir=profile_dir, skip_steps=total_steps + 1000,
            num_steps=2, every_steps=16,
        )
    elif mode == "capture":
        prof = ProfilerCallback(
            logdir=profile_dir, skip_steps=1, num_steps=2,
            every_steps=16, keep=3,
        )
    if prof is not None:
        callbacks.append(prof)
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
        callbacks=callbacks,
    )
    t0 = time.perf_counter()
    trainer.fit()
    wall = time.perf_counter() - t0
    # the dispatch's host time, from the loop's own span
    p50 = tele.registry.histogram("span/train/step").summary().get("p50", 0.0)
    telemetry.reset()  # flush + close the JSONL sink before reading it back
    return {
        "wall_s": wall,
        "steps": trainer.batches_seen,
        "p50_s": p50,
        "prof": prof,
    }


def parse_throughput(capture_dir: str, *, min_wall_s: float = 0.2) -> dict:
    """Raw trace events/second through the full parse path (gzip + json +
    classification + interval math -> one device_time record)."""
    from tpuframe.track.device_time import (
        device_time_report,
        find_trace_files,
        load_trace,
    )

    raw_events = sum(
        len(load_trace(f).get("traceEvents") or [])
        for f in find_trace_files(capture_dir)
    )
    reps = 0
    t0 = time.perf_counter()
    while True:
        device_time_report(capture_dir)
        reps += 1
        wall = time.perf_counter() - t0
        if wall >= min_wall_s and reps >= 3:
            break
    return {
        "raw_trace_events": raw_events,
        "parse_reps": reps,
        "parse_wall_s": round(wall, 4),
        "events_per_sec": round(raw_events * reps / max(wall, 1e-9)),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps-per-epoch", type=int, default=24)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--reps", type=int, default=3,
                    help="off/armed A/B pairs for the overhead medians")
    ap.add_argument("--keep-dir", action="store_true",
                    help="print + keep the capture/telemetry dirs")
    args = ap.parse_args()

    import jax

    from tpuframe.track import analyze
    from tpuframe.track.device_time import list_captures

    root = tempfile.mkdtemp(prefix="tpuframe_bench_profile_")
    prof_dir = os.path.join(root, "captures")
    tele_prof = os.path.join(root, "tele_capture")
    try:
        # warmup fit: compile cache hot before any arm is timed
        run_fit(os.path.join(root, "tele_warm"), args, mode="off")

        off, armed = [], []
        for rep in range(max(1, args.reps)):
            off.append(run_fit(
                os.path.join(root, f"tele_off{rep}"), args, mode="off"))
            armed.append(run_fit(
                os.path.join(root, f"tele_armed{rep}"), args, mode="armed"))
        off_p50 = statistics.median(r["p50_s"] for r in off)
        armed_p50 = statistics.median(r["p50_s"] for r in armed)
        off_wall = statistics.median(r["wall_s"] for r in off)
        armed_overhead_pct = 100.0 * (armed_p50 - off_p50) / off_p50

        cap = run_fit(tele_prof, args, mode="capture", profile_dir=prof_dir)
        prof = cap["prof"]
        n_caps = len(prof.captures)
        assert n_caps, "cadence callback produced no capture"
        capture_cost_s = max(0.0, cap["wall_s"] - off_wall) / n_caps
        # this fit's cadence amortization: one window's cost spread over
        # the steps between window starts, as a fraction of step wall
        amortized_pct = 100.0 * (capture_cost_s / prof.every_steps) / off_p50

        parse = parse_throughput(list_captures(prof_dir)[-1])

        # the profiled fit analyzes itself: the capture events in its
        # telemetry become the report's device_time block
        report = analyze.skew_report(analyze.load_dir(tele_prof))
        dt = report["device_time"]
        assert dt is not None, "skew report did not attach device_time"
    finally:
        if args.keep_dir:
            print(f"bench dirs kept: {root}", file=sys.stderr)
        else:
            shutil.rmtree(root, ignore_errors=True)

    rec = {
        "metric": "profile_selftest",
        "value": parse["events_per_sec"],
        "unit": "raw trace events parsed per second "
                "(gzip+json -> device_time record)",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "fit": {
            "steps": cap["steps"],
            "wall_off_s": round(off_wall, 3),
            "wall_capture_s": round(cap["wall_s"], 3),
            "step_p50_off_s": round(off_p50, 6),
            "step_p50_armed_s": round(armed_p50, 6),
            "reps": max(1, args.reps),
        },
        # the <=2% gate: steps outside a capture window (one integer
        # compare per step when armed)
        "armed_overhead_pct": round(armed_overhead_pct, 2),
        # the real price of one sampled window, and what it amortizes to
        # at this fit's cadence (every_steps) — the operator's dial
        "capture_cost_s": round(capture_cost_s, 3),
        "amortized_overhead_pct": round(amortized_pct, 2),
        "every_steps": prof.every_steps,
        "captures": n_caps,
        "capture_bytes": sum(c["bytes"] for c in prof.captures),
        "parse": parse,
        # the regression-diff anchors: step_time p50/p95 and the
        # device-level exposed-comms / device-step seconds (exit 3)
        "step_time": report["step_time"],
        "device_time": {
            "schema_version": dt["schema_version"],
            "steps": dt["steps"],
            "device_tracks": dt["device_tracks"],
            "window_s": dt["window_s"],
            "busy_s": dt["busy_s"],
            "idle_s": dt["idle_s"],
            "exposed_comms_s": dt["exposed_comms_s"],
            "exposed_comms_per_step_s": dt["exposed_comms_per_step_s"],
            "device_step_s": dt["device_step_s"],
            "overlap_efficiency": dt["overlap_efficiency"],
            "top_ops": dt["top_ops"][:5],
        },
    }
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
