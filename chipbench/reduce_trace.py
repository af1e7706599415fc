"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be held to a recorded chip trace on CPU:

1. ``flatten(xplane.pb)`` reads the trace with ``jax.profiler.ProfileData``
   and keeps, per device plane, the ``XLA Ops`` and ``XLA Modules`` lines,
   and from the host plane the harness's own ``chipbench/*`` annotations:
   ``{"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
   "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``.
2. ``reduce(flat, steps)`` does the interval arithmetic over the last
   ``steps`` executions of the step program: device busy time (union of op
   intervals), the traced window, time by operation, kernel time, exposed
   collective time, and idle gaps by what the host was doing.

All times leave here in seconds; ``per step`` divisions are the readers'.
"""

from __future__ import annotations

import glob
import itertools
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "chipbench/"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
#: idle gaps shorter than this are scheduling noise between two operations
MIN_GAP_NS = 2_000
_KERNEL = re.compile(r"tpuframe_[a-z0-9_]+")
_OP_ID = re.compile(r"\.\d+(?=_|$)")
GAP_NAMES = {"chipbench/data_wait": "data_wait", "chipbench/host_block": "host_block",
             "chipbench/dispatch": "between_programs"}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(hlo: str) -> str:
    """``%reshape.375 = bf16[256,224,224,3]{...} reshape(...)`` ->
    ``reshape.375_bf16[256,224,224,3]``: the instruction and what it makes.
    A Pallas kernel is named by its call: ``tpuframe_normalize``."""
    lhs, _, rhs = hlo.partition(" = ")
    lhs = lhs.strip().lstrip("%")
    kernel = _KERNEL.search(lhs)
    if kernel and "custom-call(" in rhs:
        # jvp_/transpose_ prefixes and trailing underscores are autodiff's
        return kernel.group(0).rstrip("_")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0].strip().lstrip("(").rstrip(",")
    return f"{lhs}_{shape}" if shape and shape != lhs else lhs


def flatten(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    flat = {"devices": {}, "host": [], "planes": []}
    for plane in data.planes:
        flat["planes"].append(plane.name)
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            if dev["ops"]:
                flat["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                flat["host"] += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                                 if e.name.startswith(HOST_PREFIX)]
    return flat


# -- interval arithmetic ---------------------------------------------------
def union(intervals: list) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: list, b: list) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def overlap(s: float, e: float, spans: list) -> float:
    return float(sum(max(0.0, min(e, b) - max(s, a)) for a, b in spans))


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def traced_window(devices: dict, steps: int) -> tuple[float, float]:
    """From the start of the ``steps``-th last execution of the step program
    (the module with the most time) to the end of the last, over all devices.

    Counted, not searched for: whatever the trace holds before those
    executions (the one window the harness adds for the profiler's start-up)
    stays outside, and every pause after their start lies inside and reads
    as idle.  A device plane with fewer executions than ``steps`` is an
    error: the trace lost events or the run did not make the steps."""
    w0s, w1s = [], []
    for plane, dev in devices.items():
        by_name: dict[str, float] = {}
        for n, _s, d in dev["modules"]:
            by_name[n] = by_name.get(n, 0.0) + d
        main = max(by_name, key=by_name.get) if by_name else None
        runs = sorted([s, s + d] for n, s, d in dev["modules"] if n == main)
        if len(runs) < steps:
            raise ValueError(f"{plane}: the trace holds {len(runs)} execution(s) of the step "
                             f"program {main!r}, the run traced {steps} to reduce")
        w0s.append(runs[-steps][0])
        w1s.append(runs[-1][1])
    return min(w0s), max(w1s)


def reduce(flat: dict, steps: int) -> dict:
    devices = flat["devices"]
    if not devices:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in the trace "
                         f"(planes: {flat.get('planes')})")
    w0, w1 = traced_window(devices, steps)
    devices = {p: {"ops": [o for o in d["ops"] if w0 <= o[1] and o[1] + o[2] <= w1],
                   "modules": [m for m in d["modules"] if w0 <= m[1] and m[1] + m[2] <= w1]}
               for p, d in devices.items()}
    host = {}
    for name, s, d in flat["host"]:
        host.setdefault(name, []).append([s, s + d])
    busy = exposed = 0.0
    ops_time: dict[str, float] = {}
    gaps: dict[str, float] = {}
    kernels: dict[str, list] = {}
    for dev in devices.values():
        spans = [[s, s + d] for _n, s, d in dev["ops"]]
        merged = union(spans)
        busy += total(merged)
        coll_spans = union([[s, s + d] for n, s, d in dev["ops"] if is_collective(n)])
        comp_spans = union([[s, s + d] for n, s, d in dev["ops"] if not is_collective(n)])
        exposed += total(subtract(coll_spans, comp_spans))
        for n, _s, d in dev["ops"]:
            # by kind and result shape, the instruction's number dropped: the
            # same fusion of 24 layers is one row, not 24 below the fold
            kind = _OP_ID.sub("", n, count=1)
            ops_time[kind] = ops_time.get(kind, 0.0) + d
            if n.startswith("tpuframe_"):
                k = kernels.setdefault(n, [0, 0.0])
                k[0] += 1
                k[1] += d
        modules = union([[s, s + d] for _n, s, d in dev["modules"]])
        for s, e in subtract([[w0, w1]], merged):
            if e - s < MIN_GAP_NS:
                continue
            mid = (s + e) / 2
            if any(a <= mid < b for a, b in modules):
                what = "inside_program"
            else:
                scores = {GAP_NAMES.get(n, n): overlap(s, e, sp) for n, sp in host.items()}
                what = max(scores, key=scores.get) if scores and max(scores.values()) > 0 \
                    else "between_programs"
            gaps[what] = gaps.get(what, 0.0) + (e - s)
    n = len(devices)
    ns = 1e-9
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "planes": len(devices),
        "steps": steps,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / n * ns,
        "exposed_collective_s": exposed / n * ns,
        "kernels": {k: {"calls": c / n, "seconds": t / n * ns} for k, (c, t) in kernels.items()},
        "breakdown": {
            "device_ops": [[k, v / n * ns] for k, v in top],
            "idle_gaps": [[k, v / n * ns] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def describe(path: str, out_path: str, per_line: int = 4) -> None:
    """Planes, lines and a few events with their stats, as text: what to look
    at by hand before trusting the reduction on a new runtime."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            rows.append(f"  LINE {line.name}")
            # every program run, a few of anything else
            for e in itertools.islice(line.events, 400 if line.name == MODULES_LINE else per_line):
                rows.append(f"    {e.name} start_ns={e.start_ns} dur_ns={e.duration_ns} "
                            f"stats={[(k, str(v)[:80]) for k, v in e.stats][:8]}")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write("\n".join(rows) + "\n")


def reduce_dir(trace_dir: str, out_dir: str, steps: int) -> dict:
    path = find_xplane(trace_dir)
    describe(path, os.path.join(out_dir, "trace_describe.txt"))
    flat = flatten(path)
    out = reduce(flat, steps)
    out["_flat"] = flat
    return out


def write_sample(reduced: dict, path: str, max_events: int = 6000) -> None:
    """Keep the start of the flattened trace (a step or two) beside the run:
    small enough to serve as the reducer's recorded fixture."""
    flat = reduced.pop("_flat", None)
    if flat is None:
        return
    starts = sorted(o[1] for d in flat["devices"].values() for o in d["ops"])
    cut = starts[min(len(starts) - 1, max_events // max(len(flat["devices"]), 1))]
    sample = {
        "planes": flat["planes"],
        "devices": {p: {"ops": [o for o in d["ops"] if o[1] <= cut],
                        "modules": [m for m in d["modules"] if m[1] <= cut]}
                    for p, d in flat["devices"].items()},
        "host": [h for h in flat["host"] if h[1] <= cut],
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(sample, f)
