"""Device-time attribution: parsed profiler traces -> exposed comms.

The skew report (`track/analyze.py`) sees only *host-side* spans — it can
say a step was slow, but not where the device itself spent the time.
This module is the device half: a stdlib-only parser over the trace
files ``jax.profiler`` writes (Chrome Trace Event JSON, gzipped, under
``<logdir>/plugins/profile/<session>/*.trace.json.gz``) that reduces a
captured window to one ``device_time`` record:

- per-class device wall (**compute** / **collective** / **transfer** /
  **idle**), classified by HLO op-name rules over the device execution
  tracks only (host python threads and runtime infra events are noise);
- **exposed_comms_s** — collective wall NOT overlapped by compute,
  computed as interval math on the device timeline
  (``union(collective) - union(compute)``).  This is THE number ROADMAP
  item 3(a) gates on: overlap scheduling shrinks it while bytes-on-wire
  stays constant;
- **overlap_efficiency** — ``1 - exposed/collective`` (1.0 means every
  collective second hid behind compute);
- a **top-k op table** (base op name, count, total seconds, % of device
  time) — the measured fused-kernel target list ROADMAP item 3(b) names.

Never imports jax: the doctor and analyzer must read traces against a
wedged backend.  The capture side lives in `track/profiler.py`
(``ProfilerCallback`` cadence mode writes the captures this parses);
``TPUFRAME_PROFILE_*`` knobs are declared here so the parser, the
capture callback, the doctor, and the launch env-shipping registry all
read one list.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Iterable, Sequence

__all__ = [
    "PROFILE_ENV_VARS",
    "PROFILE_ENV_DOMAINS",
    "DEVICE_TIME_VERSION",
    "classify_op",
    "device_time_report",
    "device_trace_events",
    "find_trace_files",
    "interval_subtract",
    "interval_union",
    "list_captures",
    "load_trace",
    "profile_env",
]

#: every env knob the profile capture path reads — consumed by
#: ``launch.remote.all_env_vars()`` (shipped to every worker) and the
#: doctor's ``profile`` section.  Declared HERE (stdlib-only module),
#: not in profiler.py, so the doctor resolves them against a wedged
#: backend.
PROFILE_ENV_VARS = (
    "TPUFRAME_PROFILE_STEPS",
    "TPUFRAME_PROFILE_EVERY",
    "TPUFRAME_PROFILE_KEEP",
    "TPUFRAME_PROFILE_DIR",
)

#: machine-readable value domains (KN007 keeps the two in lockstep).
#: All "restart": the callback resolves its cadence at construction —
#: rewriting the env under a live fit would silently do nothing.
PROFILE_ENV_DOMAINS = {
    "TPUFRAME_PROFILE_STEPS": {
        "type": "int", "range": (1, None), "apply": "restart"},
    "TPUFRAME_PROFILE_EVERY": {
        "type": "int", "range": (0, None), "apply": "restart"},
    "TPUFRAME_PROFILE_KEEP": {
        "type": "int", "range": (1, None), "apply": "restart"},
    "TPUFRAME_PROFILE_DIR": {"type": "path", "apply": "restart"},
}

_PROFILE_DEFAULTS = {
    "TPUFRAME_PROFILE_STEPS": 0,   # 0 = capture disarmed
    "TPUFRAME_PROFILE_EVERY": 0,   # 0 = one capture, no cadence
    "TPUFRAME_PROFILE_KEEP": 3,    # capture dirs retained per rank
    "TPUFRAME_PROFILE_DIR": "",
}


def profile_env(environ: dict | None = None) -> dict:
    """Parsed ``TPUFRAME_PROFILE_*`` knobs + defaults, with malformed
    values *reported* (an ``errors`` dict), never raised — the doctor
    prints this and a typo'd knob must not crash a diagnosis run."""
    env = os.environ if environ is None else environ
    out: dict = dict(_PROFILE_DEFAULTS)
    errors: dict[str, str] = {}
    for knob in ("TPUFRAME_PROFILE_STEPS", "TPUFRAME_PROFILE_EVERY",
                 "TPUFRAME_PROFILE_KEEP"):
        raw = env.get(knob, "").strip()
        if not raw:
            continue
        try:
            v = int(raw)
            if v < 0:
                raise ValueError("negative")
        except ValueError:
            errors[knob] = f"not a non-negative int: {raw!r}"
            continue
        out[knob] = v
    if env.get("TPUFRAME_PROFILE_DIR", "").strip():
        out["TPUFRAME_PROFILE_DIR"] = env["TPUFRAME_PROFILE_DIR"].strip()
    out["errors"] = errors
    return out


# -- trace file discovery -----------------------------------------------------

#: jax.profiler writes TensorBoard layout: one session dir per capture
_SESSION_GLOB = os.path.join("plugins", "profile", "*")


def find_trace_files(logdir: str) -> list[str]:
    """The ``*.trace.json.gz`` files of the **newest** profiler session
    under ``logdir`` (one per host that captured).  Accepts either the
    capture root (what ``start_trace`` was given) or a session dir
    itself.  Empty list when nothing parseable exists."""
    candidates = [logdir] + sorted(
        glob.glob(os.path.join(logdir, _SESSION_GLOB)), reverse=True
    )
    for d in candidates:
        files = sorted(glob.glob(os.path.join(d, "*.trace.json.gz")))
        files += sorted(glob.glob(os.path.join(d, "*.trace.json")))
        if files:
            return files
    return []


def list_captures(profile_dir: str) -> list[str]:
    """Capture dirs under a ``TPUFRAME_PROFILE_DIR``, oldest-first —
    the rotation order the cadence callback maintains (newest last)."""
    out = []
    try:
        names = sorted(os.listdir(profile_dir))
    except OSError:
        return []
    for name in names:
        p = os.path.join(profile_dir, name)
        if os.path.isdir(p) and name.startswith("capture-"):
            out.append(p)
    return out


def load_trace(path: str) -> dict:
    """One Chrome Trace Event JSON file (gzipped or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8", errors="replace") as f:
        return json.load(f)


# -- op classification --------------------------------------------------------

#: HLO base-name prefixes that put an op on the wire.  Matched against
#: the op name lowercased with the trailing ``.<id>`` stripped.
_COLLECTIVE_PREFIXES = (
    "all-reduce", "allreduce", "all-gather", "allgather",
    "reduce-scatter", "reducescatter", "all-to-all", "alltoall",
    "collective", "partial-reduce", "ncclallreduce", "send", "recv",
)

#: host<->device transfer ops (infeed/outfeed, explicit copies).
_TRANSFER_PREFIXES = (
    "infeed", "outfeed", "copy", "memcpy", "h2d", "d2h",
    "transfer", "device-to-host", "host-to-device",
)

_TRAILING_ID = re.compile(r"\.\d+$")


def _base_name(name: str) -> str:
    """``dot.42`` -> ``dot``: aggregate the top-op table by HLO op, not
    by per-instruction id."""
    return _TRAILING_ID.sub("", name)


def classify_op(name: str) -> str | None:
    """``"collective"`` / ``"transfer"`` / ``"compute"``, or None for
    runtime infra that is not device work (thread-pool bookkeeping etc.
    — CPU traces interleave ``ThunkExecutor::Execute`` style events with
    the real ops, and their inflated nested durations would swamp every
    class)."""
    if not name or "::" in name or name.startswith("$"):
        return None
    base = _base_name(name).lower()
    for p in _COLLECTIVE_PREFIXES:
        if base.startswith(p):
            return "collective"
    for p in _TRANSFER_PREFIXES:
        if base.startswith(p):
            return "transfer"
    return "compute"


# -- device-track selection ---------------------------------------------------


def _is_exec_track(pname: str, tname: str) -> bool:
    """Is (process, thread) a device *execution* timeline?

    TPU/GPU traces put each chip in a ``/device:...`` process whose
    "XLA Ops" threads carry per-op events; the "Steps" / "XLA Modules"
    threads frame the same time at coarser granularity and would double
    count.  CPU traces have no device process — XLA:CPU op execution
    lands on ``tf_XLAPjRtCpuClient/<tid>`` threads of the host process
    (jax 0.9; ``tf_XLATfrtCpuClient`` before it; the ``python`` thread's
    nested durations are host bookkeeping, not device time) AND on the
    ``tf_XLAEigen/<tid>`` intra-op pool, which
    is where the thunk runtime actually runs the named HLO ops —
    including every collective (an all-reduce under simulated multi-CPU
    appears ONLY there).  Both pools belong to one host process, so
    their events merge into one device timeline; ``classify_op`` drops
    the pools' ``::`` bookkeeping spans, leaving the real ops.
    """
    t = tname.lower()
    if pname.startswith("/device:"):
        return "step" not in t and "module" not in t
    return ("xlapjrtcpuclient" in t or "xlatfrtcpuclient" in t
            or "xlaeigen" in t)


def _tracks(trace: dict) -> dict[tuple[Any, Any], dict]:
    """(pid, tid) -> {"process", "thread", "events": [(name, ts, dur)]}
    for the execution tracks of one trace file (ts/dur in µs, offsets
    from trace start)."""
    events = trace.get("traceEvents") or []
    pnames: dict[Any, str] = {}
    tnames: dict[tuple[Any, Any], str] = {}
    for ev in events:
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                pnames[ev.get("pid")] = str((ev.get("args") or {}).get("name", ""))
            elif ev.get("name") == "thread_name":
                tnames[(ev.get("pid"), ev.get("tid"))] = str(
                    (ev.get("args") or {}).get("name", "")
                )
    tracks: dict[tuple[Any, Any], dict] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        key = (ev.get("pid"), ev.get("tid"))
        pname = pnames.get(key[0], "")
        tname = tnames.get(key, "")
        if not _is_exec_track(pname, tname):
            continue
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur <= 0:
            continue
        tr = tracks.setdefault(key, {"process": pname, "thread": tname,
                                     "events": []})
        tr["events"].append((str(ev.get("name", "")), ts, dur))
    return tracks


# -- interval math ------------------------------------------------------------


def interval_union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping union of ``(start, end)`` pairs."""
    ivs = sorted((a, b) for a, b in intervals if b > a)
    out: list[tuple[float, float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def interval_subtract(a: Sequence[tuple[float, float]],
                      b: Sequence[tuple[float, float]]) -> list[tuple[float, float]]:
    """``a - b`` where both are merged unions: the parts of ``a`` not
    covered by ``b`` (the exposed-comms primitive: collective time with
    the compute union carved out)."""
    out: list[tuple[float, float]] = []
    j = 0
    for a0, a1 in a:
        lo = a0
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < a1:
            b0, b1 = b[k]
            if b0 > lo:
                out.append((lo, min(b0, a1)))
            lo = max(lo, b1)
            if lo >= a1:
                break
            k += 1
        if lo < a1:
            out.append((lo, a1))
    return out


def _union_len(union: Sequence[tuple[float, float]]) -> float:
    return sum(b - a for a, b in union)


# -- the device_time record ---------------------------------------------------

#: bump when the record shape changes (the skew report embeds it; the
#: golden fixture test pins the keys).  1.1: CPU exec-track selection
#: widened to the ``tf_XLAEigen`` intra-op pool — CPU captures now see
#: their collectives, so ``overlap_efficiency`` is measurable off-chip.
DEVICE_TIME_VERSION = "1.1"

_CLASSES = ("compute", "collective", "transfer")


def device_time_report(source: str | dict, *, steps: int | None = None,
                       top_k: int = 10) -> dict | None:
    """Reduce a capture to the ``device_time`` record, or None when the
    source holds no parseable device events.

    ``source`` is a capture dir (session discovery via
    :func:`find_trace_files`), a single trace file path, or an
    already-loaded trace dict.  ``steps`` (when the capture side knows
    how many train steps the window covered) adds the per-step
    divisions ``device_step_s`` / ``exposed_comms_per_step_s``.

    All aggregate seconds are **per device track** means (a 4-chip
    capture reports one device's window, not 4x), so ``window_s`` stays
    comparable across topologies; ``device_tracks`` records the fan-in.
    The identity ``busy_s + idle_s == window_s`` holds exactly per
    track; per-class walls are interval unions, so they only sum above
    ``busy_s`` where classes genuinely overlapped (that excess IS the
    overlap being measured).
    """
    if isinstance(source, dict):
        traces = [source]
        trace_dir = None
    elif os.path.isfile(source):
        traces, trace_dir = [load_trace(source)], os.path.dirname(source)
    else:
        files = find_trace_files(source)
        if not files:
            return None
        traces, trace_dir = [], os.path.dirname(files[0])
        for p in files:
            try:
                traces.append(load_trace(p))
            except (OSError, ValueError):
                continue  # torn/partial capture file: parse what exists

    # one timeline per device: merge a device's exec *threads* (a CPU
    # thread pool runs ops concurrently) into per-class interval unions
    per_device: dict[tuple[int, Any], dict] = {}
    op_totals: dict[str, dict] = {}
    for i, trace in enumerate(traces):
        for (pid, _tid), tr in _tracks(trace).items():
            dev = per_device.setdefault(
                (i, pid),
                {cls: [] for cls in _CLASSES} | {"events": 0},
            )
            for name, ts, dur in tr["events"]:
                cls = classify_op(name)
                if cls is None:
                    continue
                dev[cls].append((ts, ts + dur))
                dev["events"] += 1
                agg = op_totals.setdefault(
                    _base_name(name), {"count": 0, "total_us": 0.0, "class": cls}
                )
                agg["count"] += 1
                agg["total_us"] += dur

    per_device = {k: d for k, d in per_device.items() if d["events"]}
    if not per_device:
        return None

    n_dev = len(per_device)
    window_s = busy_s = idle_s = exposed_s = 0.0
    classes = {cls: {"wall_s": 0.0, "events": 0} for cls in _CLASSES}
    for dev in per_device.values():
        unions = {cls: interval_union(dev[cls]) for cls in _CLASSES}
        all_union = interval_union(
            iv for cls in _CLASSES for iv in unions[cls]
        )
        if not all_union:
            continue
        span = all_union[-1][1] - all_union[0][0]
        busy = _union_len(all_union)
        window_s += span / 1e6
        busy_s += busy / 1e6
        idle_s += (span - busy) / 1e6
        exposed_s += _union_len(
            interval_subtract(unions["collective"], unions["compute"])
        ) / 1e6
        for cls in _CLASSES:
            classes[cls]["wall_s"] += _union_len(unions[cls]) / 1e6
            classes[cls]["events"] += len(dev[cls])

    window_s /= n_dev
    busy_s /= n_dev
    idle_s /= n_dev
    exposed_s /= n_dev
    for cls in _CLASSES:
        classes[cls]["wall_s"] = round(classes[cls]["wall_s"] / n_dev, 6)

    collective_wall = classes["collective"]["wall_s"]
    total_device_us = sum(a["total_us"] for a in op_totals.values())
    top = sorted(op_totals.items(), key=lambda kv: -kv[1]["total_us"])[:top_k]
    top_ops = [
        {
            "name": name,
            "class": agg["class"],
            "count": agg["count"],
            "total_s": round(agg["total_us"] / 1e6, 6),
            "pct": round(100.0 * agg["total_us"] / total_device_us, 2)
            if total_device_us > 0 else 0.0,
        }
        for name, agg in top
    ]
    out: dict = {
        "schema_version": DEVICE_TIME_VERSION,
        "trace_dir": trace_dir,
        "device_tracks": n_dev,
        "steps": steps,
        "window_s": round(window_s, 6),
        "busy_s": round(busy_s, 6),
        "idle_s": round(idle_s, 6),
        "classes": classes,
        "exposed_comms_s": round(exposed_s, 6),
        "overlap_efficiency": (
            round(1.0 - exposed_s / collective_wall, 4)
            if collective_wall > 0 else None
        ),
        "device_step_s": (
            round(window_s / steps, 6) if steps else None
        ),
        "exposed_comms_per_step_s": (
            round(exposed_s / steps, 6) if steps else None
        ),
        "top_ops": top_ops,
    }
    return out


def device_trace_events(source: str, *, limit: int = 200_000) -> list[dict]:
    """Flat device op events for Perfetto merging: ``{device, thread,
    name, class, ts_us, dur_us}`` — ts is the trace-local µs offset; the
    analyzer anchors it on the capture's recorded wall start so host
    spans and device ops share one timeline.  Bounded by ``limit`` (a
    long capture must not balloon the merged trace file)."""
    out: list[dict] = []
    if os.path.isfile(source):
        files = [source]
    else:
        files = find_trace_files(source)
    for p in files:
        try:
            trace = load_trace(p)
        except (OSError, ValueError):
            continue
        for (pid, tid), tr in sorted(_tracks(trace).items(),
                                     key=lambda kv: str(kv[0])):
            dev = tr["process"] or "device"
            for name, ts, dur in tr["events"]:
                cls = classify_op(name)
                if cls is None:
                    continue
                out.append({
                    "device": dev,
                    "thread": tr["thread"] or str(tid),
                    "name": name,
                    "class": cls,
                    "ts_us": ts,
                    "dur_us": dur,
                })
                if len(out) >= limit:
                    return out
    return out
