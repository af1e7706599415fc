"""Memory plane: live watermarks, compiled truth, and OOM forensics.

Three sources of memory truth, cheapest-first, all landing in one
telemetry namespace so the analyzer / doctor / autotune read a single
vocabulary:

- **estimate** — ``parallel.plan_memory`` (stdlib math off the
  ``ParallelPlan``); the trainer registers it here via ``set_context``
  so a crash can attribute bytes without recomputing anything.
- **compiled** — ``record_executable_memory`` reads an AOT
  executable's ``memory_analysis()`` (argument/output/temp/
  generated-code/alias bytes) under its compile label, emits one
  ``memory/executable`` event, and persists the record next to the
  compile cache (``<cache>/memory/``) so a restarted process knows its
  footprint without recompiling.
- **live** — ``update_watermarks`` folds the ``SystemMetricsMonitor``
  sample into process-wide HBM/host peaks (gauges
  ``memory/hbm_peak_mb`` / ``memory/host_peak_mb``), emitting a
  ratcheted ``memory/watermark`` *event* only when the HBM peak grows
  >5% — bounded spam, but the peak reaches the JSONL the analyzer
  reads (gauges don't).

``maybe_oom_event`` is the forensics seam: the trainer's step loop, the
precompiler, and the serve batcher call it from their except blocks;
a ``RESOURCE_EXHAUSTED``-class error becomes one ``memory/oom`` event
carrying the three-way attribution table (estimate vs compiled vs
live, top-N leaves) plus the ``suggest_fit`` escalation ladder — the
crash arrives with the remedy.  Callers always re-raise; this module
only narrates.

Stdlib-only (KN006): ``launch.remote.all_env_vars()`` imports
``MEMORY_ENV_VARS`` from here, and the doctor must read persisted
records against a wedged backend.  Anything needing jax stays in the
caller (the monitor passes already-sampled device stats in).
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any

from tpuframe.parallel.memory import plan_memory, suggest_fit

__all__ = [
    "MEMORY_ENV_VARS",
    "MEMORY_ENV_DOMAINS",
    "memory_env",
    "record_executable_memory",
    "executable_records",
    "update_watermarks",
    "peaks",
    "reset_peaks",
    "is_oom",
    "set_context",
    "clear_context",
    "maybe_oom_event",
]

#: every env knob the memory plane reads — consumed by
#: ``launch.remote.all_env_vars()`` (shipped to every worker) and the
#: doctor's ``memory`` section.
MEMORY_ENV_VARS = (
    "TPUFRAME_MEMORY_SAMPLE_S",
    "TPUFRAME_MEMORY_TOP_LEAVES",
    "TPUFRAME_MEMORY_LIVE",
    "TPUFRAME_MEMORY_BUDGET_MB",
)

#: machine-readable value domains (KN007 keeps the two in lockstep).
MEMORY_ENV_DOMAINS = {
    # watermark sample cadence: becomes SystemMetricsMonitor's default
    # interval, resolved at construction
    "TPUFRAME_MEMORY_SAMPLE_S": {
        "type": "float", "range": (0.1, None), "apply": "restart"},
    # attribution-table depth in memory/oom events
    "TPUFRAME_MEMORY_TOP_LEAVES": {
        "type": "int", "range": (1, 64), "apply": "live"},
    # falsy = estimator-only: no live sampling fold-in, no OOM
    # forensics emission (the classifying seams stay pass-through)
    "TPUFRAME_MEMORY_LIVE": {"type": "bool", "apply": "restart"},
    # planning budget per device; 0/unset = auto from the live
    # device bytes_limit when the backend reports one
    "TPUFRAME_MEMORY_BUDGET_MB": {
        "type": "float", "range": (0, None), "apply": "live"},
}

_MEMORY_DEFAULTS = {
    "TPUFRAME_MEMORY_SAMPLE_S": 10.0,
    "TPUFRAME_MEMORY_TOP_LEAVES": 8,
    "TPUFRAME_MEMORY_LIVE": True,
    "TPUFRAME_MEMORY_BUDGET_MB": 0.0,
}

_FALSY = ("0", "false", "no", "off", "disabled")


def memory_env(environ: dict | None = None) -> dict:
    """Parsed ``TPUFRAME_MEMORY_*`` knobs + defaults; malformed values
    are *reported* (an ``errors`` dict), never raised — the doctor
    prints this and a typo'd knob must not crash a diagnosis run."""
    env = os.environ if environ is None else environ
    out: dict = dict(_MEMORY_DEFAULTS)
    errors: dict[str, str] = {}
    for knob, lo in (("TPUFRAME_MEMORY_SAMPLE_S", 0.1),
                     ("TPUFRAME_MEMORY_BUDGET_MB", 0.0)):
        raw = env.get(knob, "").strip()
        if not raw:
            continue
        try:
            v = float(raw)
            if v < lo:
                raise ValueError("below minimum")
        except ValueError:
            errors[knob] = f"not a float >= {lo}: {raw!r}"
            continue
        out[knob] = v
    raw = env.get("TPUFRAME_MEMORY_TOP_LEAVES", "").strip()
    if raw:
        try:
            v = int(raw)
            if not 1 <= v <= 64:
                raise ValueError("out of range")
            out["TPUFRAME_MEMORY_TOP_LEAVES"] = v
        except ValueError:
            errors["TPUFRAME_MEMORY_TOP_LEAVES"] = f"not an int in [1, 64]: {raw!r}"
    raw = env.get("TPUFRAME_MEMORY_LIVE", "").strip().lower()
    if raw:
        out["TPUFRAME_MEMORY_LIVE"] = raw not in _FALSY
    out["errors"] = errors
    return out


def _tele():
    from tpuframe.track.telemetry import get_telemetry

    return get_telemetry()


# -- live watermarks ----------------------------------------------------------

_RATCHET = 1.05  # emit memory/watermark only on >5% HBM-peak growth

_PEAK_LOCK = threading.Lock()
_PEAKS = {
    "hbm_peak_mb": 0.0,
    "host_peak_mb": 0.0,
    "hbm_limit_mb": 0.0,
    "_emitted_mb": 0.0,
}


def update_watermarks(device_stats: dict[str, float], rss_mb: float,
                      registry: Any = None) -> dict[str, float]:
    """Fold one monitor sample into the process-wide peaks.

    ``device_stats`` is ``system_metrics.device_memory_stats()`` output
    (already sampled by the caller — no double device poll); ``rss_mb``
    the host RSS.  Sets the ``memory/hbm_peak_mb`` / ``host_peak_mb``
    gauges every call; emits the ``memory/watermark`` *event* only when
    the HBM peak ratchets up >5%, so long fits log O(log) events, not
    one per sample.  Returns the current peaks.
    """
    hbm = 0.0
    limit = 0.0
    for k, v in device_stats.items():
        if k.endswith("_mem_used_mb") and v > hbm:
            hbm = v
            util = device_stats.get(k.replace("_mem_used_mb", "_mem_util"), 0)
            if util:
                limit = v / util
    emit = False
    with _PEAK_LOCK:
        if rss_mb > _PEAKS["host_peak_mb"]:
            _PEAKS["host_peak_mb"] = rss_mb
        if limit > _PEAKS["hbm_limit_mb"]:
            _PEAKS["hbm_limit_mb"] = limit
        if hbm > _PEAKS["hbm_peak_mb"]:
            _PEAKS["hbm_peak_mb"] = hbm
            if hbm > _PEAKS["_emitted_mb"] * _RATCHET:
                _PEAKS["_emitted_mb"] = hbm
                emit = True
        snap = {k: v for k, v in _PEAKS.items() if not k.startswith("_")}
    tele = _tele()
    reg = registry if registry is not None else tele.registry
    reg.gauge("memory/hbm_peak_mb").set(snap["hbm_peak_mb"])
    reg.gauge("memory/host_peak_mb").set(snap["host_peak_mb"])
    if emit:
        tele.event("memory/watermark", **snap)
    return snap


def peaks() -> dict[str, float]:
    """Current process-wide peaks (keys without the ratchet internals)."""
    with _PEAK_LOCK:
        return {k: v for k, v in _PEAKS.items() if not k.startswith("_")}


def reset_peaks() -> None:
    """Zero the watermarks (tests; a fresh fit in a reused process)."""
    with _PEAK_LOCK:
        for k in _PEAKS:
            _PEAKS[k] = 0.0


# -- compiled truth -----------------------------------------------------------

#: stats attribute -> record key (duck-typed off CompiledMemoryStats;
#: absent attributes record as 0 so the schema is stable across
#: backends)
_STAT_FIELDS = {
    "argument_size_in_bytes": "argument_mb",
    "output_size_in_bytes": "output_mb",
    "temp_size_in_bytes": "temp_mb",
    "alias_size_in_bytes": "alias_mb",
    "generated_code_size_in_bytes": "generated_code_mb",
    "host_argument_size_in_bytes": "host_argument_mb",
    "host_output_size_in_bytes": "host_output_mb",
    "host_temp_size_in_bytes": "host_temp_mb",
}

_MB = 1024.0 * 1024.0

#: in-process registry of compiled records, by label — skew_report and
#: the OOM forensics read this without touching the filesystem
_EXECUTABLES: dict[str, dict] = {}


def _memory_dir(cache_dir: str | None = None) -> str | None:
    if cache_dir is None:
        from tpuframe.compile.cache import (
            DEFAULT_CACHE_DIR,
            cache_dir_from_env,
            enabled_dir,
        )

        # a cache placed (or switched off) by env is authoritative (the
        # doctor reads records wherever the env points, possibly from a
        # process that never enabled the cache); otherwise records live
        # next to whatever cache this process actually enabled
        cache_dir = cache_dir_from_env()
        if cache_dir == DEFAULT_CACHE_DIR:
            cache_dir = enabled_dir() or cache_dir
    return os.path.join(cache_dir, "memory") if cache_dir else None


def record_executable_memory(compiled: Any, label: str, *,
                             persist: bool = True) -> dict | None:
    """Record ``compiled.memory_analysis()`` under ``label``.

    Emits one ``memory/executable`` event and (by default) persists the
    record next to the compile cache so a cache-hit restart knows its
    footprint without recompiling.  Returns the record, or None when
    the executable exposes no analysis (interpreters, some backends) —
    never raises: memory accounting must not fail a compile.
    """
    analyze = getattr(compiled, "memory_analysis", None)
    if analyze is None:
        return None
    try:
        stats = analyze()
    except Exception:
        return None
    if stats is None:
        return None
    rec: dict[str, Any] = {"label": label}
    for attr, key in _STAT_FIELDS.items():
        rec[key] = round(float(getattr(stats, attr, 0) or 0) / _MB, 3)
    # peak approximation for a donated-state step: arguments + temps +
    # outputs, minus the buffers aliased back onto the arguments
    rec["peak_mb"] = round(
        rec["argument_mb"] + rec["temp_mb"] + rec["output_mb"]
        - rec["alias_mb"], 3,
    )
    if not rec["alias_mb"]:
        # a persistent-cache HIT deserializes the executable WITHOUT
        # aliasing info (alias = 0), inflating peak_mb by the donated
        # bytes — when a prior record of this label (this process or the
        # persisted one from the real compile) knows the aliasing, keep
        # it instead of clobbering better evidence on every restart
        prior = _EXECUTABLES.get(label) or _read_record(label)
        if prior and prior.get("alias_mb"):
            rec = dict(prior)
    _EXECUTABLES[label] = rec
    _tele().event("memory/executable", **rec)
    if persist:
        path = _record_path(label)
        if path:
            try:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(rec, f, indent=1, sort_keys=True)
                os.replace(tmp, path)
            except OSError:
                pass  # a full disk must not fail the compile either
    return rec


def _record_path(label: str, cache_dir: str | None = None) -> str | None:
    d = _memory_dir(cache_dir)
    if not d:
        return None
    name = hashlib.sha256(label.encode()).hexdigest()[:16]
    return os.path.join(d, f"{name}.json")


def _read_record(label: str) -> dict | None:
    path = _record_path(label)
    if not path:
        return None
    try:
        with open(path) as f:
            rec = json.loads(f.read())
    except (OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) and rec.get("label") == label else None


def executable_records(cache_dir: str | None = None) -> dict[str, dict]:
    """All known executable-memory records, by compile label.

    In-process records win; persisted ones (from prior runs sharing the
    compile cache) fill the rest — how a restart knows its footprint
    before compiling anything.
    """
    out: dict[str, dict] = {}
    d = _memory_dir(cache_dir)
    if d and os.path.isdir(d):
        for name in sorted(os.listdir(d)):
            if not name.endswith(".json"):
                continue
            try:
                # json.loads, not json.load: the bare name `load` would
                # alias the checkpoint loader in the lint call graph and
                # drag it into the hot-path set
                with open(os.path.join(d, name)) as f:
                    rec = json.loads(f.read())
            except (OSError, ValueError):
                continue
            if isinstance(rec, dict) and rec.get("label"):
                out[rec["label"]] = rec
    out.update(_EXECUTABLES)
    return out


# -- OOM forensics ------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "OUT OF MEMORY")


def is_oom(exc: BaseException) -> bool:
    """Is this an allocation failure?  Matches XLA's RESOURCE_EXHAUSTED
    status (jax surfaces it as ``XlaRuntimeError`` with the status name
    in the message) and the synthetic ``fault.chaos.OomError``."""
    text = f"{type(exc).__name__}: {exc}".upper()
    return any(m in text for m in _OOM_MARKERS) or "RESOURCEEXHAUSTED" in text


_CTX_LOCK = threading.Lock()
_CONTEXT: dict[str, Any] = {}


def set_context(*, plan: Any = None, model_template: Any = None,
                batch_spec: Any = None, opt_template: Any = None,
                comms_template: Any = None, microbatches: int | None = None,
                estimate: dict | None = None) -> dict | None:
    """Register what's running so an OOM can attribute bytes.

    The trainer calls this once per fit (templates from the state it
    just built — shape/dtype carriers, not live arrays, are fine and
    cheaper).  When ``estimate`` is omitted and a plan + model template
    are given, ``plan_memory`` is computed here, once.  Returns the
    estimate in effect.
    """
    est = estimate
    if est is None and plan is not None and model_template is not None:
        try:
            est = plan_memory(
                plan, model_template, batch_spec,
                opt_template=opt_template, comms_template=comms_template,
                microbatches=microbatches,
                top_leaves=memory_env()["TPUFRAME_MEMORY_TOP_LEAVES"],
            )
        except Exception:
            est = None  # forensics context must never fail the fit
    with _CTX_LOCK:
        _CONTEXT.clear()
        _CONTEXT.update(
            plan=plan, model_template=model_template, batch_spec=batch_spec,
            opt_template=opt_template, comms_template=comms_template,
            microbatches=microbatches, estimate=est,
        )
    return est


def clear_context() -> None:
    with _CTX_LOCK:
        _CONTEXT.clear()


def maybe_oom_event(exc: BaseException, *, where: str,
                    step: int | None = None) -> bool:
    """Classify ``exc``; emit ONE ``memory/oom`` event if it's an OOM.

    The event carries the three-way attribution (estimate vs compiled
    vs live peaks), the top-N leaves, and the ``suggest_fit`` ladder
    against the resolved budget (``TPUFRAME_MEMORY_BUDGET_MB``, else
    the live device limit).  Returns True iff classified — the caller
    ALWAYS re-raises; forensics never swallows.  Never raises itself.
    """
    if not is_oom(exc):
        return False
    env = memory_env()
    if not env["TPUFRAME_MEMORY_LIVE"]:
        return False
    try:
        with _CTX_LOCK:
            ctx = dict(_CONTEXT)
        live = peaks()
        budget = env["TPUFRAME_MEMORY_BUDGET_MB"] or live.get("hbm_limit_mb") or None
        execs = executable_records()
        compiled = sorted(
            ({"label": k, "peak_mb": v.get("peak_mb", 0)} for k, v in execs.items()),
            key=lambda r: -r["peak_mb"],
        )[:4]
        estimate = ctx.get("estimate")
        suggestion = None
        if ctx.get("plan") is not None and ctx.get("model_template") is not None:
            try:
                fit = suggest_fit(
                    ctx["plan"], ctx["model_template"], ctx.get("batch_spec"),
                    budget_mb=budget,
                    opt_template=ctx.get("opt_template"),
                    comms_template=ctx.get("comms_template"),
                    microbatches=ctx.get("microbatches"),
                )
                suggestion = {k: v for k, v in fit.items() if k != "candidates"}
                if suggestion.get("suggestion"):
                    # keep the event bounded: the rung, not its full estimate
                    suggestion["suggestion"] = {
                        k: v for k, v in suggestion["suggestion"].items()
                        if k != "estimate"
                    }
            except Exception:
                suggestion = None
        tele = _tele()
        tele.event(
            "memory/oom",
            where=where,
            step=step,
            error=str(exc)[:500],
            estimate_total_mb=(estimate or {}).get("per_device_mb", {}).get("total"),
            estimate=estimate,
            compiled_peaks=compiled,
            live=live,
            budget_mb=budget,
            fit=suggestion,
        )
        tele.registry.counter("memory/oom_total").inc()
    except Exception:
        pass  # narration must never mask the original error
    return True
