"""Persistent XLA compilation cache: the warm-start half of the compile spine.

Every cold start, eval switch, and supervised restart pays a full XLA
trace+compile on the hot path — a supervised recovery pays the
recompile inside its recovery wall, and the fleet analyzer must
special-case the first step because compile jitter pollutes skew numbers.
jax ships a persistent compilation cache that turns a repeat backend
compile into a file read; nothing in tpuframe wired it.  This module is
that wiring, shaped like the rest of the observability stack:

- :func:`enable` points jax's compilation cache at a directory — where
  ``JAX_COMPILATION_CACHE_DIR`` is set, that directory and no other;
  unset, one fixed path inside the checkout (:data:`DEFAULT_CACHE_DIR`),
  so a supervised restart, a new rank on the same host and the next
  run of the same checkout all hit warm cache (the path is part of the
  cache's contract: a directory that moves never hits) — drops the
  min-compile-time floor so small steps cache too, and installs
  monitoring listeners that surface every compile in tpuframe telemetry.
- :func:`trim` is the size-capped keep-K eviction, mirroring the
  telemetry-rotation pattern (``TPUFRAME_TELEMETRY_MAX_MB`` /
  ``TPUFRAME_TELEMETRY_KEEP``): newest entries always survive, oldest
  are evicted once the directory exceeds the cap, evictions are counted.
- The **listeners** map jax's ``/jax/compilation_cache/*`` and
  ``/jax/core/compile/*`` monitoring events into the metrics registry
  (``compile/cache_hits``, ``compile/cache_misses``,
  ``compile/backend_compiles`` counters), put one record a phase of
  every compile request into the span log with the program's name
  (``compile/jax_trace``, ``compile/jax_lower``, ``compile/jax_backend``:
  :func:`_on_duration`), under whatever span the compiling thread has
  open, and emit one loud ``compile/backend_compile`` JSONL event per
  *real* backend compile — a persistent-cache hit is a retrieval, not a
  compile, and is counted but not shouted.

Env knobs (``COMPILE_ENV_VARS`` — shipped to every remote worker by
``launch.remote`` and printed by the doctor, exactly like
``telemetry.OBSERVABILITY_ENV_VARS``)::

    JAX_COMPILATION_CACHE_DIR      jax's own knob: when set it IS the cache
                                   dir (placed from outside; nothing here
                                   overrides it)
    TPUFRAME_COMPILE_CACHE         0/off/false disables; a path places the
                                   cache when the jax knob is unset; unset
                                   = <checkout>/.cache/xla
    TPUFRAME_COMPILE_CACHE_MAX_MB  trim() size cap (default 1024; junk =
                                   unbounded, lenient like telemetry)
    TPUFRAME_COMPILE_CACHE_KEEP    newest entries never evicted (default 16)
    TPUFRAME_COMPILE_MIN_COMPILE_S only cache compiles at least this long
                                   (default 0: cache everything — trim()
                                   bounds the disk, not a time floor)
    TPUFRAME_PRECOMPILE            0 disables the Trainer's AOT warm-start

This module imports jax lazily (inside :func:`enable`): the doctor and
``launch.remote`` read :data:`COMPILE_ENV_VARS` and :func:`cache_info`
from processes whose backend may be wedged.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Iterator

from tpuframe.track.telemetry import get_telemetry

logger = logging.getLogger(__name__)

__all__ = [
    "COMPILE_ENV_VARS",
    "cache_dir_from_env",
    "cache_info",
    "compile_label",
    "disable",
    "enable",
    "enable_from_env",
    "enabled_dir",
    "install_listeners",
    "last_compile_verdict",
    "trim",
]

#: every env knob the compile spine reads — THE list, consumed by
#: ``launch.remote`` (shipped to every host next to
#: ``telemetry.OBSERVABILITY_ENV_VARS``) and by the doctor's compile
#: section.  Add new knobs here, not in the consumers.
COMPILE_ENV_VARS = (
    "TPUFRAME_COMPILE_CACHE",
    "TPUFRAME_COMPILE_CACHE_MAX_MB",
    "TPUFRAME_COMPILE_CACHE_KEEP",
    "TPUFRAME_COMPILE_MIN_COMPILE_S",
    "TPUFRAME_PRECOMPILE",
)

#: value domains for the knobs above (KN007; AUTOTUNE.md explains the
#: ``apply`` field: "live" = re-read at every use, "restart" = read once
#: at enable/construction, a supervised restart picks up new values).
COMPILE_ENV_DOMAINS = {
    "TPUFRAME_COMPILE_CACHE": {"type": "path", "apply": "restart"},
    "TPUFRAME_COMPILE_CACHE_MAX_MB": {
        "type": "float", "range": (0, None), "apply": "live"},
    "TPUFRAME_COMPILE_CACHE_KEEP": {
        "type": "int", "range": (0, None), "apply": "live"},
    "TPUFRAME_COMPILE_MIN_COMPILE_S": {
        "type": "float", "range": (0, None), "apply": "restart"},
    "TPUFRAME_PRECOMPILE": {"type": "bool", "apply": "restart"},
}

_FALSY = ("0", "false", "no", "off", "disabled")

#: where the cache lives when nothing places it from outside: ONE fixed
#: path inside the checkout (gitignored).  Not the temp dir, not a
#: per-rank scratch, nothing made from a pid or the time — the path is
#: part of the cache's key, so a directory that moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "xla",
)

#: process-wide state: the enabled cache dir (None = not enabled here)
_STATE: dict[str, Any] = {"dir": None, "listeners": False}

#: per-thread compile attribution: what is being compiled right now
#: (set by the AOT precompiler and the Trainer's jit-fallback path) and
#: whether an explicit compile span is already recording it (suppresses
#: the listener's duplicate JSONL event; histograms still observe).
_TLS = threading.local()


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def cache_dir_from_env() -> str | None:
    """Resolve the cache directory from the environment.

    A falsy ``TPUFRAME_COMPILE_CACHE`` disables the cache entirely.
    Otherwise ``JAX_COMPILATION_CACHE_DIR`` — jax's own knob, how a
    driver places the cache from outside — wins when set; then a path
    in ``TPUFRAME_COMPILE_CACHE``; else :data:`DEFAULT_CACHE_DIR`.
    """
    v = os.environ.get("TPUFRAME_COMPILE_CACHE", "").strip()
    if v and v.lower() in _FALSY:
        return None
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
        or v
        or DEFAULT_CACHE_DIR
    )


def enabled_dir() -> str | None:
    """The cache dir this process enabled (None when disabled)."""
    return _STATE["dir"]


def enable(cache_dir: str | None = None, *,
           min_compile_s: float | None = None) -> str | None:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Idempotent; returns the enabled directory (or None when disabled by
    env / dir uncreatable — a broken cache must degrade to cold-compile
    behavior, never take training down).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: that directory is used and ``cache_dir`` is ignored, so
    jax's config is never pointed anywhere else.  Also installs the
    telemetry listeners and runs a :func:`trim` pass so a long-lived
    host cache stays inside its size cap.
    """
    if not cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        cache_dir = cache_dir_from_env()
    if cache_dir is None:
        return None
    if min_compile_s is None:
        min_compile_s = _env_float("TPUFRAME_COMPILE_MIN_COMPILE_S", 0.0)
    try:
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        # cache small steps too: the floor exists to avoid caching
        # trivial compiles, but tpuframe bounds the cache by SIZE (trim)
        # rather than excluding exactly the restarts it wants to warm
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
        )
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # the dir knob goes LAST: a partial failure above must not leave
        # jax writing a cache the spine believes is off (trim never runs,
        # doctor/supervisor report warm-start disabled while it is live)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax memoizes its "is the cache used?" verdict at the first
        # compile of the task; a compile that ran before this enable()
        # (or after a disable()) froze it at False — reset so the next
        # compile re-evaluates against the fresh config
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    except Exception as e:  # readonly dir / exotic backend
        logger.warning("compile cache disabled: %s", e)
        try:
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
        except Exception:
            pass
        return None
    _TLS.verdict = None  # a pre-enable hit must not shadow the next compile
    _STATE["dir"] = cache_dir
    install_listeners()
    try:
        trim(cache_dir)
    except OSError:
        pass  # a concurrent trimmer or a vanishing entry is not an error
    return cache_dir


def enable_from_env() -> str | None:
    """Enable iff the env doesn't explicitly disable it — the hook
    ``core.runtime.initialize`` and the fault supervisor call."""
    return enable()


def disable() -> None:
    """Turn the persistent cache off again (tests, benchmarks' cold
    windows).  Listeners stay installed — they are harmless without a
    cache and jax offers no unregister."""
    _STATE["dir"] = None
    _TLS.verdict = None  # a stale 'hit' would mute the next real compile
    try:
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    except Exception:
        pass


# -- telemetry listeners ------------------------------------------------------


@contextlib.contextmanager
def compile_label(label: str, *, span: bool = False) -> Iterator[None]:
    """Attribute any backend compile on this thread to ``label`` (what
    shows on the ``compile/backend_compile`` event).  ``span=True``
    additionally marks that an explicit compile span is recording the
    region, so the listener does not emit a duplicate JSONL event."""
    prev_label = getattr(_TLS, "label", None)
    prev_span = getattr(_TLS, "in_span", False)
    _TLS.label = label
    _TLS.in_span = bool(span) or prev_span
    try:
        yield
    finally:
        _TLS.label = prev_label
        _TLS.in_span = prev_span


def last_compile_verdict() -> str | None:
    """How this thread's most recent compile request was served —
    ``"hit"`` (persistent-cache retrieval, no backend compile),
    ``"miss"`` (real compile, written to the cache) or ``"uncached"``
    (real compile that never consulted the cache); None when nothing
    compiled since the last read.  Reading clears it."""
    verdict = getattr(_TLS, "last_compile", None)
    _TLS.last_compile = None
    return verdict


def _on_event(name: str, **kw: Any) -> None:
    # verdict protocol: each compile request that consults the
    # persistent cache records hit/miss on this thread; the
    # backend_compile duration that follows reads (and clears) it.
    try:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            _TLS.verdict = "miss"  # until a hit proves otherwise
        elif name == "/jax/compilation_cache/cache_hits":
            _TLS.verdict = "hit"
            get_telemetry().registry.counter("compile/cache_hits").inc()
        elif name == "/jax/compilation_cache/cache_misses":
            _TLS.verdict = "miss"
            get_telemetry().registry.counter("compile/cache_misses").inc()
    except Exception:  # a metrics hiccup must never break a compile
        pass


#: jax's duration events that become span-log records, one a phase of a
#: compile request: tracing the Python into a jaxpr, lowering the jaxpr to
#: an MLIR module (Mosaic kernels are lowered here), and the backend's
#: part (a persistent-cache read or a real compile)
_PHASE_RECORDS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/jax_lower",
    "/jax/core/compile/backend_compile_duration": "compile/jax_backend",
}

#: a trace nested in another phase (a step's trace holds the trace of
#: every jitted function it calls, ``jnp.add`` among them: thousands a
#: model) becomes a record of its own from this many seconds up; a shorter
#: one stays in the time of the phase that holds it.  A compile request's
#: own phases, and every lowering and backend phase, are always records.
_NESTED_TRACE_MIN_S = 1e-3


def _on_begin(name: str, value: float, **kw: Any) -> None:
    # jax announces a phase at its start as a scalar (its start time) under
    # the name its duration arrives with: one entry a phase this thread
    # has open, holding the seconds of the records nested in it
    if name in _PHASE_RECORDS:
        _TLS.__dict__.setdefault("open", []).append(0.0)


def _on_duration(name: str, dur: float, **kw: Any) -> None:
    try:
        if name == "/jax/compilation_cache/cache_retrieval_time_sec":
            # fires inside a request the cache answered, before that
            # request's backend duration on the same thread: kept as the
            # verdict is, for that record
            _TLS.retrieval_s = dur
            return
        record = _PHASE_RECORDS.get(name)
        if record is None:
            return
        # jax's own span is on time.time(); the span log's clock is
        # perf_counter_ns, so the callback's moment is the end
        end_ns = time.perf_counter_ns()
        open_ = getattr(_TLS, "open", None)
        inner_s = open_.pop() if open_ else 0.0
        if open_:  # nested: the phase that holds this one is still open
            if record == "compile/jax_trace" and dur < _NESTED_TRACE_MIN_S:
                return
            open_[-1] += dur
        attrs: dict[str, Any] = {
            "fun": str(kw.get("fun_name") or "?"),
            # less the records nested in it, so that sums over records
            # count every moment of a thread once
            "self_s": round(max(0.0, dur - inner_s), 6),
        }
        tele = get_telemetry()
        if record == "compile/jax_backend":
            verdict = getattr(_TLS, "verdict", None)
            retrieval_s = getattr(_TLS, "retrieval_s", None)
            _TLS.verdict = _TLS.retrieval_s = None
            _TLS.last_compile = verdict or "uncached"
            attrs["cache"] = _TLS.last_compile
            attrs["label"] = getattr(_TLS, "label", None)
            if verdict == "hit" and retrieval_s is not None:
                # the read, deserialize and load; the rest of the record
                # is the hashing of the key
                attrs["retrieval_s"] = round(float(retrieval_s), 6)
            # a persistent-cache hit is a retrieval, not a compile; a
            # miss — or a compile that never consulted the cache — is
            # the real thing, counted and (unless an explicit compile
            # span is already recording it) shouted as one JSONL event
            if verdict != "hit":
                tele.registry.counter("compile/backend_compiles").inc()
                if not getattr(_TLS, "in_span", False):
                    tele.event(
                        "compile/backend_compile",
                        dur_s=round(float(dur), 6),
                        label=attrs["label"],
                        persistent_cache=(
                            verdict if _STATE["dir"] else "disabled"
                        ),
                    )
        tele.record_span(record, end_ns - int(dur * 1e9), end_ns, **attrs)
    except Exception:  # a metrics hiccup must never break a compile
        pass


def install_listeners() -> None:
    """Register the jax monitoring listeners once per process (jax's
    listener registry is append-only — double registration would double
    every counter)."""
    if _STATE["listeners"]:
        return
    try:
        from jax._src import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_begin)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _STATE["listeners"] = True
    except Exception as e:
        logger.debug("compile listeners unavailable: %s", e)


# -- keep-K / size-cap eviction ----------------------------------------------


def _entry_files(cache_dir: str) -> list[tuple[str, float, int]]:
    """(path, recency, bytes) per cache entry, newest first.  jax's file
    cache writes ``<key>-cache`` entries with an ``<key>-atime`` recency
    sidecar; older layouts use bare key files — both are handled, and
    recency falls back to the entry's own mtime."""
    out = []
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return []
    for name in names:
        if name.endswith("-atime"):
            continue
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
        except OSError:
            continue  # concurrent eviction
        if not os.path.isfile(path):
            continue
        recency = st.st_mtime
        if name.endswith("-cache"):
            try:
                recency = os.stat(
                    os.path.join(cache_dir, name[: -len("-cache")] + "-atime")
                ).st_mtime
            except OSError:
                pass
        out.append((path, recency, st.st_size))
    out.sort(key=lambda e: e[1], reverse=True)
    return out


def trim(cache_dir: str | None = None, *, max_bytes: int | None = None,
         keep: int | None = None) -> list[str]:
    """Size-capped keep-K eviction, the telemetry-rotation pattern
    applied to the compile cache: the newest ``keep`` entries always
    survive; beyond them, oldest entries are evicted until the directory
    fits ``max_bytes``.  Evictions are counted
    (``compile/cache_evictions``) and returned.  Lenient knobs: junk in
    ``TPUFRAME_COMPILE_CACHE_MAX_MB`` reads as "no cap", never a crash.
    """
    cache_dir = cache_dir or _STATE["dir"] or cache_dir_from_env()
    if cache_dir is None or not os.path.isdir(cache_dir):
        return []
    if max_bytes is None:
        mb = _env_float("TPUFRAME_COMPILE_CACHE_MAX_MB", 1024.0)
        max_bytes = int(mb * 2**20) if 0 < mb < 2**40 else 0
    if keep is None:
        v = os.environ.get("TPUFRAME_COMPILE_CACHE_KEEP", "")
        keep = int(v) if v.isdigit() else 16
    if not max_bytes:
        return []
    entries = _entry_files(cache_dir)
    total = sum(size for _, _, size in entries)
    evicted: list[str] = []
    # walk oldest-first past the protected keep-K prefix
    for path, _, size in reversed(entries[max(0, int(keep)):]):
        if total <= max_bytes:
            break
        try:
            os.remove(path)
        except FileNotFoundError:
            pass  # a concurrent trimmer won the race: it IS gone
        except OSError:
            # EACCES/EROFS (foreign-owned entries in a shared host dir):
            # the bytes are still there — accounting them as freed would
            # end the pass early and report evictions that never happened
            continue
        if path.endswith("-cache"):
            try:
                os.remove(path[: -len("-cache")] + "-atime")
            except OSError:
                pass
        total -= size
        evicted.append(path)
    if evicted:
        get_telemetry().registry.counter("compile/cache_evictions").inc(
            len(evicted)
        )
        get_telemetry().event(
            "compile/cache_evict", n=len(evicted), dir=cache_dir
        )
    return evicted


def cache_info(cache_dir: str | None = None) -> dict:
    """Doctor-ready snapshot: where the cache is (or would be), how many
    entries it holds, how big it is, and the knobs bounding it.  Never
    imports jax — callable from a wedged-backend diagnosis."""
    cache_dir = cache_dir or _STATE["dir"] or cache_dir_from_env()
    info: dict[str, Any] = {
        "dir": cache_dir,
        "enabled_in_process": _STATE["dir"] is not None,
        "entries": 0,
        "total_mb": 0.0,
    }
    if cache_dir and os.path.isdir(cache_dir):
        entries = _entry_files(cache_dir)
        info["entries"] = len(entries)
        info["total_mb"] = round(
            sum(size for _, _, size in entries) / 2**20, 3
        )
    mb = _env_float("TPUFRAME_COMPILE_CACHE_MAX_MB", 1024.0)
    info["max_mb"] = mb if 0 < mb < 2**40 else None
    v = os.environ.get("TPUFRAME_COMPILE_CACHE_KEEP", "")
    info["keep"] = int(v) if v.isdigit() else 16
    return info
