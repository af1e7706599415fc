"""Process-wide telemetry spine: spans, metrics registry, JSONL event log.

tpuframe's observability was point solutions — an XLA trace callback
(`track/profiler.py`), epoch-total wall-clock buckets buried in
``Trainer._run_epoch``, a background ``/proc`` sampler — while the
failure mode that costs the most is *silent wedging*: ``jax.devices()``
or a compile hanging with zero diagnostics.  Production pre-training
frameworks (TorchTitan, PAPERS.md) treat metrics/profiling as a
first-class subsystem; this module is that subsystem for tpuframe.

Three pieces, all stdlib-only (telemetry must keep working precisely when
jax is wedged, so this module NEVER imports jax):

- :meth:`Telemetry.span` — nestable, thread-safe ``with`` regions timed on
  the monotonic clock.  Every span feeds a per-name duration histogram in
  the registry (p50/p95/p99 for free) and, when a sink is configured, one
  rank-tagged JSONL event.  The live span stack per thread is readable by
  the watchdog (`track/watchdog.py`), so a stall report says *where* each
  thread was, in tpuframe terms, not just python frames.  Every span, emitted
  or not, also stays behind as one record of the bounded **span log**
  (:meth:`Telemetry.span_log`): id, parent id, thread, start and end on
  ``time.perf_counter_ns()`` (one clock for all threads), and the train
  ``step`` it feeds — what joins a step to the assembly and the copy that
  fed it.
- :class:`MetricsRegistry` — counters, gauges, histograms (bounded
  reservoir: long runs keep *recent* distribution data).  Exports as a
  flat dict for the existing ``TensorBoardLogger``/``MLflowLogger``
  (:func:`publish_to_loggers`, :class:`MetricsExportCallback`) and as a
  Prometheus text page (:meth:`MetricsRegistry.prometheus_text`, served by
  :func:`start_metrics_server` / ``track.http_store.MetricsServer``).
- The **JSONL event log** — one file per rank
  (``events-rank<N>.jsonl``), schema documented in ``OBSERVABILITY.md``.
  Enabled by ``TPUFRAME_TELEMETRY_DIR`` (inherited by launch workers and
  bench children) or :func:`configure`.

The process-wide instance comes from :func:`get_telemetry`; with no
configuration it is memory-only (span log + ring buffer + registry, no file
I/O), so instrumented hot paths cost two clock reads, a lock and two appends.

Env knobs::

    TPUFRAME_TELEMETRY_DIR       write events-rank<N>.jsonl under this dir
    TPUFRAME_TELEMETRY_MAX_MB    rotate the event log at this size (MB);
                                 segments shift to .1 .. .K, oldest dropped
    TPUFRAME_TELEMETRY_KEEP      rotated segments to keep (default 3;
                                 0 = rotation keeps no history)
    TPUFRAME_WATCHDOG_S          attach a stall watchdog; default deadline
                                 (seconds) for every guarded activity
    TPUFRAME_WATCHDOG_DEADLINES  per-activity overrides, e.g.
                                 "train/step=120,ckpt/save=600"

Every sink-backed log opens with a ``meta`` record (schema version, rank,
hostname, pid, and a wall-clock/monotonic **anchor pair**) and every record
carries both ``ts`` (wall) and ``mono`` (monotonic) timestamps — the fleet
analyzer (``tpuframe.track.analyze``) uses the anchors to place every
rank's events on one timeline even when a rank's wall clock steps mid-run.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import contextlib
import json
import os
import socket
import threading
import time
from collections import deque
from typing import Any, Iterable, Sequence

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsExportCallback",
    "MetricsRegistry",
    "Span",
    "Telemetry",
    "configure",
    "get_telemetry",
    "publish_to_loggers",
    "reset",
    "set_annotation_hook",
    "start_metrics_server",
]

#: bump when the JSONL record shape changes (OBSERVABILITY.md documents it)
SCHEMA_VERSION = 1

#: every env knob the observability/fault stack reads — THE list, consumed
#: by ``launch.remote`` (shipped to every host: a fleet whose ranks ran
#: without telemetry cannot be skew-analyzed after the fact) and by the
#: doctor's telemetry section.  Add new knobs here, not in the consumers.
OBSERVABILITY_ENV_VARS = (
    "TPUFRAME_TELEMETRY_DIR",
    "TPUFRAME_TELEMETRY_MAX_MB",
    "TPUFRAME_TELEMETRY_KEEP",
    "TPUFRAME_WATCHDOG_S",
    "TPUFRAME_WATCHDOG_DEADLINES",
    "TPUFRAME_STRAGGLER_STEPS",
    "TPUFRAME_STRAGGLER_FACTOR",
    "TPUFRAME_PREEMPT_SIGNALS",
    "TPUFRAME_FLEET_TIMEOUT_S",
)

#: machine-readable value domains for the knobs above (KN007 keeps the
#: two in lockstep).  ``apply`` says whether a new value takes effect on
#: a running process ("live": re-read at every use) or only on a
#: supervised restart ("restart": read once at configure/construction) —
#: the autotuner's legal search space and re-application contract.
OBSERVABILITY_ENV_DOMAINS = {
    "TPUFRAME_TELEMETRY_DIR": {"type": "path", "apply": "restart"},
    "TPUFRAME_TELEMETRY_MAX_MB": {
        "type": "float", "range": (0, None), "apply": "restart"},
    "TPUFRAME_TELEMETRY_KEEP": {
        "type": "int", "range": (0, None), "apply": "restart"},
    "TPUFRAME_WATCHDOG_S": {
        "type": "float", "range": (0, None), "apply": "restart"},
    "TPUFRAME_WATCHDOG_DEADLINES": {
        "type": "int", "range": (1, None), "apply": "restart"},
    "TPUFRAME_STRAGGLER_STEPS": {
        "type": "int", "range": (1, None), "apply": "live"},
    "TPUFRAME_STRAGGLER_FACTOR": {
        "type": "float", "range": (1.0, None), "apply": "live"},
    "TPUFRAME_PREEMPT_SIGNALS": {"type": "bool", "apply": "restart"},
    "TPUFRAME_FLEET_TIMEOUT_S": {
        "type": "float", "range": (0, None), "apply": "live"},
}


def _env_rank() -> int:
    """Process rank from the launch env (never imports jax: telemetry must
    initialize even while the backend is wedged)."""
    for var in ("TPUFRAME_PROCESS_ID", "RANK"):
        v = os.environ.get(var, "")
        if v.isdigit():
            return int(v)
    return 0


def _env_max_bytes() -> int:
    """Rotation threshold from TPUFRAME_TELEMETRY_MAX_MB (0 = unbounded).
    Lenient like every observability knob: garbage (including ``inf``,
    which would overflow int()) reads as "no cap", never as a crash."""
    v = os.environ.get("TPUFRAME_TELEMETRY_MAX_MB", "")
    try:
        mb = float(v)
    except ValueError:
        return 0
    return int(mb * 2**20) if 0 < mb < 2**40 else 0


def _env_keep_segments() -> int:
    """Rotated segments to retain; 0 is honored as "keep none" (rotation
    just truncates) — silently coercing it up would surprise exactly the
    disk-constrained operator who set it."""
    v = os.environ.get("TPUFRAME_TELEMETRY_KEEP", "")
    return int(v) if v.isdigit() else 3


# -- metrics registry ---------------------------------------------------------


class Counter:
    """Monotonic counter (events seen, batches prefetched, retries)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Last-write-wins scalar (current epoch, queue depth, HBM in use)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)


class Histogram:
    """Bounded-reservoir histogram: lifetime count/sum + a ring of the most
    recent ``max_samples`` observations for percentiles.

    A ring, not a capped list (the old ``StepTimer`` bug,
    `track/profiler.py`): a capped list stops sampling after the first
    ``max_samples`` steps, so a 10-hour run reports the distribution of its
    first minutes.  The ring keeps the *recent* window, which is what a
    stall investigation needs.
    """

    __slots__ = ("name", "max_samples", "count", "total", "_ring", "_lock")

    def __init__(self, name: str, max_samples: int = 2048):
        if max_samples < 1:
            raise ValueError(f"max_samples must be >= 1, got {max_samples}")
        self.name = name
        self.max_samples = max_samples
        self.count = 0
        self.total = 0.0
        self._ring: list[float] = []
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            i = self.count % self.max_samples
            self.count += 1
            self.total += v
            if len(self._ring) < self.max_samples:
                self._ring.append(v)
            else:
                self._ring[i] = v  # overwrite oldest: insertion-order ring

    def window(self) -> list[float]:
        """The retained (most recent) observations, unordered."""
        with self._lock:
            return list(self._ring)

    @staticmethod
    def _quantile(sorted_vals: Sequence[float], q: float) -> float:
        return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]

    def summary(self) -> dict[str, float]:
        """count/mean over the lifetime, p50/p95/p99 over the recent window."""
        with self._lock:
            vals, count, total = sorted(self._ring), self.count, self.total
        if not vals:
            return {}
        return {
            "count": float(count),
            "mean": total / count,
            "p50": self._quantile(vals, 0.50),
            "p95": self._quantile(vals, 0.95),
            "p99": self._quantile(vals, 0.99),
        }


class MetricsRegistry:
    """Name -> instrument table; get-or-create, thread-safe.

    Names are slash-namespaced (``span/train/step``, ``data/ring_allocs``
    — conventions in OBSERVABILITY.md).  Exports: :meth:`snapshot` (flat
    dict for the Trainer's logger contract) and :meth:`prometheus_text`.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str, max_samples: int = 2048) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name, max_samples)
            return h

    def snapshot(self, prefix: str = "") -> dict[str, float]:
        """Flat ``{name: value}`` dict — the shape ``log_metrics`` takes.

        Histograms expand to ``<name>_count/_mean/_p50/_p95/_p99``.
        """
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            hists = list(self._histograms.values())
        out: dict[str, float] = {}
        for c in counters:
            out[f"{prefix}{c.name}"] = c.value
        for g in gauges:
            out[f"{prefix}{g.name}"] = g.value
        for h in hists:
            for k, v in h.summary().items():
                out[f"{prefix}{h.name}_{k}"] = v
        return out

    @staticmethod
    def _prom_name(name: str) -> str:
        sane = "".join(ch if ch.isalnum() else "_" for ch in name)
        return f"tpuframe_{sane}"

    def prometheus_text(self) -> str:
        """Prometheus exposition format (text/plain; version=0.0.4).

        Histograms export as summaries: ``_count``, ``_sum``, and
        ``{quantile=...}`` sample lines over the recent window.
        """
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            hists = list(self._histograms.values())
        lines: list[str] = []
        for c in counters:
            n = self._prom_name(c.name)
            lines += [f"# TYPE {n} counter", f"{n} {c.value}"]
        for g in gauges:
            n = self._prom_name(g.name)
            lines += [f"# TYPE {n} gauge", f"{n} {g.value}"]
        for h in hists:
            n = self._prom_name(h.name)
            s = h.summary()
            if not s:
                continue
            lines.append(f"# TYPE {n} summary")
            for q in ("p50", "p95", "p99"):
                lines.append(f'{n}{{quantile="0.{q[1:]}"}} {s[q]}')
            lines += [f"{n}_sum {h.total}", f"{n}_count {int(s['count'])}"]
        return "\n".join(lines) + "\n"


# -- spans --------------------------------------------------------------------


#: installed by ``track/profiler.py`` (which may import jax; this module may
#: not): ``hook(name, step)`` returns a context manager that shows the span
#: in the profiler's trace.  None until then.
_annotate = None


def set_annotation_hook(hook) -> None:
    """Let every span also open ``hook(name, step)`` around its region."""
    global _annotate
    _annotate = hook


class Span:
    """What :meth:`Telemetry.span` returns: the ``with`` region's handle,
    and its record in the span log once it has closed.

    ``start_ns``/``end_ns`` are ``time.perf_counter_ns()`` readings (one
    clock for every thread of the process); ``elapsed`` (seconds) is valid
    after the ``with`` block exits.  ``parent_id`` is the enclosing span of
    the same thread, ``step`` the train step the span feeds (its own, or
    its parent's).  ``cpu_ns`` is None unless the span was opened with
    ``cpu=True``: then it is the CPU time its thread burned inside the
    region (``time.thread_time_ns()``, read inside the two clock readings,
    so never more than the duration).  A blocked thread burns none:
    ``cpu_ns`` is the region's work and the rest of its duration is wait,
    whichever statement the wait fell in.  ``attrs`` is held by reference:
    what the region writes into it while open
    (``sp.attrs["fresh_alloc"] = True``) is in the log and on the JSONL
    line."""

    __slots__ = ("id", "parent_id", "name", "thread", "start_ns", "end_ns",
                 "cpu_ns", "step", "attrs", "stack", "elapsed", "ok", "error",
                 "emit", "_tele", "_ident", "_ann", "_cpu0")

    def __init__(self, tele: "Telemetry", name: str, attrs: dict,
                 step: int | None = None, emit: bool = True,
                 cpu: bool = False):
        self.id = 0
        self.parent_id: int | None = None
        self.name = name
        self.thread = ""
        self.start_ns = self.end_ns = 0
        self.cpu_ns: int | None = 0 if cpu else None
        self.step = step
        self.attrs = attrs
        self.stack: list[str] = [name]
        self.elapsed = 0.0
        self.ok = True
        self.error: str | None = None
        self.emit = emit
        self._tele = tele
        self._ident = 0
        self._ann = None

    def __repr__(self):
        return f"Span({self.name!r}, elapsed={self.elapsed:.6f}, ok={self.ok})"

    def __enter__(self) -> "Span":
        tele = self._tele
        th = threading.current_thread()
        self.thread = th.name
        ident = self._ident = th.ident
        with tele._lock:
            self.id = tele._span_seq = tele._span_seq + 1
            stack = tele._active.get(ident)
            if stack is None:
                stack = tele._active[ident] = []
            elif stack:
                parent = stack[-1]
                self.parent_id = parent.id
                self.stack = parent.stack + [self.name]
                if self.step is None:
                    self.step = parent.step
            stack.append(self)
        if _annotate is not None:
            self._ann = _annotate(self.name, self.step)
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        if self.cpu_ns is not None:
            self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.cpu_ns is not None:
            self.cpu_ns = time.thread_time_ns() - self._cpu0
        self.end_ns = time.perf_counter_ns()
        self.elapsed = (self.end_ns - self.start_ns) / 1e9
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if exc is not None:
            self.ok = False
            self.error = f"{exc_type.__name__}: {exc}"[:300]
        self._tele._close_span(self)
        return False


class Telemetry:
    """One process-wide spine: span stacks, registry, ring buffer, JSONL sink.

    Args:
      jsonl_path: event-log file (appended, one JSON object per line).
        None = memory-only (ring buffer + registry, no file I/O).
      rank: tag on every record; defaults to the launch env's rank.
      max_events: ring-buffer length (the watchdog dumps the tail of this).
      max_spans: span-log length (sized for a benchmark run of a few
        hundred steps at ten spans a step, with room: a traced run of a
        benchmark cell leaves 2,490-3,008 records, 550-1,000 of them the
        ``compile/jax_*`` records of its set-up and of the reference's
        check, counted on the chip for PR 52; the set-up's, the oldest,
        survive to the readers with four fifths of the log to spare).
      registry: share an existing :class:`MetricsRegistry` (default: new).
      watchdog: a ``track.watchdog.Watchdog`` to attach (wires both ways).
      span_histograms: auto-observe every span duration into
        ``span/<name>`` in the registry.
      max_bytes: rotate the JSONL file once it reaches this size
        (default: TPUFRAME_TELEMETRY_MAX_MB; 0 = never rotate).
      keep_segments: rotated segments retained as ``<path>.1`` (newest)
        .. ``<path>.K`` (oldest); the analyzer reads them back in order.
    """

    def __init__(
        self,
        jsonl_path: str | None = None,
        *,
        rank: int | None = None,
        max_events: int = 512,
        max_spans: int = 16384,
        registry: MetricsRegistry | None = None,
        watchdog: Any = None,
        span_histograms: bool = True,
        max_bytes: int | None = None,
        keep_segments: int | None = None,
    ):
        self.jsonl_path = jsonl_path
        self.rank = _env_rank() if rank is None else int(rank)
        self.registry = registry or MetricsRegistry()
        self.span_histograms = span_histograms
        self.max_bytes = _env_max_bytes() if max_bytes is None else int(max_bytes)
        self.keep_segments = (
            _env_keep_segments() if keep_segments is None
            else max(0, int(keep_segments))
        )
        # clock anchor pair: every record carries a wall ts AND a monotonic
        # ts; the pair below (also published in the meta record) lets the
        # fleet analyzer map this rank's monotonic clock onto the wall
        # timeline fixed at configure time — immune to mid-run NTP steps
        self.anchor_wall = time.time()
        self.anchor_mono = time.monotonic()
        # ... and the span log's clock, read at the same moment: a span's
        # perf_counter_ns readings minus this, plus either anchor above,
        # place it beside any JSONL line
        self.anchor_perf_ns = time.perf_counter_ns()
        # events (envelope dicts) and emitted spans (the Span itself: its
        # envelope is built when someone asks, not at every close)
        self._recent: deque[dict | Span] = deque(maxlen=max_events)
        self._spans: deque[Span] = deque(maxlen=max_spans)
        self._span_seq = 0
        self._bytes = 0  # current JSONL segment size (approx, for rotation)
        # _lock guards only in-memory state (span stacks, ring buffer) and
        # is never held across file I/O: the watchdog reads active_spans/
        # recent_events under it WHILE a JSONL write may be hung on a dead
        # filesystem — the stall report must not deadlock on the sink it
        # is reporting about.  _io_lock serializes the file writes alone.
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self._file: Any = None
        # live span stacks by thread ident — shared (not thread-local) so the
        # watchdog thread can read every thread's position at dump time
        self._active: dict[int, list[Span]] = {}
        self.watchdog = None
        if watchdog is not None:
            self.attach_watchdog(watchdog)
        if self.jsonl_path is not None:
            # a sink-backed log's FIRST line is the meta record: rank
            # identity + the clock anchor pair must precede any event the
            # fleet analyzer would need to place on the shared timeline
            self._write(self._meta_fields())

    def _meta_fields(self) -> dict:
        try:
            hostname = socket.gethostname()
        except OSError:
            hostname = ""
        return {
            "kind": "meta",
            "name": "telemetry/meta",
            "schema": SCHEMA_VERSION,
            "hostname": hostname,
            "anchor_wall": round(self.anchor_wall, 6),
            "anchor_mono": round(self.anchor_mono, 6),
            "anchor_perf_ns": self.anchor_perf_ns,
        }

    # -- wiring --------------------------------------------------------------
    def attach_watchdog(self, watchdog: Any) -> Any:
        """Adopt ``watchdog``: it reads this telemetry's spans/events for its
        reports, and :meth:`guard` routes through it."""
        self.watchdog = watchdog
        watchdog.telemetry = self
        return watchdog

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, *, emit: bool = True, step: int | None = None,
             cpu: bool = False, **attrs: Any) -> Span:
        """Time a region; nestable, exception-transparent.

        ``step`` is the train step the region feeds (the value
        ``Trainer.batches_seen`` has after that step's dispatch); a span
        given none takes its parent's.  ``emit=False`` keeps the
        histogram, the live-stack visibility and the span-log record but
        skips the JSONL event — for per-batch inner regions where one
        event per occurrence would dominate the log.  ``cpu=True`` also
        takes the thread's CPU time over the region (``Span.cpu_ns``;
        ``cpu_ms`` on the JSONL line): work apart from wait.
        """
        return Span(self, name, attrs, step, emit, cpu)

    def record_span(self, name: str, start_ns: int, end_ns: int, *,
                    emit: bool = True, **attrs: Any) -> Span:
        """Put a region that has already ended into the span log, as any
        closed span is: next id; ``thread``, ``parent_id`` and ``step``
        from the span the calling thread has open; the ``span/<name>``
        histogram; the JSONL line where ``emit``.  ``start_ns``/``end_ns``
        are on ``time.perf_counter_ns()``.  No profiler annotation: the
        region is over.  This is how a listener's callback (a duration
        reported at its end, ``compile/cache.py``) becomes a record."""
        sp = Span(self, name, attrs, None, emit)
        th = threading.current_thread()
        sp.thread, sp._ident = th.name, th.ident
        sp.start_ns, sp.end_ns = int(start_ns), int(end_ns)
        sp.elapsed = (sp.end_ns - sp.start_ns) / 1e9
        with self._lock:
            sp.id = self._span_seq = self._span_seq + 1
            stack = self._active.get(sp._ident)
            if stack:
                parent = stack[-1]
                sp.parent_id, sp.step = parent.id, parent.step
                sp.stack = parent.stack + [name]
        self._close_span(sp)
        return sp

    def _close_span(self, sp: Span) -> None:
        with self._lock:
            stack = self._active.get(sp._ident)
            if stack:
                if stack[-1] is sp:
                    stack.pop()
                elif sp in stack:  # mis-nested exit: drop just this span
                    stack.remove(sp)
                if not stack:
                    del self._active[sp._ident]
            self._spans.append(sp)
            if sp.emit:
                self._recent.append(sp)
        if self.span_histograms:
            self.registry.histogram(f"span/{sp.name}").observe(sp.elapsed)
        if sp.emit and self.jsonl_path is not None:
            self._sink(self._span_envelope(sp))

    def span_log(self, names: Iterable[str] | None = None) -> list[Span]:
        """The closed spans still in the log, oldest first (by close),
        every thread's, emitted or not; ``names`` keeps only those."""
        with self._lock:
            out = list(self._spans)
        if names is not None:
            names = frozenset(names)
            out = [sp for sp in out if sp.name in names]
        return out

    def active_spans(self) -> dict[str, list[str]]:
        """``{thread_name (ident): [span names, outermost first]}`` — the
        watchdog's "where is everyone" view."""
        names = {t.ident: t.name for t in threading.enumerate()}
        with self._lock:
            return {
                f"{names.get(ident, '?')} ({ident})": [s.name for s in stack]
                for ident, stack in self._active.items()
                if stack
            }

    def guard(self, name: str, deadline_s: float | None = None):
        """Watchdog lease for a bounded activity (no-op without a watchdog
        or a resolvable deadline).  Compose with a span::

            with tele.span("ckpt/save"), tele.guard("ckpt/save"):
                ...
        """
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.guard(name, deadline_s)

    # -- events --------------------------------------------------------------
    def event(self, name: str, *, kind: str = "event", **fields: Any) -> None:
        """Append a free-form record (bench preflight attempts, watchdog
        stall reports, worker lifecycle marks)."""
        self._write({"kind": kind, "name": name, **fields})

    def recent_events(self, n: int = 50) -> list[dict]:
        with self._lock:
            tail = list(self._recent)[-n:]
        return [self._span_envelope(r) if isinstance(r, Span) else r
                for r in tail]

    def _envelope(self, rec: dict, closed_ns: int | None = None,
                  thread: str | None = None) -> dict:
        """Stamp ``rec`` with now and the calling thread, or (a span's) with
        the ``perf_counter_ns`` moment it closed, placed on both clocks
        through the anchors, and its own thread."""
        if closed_ns is None:
            ts, mono = time.time(), time.monotonic()
        else:
            since = (closed_ns - self.anchor_perf_ns) / 1e9
            ts, mono = self.anchor_wall + since, self.anchor_mono + since
        return {
            "v": SCHEMA_VERSION,
            "ts": round(ts, 6),
            "mono": round(mono, 6),
            "rank": self.rank,
            "pid": os.getpid(),
            "thread": thread or threading.current_thread().name,
            **rec,
        }

    def _span_envelope(self, sp: Span) -> dict:
        """A closed span in the JSONL record's shape."""
        rec = {
            "kind": "span",
            "name": sp.name,
            "stack": sp.stack,
            "dur_s": round(sp.elapsed, 6),
            "ok": sp.ok,
        }
        if sp.error:
            rec["error"] = sp.error
        if sp.step is not None:
            rec["step"] = sp.step
        if sp.cpu_ns is not None:
            rec["cpu_ms"] = round(sp.cpu_ns / 1e6, 3)
        if sp.attrs:
            rec["attrs"] = sp.attrs
        return self._envelope(rec, sp.end_ns, sp.thread)

    def _write(self, rec: dict) -> None:
        rec = self._envelope(rec)
        with self._lock:
            self._recent.append(rec)
        self._sink(rec)

    def _sink(self, rec: dict) -> None:
        if self.jsonl_path is None:
            return
        line = json.dumps(rec, default=str) + "\n"
        with self._io_lock:
            if self.jsonl_path is None:  # closed/poisoned while we waited
                return
            try:
                if self._file is None:
                    d = os.path.dirname(self.jsonl_path)
                    if d:
                        os.makedirs(d, exist_ok=True)
                    self._file = open(self.jsonl_path, "a")
                    self._bytes = self._file.tell()  # append mode: file size
                self._file.write(line)
                # trace-tagged span records ride the stdio buffer: the
                # traced serve request path emits several per request,
                # and a flush syscall each would serialize every serving
                # thread on this lock (measured ~10% on served p50).
                # Everything else still flushes per line for crash
                # durability — and each such flush carries any buffered
                # trace spans with it; the reader already tolerates a
                # torn buffered tail.
                a = rec.get("attrs")
                if not (rec.get("kind") == "span"
                        and ("trace" in rec or "traces" in rec
                             or (isinstance(a, dict)
                                 and ("trace" in a or "traces" in a)))):
                    self._file.flush()
                # encoded size, not len(line): non-ASCII payloads (error
                # strings, hostnames) are 2-4 UTF-8 bytes per char, and
                # undercounting would let the segment overshoot the cap
                # the disk-constrained operator set
                self._bytes += len(line.encode("utf-8", "replace"))
                if self.max_bytes and self._bytes >= self.max_bytes:
                    self._rotate_locked()
            except OSError:
                # a full/readonly disk must never take the training loop
                # down with it; drop to memory-only
                self._file, self.jsonl_path = None, None

    def _rotate_locked(self) -> None:
        """Shift ``path -> path.1 -> ... -> path.K`` (oldest dropped) and
        reopen a fresh segment headed by its own meta record, so each
        segment is independently alignable.  ``keep_segments=0`` keeps no
        history: the full file is simply dropped.  Caller holds
        ``_io_lock``."""
        base = self.jsonl_path
        self._file.close()
        self._file = None
        if self.keep_segments == 0:
            os.remove(base)
        else:
            oldest = f"{base}.{self.keep_segments}"
            if os.path.exists(oldest):
                os.remove(oldest)
            for k in range(self.keep_segments - 1, 0, -1):
                src = f"{base}.{k}"
                if os.path.exists(src):
                    os.replace(src, f"{base}.{k + 1}")
            os.replace(base, f"{base}.1")
        self._file = open(base, "a")
        # direct write, not _write: we already hold _io_lock, and the
        # rotation meta is a file header, not a ring-buffer event
        head = json.dumps(self._envelope(self._meta_fields()), default=str) + "\n"
        self._file.write(head)
        self._file.flush()
        self._bytes = len(head.encode("utf-8", "replace"))

    def close(self) -> None:
        """Terminal: later writes stay memory-only (a prefetcher thread
        that captured this instance must not reopen the closed file)."""
        if self.watchdog is not None:
            self.watchdog.stop()
        with self._io_lock:
            self.jsonl_path = None
            if self._file is not None:
                self._file.close()
                self._file = None


# -- the process-wide instance ------------------------------------------------

_GLOBAL: Telemetry | None = None
_GLOBAL_LOCK = threading.Lock()


def _default_jsonl_path() -> str | None:
    d = os.environ.get("TPUFRAME_TELEMETRY_DIR")
    if not d:
        return None
    return os.path.join(d, f"events-rank{_env_rank()}.jsonl")


def _parse_deadlines(spec: str) -> dict[str, float]:
    """``"train/step=120,ckpt/save=600"`` -> dict (bad entries skipped)."""
    out: dict[str, float] = {}
    for part in spec.split(","):
        name, sep, val = part.strip().partition("=")
        if not sep or not name:
            continue
        try:
            out[name] = float(val)
        except ValueError:
            continue
    return out


def _watchdog_from_env():
    default_s = os.environ.get("TPUFRAME_WATCHDOG_S")
    per_name = os.environ.get("TPUFRAME_WATCHDOG_DEADLINES")
    if not default_s and not per_name:
        return None
    from tpuframe.track.watchdog import Watchdog

    try:
        default = float(default_s) if default_s else None
    except ValueError:
        default = None
    return Watchdog(
        default_deadline_s=default,
        deadlines=_parse_deadlines(per_name) if per_name else None,
    )


def get_telemetry() -> Telemetry:
    """The process-wide telemetry (lazily created from env knobs)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = Telemetry(
                    _default_jsonl_path(), watchdog=_watchdog_from_env()
                )
    return _GLOBAL


def configure(
    jsonl_path: str | None = None,
    *,
    jsonl_dir: str | None = None,
    watchdog: Any = None,
    rank: int | None = None,
    max_events: int = 512,
    registry: MetricsRegistry | None = None,
) -> Telemetry:
    """Replace the process-wide telemetry (programmatic alternative to the
    env knobs).  ``jsonl_dir`` gives the conventional per-rank filename."""
    global _GLOBAL
    if jsonl_path is None and jsonl_dir is not None:
        r = _env_rank() if rank is None else rank
        jsonl_path = os.path.join(jsonl_dir, f"events-rank{r}.jsonl")
    tele = Telemetry(
        jsonl_path,
        rank=rank,
        max_events=max_events,
        registry=registry,
        watchdog=watchdog,
    )
    with _GLOBAL_LOCK:
        old, _GLOBAL = _GLOBAL, tele
    if old is not None:
        old.close()
    return tele


def reset() -> None:
    """Drop the process-wide instance (tests)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        old, _GLOBAL = _GLOBAL, None
    if old is not None:
        old.close()


# -- exporters ----------------------------------------------------------------


def publish_to_loggers(
    loggers: Sequence[Any],
    step: int,
    *,
    prefix: str = "telemetry/",
    registry: MetricsRegistry | None = None,
) -> dict[str, float]:
    """Bridge the registry into the existing logger contract
    (``log_metrics(dict, step=)`` — TensorBoardLogger, MLflowLogger, any
    duck-typed tracker).  Returns the published snapshot."""
    snap = (registry or get_telemetry().registry).snapshot(prefix=prefix)
    if snap:
        for lg in loggers:
            lg.log_metrics(dict(snap), step=step)
    return snap


class MetricsExportCallback:
    """Trainer callback publishing the registry to the run's loggers at
    every epoch end (rank-0, via the Trainer's own logging discipline).

    Duck-typed against ``tpuframe.train.callbacks.Callback`` rather than
    subclassing it — importing the train package would pull jax into every
    telemetry consumer (a launch parent must stay jax-free).
    """

    def __init__(self, prefix: str = "telemetry/"):
        self.prefix = prefix

    # the Trainer drives these via getattr(cb, hook) — all hooks must exist
    def on_fit_start(self, trainer) -> None: ...
    def on_epoch_start(self, trainer, epoch) -> None: ...
    def on_step_start(self, trainer) -> None: ...
    def on_step_end(self, trainer) -> None: ...
    def on_batch_end(self, trainer, metrics) -> None: ...
    def on_eval_end(self, trainer, epoch, metrics) -> None: ...
    def on_fit_end(self, trainer) -> None: ...

    def on_epoch_end(self, trainer, epoch, metrics) -> None:
        snap = get_telemetry().registry.snapshot(prefix=self.prefix)
        if snap:
            trainer._log_metrics(snap, step=epoch)


def start_metrics_server(port: int = 0, registry: MetricsRegistry | None = None):
    """Serve ``/metrics`` (Prometheus text) from a daemon thread; returns
    the ``track.http_store.MetricsServer`` (``.port``, ``.url``, ``.close()``)."""
    from tpuframe.track.http_store import MetricsServer

    return MetricsServer(registry=registry, port=port)
