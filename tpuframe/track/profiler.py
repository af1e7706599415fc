"""Profiling/tracing: programmatic ``jax.profiler`` capture.

The TPU-native equivalent of the reference's tracing toolbox (SURVEY.md §5
"Tracing / profiling"): DeepSpeed's ``wall_clock_breakdown: True`` +
``steps_per_print`` (`/root/reference/02_deepspeed/deepspeed_config.py:47-48`),
the CUDA debug env flags (`/root/reference/setup/00_setup.py:66-67,117-123`),
and the ``nvidia-smi``/screenshot evidence (`/root/reference/README.md:18-20`)
— replaced by real XLA traces:

- :func:`trace` — context manager around any region; produces a TensorBoard-
  loadable trace directory (per-op device timeline, HLO, memory viewer).
  Importing this module also lets every telemetry span show in such a trace
  as a ``tpuframe/<name>`` ``TraceAnnotation`` carrying its ``step``
  (`track/telemetry.py` may not import jax, so the factory is installed
  from here).
- :class:`ProfilerCallback` — Trainer callback that captures a window of
  train steps.  Two modes: one-shot (capture steps [skip_steps,
  skip_steps + num_steps) then log the zipped trace as a run artifact,
  rank-0 only) and **sampled continuous capture** (``every_steps > 0``:
  capture ``num_steps`` steps every ``every_steps`` steps into rotated
  ``capture-b<batch>`` dirs, newest ``keep`` retained — bounded
  on-device evidence for long runs, armed from the env via
  :meth:`ProfilerCallback.from_env` / ``TPUFRAME_PROFILE_*``).

Every completed capture emits one ``profile/capture`` telemetry event
(dir, steps, bytes, the wall/mono anchor pair of its start) and bumps
the ``profile/captures`` counter — the breadcrumbs
``tpuframe.track.analyze`` follows to attach a parsed ``device_time``
block (see `track/device_time.py`) to the skew report and merge device
ops into the Perfetto timeline.

Per-step wall-clock breakdown (data-wait vs dispatch vs host-block) is
measured by the Trainer loop itself and reported in every epoch summary —
see ``Trainer._run_epoch``.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import time
from typing import TYPE_CHECKING, Any

import jax

if TYPE_CHECKING:  # pragma: no cover
    from tpuframe.train.trainer import Trainer

from tpuframe.track import telemetry
from tpuframe.train.callbacks import Callback


def _span_annotation(name: str, step: int | None):
    """A telemetry span on the profiler's clock; a flag test while no
    profiler session runs."""
    if step is None:
        return jax.profiler.TraceAnnotation(f"tpuframe/{name}")
    return jax.profiler.TraceAnnotation(f"tpuframe/{name}", step=step)


telemetry.set_annotation_hook(_span_annotation)


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a ``jax.profiler`` trace of the enclosed region to ``logdir``.

    The caller is responsible for blocking on async work it wants included
    (``jax.block_until_ready``) before the region closes.  The trace is
    stopped on the error path too — and a stop failure there is swallowed
    so it can neither mask the real exception nor leave the profiler
    started and wedge the next capture.
    """
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    except BaseException:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        raise
    else:
        jax.profiler.stop_trace()


def trace_step_window(fn, n_steps: int, logdir: str, *args, **kwargs) -> str:
    """Run ``fn(*args, **kwargs)`` ``n_steps`` times under a trace.

    ``fn``'s return value is blocked on each step so device work lands in
    the trace.  A raising step still closes the trace (see :func:`trace`)
    — the partial window is real evidence of the step that raised.
    Returns ``logdir``.
    """
    with trace(logdir):
        for _ in range(n_steps):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
    return logdir


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                continue
    return total


class ProfilerCallback(Callback):
    """Capture XLA traces of train-step windows, with an optional cadence.

    Args:
      logdir: where to write traces.  One-shot mode defaults to a temp
        dir (removed after the artifact is logged); cadence mode needs a
        stable home and defaults to ``<tmp>/tpuframe_profile_<pid>``.
      skip_steps: batches to skip first (warmup/compile noise).
      num_steps: batches per capture window.
      every_steps: 0 (default) = one capture then done; N > 0 = start a
        fresh ``num_steps``-step capture every N batches, each into its
        own ``capture-b<batch>`` subdir of ``logdir``, oldest dirs
        dropped past ``keep`` (rotation mirrors the telemetry log's
        ``TPUFRAME_TELEMETRY_KEEP`` discipline).
      keep: capture dirs retained in cadence mode (default 3).
      rank0_only: capture on the main process only (default True — one
        host's trace prices the fleet; every rank tracing would multiply
        the overhead and the disk for identical programs).

    One-shot captures are zipped and handed to every logger exposing a
    ``run.log_artifact`` (tpuframe's MLflowLogger) or ``log_artifact`` —
    rank-0 only, matching the logging discipline.  Cadence captures stay
    on disk as parseable evidence instead (artifact-zipping every window
    of a week-long run would flood the tracker).
    """

    def __init__(
        self,
        logdir: str | None = None,
        skip_steps: int = 3,
        num_steps: int = 5,
        *,
        every_steps: int = 0,
        keep: int | None = None,
        rank0_only: bool = True,
    ):
        self.logdir = logdir
        self.skip_steps = skip_steps
        self.num_steps = max(1, int(num_steps))
        self.every_steps = max(0, int(every_steps))
        self.keep = 3 if keep is None else max(1, int(keep))
        self.rank0_only = rank0_only
        self._tmp: str | None = None
        self._active = False
        self._done = False
        self._next_start = None  # cadence: earliest batch to start at
        self._capture_dir: str | None = None
        self._anchor: tuple[float, float] | None = None  # (wall, mono)
        self.trace_dir: str | None = None
        self.artifact: str | None = None
        #: completed captures, newest last: {dir, steps, bytes, partial}
        self.captures: list[dict] = []
        #: True when the fit ended inside the capture window (the logged
        #: trace covers fewer than ``num_steps`` steps)
        self.partial = False

    @classmethod
    def from_env(cls) -> "ProfilerCallback | None":
        """The env-armed instance (``TPUFRAME_PROFILE_STEPS`` > 0 arms
        it; EVERY/KEEP/DIR refine), or None when capture is off.  The
        Trainer auto-attaches this so a launch env flag is all a long
        run needs to carry bounded device-time evidence."""
        from tpuframe.track.device_time import profile_env

        env = profile_env()
        steps = env["TPUFRAME_PROFILE_STEPS"]
        if not steps:
            return None
        return cls(
            logdir=env["TPUFRAME_PROFILE_DIR"] or None,
            num_steps=steps,
            every_steps=env["TPUFRAME_PROFILE_EVERY"],
            keep=env["TPUFRAME_PROFILE_KEEP"],
        )

    @property
    def cadence(self) -> bool:
        return self.every_steps > 0

    def _base_dir(self) -> str:
        if self.logdir is None and self._tmp is None:
            if self.cadence:
                # cadence evidence must outlive the callback: a stable
                # per-process home, not a remove-after-artifact temp dir
                self._tmp = os.path.join(
                    tempfile.gettempdir(), f"tpuframe_profile_{os.getpid()}"
                )
            else:
                self._tmp = tempfile.mkdtemp(prefix="tpuframe_trace_")
        return self.logdir or self._tmp

    def _target(self) -> str:
        base = self._base_dir()
        if self.cadence:
            return os.path.join(base, f"capture-b{self._start_batch:08d}")
        return base

    def on_step_start(self, trainer: "Trainer") -> None:
        if self._done or self._active:
            return
        if self.rank0_only and not trainer.is_main:
            self._done = True  # never arms on this rank; stop checking
            return
        start_at = (
            self._next_start if self._next_start is not None
            else self.skip_steps
        )
        if trainer.batches_seen < start_at:
            return
        self._start_batch = trainer.batches_seen
        target = self._target()
        os.makedirs(target, exist_ok=True)
        self._anchor = (time.time(), time.monotonic())
        jax.profiler.start_trace(target)
        self._active = True
        self._capture_dir = target

    def on_step_end(self, trainer: "Trainer") -> None:
        if not self._active:
            return
        if trainer.batches_seen - self._start_batch < self.num_steps:
            return
        self._finalize(trainer, partial=False)

    def on_fit_end(self, trainer: "Trainer") -> None:
        # fit ended mid-capture (duration reached / early stop / a step
        # that RAISED — on_fit_end fires from fit()'s finally): close the
        # trace so the profiler isn't left running across fits, then KEEP
        # the evidence — a partial window is still a real trace of real
        # steps, and the window containing the raising step is exactly
        # the trace someone debugging it wants.  Marked ``partial`` and
        # logged like a full capture (rank-0 discipline).
        if self._active:
            self._finalize(trainer, partial=True)
            self._done = True  # no fresh session after the fit ended

    def _finalize(self, trainer: "Trainer", *, partial: bool) -> None:
        try:
            # include in-flight device work; a poisoned state (the step
            # raised) must not leave the profiler started
            jax.block_until_ready(trainer.state)
        except Exception:
            pass
        try:
            jax.profiler.stop_trace()
        finally:
            self._active = False
        self.partial = partial
        steps = max(0, trainer.batches_seen - self._start_batch)
        cap_dir = self._capture_dir
        cap = {
            "dir": cap_dir,
            "steps": steps,
            "bytes": _dir_bytes(cap_dir) if cap_dir else 0,
            "partial": partial,
        }
        self.captures.append(cap)
        self._emit_capture_event(cap)
        if self.cadence:
            self.trace_dir = cap_dir
            self._rotate()
            # schedule the next window from this one's START, so the
            # cadence is "every N steps", not "N steps of gap"
            self._next_start = self._start_batch + max(
                self.every_steps, self.num_steps
            )
        else:
            self._done = True
            if trainer.is_main:
                self._log_artifact(trainer)
            if self._tmp is not None:
                # the temp capture dir is deleted below: publish the zipped
                # artifact (``self.artifact``) instead of a dangling path
                shutil.rmtree(self._tmp, ignore_errors=True)
                self._tmp = None
                self.trace_dir = None
            else:
                self.trace_dir = self.logdir

    def _emit_capture_event(self, cap: dict) -> None:
        from tpuframe.track.telemetry import get_telemetry

        tele = get_telemetry()
        tele.registry.counter("profile/captures").inc()
        wall, mono = self._anchor or (None, None)
        tele.event(
            "profile/capture",
            dir=cap["dir"],
            steps=cap["steps"],
            bytes=cap["bytes"],
            partial=cap["partial"],
            wall_start=wall,
            mono_start=mono,
        )

    def _rotate(self) -> None:
        """Drop capture dirs past ``keep``, oldest first (the batch-
        numbered names sort chronologically)."""
        from tpuframe.track.device_time import list_captures

        caps = list_captures(self._base_dir())
        for stale in caps[: max(0, len(caps) - self.keep)]:
            shutil.rmtree(stale, ignore_errors=True)

    def _log_artifact(self, trainer: "Trainer") -> None:
        src = self._capture_dir or self._base_dir()
        base = os.path.join(
            tempfile.mkdtemp(prefix="tpuframe_trace_zip_"), "xla_trace"
        )
        archive = shutil.make_archive(base, "zip", src)
        for lg in trainer.loggers:
            run = getattr(lg, "run", None)
            target: Any = None
            if run is not None and hasattr(run, "log_artifact"):
                target = run
            elif hasattr(lg, "log_artifact"):
                target = lg
            if target is not None:
                self.artifact = target.log_artifact(archive, "profile")
        shutil.rmtree(os.path.dirname(archive), ignore_errors=True)
