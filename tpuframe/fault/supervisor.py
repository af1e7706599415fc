"""Restart orchestration: failure-classified budgets, jittered backoff,
pre-resume checkpoint validation.

Subsumes ``launch.elastic.run_with_restarts`` (which now delegates here).
Three upgrades over the 58-line constant-backoff loop it replaces:

1. **Failure classes, not one budget.**  A preemption is routine (the
   platform took the machine) and restarts immediately under its own
   generous budget; an infra failure (I/O, lost worker, runtime error)
   retries with exponential backoff + full jitter; a code bug
   (TypeError, ValueError, ...) never retries — rerunning a bug is how a
   crash becomes a crash *loop*.
2. **Backoff with jitter.**  Constant backoff synchronizes restart
   storms across hosts hammering the same recovering dependency
   (filesystem, rendezvous); ``delay = uniform(0, min(cap, base * 2^n))``
   (AWS full jitter) decorrelates them.
3. **Pre-resume checkpoint validation.**  A crash mid-save leaves a torn
   step directory; auto-resume pointing at it crash-loops into corrupt
   state.  Before every attempt the supervisor quarantines torn steps
   (``ckpt.meta.quarantine_torn_steps``) so ``maybe_restore``
   lands on the newest *committed* step.

Every decision is observable: ``fault/restart`` events carry the
failure class, attempt number and delay; ``fault/restarts`` /
``fault/preemptions`` counters accumulate; ``fault/giveup`` records why
a run was allowed to die.

With a ``capacity_probe`` the supervisor is additionally **elastic**:
surviving capacity is probed before every attempt, a shrink/grow emits
``fault/world_resized``, the attempt fn receives the new world size
(``launch.elastic.run_elastic`` turns that into a rebuilt mesh + rebound
plan + reshard-restore), and the run gives up only when survivors fall
below ``min_world_size`` — TorchTitan's "recoverable AND reconfigurable"
production requirement, instead of retrying into a world that no longer
exists until the budget dies.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import enum
import logging
import random
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from tpuframe.fault import health as _health
from tpuframe.fault.health import Divergence
from tpuframe.fault.preempt import Preempted
from tpuframe.track.telemetry import get_telemetry

logger = logging.getLogger(__name__)

__all__ = [
    "FailureClass",
    "RestartPolicy",
    "Supervisor",
    "WorldTooSmall",
    "backoff_delay",
    "classify_failure",
    "run_supervised",
]


class WorldTooSmall(RuntimeError):
    """Surviving capacity fell below the supervisor's ``min_world_size``
    floor — the elastic giveup, distinct from budget exhaustion (the job
    *could* keep restarting; it is not worth running this small)."""


class FailureClass(enum.Enum):
    #: the platform reclaimed the machine — routine, restart immediately
    PREEMPTION = "preemption"
    #: the RUN went bad (health sentinel: non-finite/spiking loss past
    #: the skip-step budget) — roll back to the last *healthy*
    #: checkpoint, perturb (LR backoff / data skip), restart immediately
    DIVERGENCE = "divergence"
    #: transient infrastructure (I/O, lost worker, runtime) — backoff + retry
    RETRYABLE = "retryable"
    #: a code bug — retrying reruns the bug; surface it
    FATAL = "fatal"


#: Exception types that are never worth retrying (bugs, not infra).
#: Superset of the old ``launch.elastic._FATAL``.
FATAL_TYPES = (
    KeyboardInterrupt,
    SystemExit,
    TypeError,
    ValueError,
    AttributeError,
    NameError,
    ImportError,
)


def classify_failure(exc: BaseException) -> FailureClass:
    """Stock classifier: :class:`Preempted` -> PREEMPTION,
    :class:`~tpuframe.fault.health.Divergence` -> DIVERGENCE, known bug
    types -> FATAL, everything else (OSError, RuntimeError — XLA surfaces
    infra trouble as RuntimeError — lost workers, timeouts) -> RETRYABLE."""
    if isinstance(exc, Preempted):
        return FailureClass.PREEMPTION
    if isinstance(exc, Divergence):
        return FailureClass.DIVERGENCE
    if isinstance(exc, FATAL_TYPES):
        return FailureClass.FATAL
    return FailureClass.RETRYABLE


def backoff_delay(
    attempt: int,
    *,
    base_s: float = 1.0,
    max_s: float = 60.0,
    jitter: bool = True,
    rng: random.Random | None = None,
) -> float:
    """Full-jitter exponential backoff (attempt counts from 1):
    ``uniform(0, min(max_s, base_s * 2^(attempt-1)))``; ``jitter=False``
    returns the cap itself (deterministic, for schedule tests)."""
    if attempt < 1:
        raise ValueError(f"attempt counts from 1, got {attempt}")
    cap = min(float(max_s), float(base_s) * (2.0 ** (attempt - 1)))
    if not jitter:
        return cap
    return (rng or random).uniform(0.0, cap)


@dataclass
class RestartPolicy:
    """Budgets + backoff shape.  ``max_restarts`` bounds RETRYABLE
    failures; ``max_preemptions`` bounds PREEMPTION separately (a healthy
    job on spot capacity gets preempted many times without ever being
    broken); FATAL has no budget — it never retries."""

    max_restarts: int = 2
    max_preemptions: int = 16
    #: DIVERGENCE budget — rollback-to-healthy + perturbed re-entry is
    #: attempted this many times; past it the run surfaces the
    #: Divergence (a model/data problem worth a human, not more retries)
    max_divergences: int = 2
    backoff_base_s: float = 1.0
    backoff_max_s: float = 60.0
    jitter: bool = True
    #: seed for the jitter rng (None = nondeterministic, the production
    #: default — determinism here would *recorrelate* host restarts)
    seed: int | None = None
    _rng: random.Random = field(init=False, repr=False, default=None)

    def __post_init__(self):
        self._rng = random.Random(self.seed) if self.seed is not None else None

    def delay_s(self, retry_attempt: int) -> float:
        return backoff_delay(
            retry_attempt,
            base_s=self.backoff_base_s,
            max_s=self.backoff_max_s,
            jitter=self.jitter,
            rng=self._rng,
        )


class Supervisor:
    """Run a resumable fn under the restart policy.

    ``fn`` must restore from its checkpointer on entry (the Trainer's
    ``maybe_restore`` does) so a restart continues rather than recomputes.

    Args:
      policy: budgets + backoff (default :class:`RestartPolicy`).
      checkpoint_dir: when given, validated before **every** attempt —
        torn step directories are quarantined (moved aside, never
        deleted) in both this directory and its ``_intra`` sibling, so
        auto-resume lands on the newest committed step instead of
        crash-looping into corrupt state.
      classifier: exception -> :class:`FailureClass` (default
        :func:`classify_failure`).
      on_restart: ``(attempt, error)`` observability hook, called before
        the backoff sleep (log, page, mark the run).
      sleep: injectable for tests.
      capacity_probe: optional ``() -> int`` returning the currently
        *available* world size (devices/ranks), probed before **every**
        attempt.  With a probe, ``fn`` is called as ``fn(world_size)`` so
        the attempt can rebuild its runtime for the surviving capacity
        (``launch.elastic.run_elastic`` wires mesh-rebuild + plan-rebind
        + reshard-restore on top of this); a shrink/grow between
        attempts emits one ``fault/world_resized`` event.  Without a
        probe the supervisor keeps today's equal-capacity contract and
        calls ``fn()``.
      min_world_size: elastic floor — when the probe reports fewer
        survivors, give up (``fault/giveup`` reason ``min-world-size``,
        :class:`WorldTooSmall`) instead of limping below the smallest
        world the job is worth running on.
    """

    def __init__(
        self,
        policy: RestartPolicy | None = None,
        *,
        checkpoint_dir: str | None = None,
        classifier: Callable[[BaseException], FailureClass] | None = None,
        on_restart: Callable[[int, BaseException], None] | None = None,
        sleep: Callable[[float], None] = time.sleep,
        capacity_probe: Callable[[], int] | None = None,
        min_world_size: int = 1,
    ):
        self.policy = policy or RestartPolicy()
        self.checkpoint_dir = checkpoint_dir
        self.classifier = classifier or classify_failure
        self.on_restart = on_restart
        self.sleep = sleep
        self.retries = 0
        self.preemptions = 0
        self.divergences = 0
        if min_world_size < 1:
            raise ValueError(f"min_world_size must be >= 1, got {min_world_size}")
        self.capacity_probe = capacity_probe
        self.min_world_size = min_world_size
        #: current probed world size (None until the first probe; stays
        #: None for non-elastic supervisors with no probe)
        self.world_size: int | None = None

    # -- pre-resume validation ----------------------------------------------
    def validate_checkpoints(self) -> list[str]:
        """Quarantine torn steps under ``checkpoint_dir`` and its
        ``_intra`` snapshot sibling; returns quarantined paths."""
        if self.checkpoint_dir is None:
            return []
        from tpuframe.ckpt.meta import quarantine_torn_steps

        moved: list[str] = []
        for d in (self.checkpoint_dir, str(self.checkpoint_dir) + "_intra"):
            moved += quarantine_torn_steps(d)
        return moved

    # -- compile warm-start --------------------------------------------------
    def _ensure_compile_cache(self) -> str | None:
        """Make sure the persistent compilation cache is live before the
        first attempt: attempt 1 then *writes* every program it compiles,
        and an in-process restart (fresh Trainer => fresh traces) or a
        replacement process on the same host *reads* them back instead of
        recompiling — the dominant share of a recovery's wall (its
        ``compile_s`` component).  Guarded on jax already being
        imported: the supervisor itself is stdlib-only and must keep
        working while jax is wedged; if the training fn imports jax
        later, ``core.runtime.initialize`` enables the cache then.
        """
        import sys

        if "jax" not in sys.modules:
            return None
        try:
            from tpuframe.compile import cache as compile_cache

            return compile_cache.enable_from_env()
        except Exception:
            return None  # a broken cache must not block recovery

    # -- elastic capacity ----------------------------------------------------
    def _probe_world(self) -> None:
        """Probe surviving capacity before an attempt: record resizes as
        one loud ``fault/world_resized`` event each, and give up
        (:class:`WorldTooSmall`) when survivors fall below the floor —
        raised *outside* the retry try-block, so it is never itself
        retried."""
        if self.capacity_probe is None:
            return
        n = int(self.capacity_probe())
        tele = get_telemetry()
        old = self.world_size
        if old is not None and n != old:
            tele.registry.counter("fault/world_resizes").inc()
            tele.event(
                "fault/world_resized",
                from_world=old,
                to_world=n,
                min_world_size=self.min_world_size,
                attempt=self.retries + self.preemptions,
            )
            logger.warning(
                "world resized %d -> %d survivor(s); restarting at the "
                "smaller world (floor: %d)", old, n, self.min_world_size,
            )
        self.world_size = n
        if n < self.min_world_size:
            tele.event(
                "fault/giveup", reason="min-world-size",
                world_size=n, min_world_size=self.min_world_size,
            )
            raise WorldTooSmall(
                f"surviving capacity {n} fell below min_world_size="
                f"{self.min_world_size}; giving up rather than training "
                "on a world too small to be worth the schedule"
            )

    # -- divergence rollback -------------------------------------------------
    def _divergence_recovery(self, error: BaseException | None = None) -> dict:
        """The DIVERGENCE restart's extra work: roll both checkpoint
        directories back to their last *healthy* committed step
        (newer steps quarantined — one loud ``fault/rollback`` event
        each) and escalate the process-wide recovery directive (LR
        backoff compounds, data-order skip arms) that the next
        attempt's Trainer consumes.  Without a ``checkpoint_dir`` only
        the perturbation applies — there is nothing to roll back.

        The raising Trainer's :class:`~tpuframe.fault.health.Divergence`
        carries its policy, so a programmatic
        ``HealthPolicy(lr_backoff=..., skip_batches=...)`` shapes the
        perturbation; a policy-less error falls back to the env knobs."""
        directive = _health.escalate_recovery(getattr(error, "policy", None))
        out: dict = {
            "lr_scale": round(directive.lr_scale, 6),
            "skip_batches": directive.skip_batches,
        }
        if self.checkpoint_dir is not None:
            from tpuframe.ckpt.meta import rollback_to_last_healthy

            targets: list[int | None] = []
            for d in (self.checkpoint_dir, str(self.checkpoint_dir) + "_intra"):
                rb = rollback_to_last_healthy(d)
                targets.append(rb["to_step"])
                if rb["quarantined"]:
                    logger.warning(
                        "divergence rollback: quarantined step(s) %s under "
                        "%s; resuming at %s",
                        rb["quarantined"], d, rb["to_step"],
                    )
            # auto-resume takes the newer of the two directories' steps
            landed = [t for t in targets if t is not None]
            out["rolled_back_to"] = max(landed) if landed else None
        return out

    # -- the loop ------------------------------------------------------------
    def run(self, fn: Callable[..., Any]) -> Any:
        tele = get_telemetry()
        compile_cache_dir = self._ensure_compile_cache()
        # a previous run's divergence escalations (compounded LR backoff,
        # armed skip) must not leak into this one
        _health.reset_recovery()
        while True:
            quarantined = self.validate_checkpoints()
            if quarantined:
                logger.warning(
                    "quarantined %d torn checkpoint step(s): %s",
                    len(quarantined), quarantined,
                )
            self._probe_world()
            try:
                return fn(self.world_size) if self.capacity_probe else fn()
            except BaseException as e:
                cls = self.classifier(e)
                if cls is FailureClass.FATAL:
                    tele.event("fault/giveup", reason="fatal",
                               error=repr(e)[:300])
                    raise
                rollback: dict | None = None
                if cls is FailureClass.DIVERGENCE:
                    self.divergences += 1
                    attempt, budget = (
                        self.divergences, self.policy.max_divergences
                    )
                    counter, delay = "fault/divergences", 0.0
                    if attempt <= budget:
                        # roll back + escalate the perturbation BEFORE
                        # the restart event, so the event can say where
                        # the next attempt re-enters; no backoff — the
                        # rollback itself already re-trains lost steps
                        rollback = self._divergence_recovery(e)
                elif cls is FailureClass.PREEMPTION:
                    self.preemptions += 1
                    attempt, budget = self.preemptions, self.policy.max_preemptions
                    counter, delay = "fault/preemptions", 0.0
                    # the notice is consumed by this restart: a real
                    # preemption replaces the process (fresh flag), but a
                    # single-host in-process restart shares the watcher —
                    # left set, attempt N+1 would re-preempt at step 1
                    from tpuframe.fault.preempt import active_watcher

                    w = active_watcher()
                    if w is not None:
                        w.clear()
                else:
                    self.retries += 1
                    attempt, budget = self.retries, self.policy.max_restarts
                    counter = "fault/restarts"
                    delay = self.policy.delay_s(self.retries)
                if attempt > budget:
                    tele.event(
                        "fault/giveup", reason=f"{cls.value}-budget",
                        attempts=attempt - 1, budget=budget,
                        error=repr(e)[:300],
                    )
                    raise
                tele.registry.counter(counter).inc()
                tele.event(
                    "fault/restart",
                    failure_class=cls.value,
                    attempt=attempt,
                    budget=budget,
                    delay_s=round(delay, 3),
                    error=repr(e)[:300],
                    # warm-cache provenance: a restart that recompiled
                    # from scratch vs one that retrieved its programs is
                    # the first question a slow-recovery report asks
                    compile_cache=compile_cache_dir,
                    **({"rollback": rollback} if rollback else {}),
                )
                logger.warning(
                    "train fn failed (%s, class=%s); restart %d/%d after %.2fs",
                    repr(e), cls.value, attempt, budget, delay,
                )
                if self.on_restart is not None:
                    # the hook keeps the old loop's contract: a single
                    # monotonic restart count across classes (budgets are
                    # per-class, but "restart N" in logs/pages must not
                    # repeat or go backwards)
                    self.on_restart(
                        self.retries + self.preemptions + self.divergences, e
                    )
                if delay > 0:
                    self.sleep(delay)


def run_supervised(
    fn: Callable[[], Any],
    *,
    policy: RestartPolicy | None = None,
    checkpoint_dir: str | None = None,
    **kwargs: Any,
) -> Any:
    """One-shot convenience: ``Supervisor(policy, ...).run(fn)``."""
    return Supervisor(policy, checkpoint_dir=checkpoint_dir, **kwargs).run(fn)
