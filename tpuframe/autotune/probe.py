"""The measured-probe harness: short timeboxed A/B runs.

Methodology:
per-step walls with the warmup prefix discarded, medians (robust to the
one GC pause), and the fleet analyzer's exit-3 regression-gate stance —
a probe can observe whatever it likes, but it can only *commit* a config
whose median beats the baseline by the guard margin.  A slower probe is
recorded (the decision trail persists with the config) and rolled back.

The probe's contract with its caller is one function:
``run_fn(env: dict[str, str]) -> list[float]`` — run a short workload
with ``env`` overlaid on the environment and return per-step wall
seconds.  The overlay/restore is handled HERE (``_env_overlay``), so a
run_fn that crashes can never leak probe env into the real run.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable, Iterator

__all__ = ["ProbeResult", "measure", "run_probe"]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "").strip() or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "").strip() or default)
    except ValueError:
        return default


def probe_steps() -> int:
    """Steps per probe run (``TPUFRAME_AUTOTUNE_PROBE_STEPS``, default 8)."""
    return max(2, _env_int("TPUFRAME_AUTOTUNE_PROBE_STEPS", 8))


def warmup_steps() -> int:
    """Warmup prefix discarded from every probe
    (``TPUFRAME_AUTOTUNE_WARMUP_STEPS``, default 2)."""
    return max(0, _env_int("TPUFRAME_AUTOTUNE_WARMUP_STEPS", 2))


def guard_ratio() -> float:
    """Commit threshold (``TPUFRAME_AUTOTUNE_GUARD``, default 0.97): a
    probe commits only when ``median <= baseline * guard`` — capped at
    1.0 so no configuration can ever commit slower than its baseline."""
    return min(1.0, max(0.5, _env_float("TPUFRAME_AUTOTUNE_GUARD", 0.97)))


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


@dataclasses.dataclass
class ProbeResult:
    """One A/B probe's verdict (persists in ``TunedConfig.probes``)."""

    env: dict[str, str]
    p50_s: float
    baseline_p50_s: float
    committed: bool
    reason: str
    steps: int

    @property
    def ratio(self) -> float:
        return (self.p50_s / self.baseline_p50_s
                if self.baseline_p50_s > 0 else float("inf"))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ratio"] = round(self.ratio, 4)
        return d


@contextlib.contextmanager
def _env_overlay(env: dict[str, str]) -> Iterator[None]:
    """Apply ``env`` to ``os.environ`` for the probe's duration and
    restore EXACTLY the prior state afterwards, crash or not."""
    saved = {k: os.environ.get(k) for k in env}
    try:
        os.environ.update(env)
        yield
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def measure(run_fn: Callable[[dict], list[float]],
            env: dict[str, str] | None = None, *,
            warmup: int | None = None) -> float:
    """Warmup-discarded median step wall of one run under ``env``."""
    env = dict(env or {})
    w = warmup_steps() if warmup is None else warmup
    with _env_overlay(env):
        walls = list(run_fn(env))
    if not walls:
        raise ValueError("run_fn returned no step walls")
    kept = walls[w:] if len(walls) > w else walls[-1:]
    return _median(kept)


def run_probe(run_fn: Callable[[dict], list[float]],
              env: dict[str, str], baseline_p50_s: float, *,
              guard: float | None = None,
              warmup: int | None = None) -> ProbeResult:
    """One A/B probe of ``env`` against ``baseline_p50_s``.

    Never raises out of a failing candidate: a run_fn that dies under
    the probe env yields an uncommitted result (reason carries the
    error) — a config that cannot even run must never commit.
    """
    g = guard_ratio() if guard is None else min(1.0, guard)
    try:
        p50 = measure(run_fn, env, warmup=warmup)
    except Exception as e:  # the probe boundary: contain, report, roll back
        return ProbeResult(
            env=dict(env), p50_s=float("inf"),
            baseline_p50_s=baseline_p50_s, committed=False,
            reason=f"probe run failed: {type(e).__name__}: {e}",
            steps=0,
        )
    committed = p50 <= baseline_p50_s * g
    reason = (
        f"p50 {p50:.4f}s vs baseline {baseline_p50_s:.4f}s "
        f"(guard x{g:.2f}): " + ("committed" if committed else "rolled back")
    )
    return ProbeResult(
        env=dict(env), p50_s=p50, baseline_p50_s=baseline_p50_s,
        committed=committed, reason=reason, steps=probe_steps(),
    )
