"""Diagnosis: turn the analyzer's skew report into ordered knob moves.

The decision table (AUTOTUNE.md mirrors it) reads the same signals a
human reads off ``python -m tpuframe.track.analyze``:

- **input-bound** (``lost_by_bound.input`` dominates, or — single-rank
  runs, where cross-rank skew is zero by construction — the per-step
  ``bound`` votes / ``data_wait_total_s`` fraction say the step waits on
  the host pipeline): more loader workers, deeper prefetch, more ring
  buffers, uint8 transfer.
- **checkpoint-bound** (``lost_by_bound.checkpoint`` dominates): stretch
  the mid-epoch snapshot cadence.
- **comms-bound** (the ``comms`` block shows allreduce wall a large
  fraction of step wall at mode "none"): int8 wire compression, then
  bucket sizing.
- **memory-bound** (the ``memory`` block carries an OOM, or the live
  HBM watermark sits above ~92% of the device limit): raise the ZeRO
  stage, split grad-accum microbatches, offload the optimizer — the
  ``memory/oom`` event's ``suggest_fit`` rung seeds the values when one
  exists.  Checked FIRST: a plan that doesn't fit can't be tuned
  faster.
- **compile** (cold-compile wall dominates total): make sure the AOT
  precompiler and the persistent compile cache are on.

Every proposed value passes through :func:`tpuframe.autotune.config.clamp`
against the lint-enforced ``*_ENV_DOMAINS`` registry — a move outside a
knob's legal domain is dropped here, before it can reach a probe.  The
diagnosis only *proposes*; the probe harness decides (a committed move
must beat its baseline, so a wrong diagnosis costs probe time, never a
slower run).
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import dataclasses

from tpuframe.autotune.config import all_env_domains, clamp
from tpuframe.ops.registry import normalize_top_ops

__all__ = ["Diagnosis", "KnobMove", "diagnose"]

#: below this fraction of total step wall, a bottleneck class is noise
_SIGNIFICANT = 0.10

#: HBM watermark / device limit above which the fit is one fragmentation
#: spike away from RESOURCE_EXHAUSTED — memory-bound even without an OOM
_MEM_PRESSURE = 0.92

#: base-op name tokens that identify the compressed wire's staged
#: encode/decode math in a ``device_time.top_ops`` row — the
#: scale/round/clip/dequant chain XLA emits around a staged collective
#: (convert + round-nearest + clamp/floor on the bucket arrays)
_WIRE_MATH_OPS = ("convert", "round", "clamp", "floor", "clip", "quant")


@dataclasses.dataclass(frozen=True)
class KnobMove:
    """One candidate env write: knob -> value, with the symptom that
    motivated it (the doctor prints these as the decision trail)."""

    knob: str
    value: str
    reason: str


@dataclasses.dataclass
class Diagnosis:
    """What the report says is slow, and the ordered probe candidates."""

    bound: str  # "input" | "checkpoint" | "comms" | "memory" | "compute" | "none"
    detail: dict
    moves: list[KnobMove]


def _bound_votes(report: dict) -> dict[str, int]:
    """Per-step bound classification tally — the single-rank-safe signal
    (``lost_by_bound`` only accumulates on straggling steps, which need
    cross-rank skew to exist)."""
    votes: dict[str, int] = {}
    for row in report.get("per_step") or []:
        b = row.get("bound")
        if b:
            votes[b] = votes.get(b, 0) + 1
    return votes


def _data_wait_fraction(report: dict) -> float:
    """Fleet data-wait seconds over fleet step seconds — how much of the
    run the devices spent waiting on the host pipeline."""
    wait = sum(r.get("data_wait_total_s") or 0.0
               for r in report.get("per_rank") or [])
    st = report.get("step_time") or {}
    total = (st.get("mean") or 0.0) * (st.get("count") or 0)
    n_ranks = max(1, report.get("ranks") or 1)
    return wait / (total * n_ranks) if total > 0 else 0.0


def _classify(report: dict) -> tuple[str, dict]:
    st = report.get("step_time") or {}
    total_step_s = (st.get("mean") or 0.0) * (st.get("count") or 0)
    lost = dict(report.get("lost_by_bound") or {})
    votes = _bound_votes(report)
    wait_frac = _data_wait_fraction(report)
    detail = {
        "lost_by_bound": lost,
        "bound_votes": votes,
        "data_wait_fraction": round(wait_frac, 4),
    }

    # memory first: an OOM (or a watermark one fragmentation spike from
    # the limit) trumps every speed signal — a plan that doesn't fit
    # can't be tuned faster
    mem = report.get("memory") or None
    if mem:
        util = mem.get("hbm_peak_util") or 0.0
        detail["memory"] = {
            "ooms": mem.get("ooms") or 0,
            "hbm_peak_util": round(util, 4),
        }
        if (mem.get("ooms") or 0) > 0 or util >= _MEM_PRESSURE:
            return "memory", detail

    # multi-rank: straggler-attributed lost seconds name the bound
    if total_step_s > 0 and lost:
        top = max(lost, key=lambda k: lost[k])
        if lost[top] / total_step_s >= _SIGNIFICANT:
            return top, detail

    # comms: allreduce wall as a fraction of step wall.  The report's
    # allreduce_s is a percentile block (standalone/bench collectives
    # only) — p50 x count approximates the total collective wall.
    comms = report.get("comms") or None
    if comms and total_step_s > 0:
        ar = comms.get("allreduce_s") or 0.0
        if isinstance(ar, dict):
            ar = (ar.get("p50") or 0.0) * (ar.get("count") or 0)
        frac = float(ar) / total_step_s
        detail["comms_fraction"] = round(frac, 4)
        if frac >= _SIGNIFICANT:
            return "comms", detail

    # device-level comms: the parsed profiler capture's exposed-comms
    # fraction of the device step — the DIRECT measurement (the
    # allreduce heuristic above only sees standalone/bench collectives;
    # a fused step's collective is invisible to it but not to the trace)
    dt = report.get("device_time") or None
    if dt and (dt.get("device_step_s") or 0) > 0:
        frac = (
            (dt.get("exposed_comms_per_step_s") or 0.0)
            / dt["device_step_s"]
        )
        detail["exposed_comms_fraction"] = round(frac, 4)
        if frac >= _SIGNIFICANT:
            return "comms", detail

    # single-rank fallback: the device waiting on the host IS input-bound
    # even though no step ever "straggles"
    if wait_frac >= _SIGNIFICANT:
        return "input", detail
    steps = sum(votes.values())
    if steps:
        top = max(votes, key=lambda k: votes[k])
        if top != "compute" and votes[top] / steps >= 0.5:
            return top, detail
    return ("compute", detail) if steps else ("none", detail)


def diagnose(report: dict, *, gauges: dict | None = None) -> Diagnosis:
    """Ordered, domain-clamped knob moves for ``report``'s bottleneck.

    ``gauges`` (optional) is a snapshot of live registry gauges (name ->
    value) — currently consulted for the loader's ring-alloc pressure
    (``data/ring_allocs`` growing means the pool is undersized).
    """
    domains = all_env_domains()
    bound, detail = _classify(report)
    moves: list[KnobMove] = []

    def move(knob: str, value, reason: str) -> None:
        v = clamp(knob, value, domains)
        if v is not None:
            moves.append(KnobMove(knob=knob, value=v, reason=reason))

    if bound == "input":
        why = (f"input-bound: data_wait {detail['data_wait_fraction']:.0%} "
               "of step wall")
        move("TPUFRAME_LOADER_WORKERS", 2, why)
        move("TPUFRAME_LOADER_WORKERS", 4, why)
        move("TPUFRAME_PREFETCH_DEPTH", 4, why)
        move("TPUFRAME_LOADER_TRANSFER_DTYPE", "uint8",
             "input-bound: uint8 transfer is 4x less host->device bytes")
        move("TPUFRAME_LOADER_RING_BUFFERS", 8,
             "input-bound: deeper assembly ring")
        if gauges and (gauges.get("data/ring_allocs") or 0) > 0:
            move("TPUFRAME_LOADER_RING_BUFFERS", 16,
                 "ring pool undersized: data/ring_allocs still growing")
    elif bound == "checkpoint":
        lost = detail["lost_by_bound"].get("checkpoint", 0.0)
        move("TPUFRAME_CKPT_INTERVAL_BATCHES", 200,
             f"checkpoint-bound: {lost:.2f}s lost to snapshot stalls — "
             "stretch the mid-epoch cadence")
    elif bound == "comms":
        comms = report.get("comms") or {}
        dt = report.get("device_time") or {}
        exposed = dt.get("exposed_comms_per_step_s")
        why_bucket = "comms-bound: larger buckets amortize per-collective latency"
        if exposed:
            # the measured number the bucket probe must shrink: exposed
            # wall, not bytes — overlap is the win on real topology
            why_bucket = (
                f"comms-bound: {exposed * 1e3:.2f}ms/step of collective "
                "wall exposed (not hidden behind compute) — probe bucket "
                "sizing against overlap"
            )
        if (comms.get("mode") or "none") in ("none", ""):
            move("TPUFRAME_COMMS_COMPRESSION", "int8",
                 "comms-bound at f32 wire: int8 is ~4x fewer sync bytes")
        if exposed:
            # the overlap probe: gated on the MEASURED exposed wall (a
            # parsed capture), because group scheduling only pays when
            # collective seconds are provably NOT hidden behind compute
            # — bytes-on-wire is invariant under grouping, so the probe
            # must judge itself on exposed ms/step, nothing else
            move("TPUFRAME_COMMS_GROUPS", 4,
                 f"comms-bound: {exposed * 1e3:.2f}ms/step exposed — "
                 "fire the sync as 4 bucket groups in reverse-backward "
                 "order so the wire hides behind the remaining backward")
        move("TPUFRAME_COMMS_BUCKET_MB", 8.0, why_bucket)
        move("TPUFRAME_GRAD_ACCUM", 2,
             "comms-bound: accumulate micro-batches, sync once per "
             "super-batch")
    elif bound == "memory":
        mem = report.get("memory") or {}
        oom = mem.get("last_oom") or {}
        sug = oom.get("suggestion") or {}
        util = (detail.get("memory") or {}).get("hbm_peak_util") or 0.0
        why = (
            f"memory-bound: {mem.get('ooms') or 0} OOM event(s)"
            if (mem.get("ooms") or 0) > 0
            else f"memory-bound: HBM watermark at {util:.0%} of the limit"
        )
        # the estimator's nearest-fitting rung seeds the values when the
        # OOM event carried one; the escalation-ladder defaults
        # otherwise.  Every move still passes clamp + the
        # never-commit-slower probe — a bad suggestion costs probe time,
        # never a slower (or still-OOMing) run.
        move("TPUFRAME_ZERO_STAGE", sug.get("zero_stage", 3),
             why + " — shard optimizer/params over the data-parallel "
             "world (restart)")
        move("TPUFRAME_GRAD_ACCUM", sug.get("microbatches", 2),
             why + " — smaller microbatch slices shrink live activations")
        if sug.get("offload_optimizer") or not sug:
            move("TPUFRAME_OFFLOAD_OPTIMIZER", True,
                 why + " — optimizer state to pinned host memory")
    elif bound == "compute":
        # compute-bound is the healthy baseline; moves exist only when a
        # parsed capture NAMES where the compute goes — the top-op table
        # is the fusion target list (ROADMAP item 3(b)), and this branch
        # is its first consumer.  Every move still has to win the
        # never-commit-slower probe, so a wrong attribution costs probe
        # time, never a slower run.
        top = (report.get("device_time") or {}).get("top_ops")
        if top:
            # the registry's name map turns raw profiler names into
            # actionable tpuframe ops: a detail row says
            # "cross_entropy", not "log_softmax_fusion" — an operator
            # can act on the former
            top = normalize_top_ops(top[:5])
            detail["top_ops"] = top
            comms = report.get("comms") or {}
            wire_on = (comms.get("mode") or "none") not in ("none", "")
            wire_math = [
                op for op in top[:5]
                if any(tok in (op.get("raw") or op.get("name") or "").lower()
                       for tok in _WIRE_MATH_OPS)
            ]
            if wire_math and wire_on:
                names = ",".join(op.get("name") or "?" for op in wire_math[:3])
                pct = sum(op.get("pct") or 0.0 for op in wire_math)
                move("TPUFRAME_COMMS_FUSED", True,
                     f"compute-bound on staged wire math ({names}: "
                     f"{pct:.1f}% of device time with compression on) — "
                     "fuse encode/decode into the collective hops and let "
                     "the quant_wire kernels do each stage in one VMEM "
                     "pass")
            fusable = [
                op for op in top[:5]
                if op.get("class") == "compute"
                and (op.get("pct") or 0.0) >= 100.0 * _SIGNIFICANT
            ]
            if fusable:
                names = ",".join(op.get("name") or "?" for op in fusable[:3])
                move("TPUFRAME_DISABLE_PALLAS", False,
                     f"compute-bound on fusable ops ({names}) — make sure "
                     "the Pallas kernel paths (layer_norm, cross_entropy, "
                     "quant_wire) are engaged, not the staged jnp "
                     "references")

    # compile block rides along regardless of bound: a cold compile that
    # dominates the window says the cache/precompiler are off
    compile_block = report.get("compile") or {}
    ttfs = report.get("time_to_first_step") or {}
    if (compile_block.get("wall_s") or 0.0) > 0 and (
        ttfs.get("s") or 0.0
    ) > 0 and compile_block["wall_s"] >= 0.5 * ttfs["s"]:
        move("TPUFRAME_PRECOMPILE", True,
             "compile wall dominates time-to-first-step: keep AOT "
             "precompile on")

    return Diagnosis(bound=bound, detail=detail, moves=moves)
