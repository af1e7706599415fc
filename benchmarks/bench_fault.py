#!/usr/bin/env python
"""Fault-recovery benchmark: measured recovery, not assumed recovery.

Two windows, one JSON line:

1. **Recovery** — a seeded chaos injector kills a training run at a
   mid-epoch step (loader raise: the in-process stand-in for a worker
   kill — the same code path a dead worker pool surfaces through); the
   :class:`tpuframe.fault.Supervisor` restarts it; the fresh Trainer
   auto-resumes from the last mid-epoch snapshot.  Reported:
   ``recovery_wall_s`` (failure -> first completed post-restart step:
   re-init + checkpoint restore + recompile + step), ``resumed_step``
   vs ``last_ckpt_step`` (the resume-exactness proof), and
   ``lost_steps`` (work replayed because it post-dated the snapshot).

2. **Checkpoint stall** — the same fit with no checkpointing, with
   synchronous per-interval saves, and with ``async_save=True``:
   per-save stall overhead and the epoch-time tax of each, i.e. the
   number that justifies async checkpointing on real pods.

CPU-friendly by design (tiny MnistNet on synthetic data) so the chaos
path runs in CI; on a TPU host the same script measures the real
restore + recompile cost (on chip: not measured).

Usage: python benchmarks/bench_fault.py [--steps-per-epoch N] [--epochs N]
           [--snapshot-every N] [--kill-seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def build_trainer(ds, ckpt, *, snapshot_every, epochs, callbacks=(), plan=None,
                  health=None, transfer_dtype=None):
    from tpuframe.data import DataLoader
    from tpuframe.models import MnistNet
    from tpuframe.train import Trainer

    return Trainer(
        MnistNet(num_classes=4),
        train_dataloader=DataLoader(ds, batch_size=16, shuffle=True, seed=3,
                                    transfer_dtype=transfer_dtype),
        max_duration=f"{epochs}ep",
        checkpointer=ckpt,
        checkpoint_interval_batches=snapshot_every,
        eval_interval=0,
        log_interval=0,
        callbacks=list(callbacks),
        plan=plan,
        health=health,
    )


def _compile_snapshot() -> dict:
    """Registry totals that decompose a recovery window: checkpoint
    restore wall, compile wall (lower + backend), persistent-cache
    traffic.  Deltas between two snapshots attribute the window."""
    from tpuframe.track.telemetry import get_telemetry

    reg = get_telemetry().registry
    return {
        "restore": reg.histogram("span/ckpt/restore").total,
        "backend": reg.histogram("compile/backend_compile_s").total,
        "lower": reg.histogram("compile/lower_s").total,
        "hits": reg.counter("compile/cache_hits").value,
        "misses": reg.counter("compile/cache_misses").value,
    }


def measure_recovery(workdir: str, args) -> dict:
    """Window 1: seeded mid-epoch kill -> supervised restart -> resume."""
    from tpuframe.ckpt import Checkpointer
    from tpuframe.ckpt.checkpoint import latest_step
    from tpuframe.data import SyntheticImageDataset
    from tpuframe.fault import ChaosPlan, RestartPolicy, Supervisor
    from tpuframe.train import Callback

    ds = SyntheticImageDataset(
        n=16 * args.steps_per_epoch, image_size=28, channels=1,
        num_classes=4, seed=0,
    )
    ckpt_dir = os.path.join(workdir, "recovery_ck")
    timeline: dict = {"attempt_first_step_t": [], "resume_start_step": [],
                      "first_step_snap": []}

    class Probe(Callback):
        """First-completed-step wall-clock + the step each attempt
        resumed at (read after maybe_restore, before any training)."""

        def __init__(self):
            self.saw_step = False

        def on_fit_start(self, trainer) -> None:
            import jax

            self.saw_step = False
            timeline["resume_start_step"].append(
                int(jax.device_get(trainer.init_state().step))
            )

        def on_step_end(self, trainer) -> None:
            if not self.saw_step:
                self.saw_step = True
                timeline["attempt_first_step_t"].append(time.perf_counter())
                timeline["first_step_snap"].append(_compile_snapshot())

    def attempt():
        ck = Checkpointer(ckpt_dir)
        try:
            tr = build_trainer(
                ds, ck, snapshot_every=args.snapshot_every,
                epochs=args.epochs, callbacks=[Probe()],
            )
            res = tr.fit()
            import jax

            return int(jax.device_get(tr.state.step)), res
        finally:
            ck.close()

    # seeded kill step: mid-epoch, strictly after the first snapshot so
    # there is state to resume (reproduce any run by its --kill-seed)
    plan = ChaosPlan.scheduled(
        args.kill_seed,
        sites=("loader",),
        min_step=args.snapshot_every + 1,
        max_step=args.steps_per_epoch * args.epochs - 1,
    )
    kill_step = plan.injectors[0].step
    fail_t: list[float] = []
    fail_snap: list[dict] = []
    last_ckpt_step: list[int] = []

    def on_restart(attempt_n, error):
        fail_t.append(time.perf_counter())
        fail_snap.append(_compile_snapshot())
        last_ckpt_step.append(latest_step(ckpt_dir + "_intra") or 0)

    sup = Supervisor(
        RestartPolicy(max_restarts=2, backoff_base_s=0.0),
        checkpoint_dir=ckpt_dir,
        on_restart=on_restart,
    )
    t0 = time.perf_counter()
    with plan.active():
        final_step, result = sup.run(attempt)
    total_s = time.perf_counter() - t0

    # first completed step of attempt 2 minus the failure instant
    recovery_wall_s = timeline["attempt_first_step_t"][1] - fail_t[0]
    resumed_step = timeline["resume_start_step"][1]
    # component split across the recovery window (failure -> first
    # post-restart step): checkpoint restore, compile (trace+lower plus
    # backend compile OR cache retrieval), and everything else (Trainer
    # re-construction, loader spin-up, the step itself)
    a, b = fail_snap[0], timeline["first_step_snap"][1]
    restore_s = b["restore"] - a["restore"]
    compile_s = (b["backend"] - a["backend"]) + (b["lower"] - a["lower"])
    from tpuframe.compile import cache as compile_cache

    return {
        "kill_seed": args.kill_seed,
        "kill_site": "loader",
        "kill_step": kill_step,
        "last_ckpt_step": last_ckpt_step[0],
        "resumed_step": resumed_step,
        "resume_exact": resumed_step == last_ckpt_step[0],
        "lost_steps": kill_step - resumed_step,
        "final_step": final_step,
        "expected_final_step": args.steps_per_epoch * args.epochs,
        "restarts": sup.retries,
        "recovery_wall_s": round(recovery_wall_s, 3),
        "recovery_components": {
            "restore_s": round(restore_s, 3),
            "compile_s": round(compile_s, 3),
            "other_s": round(
                max(recovery_wall_s - restore_s - compile_s, 0.0), 3
            ),
            "cache_hits": b["hits"] - a["hits"],
            "cache_misses": b["misses"] - a["misses"],
        },
        "compile_cache": compile_cache.enabled_dir() is not None,
        "total_wall_s": round(total_s, 3),
    }


def measure_ckpt_stall(workdir: str, args) -> dict:
    """Window 2: per-save stall of sync vs async checkpointing."""
    from tpuframe.ckpt import Checkpointer
    from tpuframe.data import SyntheticImageDataset
    from tpuframe.train import Callback

    ds = SyntheticImageDataset(
        n=16 * args.steps_per_epoch, image_size=28, channels=1,
        num_classes=4, seed=0,
    )

    class StepClock(Callback):
        """Wall time across the steady-state steps only (skips step 0's
        compile, which would swamp a CPU-sized measurement)."""

        def __init__(self):
            self.t0 = None
            self.t1 = None

        def on_step_end(self, trainer) -> None:
            now = time.perf_counter()
            if self.t0 is None:
                self.t0 = now
            self.t1 = now

        @property
        def elapsed(self):
            return (self.t1 or 0.0) - (self.t0 or 0.0)

    def run(mode: str) -> tuple[float, int]:
        from tpuframe.track.telemetry import get_telemetry

        sub = os.path.join(workdir, f"stall_{mode}")
        ck = None
        if mode != "none":
            ck = Checkpointer(
                os.path.join(sub, "ck"), async_save=(mode == "async")
            )
        clock = StepClock()
        saves = get_telemetry().registry.histogram("span/ckpt/save")
        n0 = saves.count
        try:
            tr = build_trainer(
                ds, ck,
                snapshot_every=args.snapshot_every if ck else None,
                epochs=args.epochs, callbacks=[clock],
            )
            tr.fit()
            if ck is not None:
                ck.wait()  # drain in-flight async writes before teardown
        finally:
            if ck is not None:
                ck.close()
        # the run's final epoch-end save lands after the last step, i.e.
        # outside the steady-state clock window (same for both modes) —
        # it dilutes per-save overhead, so it leaves the divisor too
        return clock.elapsed, max(saves.count - n0 - 1, 1)

    base, _ = run("none")
    n_steps = args.steps_per_epoch * args.epochs
    out = {"baseline_wall_s": round(base, 3), "n_steps": n_steps}
    for mode in ("sync", "async"):
        wall, n_saves = run(mode)
        out[f"{mode}_wall_s"] = round(wall, 3)
        out[f"{mode}_saves_in_window"] = n_saves
        out[f"{mode}_overhead_per_save_s"] = round((wall - base) / n_saves, 4)
        out[f"{mode}_stall_pct"] = round(100.0 * max(wall - base, 0.0) / wall, 1)
    return out


def measure_shrink(workdir: str, args) -> dict:
    """Window 3 (``--shrink``): seeded LoseRank kill -> supervised restart
    at a SMALLER world -> reshard-restore from the topology manifest ->
    run completes at full step count.  The elastic half of the fault
    story, measured: recovery wall split (restore *including* the
    reshard gather/slice, compile of the rebound plan's programs,
    everything else), ``resume_exact``, and the event proof
    (``fault/world_resized`` + ``fault/reshard``, zero quarantines)."""
    import jax

    from tpuframe.ckpt import Checkpointer
    from tpuframe.ckpt.checkpoint import latest_step
    from tpuframe.core import MeshSpec
    from tpuframe.data import SyntheticImageDataset
    from tpuframe.fault import ChaosPlan, LoseRank, RestartPolicy
    from tpuframe.launch import run_elastic
    from tpuframe.parallel import ParallelPlan
    from tpuframe.track.telemetry import get_telemetry
    from tpuframe.train import Callback

    world_from, world_to = args.shrink_from, args.shrink_to
    devs = jax.devices()
    if len(devs) < world_from:
        raise SystemExit(
            f"--shrink needs >= {world_from} devices ({len(devs)} visible)"
        )
    plan0 = ParallelPlan(
        mesh=MeshSpec(data=world_from).build(devs[:world_from]),
        zero_stage=1, min_shard_elems=1,
    )
    ds = SyntheticImageDataset(
        n=16 * args.steps_per_epoch, image_size=28, channels=1,
        num_classes=4, seed=0,
    )
    ckpt_dir = os.path.join(workdir, "shrink_ck")
    timeline: dict = {"attempt_first_step_t": [], "resume_start_step": [],
                      "first_step_snap": [], "worlds": []}

    class Probe(Callback):
        def __init__(self):
            self.saw_step = False

        def on_fit_start(self, trainer) -> None:
            self.saw_step = False
            timeline["resume_start_step"].append(
                int(jax.device_get(trainer.init_state().step))
            )

        def on_step_end(self, trainer) -> None:
            if not self.saw_step:
                self.saw_step = True
                timeline["attempt_first_step_t"].append(time.perf_counter())
                timeline["first_step_snap"].append(_compile_snapshot())

    def train(ctx):
        timeline["worlds"].append(ctx.world_size)
        ck = Checkpointer(ckpt_dir)
        try:
            tr = build_trainer(
                ds, ck, snapshot_every=args.snapshot_every,
                epochs=args.epochs, callbacks=[Probe()], plan=ctx.plan,
            )
            res = tr.fit()
            return int(jax.device_get(tr.state.step)), res
        finally:
            ck.close()

    # seeded loss step, strictly after the first snapshot; the lost ranks
    # are the tail [world_to, world_from) — one "host" taking its chips
    lost = tuple(range(world_to, world_from))
    plan = ChaosPlan.scheduled(
        args.kill_seed,
        sites={"step": LoseRank(lost)},
        min_step=args.snapshot_every + 1,
        max_step=args.steps_per_epoch * args.epochs - 1,
    )
    kill_step = plan.injectors[0].step
    fail_t: list[float] = []
    fail_snap: list[dict] = []
    last_ckpt_step: list[int] = []

    def on_restart(attempt_n, error):
        fail_t.append(time.perf_counter())
        fail_snap.append(_compile_snapshot())
        last_ckpt_step.append(max(
            latest_step(ckpt_dir + "_intra") or 0, latest_step(ckpt_dir) or 0
        ))

    reg = get_telemetry().registry
    ev0 = {
        "reshards": reg.counter("fault/reshards").value,
        "resizes": reg.counter("fault/world_resizes").value,
        "quarantined": reg.counter("fault/quarantined_steps").value,
    }
    t0 = time.perf_counter()
    with plan.active():
        final_step, result = run_elastic(
            train, plan=plan0,
            policy=RestartPolicy(max_restarts=2, backoff_base_s=0.0),
            checkpoint_dir=ckpt_dir,
            min_world_size=args.min_world_size,
            on_restart=on_restart,
        )
    total_s = time.perf_counter() - t0

    recovery_wall_s = timeline["attempt_first_step_t"][1] - fail_t[0]
    resumed_step = timeline["resume_start_step"][1]
    a, b = fail_snap[0], timeline["first_step_snap"][1]
    restore_s = b["restore"] - a["restore"]
    compile_s = (b["backend"] - a["backend"]) + (b["lower"] - a["lower"])
    return {
        "kill_seed": args.kill_seed,
        "kill_step": kill_step,
        "lost_ranks": list(lost),
        "world_from": world_from,
        "world_to": world_to,
        "worlds_per_attempt": timeline["worlds"],
        "min_world_size": args.min_world_size,
        "last_ckpt_step": last_ckpt_step[0],
        "resumed_step": resumed_step,
        "resume_exact": resumed_step == last_ckpt_step[0],
        "lost_steps": kill_step - resumed_step,
        "final_step": final_step,
        "expected_final_step": args.steps_per_epoch * args.epochs,
        "recovery_wall_s": round(recovery_wall_s, 3),
        "recovery_components": {
            # restore_s INCLUDES the reshard gather/slice: orbax reads
            # each target shard from the saved layout inside the
            # ckpt/restore span, so the reshard cost is priced here
            "restore_incl_reshard_s": round(restore_s, 3),
            "compile_s": round(compile_s, 3),
            "other_s": round(
                max(recovery_wall_s - restore_s - compile_s, 0.0), 3
            ),
            "cache_hits": b["hits"] - a["hits"],
            "cache_misses": b["misses"] - a["misses"],
        },
        "reshard_events": reg.counter("fault/reshards").value - ev0["reshards"],
        "world_resized_events": (
            reg.counter("fault/world_resizes").value - ev0["resizes"]
        ),
        "quarantined_steps": (
            reg.counter("fault/quarantined_steps").value - ev0["quarantined"]
        ),
        "total_wall_s": round(total_s, 3),
    }


def measure_sentinel_overhead(workdir: str, args) -> dict:
    """Per-step cost of the health sentinel (the fused grad-norm/
    finiteness reduction + branch-free where-skip + EWMA update),
    measured as steady-state step wall with the sentinel off vs on —
    no injection, same data, same schedule.  The committed criterion:
    <= 2% of step time."""
    from tpuframe.data import SyntheticImageDataset
    from tpuframe.fault import HealthPolicy
    from tpuframe.train import Callback

    steps = args.overhead_steps
    ds = SyntheticImageDataset(
        n=16 * steps, image_size=28, channels=1, num_classes=4, seed=0,
    )

    class StepClock(Callback):
        def __init__(self):
            self.last = None
            self.periods: list = []

        def on_step_end(self, trainer) -> None:
            now = time.perf_counter()
            if self.last is not None:  # step 1 carries the compile
                self.periods.append(now - self.last)
            self.last = now

    def run(health):
        clock = StepClock()
        tr = build_trainer(
            ds, None, snapshot_every=None, epochs=1, callbacks=[clock],
            health=health,
        )
        tr.fit()
        # median period: a GC pause or scheduler hiccup on one 8 ms CPU
        # step would otherwise swamp the sub-ms sentinel cost under test
        return statistics.median(clock.periods), len(clock.periods)

    # alternating A/B pairs behind one discarded warmup fit (allocator,
    # page cache, loader threads — everything process-warm EXCEPT the
    # programs under test, which differ between the two arms anyway);
    # medians across pairs so thermal/scheduler drift between arms
    # cannot masquerade as sentinel cost
    run(False)
    offs, ons, n_steps = [], [], 0
    for _ in range(max(args.overhead_repeats, 1)):
        off_s, n_steps = run(False)
        on_s, _ = run(HealthPolicy())
        offs.append(off_s)
        ons.append(on_s)
    off_s, on_s = statistics.median(offs), statistics.median(ons)
    overhead_pct = 100.0 * (on_s - off_s) / max(off_s, 1e-12)
    return {
        "steps_measured": n_steps,
        "ab_repeats": len(offs),
        "step_wall_off_s": round(off_s, 6),
        "step_wall_on_s": round(on_s, 6),
        "overhead_per_step_s": round(on_s - off_s, 6),
        "overhead_pct": round(overhead_pct, 2),
    }


def measure_divergence(workdir: str, args) -> dict:
    """The ``--divergence`` window: seeded NaN poison window -> on-device
    detection + bad-step skips -> :class:`Divergence` -> supervisor
    rollback to the last *healthy* committed step -> perturbed re-entry
    -> run completes at full step count.  Reported: detection lag,
    recovery wall split (restore / compile / other), the event proof
    (``health/bad_step`` + ``fault/rollback``, zero recompiles), and
    final-loss parity vs an uninjected run."""
    import jax

    from tpuframe.ckpt import Checkpointer
    from tpuframe.ckpt.checkpoint import latest_step
    from tpuframe.data import SyntheticImageDataset
    from tpuframe.fault import ChaosPlan, HealthPolicy, NaNAt, RestartPolicy, Supervisor
    from tpuframe.track.telemetry import get_telemetry
    from tpuframe.train import Callback

    # parity conditions: no LR perturbation, so the recovered run is
    # directly comparable to the uninjected reference
    os.environ["TPUFRAME_HEALTH_LR_BACKOFF"] = "1.0"
    os.environ["TPUFRAME_HEALTH_SKIP_BATCHES"] = "0"
    pol = HealthPolicy(
        window=args.health_window, max_bad=args.health_max_bad,
        warmup_steps=2, lr_backoff=1.0,
    )
    spe, epochs = args.steps_per_epoch, args.epochs
    ds = SyntheticImageDataset(
        n=16 * spe, image_size=28, channels=1, num_classes=4, seed=0,
    )

    # uninjected reference (same schedule) for the loss-parity claim
    ref = build_trainer(ds, None, snapshot_every=None, epochs=epochs,
                        health=pol, transfer_dtype="float32")
    ref_loss = ref.fit().metrics["train_loss"]

    ckpt_dir = os.path.join(workdir, "divergence_ck")
    timeline: dict = {"attempt_first_step_t": [], "resume_start_step": [],
                      "first_step_snap": []}

    class Probe(Callback):
        def __init__(self):
            self.saw_step = False

        def on_fit_start(self, trainer) -> None:
            self.saw_step = False
            timeline["resume_start_step"].append(
                int(jax.device_get(trainer.init_state().step))
            )

        def on_step_end(self, trainer) -> None:
            if not self.saw_step:
                self.saw_step = True
                timeline["attempt_first_step_t"].append(time.perf_counter())
                timeline["first_step_snap"].append(_compile_snapshot())

    def attempt():
        ck = Checkpointer(ckpt_dir)
        try:
            tr = build_trainer(
                ds, ck, snapshot_every=args.snapshot_every, epochs=epochs,
                callbacks=[Probe()], health=pol, transfer_dtype="float32",
            )
            res = tr.fit()
            return int(jax.device_get(tr.state.step)), res
        finally:
            ck.close()

    # seeded poison window in the final epoch — strictly after the first
    # epoch-end save, so a healthy rollback target exists on disk
    lo = spe * (epochs - 1) + 1
    hi = spe * epochs - args.poison_steps
    plan = ChaosPlan.scheduled(
        args.kill_seed,
        sites={"batch": NaNAt(times=args.poison_steps)},
        min_step=lo, max_step=max(hi, lo + 1),
    )
    poison_step = plan.injectors[0].step
    fail_t: list[float] = []
    fail_snap: list[dict] = []
    rolled_back_to: list[int] = []

    def on_restart(attempt_n, error):
        # called AFTER the rollback: the dirs' newest committed step is
        # the healthy frontier the next attempt resumes from
        fail_t.append(time.perf_counter())
        fail_snap.append(_compile_snapshot())
        rolled_back_to.append(max(
            latest_step(ckpt_dir) or 0, latest_step(ckpt_dir + "_intra") or 0
        ))

    reg = get_telemetry().registry
    ev0 = {
        "bad_steps": reg.counter("health/bad_steps").value,
        "rollbacks": reg.counter("fault/rollbacks").value,
        "divergences": reg.counter("fault/divergences").value,
        "recompiles": reg.counter("compile/recompiles").value,
    }
    t0 = time.perf_counter()
    with plan.active():
        sup = Supervisor(
            RestartPolicy(max_restarts=1, max_divergences=2,
                          backoff_base_s=0.0),
            checkpoint_dir=ckpt_dir,
            on_restart=on_restart,
        )
        final_step, result = sup.run(attempt)
    total_s = time.perf_counter() - t0

    recovery_wall_s = timeline["attempt_first_step_t"][1] - fail_t[0]
    resumed_step = timeline["resume_start_step"][1]
    a, b = fail_snap[0], timeline["first_step_snap"][1]
    restore_s = b["restore"] - a["restore"]
    compile_s = (b["backend"] - a["backend"]) + (b["lower"] - a["lower"])
    loss = result.metrics["train_loss"]
    return {
        "kill_seed": args.kill_seed,
        "poison_step": poison_step,
        "poison_steps": args.poison_steps,
        "health_window": pol.window,
        "health_max_bad": pol.max_bad,
        "bad_steps_detected": (
            reg.counter("health/bad_steps").value - ev0["bad_steps"]
        ),
        "divergences": sup.divergences,
        "rollback_events": (
            reg.counter("fault/rollbacks").value - ev0["rollbacks"]
        ),
        "recompile_events": (
            reg.counter("compile/recompiles").value - ev0["recompiles"]
        ),
        "rolled_back_to": rolled_back_to[0],
        "resumed_step": resumed_step,
        "resume_exact": resumed_step == rolled_back_to[0],
        "final_step": final_step,
        "expected_final_step": spe * epochs,
        "recovery_wall_s": round(recovery_wall_s, 3),
        "recovery_components": {
            "restore_s": round(restore_s, 3),
            "compile_s": round(compile_s, 3),
            "other_s": round(
                max(recovery_wall_s - restore_s - compile_s, 0.0), 3
            ),
            "cache_hits": b["hits"] - a["hits"],
            "cache_misses": b["misses"] - a["misses"],
        },
        "final_loss": round(float(loss), 5),
        "reference_loss": round(float(ref_loss), 5),
        "loss_ratio": round(float(loss) / max(float(ref_loss), 1e-9), 4),
        "total_wall_s": round(total_s, 3),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps-per-epoch", type=int, default=8)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--snapshot-every", type=int, default=2)
    p.add_argument("--kill-seed", type=int, default=7)
    p.add_argument("--workdir", default=None)
    p.add_argument("--shrink", action="store_true",
                   help="measure the elastic shrink-recovery window "
                        "(LoseRank kill -> restart at a smaller world -> "
                        "reshard-restore) instead of the equal-capacity "
                        "windows")
    p.add_argument("--shrink-from", type=int, default=4,
                   help="initial data-parallel world for --shrink")
    p.add_argument("--shrink-to", type=int, default=2,
                   help="surviving world for --shrink")
    p.add_argument("--min-world-size", type=int, default=2)
    p.add_argument("--divergence", action="store_true",
                   help="measure the health-sentinel window: per-step "
                        "detection overhead (off vs on) + the seeded "
                        "NaN -> skip -> Divergence -> rollback-to-last-"
                        "healthy recovery wall split")
    p.add_argument("--poison-steps", type=int, default=3,
                   help="consecutive NaN-poisoned batches for --divergence")
    p.add_argument("--health-window", type=int, default=4)
    p.add_argument("--health-max-bad", type=int, default=2)
    p.add_argument("--overhead-steps", type=int, default=48,
                   help="steady-state steps for the sentinel-overhead A/B")
    p.add_argument("--overhead-repeats", type=int, default=3,
                   help="alternating off/on pairs for the overhead A/B "
                        "(median across pairs)")
    args = p.parse_args(argv)

    if args.shrink and os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # the shrink window needs a multi-device world; explicit CPU runs
        # (CI, capture ladder's CPU fallback) get the test suite's
        # simulated mesh, armed BEFORE the backend initializes.  TPU
        # hosts use their real chips.
        from tpuframe.core.runtime import simulate_cpu_devices

        simulate_cpu_devices(max(args.shrink_from, 8))

    import tempfile

    workdir = args.workdir or tempfile.mkdtemp(prefix="tpuframe_bench_fault_")

    import jax

    from tpuframe.core import runtime as rt
    from tpuframe.compile import cache as compile_cache

    if args.divergence:
        # shipped-default conditions: warm persistent compile cache, so
        # the rollback recovery split shows retrieval (the honest
        # recovery price), and the overhead A/B is steady-state
        warm_dir = tempfile.mkdtemp(prefix="tpuframe_bf_cache_")
        os.environ["TPUFRAME_COMPILE_CACHE"] = warm_dir
        compile_cache.enable(warm_dir)
        overhead = measure_sentinel_overhead(workdir, args)
        divergence = measure_divergence(workdir, args)
        print(json.dumps({
            "metric": "fault_divergence_recovery_wall_s",
            "value": divergence["recovery_wall_s"],
            "unit": ("seconds from the Divergence raise (seeded NaN window "
                     "past the skip budget) to the first completed step "
                     "after rollback to the last healthy committed "
                     "checkpoint (restore + compile-or-retrieve + step; "
                     f"MnistNet 28px b16, {jax.default_backend()})"),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "sentinel_overhead": overhead,
            "divergence": divergence,
        }))
        return

    if args.shrink:
        # shipped-default conditions: warm persistent compile cache (the
        # restart's programs for the REBOUND plan are new lowerings, so
        # the split shows real compile, not retrieval — that is the
        # honest reshard-recovery price)
        warm_dir = tempfile.mkdtemp(prefix="tpuframe_bf_cache_")
        os.environ["TPUFRAME_COMPILE_CACHE"] = warm_dir
        compile_cache.enable(warm_dir)
        shrink = measure_shrink(workdir, args)
        print(json.dumps({
            "metric": "fault_shrink_recovery_wall_s",
            "value": shrink["recovery_wall_s"],
            "unit": ("seconds from injected rank loss to first completed "
                     "step at the SHRUNKEN world (supervisor probe + mesh "
                     "rebuild + plan rebind + reshard-restore + rebound-"
                     "plan compile + step; MnistNet 28px b16, dp "
                     f"{shrink['world_from']}->{shrink['world_to']}, "
                     f"{jax.default_backend()})"),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "shrink": shrink,
        }))
        return

    # recovery is measured twice: a COLD window (persistent compile
    # cache off — the pre-compile-spine behavior, attempt 2 pays a full
    # recompile) and a WARM window (fresh cache dir — attempt 1 writes
    # every program, the restart retrieves them).  The delta is the
    # compile spine's contribution to recovery; warm is the shipped
    # default and the headline value.
    rt.current_runtime()  # initialize (and its enable_from_env) first
    # env-level disable: the supervisor's own warm-start hook calls
    # enable_from_env() before each run, which would silently re-enable
    # a merely disable()d cache mid-window
    os.environ["TPUFRAME_COMPILE_CACHE"] = "0"
    compile_cache.disable()
    recovery_cold = measure_recovery(os.path.join(workdir, "cold"), args)
    warm_dir = tempfile.mkdtemp(prefix="tpuframe_bf_cache_")
    os.environ["TPUFRAME_COMPILE_CACHE"] = warm_dir
    compile_cache.enable(warm_dir)
    recovery = measure_recovery(os.path.join(workdir, "warm"), args)
    stall = measure_ckpt_stall(workdir, args)
    print(json.dumps({
        "metric": "fault_recovery_wall_s",
        "value": recovery["recovery_wall_s"],
        "unit": ("seconds from injected mid-epoch kill to first completed "
                 "post-restart step (re-init + restore + compile-or-"
                 "retrieve + step, warm compile cache; MnistNet 28px b16, "
                 f"{jax.default_backend()})"),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "recovery": recovery,
        "recovery_cold": recovery_cold,
        "warm_cache_recovery_delta_s": round(
            recovery_cold["recovery_wall_s"] - recovery["recovery_wall_s"], 3
        ),
        "ckpt_stall": stall,
    }))


if __name__ == "__main__":
    main()
