"""tpuframe.fault acceptance: chaos-driven resume, torn-checkpoint
quarantine, preemption last-chance checkpoints, classified restart
budgets, backoff schedule."""

import os
import random

import jax
import numpy as np
import pytest

from tpuframe.ckpt import Checkpointer, latest_step, quarantine_torn_steps, valid_steps
from tpuframe.ckpt.checkpoint import COMMIT_MARKERS
from tpuframe.data import DataLoader, SyntheticImageDataset
from tpuframe.fault import (
    ChaosError,
    ChaosPlan,
    FailureClass,
    Preempted,
    PreemptionWatcher,
    PreemptNotice,
    RaiseAt,
    RestartPolicy,
    StallAt,
    Supervisor,
    TornCheckpoint,
    backoff_delay,
    classify_failure,
)
from tpuframe.fault import preempt as preempt_mod
from tpuframe.models import MnistNet
from tpuframe.train import Callback, Trainer


@pytest.fixture(autouse=True)
def _clean_preempt_state():
    """Chaos/preempt tests must not leak a set flag into each other."""
    yield
    preempt_mod.uninstall()


def _ds(n=64):
    return SyntheticImageDataset(
        n=n, image_size=28, channels=1, num_classes=4, seed=0
    )


def _trainer(ds, ckpt, **kw):
    kw.setdefault("max_duration", "2ep")
    kw.setdefault("eval_interval", 0)
    kw.setdefault("log_interval", 0)
    return Trainer(
        MnistNet(num_classes=4),
        train_dataloader=DataLoader(ds, batch_size=16, shuffle=True, seed=3),
        checkpointer=ckpt,
        **kw,
    )


# -- backoff schedule ---------------------------------------------------------


def test_backoff_exponential_and_capped():
    delays = [
        backoff_delay(a, base_s=1.0, max_s=8.0, jitter=False)
        for a in range(1, 7)
    ]
    assert delays == [1.0, 2.0, 4.0, 8.0, 8.0, 8.0]


def test_backoff_full_jitter_bounds_and_seeding():
    rng = random.Random(42)
    vals = [
        backoff_delay(3, base_s=1.0, max_s=60.0, rng=rng) for _ in range(50)
    ]
    assert all(0.0 <= v <= 4.0 for v in vals)
    assert len(set(vals)) > 1  # actually jittered
    # seeded rng -> reproducible schedule
    a = [backoff_delay(2, rng=random.Random(7)) for _ in range(3)]
    b = [backoff_delay(2, rng=random.Random(7)) for _ in range(3)]
    assert a[0] == b[0]


def test_backoff_attempt_counts_from_one():
    with pytest.raises(ValueError):
        backoff_delay(0)


def test_policy_delay_uses_seeded_rng():
    p1 = RestartPolicy(backoff_base_s=1.0, backoff_max_s=60.0, seed=5)
    p2 = RestartPolicy(backoff_base_s=1.0, backoff_max_s=60.0, seed=5)
    assert [p1.delay_s(a) for a in (1, 2, 3)] == [p2.delay_s(a) for a in (1, 2, 3)]


# -- failure classification ---------------------------------------------------


def test_classify_failure():
    assert classify_failure(Preempted()) is FailureClass.PREEMPTION
    assert classify_failure(ValueError("bug")) is FailureClass.FATAL
    assert classify_failure(TypeError("bug")) is FailureClass.FATAL
    assert classify_failure(OSError("io")) is FailureClass.RETRYABLE
    assert classify_failure(RuntimeError("xla")) is FailureClass.RETRYABLE
    assert classify_failure(ChaosError("chaos")) is FailureClass.RETRYABLE


def test_supervisor_fatal_not_retried():
    calls = []

    def buggy():
        calls.append(1)
        raise ValueError("a code bug")

    with pytest.raises(ValueError):
        Supervisor(RestartPolicy(max_restarts=5, backoff_base_s=0.0)).run(buggy)
    assert len(calls) == 1


def test_supervisor_retryable_budget_exhaustion():
    calls = []

    def always_failing():
        calls.append(1)
        raise OSError("transient forever")

    sup = Supervisor(RestartPolicy(max_restarts=2, backoff_base_s=0.0))
    with pytest.raises(OSError):
        sup.run(always_failing)
    assert len(calls) == 3  # initial + 2 restarts
    assert sup.retries == 3  # third increment hit the budget wall


def test_supervisor_preemption_budget_separate():
    """Preemptions draw on their own budget and restart with zero delay,
    so a spot-heavy run is not killed by an unrelated infra budget."""
    sequence = [Preempted(), OSError("infra"), Preempted(), None]
    slept = []

    def fn():
        e = sequence.pop(0)
        if e is not None:
            raise e
        return "done"

    sup = Supervisor(
        RestartPolicy(max_restarts=1, max_preemptions=5, backoff_base_s=0.0),
        sleep=slept.append,
    )
    assert sup.run(fn) == "done"
    assert sup.preemptions == 2 and sup.retries == 1
    assert slept == []  # base 0 -> no sleep; preemptions never sleep


def test_supervisor_backoff_delays_grow():
    slept = []
    attempts = []

    def fn():
        attempts.append(1)
        if len(attempts) < 4:
            raise OSError("transient")
        return "ok"

    sup = Supervisor(
        RestartPolicy(max_restarts=5, backoff_base_s=1.0, backoff_max_s=60.0,
                      jitter=False),
        sleep=slept.append,
    )
    assert sup.run(fn) == "ok"
    assert slept == [1.0, 2.0, 4.0]


# -- torn checkpoints: detection, fallback, quarantine ------------------------


def _tear(step_dir):
    for m in COMMIT_MARKERS:
        try:
            os.remove(os.path.join(step_dir, m))
        except FileNotFoundError:
            pass


def _save_steps(directory, steps):
    state = {"w": np.arange(4, dtype=np.float32)}
    with Checkpointer(directory) as ck:
        for s in steps:
            ck.save(state, step=s)
        ck.wait()


def test_latest_step_ignores_torn_dirs(tmp_path):
    d = tmp_path / "ck"
    _save_steps(d, [1, 2])
    os.makedirs(d / "3" / "state")  # torn: digit dir, no commit marker
    assert latest_step(d) == 2
    assert valid_steps(d) == [1, 2]


def test_latest_step_ignores_decommitted_real_save(tmp_path):
    d = tmp_path / "ck"
    _save_steps(d, [1, 2, 3])
    _tear(str(d / "3"))  # a real save whose commit marker was lost
    assert latest_step(d) == 2


@pytest.mark.chaos
def test_maybe_restore_falls_back_to_newest_valid_step(tmp_path):
    """TornCheckpoint chaos: the latest save is torn post-write; resume
    must land on the previous committed step, not crash on the torn one."""
    d = str(tmp_path / "ck")
    state = {"w": np.arange(4, dtype=np.float32)}
    plan = ChaosPlan([TornCheckpoint(step=3)])
    with plan.active(), Checkpointer(d) as ck:
        for s in (1, 2, 3):
            ck.save({"w": state["w"] * s}, step=s)
        ck.wait()
        assert plan.fired_count() == 1
        assert ck.latest_step() == 2
        restored, _ = ck.maybe_restore(state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"] * 2)


def test_maybe_restore_all_torn_passes_through(tmp_path):
    d = str(tmp_path / "ck")
    _save_steps(d, [1])
    _tear(os.path.join(d, "1"))
    state = {"w": np.zeros(4, dtype=np.float32)}
    with Checkpointer(d) as ck:
        out, meta = ck.maybe_restore(state)
    assert out is state and meta is None


def test_quarantine_torn_steps(tmp_path):
    d = tmp_path / "ck"
    _save_steps(d, [1, 2])
    _tear(str(d / "2"))
    moved = quarantine_torn_steps(d)
    assert len(moved) == 1 and moved[0].endswith(os.path.join("_quarantine", "2"))
    assert not (d / "2").exists()
    assert (d / "_quarantine" / "2").exists()  # moved aside, not deleted
    assert valid_steps(d) == [1]
    # idempotent + name-collision-safe on a second torn step 2
    os.makedirs(d / "2")
    moved2 = quarantine_torn_steps(d)
    assert moved2[0].endswith("2.1")


def test_supervisor_prevalidation_quarantines_before_each_attempt(tmp_path):
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    _tear(os.path.join(d, "2"))
    seen = []

    def fn():
        seen.append(latest_step(d))
        return "ok"

    sup = Supervisor(RestartPolicy(backoff_base_s=0.0), checkpoint_dir=d)
    assert sup.run(fn) == "ok"
    assert seen == [1]
    assert os.path.isdir(os.path.join(d, "_quarantine", "2"))


# -- chaos plans --------------------------------------------------------------


def test_chaos_plan_scheduled_is_seed_deterministic():
    a = ChaosPlan.scheduled(11, max_step=100, sites=("loader", "step"))
    b = ChaosPlan.scheduled(11, max_step=100, sites=("loader", "step"))
    c = ChaosPlan.scheduled(12, max_step=100, sites=("loader", "step"))
    assert [(i.site, i.step) for i in a.injectors] == [
        (i.site, i.step) for i in b.injectors
    ]
    assert [(i.site, i.step) for i in a.injectors] != [
        (i.site, i.step) for i in c.injectors
    ]


def test_chaos_injector_fires_once_at_its_step():
    from tpuframe.fault import chaos

    plan = ChaosPlan([RaiseAt("loader", step=3)])
    with plan.active():
        for step in range(3):
            chaos.maybe_fire("loader", step=step)  # no match, no fire
        chaos.maybe_fire("step", step=3)  # wrong site
        with pytest.raises(ChaosError):
            chaos.maybe_fire("loader", step=3)
        chaos.maybe_fire("loader", step=3)  # times=1: spent
    assert plan.fired_count() == 1


def test_chaos_plans_do_not_nest():
    plan = ChaosPlan([])
    with plan.active():
        with pytest.raises(RuntimeError):
            with ChaosPlan([]).active():
                pass


def test_chaos_stall_injector_sleeps():
    import time

    from tpuframe.fault import chaos

    plan = ChaosPlan([StallAt("step", step=0, stall_s=0.05)])
    t0 = time.perf_counter()
    with plan.active():
        chaos.maybe_fire("step", step=0)
    assert time.perf_counter() - t0 >= 0.05


# -- the integrated stories (tier-1 fast subset) ------------------------------


@pytest.mark.chaos
def test_chaos_kill_resumes_from_last_snapshot(tmp_path):
    """Acceptance: seeded mid-epoch kill -> supervised restart -> the step
    counter and metrics continue from the last checkpoint (no from-scratch
    restart, no skipped training)."""
    ds = _ds()
    ckpt_dir = str(tmp_path / "ck")
    resume_steps, histories = [], []

    class RecordResume(Callback):
        def on_fit_start(self, trainer):
            resume_steps.append(int(jax.device_get(trainer.init_state().step)))

    def attempt():
        ck = Checkpointer(ckpt_dir)
        try:
            tr = _trainer(
                ds, ck, checkpoint_interval_batches=2,
                callbacks=[RecordResume()],
            )
            res = tr.fit()
            histories.append(res.history)
            return tr, res
        finally:
            ck.close()

    # seeded: the kill step is drawn from the seed, mid-epoch by
    # construction (4 batches/epoch at n=64 b16 -> step 5 is in epoch 2)
    plan = ChaosPlan.scheduled(3, sites=("loader",), min_step=5, max_step=8)
    kill_step = plan.injectors[0].step
    sup = Supervisor(
        RestartPolicy(max_restarts=1, backoff_base_s=0.0),
        checkpoint_dir=ckpt_dir,
    )
    with plan.active():
        tr, res = sup.run(attempt)

    assert res.error is None and sup.retries == 1
    assert plan.fired_count() == 1
    # attempt 1 cold-started; attempt 2 resumed from the last even-step
    # snapshot before the kill — never from zero
    assert resume_steps[0] == 0
    assert resume_steps[1] == (kill_step // 2) * 2 == kill_step - kill_step % 2
    # training completed the full duration after resume
    assert int(tr.state.step) == 8
    # metrics continue: the resumed run still reports per-epoch history
    assert len(histories[-1]) >= 1
    assert all("train_loss" in h for h in histories[-1])


@pytest.mark.chaos
def test_preemption_notice_saves_and_raises_preempted(tmp_path):
    """PreemptNotice chaos at a seeded step: the trainer writes a
    last-chance snapshot (with loader position) and exits Preempted."""
    ds = _ds()
    ck = Checkpointer(str(tmp_path / "ck"))
    tr = _trainer(ds, ck)
    plan = ChaosPlan([PreemptNotice("step", step=2)])
    with plan.active():
        with pytest.raises(Preempted) as exc_info:
            tr.fit()
    ck.close()
    e = exc_info.value
    assert e.step == 3  # notice at step 2's dispatch, exit at the boundary
    assert e.checkpoint and os.path.isdir(e.checkpoint)
    intra = str(tmp_path / "ck") + "_intra"
    assert latest_step(intra) == 3
    assert tr._stop_reason.startswith("preempted")


@pytest.mark.chaos
def test_preempted_run_resumes_under_supervisor(tmp_path):
    """The full preemption story: notice -> last-chance save -> Preempted
    -> supervised restart (own budget, no backoff) -> resume at the saved
    step -> run completes."""
    ds = _ds()
    ckpt_dir = str(tmp_path / "ck")
    resume_steps = []

    class RecordResume(Callback):
        def on_fit_start(self, trainer):
            resume_steps.append(int(jax.device_get(trainer.init_state().step)))

    def attempt():
        ck = Checkpointer(ckpt_dir)
        try:
            tr = _trainer(ds, ck, callbacks=[RecordResume()])
            res = tr.fit()
            return tr, res
        finally:
            ck.close()

    plan = ChaosPlan([PreemptNotice("step", step=2)])
    sup = Supervisor(
        RestartPolicy(max_restarts=0, max_preemptions=2, backoff_base_s=0.0),
        checkpoint_dir=ckpt_dir,
    )
    with plan.active():
        tr, res = sup.run(attempt)
    assert res.error is None
    assert sup.preemptions == 1 and sup.retries == 0
    assert resume_steps == [0, 3]  # resumed exactly at the preempt save
    assert int(tr.state.step) == 8  # 2ep x 4 steps: nothing lost


def test_trainer_preemption_false_disables(tmp_path):
    ds = _ds(n=32)
    preempt_mod.install().request("test")  # process-wide flag is set...
    ck = Checkpointer(str(tmp_path / "ck"))
    tr = _trainer(ds, ck, max_duration="1ep", preemption=False)
    res = tr.fit()  # ...and preemption=False ignores it end-to-end
    ck.close()
    assert res.error is None


@pytest.mark.chaos
def test_explicit_watcher_consumed_on_supervised_restart(tmp_path):
    """A watcher passed as Trainer(preemption=<instance>) registers
    process-wide at fit() so the supervisor can consume its flag on
    restart — otherwise every in-process attempt would re-preempt at its
    first boundary until the budget died."""
    ds = _ds()
    ckpt_dir = str(tmp_path / "ck")
    watcher = PreemptionWatcher()
    fired = []

    class TripOnce(Callback):
        def on_step_end(self, trainer):
            if not fired and trainer.batches_seen == 2:
                fired.append(1)
                watcher.request("explicit")

    def attempt():
        ck = Checkpointer(ckpt_dir)
        try:
            tr = _trainer(ds, ck, preemption=watcher, callbacks=[TripOnce()])
            res = tr.fit()
            return tr, res
        finally:
            ck.close()

    sup = Supervisor(
        RestartPolicy(max_restarts=0, max_preemptions=2, backoff_base_s=0.0),
        checkpoint_dir=ckpt_dir,
    )
    tr, res = sup.run(attempt)
    assert sup.preemptions == 1  # consumed, not re-tripped every attempt
    assert res.error is None and int(tr.state.step) == 8


def test_worker_exits_preempted_exit_code(tmp_path):
    """A worker whose fn raises Preempted exits with the distinguishable
    PREEMPTED_EXIT code (143), not a generic crash code."""
    import subprocess
    import sys

    import cloudpickle

    from tpuframe.fault import PREEMPTED_EXIT

    def boom():
        from tpuframe.fault import Preempted

        raise Preempted("spot reclaim", step=7)

    payload = str(tmp_path / "payload.pkl")
    result = str(tmp_path / "result.pkl")
    with open(payload, "wb") as f:
        cloudpickle.dump((boom, (), {}), f)
    proc = subprocess.run(
        [sys.executable, "-m", "tpuframe.launch._worker", payload, result],
        capture_output=True, timeout=120,
    )
    assert proc.returncode == PREEMPTED_EXIT, proc.stderr.decode()[-500:]
    with open(result, "rb") as f:
        import pickle

        outcome = pickle.load(f)
    assert not outcome["ok"]
    assert outcome["error"].step == 7  # the typed frame still rides along


def test_trainer_preemption_true_and_bad_values(tmp_path):
    ds = _ds(n=32)
    with pytest.raises(ValueError, match="preemption must be"):
        _trainer(ds, None, preemption="yes please")
    ck = Checkpointer(str(tmp_path / "ck"))
    tr = _trainer(ds, ck, max_duration="1ep", preemption=True)
    res = tr.fit()  # True -> installs the process-wide watcher, no notice
    ck.close()
    assert res.error is None
    assert preempt_mod.active_watcher() is not None


def test_install_attaches_poller_to_existing_watcher():
    """User code asking for maintenance polling after a bootstrap-style
    signal-only install must get polling, not a silent drop."""
    w = preempt_mod.install()
    assert w.poller is None
    w2 = preempt_mod.install(poller=lambda: False, poll_interval_s=60.0)
    assert w2 is w and w.poller is not None
    assert w._poll_thread is not None and w._poll_thread.is_alive()


def test_maybe_restore_explicit_step_empty_dir_passes_through(tmp_path):
    """The 'maybe' contract holds for an explicit step too: no valid
    checkpoints at all -> pass through, never raise."""
    state = {"w": np.zeros(4, dtype=np.float32)}
    with Checkpointer(str(tmp_path / "empty")) as ck:
        out, meta = ck.maybe_restore(state, step=5)
    assert out is state and meta is None


def test_install_merges_signals_into_existing_watcher():
    import signal as _signal

    w = preempt_mod.install()  # bootstrap-style: SIGTERM only
    assert _signal.SIGUSR1 not in w.signals
    w2 = preempt_mod.install(signals=(_signal.SIGTERM, _signal.SIGUSR1))
    assert w2 is w and _signal.SIGUSR1 in w.signals
    os.kill(os.getpid(), _signal.SIGUSR1)
    assert w.wait(timeout=5.0) and w.reason == "signal:SIGUSR1"


def test_raising_injector_does_not_consume_later_same_site_injectors():
    from tpuframe.fault import chaos

    raiser = RaiseAt("step", step=5)
    stall = StallAt("step", step=5, stall_s=0.0)
    plan = ChaosPlan([raiser, stall])
    with plan.active():
        with pytest.raises(ChaosError):
            chaos.maybe_fire("step", step=5)
        assert raiser.fired == 1 and stall.fired == 0  # budget preserved
        chaos.maybe_fire("step", step=5)  # the survivor fires on revisit
    assert stall.fired == 1


def test_injector_times_counts_visits_not_loops():
    """times=N spreads over N site visits — a multi-shot injector must
    not collapse into N firings at the first visit."""
    from tpuframe.fault import chaos

    stall = StallAt("step", stall_s=0.0, times=3)
    plan = ChaosPlan([stall])
    with plan.active():
        chaos.maybe_fire("step", step=0)
        assert stall.fired == 1
        chaos.maybe_fire("step", step=1)
        chaos.maybe_fire("step", step=2)
        chaos.maybe_fire("step", step=3)  # budget spent: no 4th fire
    assert stall.fired == 3


def test_on_restart_attempt_count_is_monotonic_across_classes():
    sequence = [Preempted(), OSError("infra"), None]
    seen = []

    def fn():
        e = sequence.pop(0)
        if e is not None:
            raise e
        return "done"

    sup = Supervisor(
        RestartPolicy(max_restarts=2, max_preemptions=2, backoff_base_s=0.0),
        on_restart=lambda attempt, e: seen.append(attempt),
    )
    assert sup.run(fn) == "done"
    assert seen == [1, 2]  # one counter across classes, old-loop contract


def test_watcher_request_and_clear():
    w = PreemptionWatcher()
    assert not w.requested
    w.request("maintenance")
    assert w.requested and w.reason == "maintenance"
    w.request("second")  # first reason wins
    assert w.reason == "maintenance"
    w.clear()
    assert not w.requested and w.reason is None


def _chaos_killed_worker(flag_path):
    """Worker fn: first attempt fires a KillWorker injector (real SIGKILL,
    no handlers, no atexit); later attempts find the flag file and finish."""
    import os

    from tpuframe.fault import ChaosPlan, KillWorker, chaos

    if not os.path.exists(flag_path):
        with open(flag_path, "w") as f:
            f.write("armed")
        with ChaosPlan([KillWorker("step", step=0)]).active():
            chaos.maybe_fire("step", step=0)  # does not return
    return f"done-{os.environ.get('RANK', '0')}"


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_killworker_through_distributor_recovers(tmp_path):
    """The hardest crash class end-to-end: a chaos SIGKILL inside a
    Distributor worker surfaces as a typed worker loss, the supervisor
    restarts the whole run, attempt 2 completes."""
    from tpuframe.launch import Distributor, run_with_restarts

    flag = str(tmp_path / "killed_once")
    d = Distributor(num_processes=2, simulate_devices=1, timeout_s=300.0)
    out = run_with_restarts(
        lambda: d.run(_chaos_killed_worker, flag), max_restarts=1,
        backoff_s=0.0,
    )
    assert out == "done-0"
    assert os.path.exists(flag)  # attempt 1 really did die by SIGKILL


def test_run_with_restarts_classifies_preemption_separately():
    """The legacy entry point inherits the classified budgets: a
    preemption does not consume the infra retry budget."""
    from tpuframe.launch import run_with_restarts

    sequence = [Preempted(), OSError("infra"), None]

    def fn():
        e = sequence.pop(0)
        if e is not None:
            raise e
        return "done"

    assert run_with_restarts(fn, max_restarts=1, backoff_s=0.0) == "done"
