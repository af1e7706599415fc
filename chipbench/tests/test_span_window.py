"""The span-log readers: the window helper on a hand-made log, each reader on
it, and a whole rehearsal run that reads all seven."""

import types

import pytest

from chipbench import correct
from chipbench.layer_metrics import span_window

MS = 1_000_000  # ns
NEW = ("input.assemble_max_ms", "input.h2d_max_ms", "input.ready_ahead_ms",
       "input.fresh_alloc_batches", "trainer.empty_queue_dispatches",
       "trainer.health_fetch_ms", "trainer.unattributed_ms")


def rec(ids, name, start_ms, dur_ms, step, parent_id=None, **attrs):
    return types.SimpleNamespace(
        id=next(ids), parent_id=parent_id, name=name, start_ns=int(start_ms * MS),
        end_ns=int((start_ms + dur_ms) * MS), step=step, attrs=attrs)


def make_log(n_steps=16, log_interval=4, stall_at=None):
    """A loop of 10 ms iterations: 1 ms pull, 2 ms dispatch, a 3 ms drain
    every ``log_interval`` steps, a 1 ms health fetch every 8; the producer
    assembles 4 ms and copies 2 ms, two steps ahead.  ``stall_at``: that
    step's assembly takes 100 ms and its pull waits for it."""
    ids = iter(range(1, 10**6))
    log = []
    for n in range(1, n_steps + 1):
        t = 10.0 * n
        slow = n == stall_at
        log.append(rec(ids, "data/assemble", t - 26, 100 if slow else 4, n,
                       batch=n - 1, fresh_alloc=n <= 2))
        log.append(rec(ids, "data/h2d", t + 74 if slow else t - 22, 2, n))
        it = rec(ids, "train/iter", t, 110 if slow else 10, n)
        log.append(rec(ids, "train/data_wait", t, 77 if slow else 1, n, it.id))
        log.append(rec(ids, "train/step", t + (78 if slow else 2), 2, n, it.id,
                       device_idle_at_dispatch=n % log_interval == 1 or slow))
        if n % 8 == 0:
            log.append(rec(ids, "train/health_fetch", t + 5, 1, n, it.id))
        if n % log_interval == 0:
            log.append(rec(ids, "train/host_block", t + 6, 3, n, it.id,
                           first_step=n - log_interval + 1))
        log.append(it)
    # the pull that found the stop: an iteration that feeds no step
    log.append(rec(ids, "train/iter", 10.0 * (n_steps + 1), 1, None))
    return log


def make_ctx(steps=8, log_interval=4, trace_windows=1, data_wait_s=0.008, host_block_s=0.006):
    return {"steps": steps,
            "mix": {"log_interval": log_interval, "trace_windows": trace_windows},
            "spans": {"span/train/data_wait": (data_wait_s, steps),
                      "span/train/host_block": (host_block_s, steps // log_interval)}}


def test_the_helper_finds_the_span_by_counting_back_from_the_last_step():
    # 16 steps; (1 + 1) windows of 4 follow the span; the span is the 8 before: 1..8
    got = span_window.select(make_log(), make_ctx())
    assert [r.step for r in got["train/step"]] == list(range(1, 9))
    assert [r.step for r in got["train/host_block"]] == [4, 8]
    assert {name: len(rs) for name, rs in got.items()} == {
        "data/assemble": 8, "data/h2d": 8, "train/iter": 8, "train/data_wait": 8,
        "train/step": 8, "train/health_fetch": 1, "train/host_block": 2}
    # a longer tail moves the span: 4 steps, 5..8, after 2 + 1 windows... of a 20-step log
    got = span_window.select(make_log(20), make_ctx(steps=4, trace_windows=2,
                                                    data_wait_s=0.004, host_block_s=0.003))
    assert [r.step for r in got["train/step"]] == [5, 6, 7, 8]


@pytest.mark.parametrize("why, log, ctx", [
    ("a log one step short", make_log()[8:], make_ctx()),
    ("more steps asked for than were made", make_log(), make_ctx(steps=12)),
    ("data_wait sums that disagree", make_log(), make_ctx(data_wait_s=0.0095)),
    ("host_block sums that disagree", make_log(), make_ctx(host_block_s=0.004)),
    ("no train/step at all", [r for r in make_log() if r.name != "train/step"], make_ctx()),
])
def test_the_helper_refuses(why, log, ctx):
    assert span_window.select(log, ctx) is None, why


def test_sums_within_a_millisecond_pass():
    assert span_window.select(make_log(), make_ctx(data_wait_s=0.0089)) is not None


@pytest.fixture()
def program_log(monkeypatch):
    """Stand in for the running program's ``get_telemetry().span_log()``."""
    from tpuframe.track import telemetry

    def install(log):
        tele = types.SimpleNamespace() if log is None else types.SimpleNamespace(
            span_log=lambda: log)
        monkeypatch.setattr(telemetry, "get_telemetry", lambda: tele)

    return install


def read(name, ctx):
    return correct.load_by_name("layer_metrics", name).read(ctx)


def test_each_reader_on_the_hand_made_log(program_log):
    program_log(make_log())
    ctx = make_ctx()
    assert read("input.assemble_max_ms", ctx) == pytest.approx(4.0)
    assert read("input.h2d_max_ms", ctx) == pytest.approx(2.0)
    # copied two steps ahead: ready 20 ms before the pull
    assert read("input.ready_ahead_ms", ctx) == pytest.approx(20.0)
    assert read("input.fresh_alloc_batches", ctx) == 2
    assert read("trainer.empty_queue_dispatches", ctx) == 2      # steps 1 and 5
    assert read("trainer.health_fetch_ms", ctx) == pytest.approx(1.0)
    # 10 ms an iteration less 1 + 2 a step, 3 a drain (2 of 8), 1 a fetch (1 of 8)
    assert read("trainer.unattributed_ms", ctx) == pytest.approx(10 - 3 - 6 / 8 - 1 / 8)


def test_a_stalled_assembly_shows_whole_and_as_one_more_empty_queue(program_log):
    program_log(make_log(stall_at=6))
    ctx = make_ctx(data_wait_s=0.007 + 0.077)
    assert read("input.assemble_max_ms", ctx) == pytest.approx(100.0)
    assert read("trainer.empty_queue_dispatches", ctx) == 3
    assert read("input.ready_ahead_ms", ctx) == pytest.approx(20.0)  # the median holds


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(program_log, name):
    program_log(None)                      # a program without a span log (the parent)
    assert read(name, make_ctx()) is None
    program_log(make_log()[8:])            # a log that does not hold the span
    assert read(name, make_ctx()) is None
    # a log without the span or the attribute that the reader needs
    bare = [r for r in make_log() if r.name in ("train/step", "train/data_wait",
                                                "train/host_block")]
    for r in bare:
        r.attrs.clear()
    program_log(bare)
    assert read(name, make_ctx()) is None


def test_the_rehearsal_run_reads_all_seven(tmp_path):
    from chipbench import run
    from tpuframe.track import telemetry

    telemetry.reset()  # a run is a process of its own: no earlier run's steps in the log
    out = run.run_cell("gpt2m_seq1024", 2**31 + 7, 2.0, True, rehearsal=True,
                       out_dir=str(tmp_path))
    assert out["correct"] and set(NEW) <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["input.assemble_max_ms"] > 0 and m["input.h2d_max_ms"] > 0
    assert 0 <= m["trainer.empty_queue_dispatches"] <= out["attempted"]
    assert m["input.fresh_alloc_batches"] == 0      # the ring recycles after warm-up
    assert m["trainer.unattributed_ms"] > 0 and m["trainer.health_fetch_ms"] > 0
