"""The seven readers of the host's work and wait (PR 35): each on a hand-made
span log with the slot, attrs and span they read, and on a log of the shape the
commits before had (no ``cpu_ns`` slot, no ``steps_in_flight``, no record on
the drain, no ``train/metrics_window``), where each reads as nothing."""

import json
import os
import types

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000  # ns
NAMES = ("trainer.loop_cpu_ms", "trainer.loop_wait_pct", "trainer.steps_in_flight_median",
         "trainer.metrics_window_ms", "input.producer_cpu_ms", "trainer.process_cpu_ms",
         "trainer.involuntary_switches")
STEPS, LOG_INTERVAL = 8, 4


def make_log(new=True, n_steps=16):
    """Iterations of 10 ms: 1 ms pull, 2 ms dispatch, a 4 ms add into the
    window (none on a window's first step), a 3 ms drain every fourth step;
    the producer assembles 4 ms and copies 2 ms.  ``new``: the loop thread
    works 2 ms an iteration (3 on a drain), the producer 3 + 0.5 ms a batch,
    the queue is 0, 1, 2, 2 deep along a window, and a drain's record says
    40 ms of CPU and 2 involuntary switches; else records as the commits
    before PR 35 left them."""
    ids = iter(range(1, 10**6))

    def rec(name, start_ms, dur_ms, step, parent_id=None, cpu_ms=None, **attrs):
        r = types.SimpleNamespace(
            id=next(ids), parent_id=parent_id, name=name, start_ns=int(start_ms * MS),
            end_ns=int((start_ms + dur_ms) * MS), step=step, attrs=attrs)
        if new:
            r.cpu_ns = None if cpu_ms is None else int(cpu_ms * MS)
        return r

    log = []
    for n in range(1, n_steps + 1):
        t = 10.0 * n
        at = (n - 1) % LOG_INTERVAL
        drains = at == LOG_INTERVAL - 1
        log.append(rec("data/assemble", t - 26, 4, n, cpu_ms=3, batch=n - 1, fresh_alloc=False))
        log.append(rec("data/h2d", t - 22, 2, n, cpu_ms=0.5, batch=n - 1))
        it = rec("train/iter", t, 10, n, cpu_ms=3 if drains else 2)
        log.append(rec("train/data_wait", t, 1, n, it.id))
        depth = {"steps_in_flight": min(at, 2)} if new else {}
        log.append(rec("train/step", t + 2, 2, n, it.id,
                       device_idle_at_dispatch=at == 0, **depth))
        if new and at:
            log.append(rec("train/metrics_window", t + 4, 4, n, it.id, leaves=9))
        if drains:
            record = {"nivcsw": 2, "nvcsw": 900, "majflt": 0, "cpu_s": 0.04} if new else {}
            log.append(rec("train/host_block", t + 6, 3, n, it.id,
                           first_step=n - LOG_INTERVAL + 1, **record))
        log.append(it)
    return log


def make_ctx():
    return {"steps": STEPS,
            "mix": {"log_interval": LOG_INTERVAL, "trace_windows": 1},
            "spans": {"span/train/data_wait": (0.008, STEPS),
                      "span/train/host_block": (0.006, STEPS // LOG_INTERVAL)}}


@pytest.fixture()
def program_log(monkeypatch):
    """Stand in for the running program's ``get_telemetry().span_log()``."""
    from tpuframe.track import telemetry

    def install(log):
        monkeypatch.setattr(telemetry, "get_telemetry",
                            lambda: types.SimpleNamespace(span_log=lambda: log))

    return install


def read(name, ctx):
    return correct.load_by_name("layer_metrics", name).read(ctx)


def test_each_reader_on_the_hand_made_log(program_log):
    program_log(make_log())
    ctx = make_ctx()
    # 2 ms of work an iteration, 3 on the two that drain: (6 x 2 + 2 x 3) / 8
    assert read("trainer.loop_cpu_ms", ctx) == pytest.approx(2.25)
    assert read("trainer.loop_wait_pct", ctx) == pytest.approx(100 * (1 - 18 / 80))
    # 0, 1, 2, 2 along each of the two windows
    assert read("trainer.steps_in_flight_median", ctx) == pytest.approx(1.5)
    assert read("trainer.metrics_window_ms", ctx) == pytest.approx(4.0)
    assert read("input.producer_cpu_ms", ctx) == pytest.approx(3.5)
    # two drains in the span: 80 ms of CPU and 4 switches over 8 steps
    assert read("trainer.process_cpu_ms", ctx) == pytest.approx(10.0)
    assert read("trainer.involuntary_switches", ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_reports_nothing_on_a_log_of_the_parents_shape(program_log, name):
    program_log(make_log(new=False))
    assert read(name, make_ctx()) is None
    program_log(make_log()[16:])  # a log that does not hold the span
    assert read(name, make_ctx()) is None


@pytest.mark.parametrize("name", ("trainer.loop_cpu_ms", "trainer.loop_wait_pct",
                                  "input.producer_cpu_ms"))
def test_a_span_opened_without_the_clock_reads_as_nothing(program_log, name):
    log = make_log()
    for r in log:
        r.cpu_ns = None  # the slot is there, nobody asked for it
    program_log(log)
    assert read(name, make_ctx()) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_is_appended_and_lists_no_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-len(NAMES):]] == list(NAMES)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}  # every cell
    assert entry["moves"] == "samples_per_s_chip" and entry["source"] == "program_span"
    assert entry["layer"] == name.split(".")[0]


def test_the_rehearsal_run_reads_all_seven(tmp_path):
    from chipbench import run
    from tpuframe.track import telemetry

    telemetry.reset()  # a run is a process of its own: no earlier run's steps in the log
    out = run.run_cell("lfm2moe_seq4096", 2**31 + 35, 2.0, True, rehearsal=True,
                       out_dir=str(tmp_path))
    assert out["correct"] and set(NAMES) <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["trainer.loop_cpu_ms"] and 0 <= m["trainer.loop_wait_pct"] < 100
    assert m["trainer.process_cpu_ms"] >= m["trainer.loop_cpu_ms"]
    assert m["trainer.steps_in_flight_median"] >= 0 and m["trainer.metrics_window_ms"] > 0
    assert m["input.producer_cpu_ms"] > 0 and m["trainer.involuntary_switches"] >= 0
    assert m["trainer.unattributed_ms"] < 5  # no dispatch left outside a child span
