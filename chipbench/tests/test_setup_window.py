"""The set-up's readers: the window helper on a hand-made log, each of the
nine readers on it, the agreement rule, and two whole rehearsal runs."""

import json
import os
import types

import pytest

from chipbench import correct
from chipbench.layer_metrics import setup_window

MS = 1_000_000  # ns
NEW = ("compile.trace_s", "compile.lower_s", "compile.cache_load_s",
       "compile.backend_compile_s", "compile.programs", "compile.late_programs",
       "compile.precompile_unused_s", "compile.first_step_other_s",
       "trainer.state_init_s")
LOOP, PRE = "MainThread", "tpuframe-precompile"


def rec(ids, name, start_ms, dur_ms, thread=LOOP, step=None, parent_id=None, **attrs):
    return types.SimpleNamespace(
        id=next(ids), parent_id=parent_id, name=name, thread=thread,
        start_ns=int(start_ms * MS), end_ns=int((start_ms + dur_ms) * MS), step=step, attrs=attrs)


def jax_rec(ids, phase, fun, start_ms, dur_ms, self_ms=None, **kw):
    return rec(ids, f"compile/jax_{phase}", start_ms, dur_ms, fun=fun,
               self_s=(dur_ms if self_ms is None else self_ms) / 1e3, **kw)


def make_log(used=False, fit_start=True):
    """A process of 30 s.  The initialiser (4 s: trace 0.5, lowering 0.3, a
    load of 2) and the harness's seeded weights (a trace of 0.2 and a real
    compile of 1) come before ``fit()``; ``fit()`` enters at 10 s, its loop
    opens at 10.5 s and its first step ends at 16.6 s: it waits 1 s for a
    precompile of 1.5 s (whose trace of 1.2 s overlaps a trace of 0.2 s on
    the loop thread), then traces 2 s (0.5 of it a nested function's),
    lowers 1.5 and loads 1.3.  The second step's metrics window loads one
    more program.  Steps 4-11 are the measured span (a load inside it is no
    part of the set-up), 12-19 the traced windows."""
    ids = iter(range(1, 10**6))
    log = [
        jax_rec(ids, "trace", "init_fn", 100, 500),
        jax_rec(ids, "lower", "jit(init_fn)", 600, 300),
        jax_rec(ids, "backend", "jit(init_fn)", 900, 2000, cache="hit", retrieval_s=1.5),
        rec(ids, "setup/state_init", 100, 4000),
        jax_rec(ids, "trace", "make", 4500, 200),
        jax_rec(ids, "backend", "jit(make)", 4700, 1000, cache="miss"),
        jax_rec(ids, "trace", "probe", 10150, 200),
        jax_rec(ids, "trace", "step", 10200, 1200, thread=PRE),
        rec(ids, "compile/precompile_step", 10100, 1500, thread=PRE, kind="train", used=used),
    ]
    if fit_start:
        log.append(rec(ids, "setup/fit_start", 10000, 500))
    log += [
        rec(ids, "train/data_wait", 10500, 100, step=1),
        rec(ids, "compile/wait", 10600, 1000, step=1),
        jax_rec(ids, "trace", "layer_norm", 12000, 500, step=1),
        jax_rec(ids, "trace", "step", 11700, 2000, self_ms=1500, step=1),
        jax_rec(ids, "lower", "jit(step)", 13700, 1500, step=1),
        jax_rec(ids, "backend", "jit(step)", 15200, 1300, step=1, cache="hit", retrieval_s=1.2),
        rec(ids, "train/step", 10600, 6000, step=1, aot=used),
        rec(ids, "train/iter", 10500, 6200, step=1),
        jax_rec(ids, "backend", "jit(add)", 16800, 20, step=2, cache="hit", retrieval_s=0.01),
    ]
    for n in range(2, 20):
        t = 20000 + 10.0 * n
        it = rec(ids, "train/iter", t, 10, step=n)
        log.append(rec(ids, "train/data_wait", t, 1, step=n, parent_id=it.id))
        log.append(rec(ids, "train/step", t + 2, 2, step=n, parent_id=it.id))
        if n == 6:
            log.append(jax_rec(ids, "backend", "jit(late)", t + 5, 1, step=n, cache="miss"))
        if n % 4 == 3:
            log.append(rec(ids, "train/host_block", t + 6, 3, step=n, parent_id=it.id))
        log.append(it)
    return log


def make_ctx(lap_s=6.6):
    return {"steps": 8, "compile_first_step_s": lap_s,
            "mix": {"log_interval": 4, "trace_windows": 1},
            "spans": {"span/train/data_wait": (0.008, 8),
                      "span/train/host_block": (0.006, 2)}}


def test_the_helper_keeps_what_closed_before_the_span_opened():
    got = setup_window.select(make_log(), make_ctx())
    assert got["fit_start"].name == "setup/fit_start" and got["first_step"].step == 1
    kept = got["records"]
    # the span is steps 4..11: its first iteration opens at 20040 ms
    assert max(r.end_ns for r in kept) <= 20040 * MS
    assert {r.step for r in kept if r.name == "train/step"} == {1, 2, 3}
    assert not [r for r in kept if r.attrs.get("fun") == "jit(late)"]


@pytest.mark.parametrize("why, log, ctx", [
    ("a lap 0.3 s off the harness's", make_log(), make_ctx(lap_s=6.9)),
    ("a lap 0.3 s under it", make_log(), make_ctx(lap_s=6.3)),
    ("no setup/fit_start", make_log(fit_start=False), make_ctx()),
    ("a log that does not hold the span", make_log()[:-40], make_ctx()),
    ("no first step", [r for r in make_log() if r.step != 1], make_ctx()),
])
def test_the_helper_refuses(why, log, ctx):
    assert setup_window.select(log, ctx) is None, why


def test_a_lap_within_a_quarter_second_passes():
    assert setup_window.select(make_log(), make_ctx(lap_s=6.8)) is not None


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
    # two threads' traces that overlap (0.2 s on the loop, 1.2 s on the
    # precompile thread) are summed, not merged; a nested trace counts once
    assert read("compile.trace_s", ctx) == pytest.approx(0.5 + 0.2 + 0.2 + 1.2 + 1.5 + 0.5)
    assert read("compile.lower_s", ctx) == pytest.approx(0.3 + 1.5)
    assert read("compile.cache_load_s", ctx) == pytest.approx(2.0 + 1.3 + 0.02)
    assert read("compile.backend_compile_s", ctx) == pytest.approx(1.0)
    assert read("compile.programs", ctx) == 4
    assert read("compile.late_programs", ctx) == 1
    assert read("compile.precompile_unused_s", ctx) == pytest.approx(1.5)
    # 6.6 s less the wait (1) and the loop thread's own trace (0.2 + 2),
    # lowering (1.5) and load (1.3); the precompile thread's trace is not its
    assert read("compile.first_step_other_s", ctx) == pytest.approx(6.6 - 1 - 2.2 - 1.5 - 1.3)
    assert read("trainer.state_init_s", ctx) == pytest.approx(4.0)


def test_a_precompile_that_was_used_wastes_nothing(program_log):
    program_log(make_log(used=True))
    assert read("compile.precompile_unused_s", make_ctx()) == 0


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(program_log, name):
    program_log(None)                          # a program without a span log
    assert read(name, make_ctx()) is None
    program_log(make_log(fit_start=False))     # the parent: spans, no set-up records
    assert read(name, make_ctx()) is None
    program_log(make_log())
    assert read(name, make_ctx(lap_s=6.9)) is None   # log and harness disagree


@pytest.mark.parametrize("name", NEW)
def test_the_new_entries_are_in_per_layer(name):
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    # membership, not place (ROADMAP M6(v)); every cell reports them
    assert {k: v for k, v in entries[name].items() if k not in ("name", "unit")} == {
        "better": "lower", "source": "program_span", "layer": "compile spine",
        "moves": "setup_s"}


@pytest.mark.parametrize("cell, lazy", [("resnet50_cached", False), ("gpt2m_seq1024", True)])
def test_a_rehearsal_run_reads_all_nine(tmp_path, cell, lazy):
    from chipbench import run
    from tpuframe.track import telemetry

    telemetry.reset()  # a run is a process of its own: no earlier run's records in the log
    out = run.run_cell(cell, 2**31 + 11, 2.0, True, rehearsal=True, out_dir=str(tmp_path))
    assert out["correct"] and set(NEW) <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compile.first_step_other_s"] >= 0 and m["trainer.state_init_s"] > 0
    assert m["compile.programs"] > m["compile.late_programs"] >= 0
    assert m["compile.trace_s"] > 0 and m["compile.lower_s"] > 0
    # the image cell's first step is the join of its precompile; the token
    # cell's labels are of rank 2, its template never matches
    assert (m["compile.precompile_unused_s"] > 0) is lazy
    log = telemetry.get_telemetry().span_log()
    assert all(r.attrs["fun"] for r in log if r.name.startswith("compile/jax_"))
