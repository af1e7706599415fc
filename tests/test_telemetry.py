"""Telemetry spine: spans, metrics registry, JSONL log, stall watchdog.

The acceptance contract this file demonstrates (ISSUE 1):

- a deliberately-stalled CPU training step triggers a watchdog report
  carrying all-thread stacks and the active span path within 2x the
  configured deadline;
- the Trainer's epoch summary still reports ``data_wait_s`` /
  ``dispatch_s`` / ``host_block_s``, now derived from spans;
- a 3-step CPU fit leaves ``train/step`` spans with non-negative
  durations in the JSONL event log (the tier-1 smoke for the bench/CI
  wiring).

No test sleeps longer than ~1s; everything runs on the simulated-CPU
platform from conftest.
"""

import io
import json
import os
import threading
import time
import urllib.request

import pytest

from tpuframe.track import telemetry as T
from tpuframe.track.watchdog import Watchdog


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """Each test gets (and cleans up) its own process-wide instance."""
    T.reset()
    yield
    T.reset()


# -- spans --------------------------------------------------------------------


class TestSpans:
    def test_nesting_records_stack_and_durations(self):
        tele = T.configure()
        with tele.span("outer") as so:
            with tele.span("inner") as si:
                time.sleep(0.01)
            assert si.stack == ["outer", "inner"]
        assert so.stack == ["outer"]
        assert so.elapsed >= si.elapsed > 0
        # both feed per-name histograms automatically
        assert tele.registry.histogram("span/outer").count == 1
        assert tele.registry.histogram("span/inner").count == 1

    def test_exception_marks_span_failed_and_propagates(self):
        tele = T.configure()
        with pytest.raises(ValueError, match="boom"):
            with tele.span("explodes") as sp:
                raise ValueError("boom")
        assert sp.ok is False
        assert "ValueError" in sp.error
        ev = [e for e in tele.recent_events() if e["name"] == "explodes"]
        assert ev and ev[0]["ok"] is False and "ValueError" in ev[0]["error"]
        # the failed span was popped: no stuck entry in the live stacks
        assert tele.active_spans() == {}

    def test_threads_have_independent_stacks(self):
        tele = T.configure()
        ready = threading.Barrier(3, timeout=5)
        release = threading.Event()
        seen: dict[str, list[str]] = {}

        def run(name):
            with tele.span(name):
                ready.wait()
                release.wait(timeout=5)

        threads = [
            threading.Thread(target=run, args=(f"t{i}",), name=f"spanner-{i}")
            for i in range(2)
        ]
        for t in threads:
            t.start()
        ready.wait()
        seen = tele.active_spans()
        release.set()
        for t in threads:
            t.join()
        stacks = sorted(tuple(v) for k, v in seen.items() if "spanner" in k)
        assert stacks == [("t0",), ("t1",)]  # no cross-thread mixing
        assert tele.active_spans() == {}

    def test_emit_false_skips_event_but_keeps_histogram(self):
        tele = T.configure()
        with tele.span("quiet", emit=False):
            pass
        assert not [e for e in tele.recent_events() if e.get("name") == "quiet"]
        assert tele.registry.histogram("span/quiet").count == 1


# -- the span log -------------------------------------------------------------


class TestSpanLog:
    def test_one_clock_across_threads_and_nested_parent_ids(self):
        tele = T.configure()
        seen = {}

        def worker():
            with tele.span("w/outer", emit=False) as o:
                with tele.span("w/inner", emit=False) as i:
                    time.sleep(0.002)
            seen["w"] = (o, i)

        before = time.perf_counter_ns()
        with tele.span("m/outer") as mo:
            th = threading.Thread(target=worker, name="span-log-worker")
            th.start()
            with tele.span("m/inner") as mi:
                time.sleep(0.002)
            th.join()
        after = time.perf_counter_ns()
        log = tele.span_log()
        # oldest first (by close), every thread's, emitted or not
        assert {r.name for r in log} == {"w/outer", "w/inner", "m/outer", "m/inner"}
        assert log[-1] is mo
        for r in log:
            assert before <= r.start_ns < r.end_ns <= after
            assert r.elapsed == pytest.approx((r.end_ns - r.start_ns) / 1e9)
        wo, wi = seen["w"]
        assert (mi.parent_id, wi.parent_id) == (mo.id, wo.id)
        assert mo.parent_id is None and wo.parent_id is None
        assert len({r.id for r in log}) == 4
        assert {r.thread for r in (wo, wi)} == {"span-log-worker"}
        assert mo.thread == threading.current_thread().name
        # children lie inside their parents on the shared clock
        for child, parent in ((mi, mo), (wi, wo)):
            assert parent.start_ns <= child.start_ns
            assert child.end_ns <= parent.end_ns
        inner = tele.span_log(names=["m/inner", "w/inner"])
        assert sorted(r.name for r in inner) == ["m/inner", "w/inner"]

    def test_anchor_places_the_log_beside_the_wall_clock(self):
        tele = T.configure()
        with tele.span("a") as sp:
            pass
        wall_ns = int(tele.anchor_wall * 1e9) + (sp.end_ns - tele.anchor_perf_ns)
        assert abs(wall_ns - time.time_ns()) < 1e9
        env = tele.recent_events()[-1]
        assert env["name"] == "a" and env["kind"] == "span"
        assert env["ts"] == pytest.approx(wall_ns / 1e9, abs=1e-3)

    def test_ring_drops_the_oldest(self):
        tele = T.Telemetry(None, max_spans=4)
        for i in range(10):
            with tele.span("s", emit=False, i=i):
                pass
        assert [r.attrs["i"] for r in tele.span_log()] == [6, 7, 8, 9]

    def test_raising_span_is_recorded_not_ok(self):
        tele = T.configure()
        with pytest.raises(KeyError):
            with tele.span("boom", emit=False):
                raise KeyError("k")
        (rec,) = tele.span_log()
        assert rec.ok is False and rec.error.startswith("KeyError")
        assert rec.end_ns > rec.start_ns

    def test_step_is_inherited_and_attrs_are_held_by_reference(self):
        tele = T.configure()
        with tele.span("parent", step=7):
            with tele.span("child", emit=False) as c:
                with tele.span("own", step=9) as o:
                    pass
                c.attrs["late"] = True
        with tele.span("orphan") as orphan:
            pass
        assert (c.step, o.step, orphan.step) == (7, 9, None)
        assert tele.span_log(names=["child"])[0].attrs == {"late": True}
        events = {e["name"]: e for e in tele.recent_events()}
        assert "child" not in events  # emit=False: in the log, not an event
        assert events["own"]["step"] == 9 and "step" not in events["orphan"]

    def test_record_span_logs_a_region_that_has_already_ended(self, tmp_path):
        """How a listener's callback becomes a record: parent and step from
        the span its thread has open, the histogram, one JSONL line."""
        tele = T.configure(jsonl_dir=str(tmp_path), rank=0)
        t0 = time.perf_counter_ns()
        with tele.span("parent", step=5) as parent:
            rec = tele.record_span("late/region", t0, t0 + 2_000_000, fun="f")
        bare = tele.record_span("late/region", t0, t0 + 1_000_000, emit=False)
        assert (rec.parent_id, rec.step) == (parent.id, 5)
        assert rec.id > parent.id and rec.stack == ["parent", "late/region"]
        assert rec.thread == threading.current_thread().name
        assert (rec.start_ns, rec.end_ns, rec.elapsed) == (t0, t0 + 2_000_000, 0.002)
        assert (bare.parent_id, bare.step, bare.stack) == (None, None, ["late/region"])
        # in the log in the order they were put there; the open span's stack
        # is untouched by a record that was never on it
        assert [r.id for r in tele.span_log()] == [rec.id, parent.id, bare.id]
        assert tele.active_spans() == {}
        hist = tele.registry.histogram("span/late/region")
        assert hist.count == 2 and hist.total == pytest.approx(0.003)
        lines = [json.loads(line) for line in
                 (tmp_path / "events-rank0.jsonl").read_text().splitlines()]
        (line,) = [r for r in lines if r["name"] == "late/region"]  # emit=False: none
        assert line["kind"] == "span" and line["dur_s"] == 0.002 and line["step"] == 5
        assert line["attrs"] == {"fun": "f"} and line["stack"] == rec.stack

    def test_annotation_hook_sees_every_span_with_its_step(self, monkeypatch):
        opened = []

        class Ann:
            def __init__(self, name, step):
                self.rec = [name, step, "open"]
                opened.append(self.rec)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.rec[2] = "closed"

        monkeypatch.setattr(T, "_annotate", Ann)
        tele = T.configure()
        with tele.span("train/iter", emit=False, step=3):
            with tele.span("train/step"):
                assert opened[-1] == ["train/step", 3, "open"]
        assert opened == [["train/iter", 3, "closed"], ["train/step", 3, "closed"]]

    @pytest.mark.parametrize("region, low_ms, high_ms", [
        ("sleep", 0, 5), ("busy", 40, 1000)])
    def test_cpu_time_tells_work_from_wait(self, region, low_ms, high_ms):
        tele = T.configure()
        with tele.span("work", cpu=True) as sp:
            if region == "sleep":
                time.sleep(0.05)
            else:
                until = time.thread_time_ns() + 50_000_000
                while time.thread_time_ns() < until:
                    pass
        assert low_ms * 1e6 <= sp.cpu_ns < high_ms * 1e6
        assert sp.cpu_ns <= sp.end_ns - sp.start_ns  # read inside the clock's readings
        assert tele.span_log()[0].cpu_ns == sp.cpu_ns

    def test_cpu_time_is_taken_only_where_asked(self, tmp_path):
        tele = T.configure(jsonl_dir=str(tmp_path), rank=0)
        with tele.span("plain"):
            pass
        with tele.span("timed", cpu=True, batch=2):
            pass
        plain, timed = tele.span_log()
        assert plain.cpu_ns is None and timed.cpu_ns >= 0
        assert "cpu" not in timed.attrs  # a switch, not an attribute
        lines = {r["name"]: r for r in map(json.loads, (
            tmp_path / "events-rank0.jsonl").read_text().splitlines())}
        assert "cpu_ms" not in lines["plain"]
        assert lines["timed"]["cpu_ms"] == round(timed.cpu_ns / 1e6, 3)
        assert tele.recent_events()[-1]["cpu_ms"] == lines["timed"]["cpu_ms"]

    def test_telemetry_imports_without_jax(self):
        import subprocess
        import sys

        code = (
            "import sys; sys.modules['jax'] = None\n"
            "from tpuframe.track import telemetry as T\n"
            "t = T.Telemetry(None)\n"
            "with t.span('a', step=1): pass\n"
            "assert t.span_log()[0].step == 1 and T._annotate is None\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)))


# -- metrics registry ---------------------------------------------------------


class TestRegistry:
    def test_histogram_percentiles(self):
        h = T.Histogram("h", max_samples=4096)
        for v in range(1, 101):  # 1..100
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100
        assert s["mean"] == pytest.approx(50.5)
        assert s["p50"] == 51.0  # index int(0.5*100) of sorted 1..100
        assert s["p95"] == 96.0
        assert s["p99"] == 100.0

    def test_histogram_ring_keeps_recent_window(self):
        # the old StepTimer bug inverted: lifetime totals keep counting,
        # the percentile window holds the most RECENT max_samples
        h = T.Histogram("h", max_samples=10)
        for v in range(100):
            h.observe(float(v))
        assert h.count == 100
        assert h.total == pytest.approx(sum(range(100)))
        assert sorted(h.window()) == [float(v) for v in range(90, 100)]

    def test_counter_gauge_snapshot(self):
        reg = T.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2)
        reg.gauge("g").set(7.5)
        reg.histogram("h").observe(1.0)
        snap = reg.snapshot(prefix="p/")
        assert snap["p/c"] == 3.0
        assert snap["p/g"] == 7.5
        assert snap["p/h_count"] == 1.0 and snap["p/h_p50"] == 1.0

    def test_prometheus_text(self):
        reg = T.MetricsRegistry()
        reg.counter("data/batches").inc(4)
        reg.gauge("train/epoch").set(2)
        reg.histogram("span/train/step").observe(0.5)
        text = reg.prometheus_text()
        assert "# TYPE tpuframe_data_batches counter" in text
        assert "tpuframe_data_batches 4.0" in text
        assert "tpuframe_train_epoch 2.0" in text
        assert 'tpuframe_span_train_step{quantile="0.50"} 0.5' in text
        assert "tpuframe_span_train_step_count 1" in text

    def test_metrics_server_serves_registry(self):
        tele = T.configure()
        tele.registry.counter("hits").inc(3)
        srv = T.start_metrics_server()
        try:
            body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
            assert "tpuframe_hits 3.0" in body
            health = urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/healthz", timeout=5
            ).read()
            assert json.loads(health)["status"] == "ok"
        finally:
            srv.close()


# -- JSONL event log ----------------------------------------------------------


class TestJsonl:
    def test_schema_round_trip(self, tmp_path):
        tele = T.configure(jsonl_dir=str(tmp_path), rank=2)
        with tele.span("a", note="hi"):
            pass
        tele.event("custom", kind="bench_attempt", rung="accel", verdict="ok")
        path = tmp_path / "events-rank2.jsonl"
        assert tele.jsonl_path == str(path)
        recs = [json.loads(line) for line in path.read_text().splitlines()]
        assert len(recs) == 3
        for rec in recs:  # the envelope every record carries
            for key in ("v", "ts", "mono", "rank", "pid", "thread", "kind",
                        "name"):
                assert key in rec, key
            assert rec["v"] == T.SCHEMA_VERSION
            assert rec["rank"] == 2
        meta, span, ev = recs
        # first line of every sink-backed log: the clock-anchor meta record
        assert meta["kind"] == "meta" and meta["schema"] == T.SCHEMA_VERSION
        assert meta["anchor_wall"] > 0 and meta["anchor_mono"] >= 0
        assert "hostname" in meta
        assert span["kind"] == "span" and span["name"] == "a"
        assert span["dur_s"] >= 0 and span["ok"] is True
        assert span["stack"] == ["a"] and span["attrs"] == {"note": "hi"}
        assert ev["kind"] == "bench_attempt" and ev["verdict"] == "ok"

    def test_memory_only_without_configuration(self):
        tele = T.configure()
        with tele.span("x"):
            pass
        assert tele.jsonl_path is None
        assert tele.recent_events()[-1]["name"] == "x"

    def test_env_dir_is_picked_up(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPUFRAME_TELEMETRY_DIR", str(tmp_path))
        monkeypatch.setenv("TPUFRAME_PROCESS_ID", "5")
        T.reset()
        tele = T.get_telemetry()
        assert tele.jsonl_path == str(tmp_path / "events-rank5.jsonl")
        assert tele.rank == 5


# -- watchdog -----------------------------------------------------------------


class TestWatchdog:
    def test_stalled_activity_reports_within_2x_deadline(self, tmp_path):
        deadline = 0.4
        tele = T.configure(jsonl_dir=str(tmp_path), rank=0)
        sink = io.StringIO()
        wd = tele.attach_watchdog(Watchdog(default_deadline_s=deadline, sink=sink))

        def stalled():
            with tele.span("train/step"), tele.guard("train/step"):
                time.sleep(2.4 * deadline)

        t = threading.Thread(target=stalled, name="stalled-step")
        t0 = time.monotonic()
        t.start()
        while not wd.reports and time.monotonic() - t0 < 3 * deadline:
            time.sleep(0.02)
        detected = time.monotonic() - t0
        t.join()

        assert wd.reports, "watchdog produced no stall report"
        assert detected <= 2 * deadline, f"report took {detected:.2f}s"
        rep = wd.reports[0]
        assert rep["name"] == "train/step"
        assert rep["overdue_s"] <= deadline  # i.e. within 2x overall
        # the active span path of the stalled thread is in the report
        assert any("train/step" in v for v in rep["spans"].values())
        # all-thread python stacks, including the sleeping line
        assert "stalled-step" in rep["stacks"]
        assert "time.sleep" in rep["stacks"] or "sleep" in rep["stacks"]
        # stderr-style report went to the sink
        text = sink.getvalue()
        assert "STALL 'train/step'" in text
        assert "all-thread python stacks" in text
        # ... and the JSONL log has the stall + the recovery marker
        kinds = [
            (e["kind"], e["name"])
            for e in map(json.loads,
                         (tmp_path / "events-rank0.jsonl").read_text().splitlines())
        ]
        assert ("stall", "train/step") in kinds
        assert ("stall_recovered", "train/step") in kinds

    def test_beat_defers_the_deadline(self):
        tele = T.configure()
        wd = tele.attach_watchdog(
            Watchdog(default_deadline_s=0.3, sink=io.StringIO())
        )
        with wd.guard("loop") as g:
            for _ in range(4):  # 0.6s of work, never >0.3s between beats
                time.sleep(0.15)
                g.beat()
        assert not wd.reports

    def test_stall_then_beat_still_records_recovery(self):
        # a reported stall that later heartbeats and completes must still
        # emit stall_recovered (ever_dumped is sticky; dumped re-arms)
        tele = T.configure()
        wd = tele.attach_watchdog(
            Watchdog(default_deadline_s=0.15, sink=io.StringIO())
        )
        with wd.guard("bursty") as g:
            time.sleep(0.3)  # stall: report fires
            while not wd.reports:
                time.sleep(0.02)
            g.beat()  # recovers, re-arms
        kinds = [e["kind"] for e in tele.recent_events()]
        assert "stall" in kinds and "stall_recovered" in kinds

    def test_stopped_watchdog_refuses_new_leases(self):
        wd = Watchdog(default_deadline_s=5.0, sink=io.StringIO())
        with wd.guard("a") as g:
            assert g.monitored
        wd.stop()
        with wd.guard("a") as g:
            assert not g.monitored  # no resurrection of the monitor thread
        assert wd._thread is None

    def test_unresolved_deadline_is_unmonitored(self):
        tele = T.configure()
        wd = tele.attach_watchdog(Watchdog(sink=io.StringIO()))  # no defaults
        with wd.guard("anything") as g:
            assert not g.monitored
        with wd.guard("named", deadline_s=5.0) as g:
            assert g.monitored

    def test_deadline_resolution_order(self):
        wd = Watchdog(default_deadline_s=10.0, deadlines={"a": 1.0})
        assert wd.resolve_deadline("a", None) == 1.0
        assert wd.resolve_deadline("b", None) == 10.0
        assert wd.resolve_deadline("a", 3.0) == 3.0

    def test_env_deadline_parsing(self):
        assert T._parse_deadlines("train/step=120,ckpt/save=600") == {
            "train/step": 120.0,
            "ckpt/save": 600.0,
        }
        assert T._parse_deadlines("garbage,=,x=notafloat") == {}


# -- trainer integration ------------------------------------------------------


def _tiny_loader(n=64, batch=16):
    from tpuframe.data import DataLoader, SyntheticImageDataset

    ds = SyntheticImageDataset(n=n, num_classes=4, image_size=28, channels=1)
    return DataLoader(ds, batch_size=batch, process_index=0, process_count=1)


@pytest.fixture()
def cpu_runtime():
    from tpuframe.core import MeshSpec
    from tpuframe.core import runtime as rt

    rt.reset_runtime()
    rt.initialize(MeshSpec(data=-1))
    yield
    rt.reset_runtime()


class TestTrainerTelemetry:
    def test_three_step_fit_leaves_step_spans_in_event_log(
        self, tmp_path, cpu_runtime
    ):
        """The tier-1 smoke the CI satellite asks for: 3 steps on CPU, then
        the JSONL event log holds train/step spans with non-negative
        durations."""
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        tele = T.configure(jsonl_dir=str(tmp_path), rank=0)
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(),
            max_duration="3ba",
            num_classes=4,
        )
        result = trainer.fit()

        recs = [
            json.loads(line)
            for line in (tmp_path / "events-rank0.jsonl").read_text().splitlines()
        ]
        steps = [r for r in recs if r["kind"] == "span" and r["name"] == "train/step"]
        assert len(steps) == 3
        for s in steps:
            assert s["dur_s"] >= 0 and s["ok"] is True
            assert s["stack"][-1] == "train/step"
        epochs = [r for r in recs if r["name"] == "train/epoch"]
        assert epochs and epochs[0]["attrs"] == {"epoch": 0}
        # per-step distributions come free via the registry
        assert tele.registry.histogram("span/train/step").count == 3
        assert tele.registry.histogram("span/data/h2d").count >= 3
        # the legacy wall-clock breakdown keys survive, span-derived now
        for key in ("data_wait_s", "dispatch_s", "host_block_s", "epoch_time_s"):
            assert key in result.metrics and result.metrics[key] >= 0
        # components measured inside the epoch cannot exceed the epoch total
        inside = (
            result.metrics["data_wait_s"]
            + result.metrics["dispatch_s"]
            + result.metrics["host_block_s"]
        )
        assert inside <= result.metrics["epoch_time_s"] + 0.05
        assert result.metrics["dispatch_s"] > 0

    def test_span_log_joins_every_step_to_what_fed_it(self, cpu_runtime):
        """Three windows on the CPU: every step has exactly one iteration,
        pull, dispatch and device copy and at least one assembly under the
        same step id, every drain names its window, and no child outlasts
        its ``train/iter``."""
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer
        from tpuframe.train.callbacks import Callback

        tele = T.configure()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(n=16 * 12),
            max_duration="12ba",
            num_classes=4,
            log_interval=4,
            eval_interval=0,
            callbacks=[Callback()],
        )
        trainer.fit()
        log = tele.span_log()
        by = {}
        for r in log:
            by.setdefault(r.name, []).append(r)
        steps = list(range(1, 13))
        for name in ("train/iter", "train/data_wait", "train/step", "data/h2d"):
            assert sorted(r.step for r in by[name] if r.step is not None) == steps, name
        assert {r.step for r in by["data/assemble"]} >= set(steps)
        assert all("fresh_alloc" in r.attrs for r in by["data/assemble"])
        # the producer's spans are another thread's, on the same clock
        assert {r.thread for r in by["data/h2d"]} != {r.thread for r in by["train/step"]}
        # a step's batch was on the device before its pull returned
        h2d = {r.step: r for r in by["data/h2d"]}
        for w in by["train/data_wait"]:
            if w.step is not None:
                assert h2d[w.step].end_ns <= w.end_ns
        # every drain names its window
        drains = [(r.attrs["first_step"], r.step) for r in by["train/host_block"]]
        assert drains == [(1, 4), (5, 8), (9, 12)]
        # the iteration that found the stop feeds no step
        assert [r.step for r in by["train/iter"]][-1] is None
        # children: inside their iteration, and never longer than it
        iters = {r.id: r for r in by["train/iter"]}
        covered = dict.fromkeys(iters, 0)
        kids = [r for r in log if r.parent_id in iters]
        assert {"train/data_wait", "train/step", "train/callbacks",
                "train/host_block"} <= {r.name for r in kids}
        assert {r.attrs["hook"] for r in by["train/callbacks"]} >= {
            "on_step_start", "on_step_end", "on_batch_end"}
        for r in kids:
            it = iters[r.parent_id]
            assert it.start_ns <= r.start_ns and r.end_ns <= it.end_ns
            assert r.step == it.step
            covered[it.id] += r.end_ns - r.start_ns
        for i, it in iters.items():
            assert covered[i] <= it.end_ns - it.start_ns
        # the health sentinel's fetch has a span of its own, with its step
        assert [r.step for r in by["train/health_fetch"]] == [12]
        # the step's add into the window is dispatched before the fetch
        # waits for the step, not in the device's idle time after it
        add = [r for r in by["train/metrics_window"] if r.step == 12]
        assert len(add) == 1
        assert add[0].end_ns <= by["train/health_fetch"][0].start_ns

    def test_a_drain_and_a_health_check_are_one_transfer_each(
            self, cpu_runtime, monkeypatch):
        """Between the end of a window's last step and the next dispatch the
        device is idle: the loop fetches the drained window in one
        ``device_get`` and the health check's flags and state in one,
        whatever the number of leaves."""
        import traceback

        import jax

        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        callers = []
        real = jax.device_get

        def counting(x):
            callers.append(traceback.extract_stack()[-2].name)
            return real(x)

        monkeypatch.setattr(jax, "device_get", counting)
        T.configure()
        result = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(n=16 * 12),
            max_duration="12ba",
            num_classes=4,
            log_interval=4,
            eval_interval=0,
        ).fit()
        assert callers == ["drain", "drain", "drain", "_health_check"]
        assert result.metrics["host_block_s"] > 0

    def test_empty_queue_dispatch_is_seen_and_counted(self, cpu_runtime):
        """A step dispatched after the device ran dry says so."""
        import jax

        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer
        from tpuframe.train.callbacks import Callback

        class DrainAfter(Callback):
            def on_step_end(self, trainer):
                if trainer.batches_seen in (2, 4):
                    jax.block_until_ready(trainer.state)

        tele = T.configure()
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(n=16 * 6),
            max_duration="6ba",
            num_classes=4,
            log_interval=0,
            eval_interval=0,
            callbacks=[DrainAfter()],
        ).fit()
        flags = {r.step: r.attrs.get("device_idle_at_dispatch")
                 for r in tele.span_log(names=["train/step"])}
        assert flags[1] is True  # nothing dispatched before it
        assert flags[3] is True and flags[5] is True
        assert all(isinstance(flags[n], bool) for n in range(2, 7))
        assert tele.registry.counter("train/empty_queue_dispatches").value == sum(
            1 for v in flags.values() if v)
        # one mechanism: the flag is the queue's depth read as a bit
        depths = {r.step: r.attrs["steps_in_flight"]
                  for r in tele.span_log(names=["train/step"])}
        assert depths[3] == depths[5] == 0
        assert {n for n, d in depths.items() if d == 0} == {
            n for n, idle in flags.items() if idle}
        assert all(0 <= d < n for n, d in depths.items())
        hist = tele.registry.histogram("train/steps_in_flight")
        assert hist.count == 6 and sorted(hist.window()) == sorted(depths.values())

    def test_the_loop_tells_work_from_wait_and_leaves_nothing_unnamed(
            self, cpu_runtime):
        """Every iteration: CPU time within its duration, a
        ``train/metrics_window`` child wherever a window is added to, and
        what no child covers (the meters, host adds) a few milliseconds
        at most; every drain carries the machine's record."""
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        tele = T.configure()
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(n=16 * 12),
            max_duration="12ba",
            num_classes=4,
            log_interval=4,
            eval_interval=0,
        ).fit()
        log = tele.span_log()
        iters = {r.id: r for r in log
                 if r.name == "train/iter" and r.step is not None}
        assert sorted(r.step for r in iters.values()) == list(range(1, 13))
        covered = dict.fromkeys(iters, 0)
        for r in log:
            if r.parent_id in iters:
                covered[r.parent_id] += r.end_ns - r.start_ns
        own_ms = []
        for i, it in iters.items():
            assert 0 <= it.cpu_ns <= it.end_ns - it.start_ns
            own_ms.append((it.end_ns - it.start_ns - covered[i]) / 1e6)
        # the bound: 50 ms for any one iteration on a loaded CPU, 5 for the
        # median (on the chip the benchmark holds the mean under 1)
        assert max(own_ms) < 50 and sorted(own_ms)[6] < 5, own_ms
        # a window's first step starts it; every other step adds to it
        adds = {r.step: r for r in log if r.name == "train/metrics_window"}
        assert sorted(adds) == [2, 3, 4, 6, 7, 8, 10, 11, 12]
        for r in adds.values():
            assert r.parent_id in iters and r.attrs["leaves"] >= 3
        assert all(r.cpu_ns is None for r in adds.values())
        # the producer's work, on its own thread
        for name in ("data/assemble", "data/h2d"):
            for r in tele.span_log(names=[name]):
                assert 0 <= r.cpu_ns <= r.end_ns - r.start_ns
        drains = tele.span_log(names=["train/host_block"])
        assert len(drains) == 3
        for r in drains:
            assert {"first_step", "nivcsw", "nvcsw", "majflt", "cpu_s"} <= set(r.attrs)
            assert set(r.attrs) <= {"first_step", "nivcsw", "nvcsw", "majflt",
                                    "cpu_s", "psi_cpu_some_us"}
            assert r.attrs["cpu_s"] > 0 and r.attrs["nivcsw"] >= 0
        assert not [e for e in tele.recent_events() if e["name"] == "train/slow_window"]

    def test_the_interval_snapshot_and_the_fleet_gather_wait_under_names(
            self, cpu_runtime, tmp_path, monkeypatch):
        """The two statements of an iteration that wait on the device only in
        some runs: the interval snapshot (its health stamp is a
        ``device_get``) and, on a pod, the fleet's gather."""
        import jax

        from tpuframe.ckpt import Checkpointer
        from tpuframe.models import MnistNet
        from tpuframe.track import analyze
        from tpuframe.train import Trainer

        tele = T.configure()
        ck = Checkpointer(str(tmp_path / "ck"))
        try:
            Trainer(
                MnistNet(num_classes=4),
                train_dataloader=_tiny_loader(n=16 * 6),
                max_duration="6ba",
                num_classes=4,
                log_interval=0,
                eval_interval=0,
                checkpointer=ck,
                checkpoint_interval_batches=2,
            ).fit()
        finally:
            ck.close()
        iters = {r.id: r.step for r in tele.span_log(names=["train/iter"])}
        snaps = tele.span_log(names=["train/snapshot"])
        assert [iters[r.parent_id] for r in snaps] == [2, 4]
        saves = [r for r in tele.span_log(names=["ckpt/save"]) if r.step in (2, 4)]
        assert {r.parent_id for r in saves} == {r.id for r in snaps}
        # a pod's gather: two processes on a backend that can run it
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(analyze, "_bounded_gather", lambda v: [v, v + 1])
        assert analyze.fleet_allgather(1.0) == [1.0, 2.0]
        assert len(tele.span_log(names=["fleet/allgather"])) == 1

    def test_a_slow_window_is_reported_once_with_its_evidence(self, cpu_runtime):
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer
        from tpuframe.train.callbacks import Callback

        class Pace(Callback):
            # 50 ms a step, so that no hiccup of the CPU makes a window
            # half as long again; and one stall, after step 14
            def on_step_end(self, trainer):
                time.sleep(1.0 if trainer.batches_seen == 14 else 0.05)

        tele = T.configure()
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(n=16 * 24),
            max_duration="24ba",
            num_classes=4,
            log_interval=4,
            eval_interval=0,
            callbacks=[Pace()],
        ).fit()
        slow = [e for e in tele.recent_events() if e["name"] == "train/slow_window"]
        # one for the stalled window (a loaded CPU may stall another of its own)
        assert all(e["window_s"] > 1.5 * e["median_s"] for e in slow), slow
        (e,) = [e for e in slow if e["first_step"] == 13]
        assert (e["first_step"], e["step"], e["steps"]) == (13, 16, 4)
        assert e["window_s"] > 1.0 > 1.5 * e["median_s"] > 0
        # the stall was the host's sleep: asleep it burned no CPU, and the
        # input and the queue say it was neither of them
        assert e["cpu_s"] < 0.9 and e["data_wait_max_s"] < 0.5
        assert e["assemble_max_s"] < 0.5 and e["h2d_max_s"] < 0.5
        # (the depth step 15's dispatch found: step 13's, after a drain, does not count)
        assert e["steps_in_flight_min"] == 0
        assert {"nivcsw", "nvcsw", "majflt"} <= set(e)

    def test_a_slow_window_whose_queue_never_emptied_says_so(self):
        """The evidence for "the device or its runtime held the step": the
        window's first dispatch follows a drain and finds nothing queued, so
        the shallowest queue is taken over the dispatches after it."""
        from tpuframe.train import Trainer

        tele = T.configure()
        for step, depth in ((5, 0), (6, 1), (7, 2), (8, 2), (9, 0)):
            with tele.span("train/step", step=step, steps_in_flight=depth):
                pass
        with tele.span("data/h2d", step=7):
            time.sleep(0.02)
        Trainer._slow_window(None, 5, 4, 0.3, 0.1, {"cpu_s": 0.01})
        (e,) = [e for e in tele.recent_events() if e["name"] == "train/slow_window"]
        assert (e["first_step"], e["step"], e["steps_in_flight_min"]) == (5, 8, 1)
        assert e["h2d_max_s"] >= 0.02 and e["assemble_max_s"] == e["data_wait_max_s"] == 0
        assert (e["window_s"], e["median_s"], e["cpu_s"]) == (1.2, 0.4, 0.01)

    def test_an_unreadable_pressure_file_leaves_its_key_out(
            self, cpu_runtime, monkeypatch):
        import builtins

        from tpuframe.models import MnistNet
        from tpuframe.track import system_metrics
        from tpuframe.train import Trainer

        real_open = builtins.open

        def no_pressure(path, *args, **kwargs):
            if path == "/proc/pressure/cpu":
                raise PermissionError(13, "Permission denied", path)
            return real_open(path, *args, **kwargs)

        monkeypatch.setattr(builtins, "open", no_pressure)
        assert set(system_metrics.machine_counters()) == {
            "nivcsw", "nvcsw", "majflt", "cpu_s"}
        tele = T.configure()
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(),
            max_duration="4ba",
            num_classes=4,
            log_interval=2,
            eval_interval=0,
        ).fit()
        drains = tele.span_log(names=["train/host_block"])
        assert len(drains) == 2
        for r in drains:
            assert set(r.attrs) == {"first_step", "nivcsw", "nvcsw", "majflt", "cpu_s"}

    def test_fresh_alloc_marks_exactly_the_ring_allocations(self, cpu_runtime):
        from tpuframe.data import DevicePrefetcher

        tele = T.configure()
        loader = _tiny_loader(n=16 * 8)
        allocs0 = tele.registry.counter("data/ring_allocs").value
        for _ in DevicePrefetcher(loader, first_step=1):
            pass
        fresh = [r.attrs["fresh_alloc"] for r in tele.span_log(names=["data/assemble"])]
        assert len(fresh) == 8
        n_alloc = tele.registry.counter("data/ring_allocs").value - allocs0
        assert 1 <= sum(fresh) == n_alloc < 8
        assert fresh[0] is True and fresh[-1] is False  # the ring recycles
        assert [r.step for r in tele.span_log(names=["data/h2d"])] == list(range(1, 9))

    def test_hot_path_spans_reach_the_profiler_hook(self, cpu_runtime, monkeypatch):
        """With the factory replaced by a recorder: one ``tpuframe/<name>``
        annotation for each hot-path span, carrying its step."""
        import contextlib

        from tpuframe.models import MnistNet
        from tpuframe.track import profiler
        from tpuframe.train import Trainer

        made = []

        def recorder(name, **kwargs):
            made.append((name, kwargs.get("step")))
            return contextlib.nullcontext()

        monkeypatch.setattr(profiler.jax.profiler, "TraceAnnotation", recorder)
        tele = T.configure()
        assert T._annotate is profiler._span_annotation
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(),
            max_duration="3ba",
            num_classes=4,
            eval_interval=0,
        ).fit()
        fed = {1, 2, 3}
        for name in ("train/iter", "train/data_wait", "train/step",
                     "train/metrics_window", "train/host_block",
                     "data/prefetch_fetch", "data/assemble", "data/h2d"):
            # (the pull that finds the stop is opened for a step it never feeds)
            want = sorted((f"tpuframe/{name}", r.step)
                          for r in tele.span_log(names=[name]) if r.step in fed)
            got = sorted(m for m in made
                         if m[0] == f"tpuframe/{name}" and m[1] in fed)
            assert got == want and got, name
            if name == "train/metrics_window":  # the first step starts the window
                assert {s for _, s in got} == fed - {1}
            elif name != "train/host_block":  # one drain, at the end
                assert {s for _, s in got} == fed, name

    def test_stalled_train_step_triggers_watchdog_report(
        self, tmp_path, cpu_runtime
    ):
        """ISSUE acceptance: a deliberately-stalled CPU training step
        produces a stall report with all-thread stacks and the active span
        path within 2x the configured deadline."""
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        deadline = 0.4
        tele = T.configure(
            jsonl_dir=str(tmp_path),
            rank=0,
            watchdog=Watchdog(default_deadline_s=deadline, sink=io.StringIO()),
        )
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(),
            max_duration="1ba",
            num_classes=4,
        )
        real_step = trainer._train_step

        def stalled_step(state, batch):
            time.sleep(2.4 * deadline)  # the deliberate stall
            return real_step(state, batch)

        trainer._train_step = stalled_step
        trainer.fit()

        wd = tele.watchdog
        assert wd.reports, "stalled step produced no watchdog report"
        rep = wd.reports[0]
        assert rep["name"] == "train/step"
        assert rep["overdue_s"] <= deadline  # detected within 2x deadline
        span_paths = list(rep["spans"].values())
        assert any(p[-2:] == ["train/epoch", "train/step"]
                   or "train/step" in p for p in span_paths)
        assert "stalled_step" in rep["stacks"]  # the wedged frame, named
        stalls = [
            json.loads(line)
            for line in (tmp_path / "events-rank0.jsonl").read_text().splitlines()
            if json.loads(line)["kind"] == "stall"
        ]
        assert stalls and stalls[0]["name"] == "train/step"

    def test_metrics_export_callback_bridges_registry_to_loggers(
        self, cpu_runtime
    ):
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        T.configure()

        class CaptureLogger:
            def __init__(self):
                self.metrics: list[dict] = []

            def log_metrics(self, metrics, step=0):
                self.metrics.append(dict(metrics))

        cap = CaptureLogger()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=_tiny_loader(),
            max_duration="2ba",
            num_classes=4,
            callbacks=[T.MetricsExportCallback()],
            loggers=[cap],
        )
        trainer.fit()
        bridged = [m for m in cap.metrics if any(k.startswith("telemetry/") for k in m)]
        assert bridged, "no telemetry/ snapshot reached the logger"
        last = bridged[-1]
        assert last["telemetry/span/train/step_count"] == 2.0
        assert last["telemetry/span/train/step_p50"] >= 0


# -- doctor integration (satellite) ------------------------------------------


class TestDoctorTelemetry:
    def test_telemetry_section_shape(self, tmp_path):
        from tpuframe import doctor

        T.configure(
            jsonl_dir=str(tmp_path),
            rank=0,
            watchdog=Watchdog(default_deadline_s=90.0, sink=io.StringIO()),
        )
        sec = doctor.telemetry_section()
        assert sec["event_log"] == str(tmp_path / "events-rank0.jsonl")
        assert "jsonl" in sec["exporters"]
        assert sec["watchdog"]["active"] is True
        assert sec["watchdog"]["default_deadline_s"] == 90.0

    def test_wedged_probe_report_carries_wall_time(self, monkeypatch):
        from tpuframe import doctor

        T.configure()
        monkeypatch.setattr(doctor, "_PROBE_SRC", "import time; time.sleep(60)")
        rec = doctor.probe_devices(timeout_s=0.5)
        assert "wedged" in rec["error"]
        assert rec["probe_wall_s"] >= 0.5  # timing evidence rides along
        ev = [
            e for e in T.get_telemetry().recent_events()
            if e.get("name") == "doctor/device_probe"
        ]
        assert ev and ev[0]["dur_s"] >= 0.5
