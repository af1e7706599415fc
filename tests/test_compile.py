"""Compile spine tests: persistent cache, AOT warm-start, shape guard,
zero-recompile restart, analyzer/doctor/launch integration.

All CPU tier-1 against the 8-virtual-device conftest topology.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from tpuframe.compile import cache as cc
from tpuframe.compile.precompile import (
    ShapeGuard,
    batch_signature,
    format_signature,
    loader_batch_template,
)
from tpuframe.track.telemetry import Telemetry, get_telemetry


@pytest.fixture()
def cache_env(tmp_path, monkeypatch):
    """Fresh cache dir enabled for the test; prior process state
    (enabled dir or disabled) restored afterwards — the global default
    cache must not be silently switched off for later tests."""
    prev = cc.enabled_dir()
    d = str(tmp_path / "compile_cache")
    monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", d)
    assert cc.enable(d) == d
    yield d
    if prev is not None:
        cc.enable(prev)
    else:
        cc.disable()


def _counters():
    snap = get_telemetry().registry.snapshot()
    return {
        k: snap.get(f"compile/{k}", 0.0)
        for k in ("cache_hits", "cache_misses", "backend_compiles",
                  "recompiles")
    }


def _delta(a, b):
    return {k: b[k] - a[k] for k in a}


# -- cache dir resolution -----------------------------------------------------


class TestCacheDir:
    def test_knob_path_places_the_cache(self, monkeypatch, tmp_path):
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path / "x"))
        assert cc.cache_dir_from_env() == str(tmp_path / "x")

    @pytest.mark.parametrize("v", ["0", "off", "false", "no", "disabled"])
    def test_falsy_disables(self, monkeypatch, tmp_path, v):
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", v)
        assert cc.cache_dir_from_env() is None
        assert cc.enable() is None
        # ... even a cache placed from outside
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.cache_dir_from_env() is None
        assert cc.enable(str(tmp_path / "other")) is None

    def test_default_is_one_fixed_path_inside_the_checkout(self, monkeypatch):
        """Not the temp dir, not a per-rank scratch, nothing made from a
        pid or the time: the path is part of the cache's key, so every
        process of a checkout must resolve the same one."""
        monkeypatch.delenv("TPUFRAME_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("TPUFRAME_LOCAL_SCRATCH", "/somewhere/else")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.cache_dir_from_env() == os.path.join(repo, ".cache", "xla")
        env = {k: v for k, v in os.environ.items()
               if k not in ("TPUFRAME_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR")}
        src = ("from tpuframe.compile.cache import cache_dir_from_env; "
               "print(cache_dir_from_env())")
        seen = {
            subprocess.run(
                [sys.executable, "-c", src], cwd=cwd, capture_output=True,
                text=True, check=True,
                env={**env, "RANK": rank, "PYTHONPATH": repo},
            ).stdout.strip()
            for rank, cwd in (("0", repo), ("1", os.path.join(repo, "tests")))
        }
        assert seen == {os.path.join(repo, ".cache", "xla")}

    def test_cache_placed_from_outside_is_the_only_cache(
            self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set => that directory, whatever the
        knob or an explicit argument says, and still so after
        core.initialize()."""
        import jax

        from tpuframe.core import runtime as rt

        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path / "knob"))
        prev = cc.enabled_dir()
        try:
            assert cc.cache_dir_from_env() == placed
            assert cc.enable(str(tmp_path / "argument")) == placed
            assert jax.config.jax_compilation_cache_dir == placed
            rt.reset_runtime()
            rt.initialize()
            assert cc.enabled_dir() == placed
            assert jax.config.jax_compilation_cache_dir == placed
            assert not os.path.exists(tmp_path / "knob")
            assert not os.path.exists(tmp_path / "argument")
        finally:
            rt.reset_runtime()
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            monkeypatch.delenv("TPUFRAME_COMPILE_CACHE")
            cc.enable(prev) if prev is not None else cc.disable()


# -- keep-K / size-cap eviction ----------------------------------------------


class TestTrim:
    def _fill(self, d, n, size=1000):
        os.makedirs(d, exist_ok=True)
        for i in range(n):
            p = os.path.join(d, f"jit_f{i}-{'a' * 8}-cache")
            with open(p, "wb") as f:
                f.write(b"x" * size)
            at = p[: -len("-cache")] + "-atime"
            with open(at, "w"):
                pass
            t = time.time() - (n - i) * 60  # entry i older when i small
            os.utime(p, (t, t))
            os.utime(at, (t, t))

    def test_evicts_oldest_beyond_cap(self, tmp_path):
        d = str(tmp_path / "cache")
        self._fill(d, 10, size=1000)
        evicted = cc.trim(d, max_bytes=5000, keep=2)
        # 10 entries x 1000B, cap 5000 -> 5 oldest evicted
        assert len(evicted) == 5
        left = [f for f in os.listdir(d) if f.endswith("-cache")]
        assert len(left) == 5
        # oldest entries (low i) went first; their atime sidecars too
        assert not any("jit_f0-" in f or "jit_f4-" in f
                       for f in os.listdir(d))

    def test_keep_k_newest_survive_any_cap(self, tmp_path):
        d = str(tmp_path / "cache")
        self._fill(d, 6, size=1000)
        cc.trim(d, max_bytes=1, keep=4)
        left = sorted(f for f in os.listdir(d) if f.endswith("-cache"))
        assert len(left) == 4  # cap says zero, keep-K says 4: K wins

    def test_unbounded_and_missing_dir_are_noops(self, tmp_path):
        d = str(tmp_path / "cache")
        self._fill(d, 3)
        assert cc.trim(d, max_bytes=0, keep=1) == []
        assert cc.trim(str(tmp_path / "nope"), max_bytes=10, keep=0) == []

    def test_junk_env_cap_reads_as_unbounded(self, tmp_path, monkeypatch):
        d = str(tmp_path / "cache")
        self._fill(d, 3)
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE_MAX_MB", "banana")
        assert cc.trim(d) == []

    def test_cache_info_counts(self, tmp_path):
        d = str(tmp_path / "cache")
        self._fill(d, 4, size=2048)
        info = cc.cache_info(d)
        assert info["entries"] == 4
        assert info["total_mb"] == pytest.approx(4 * 2048 / 2**20, abs=1e-3)


# -- enable + listeners -------------------------------------------------------


class TestPersistentCache:
    def test_miss_then_hit_counted_and_entries_written(self, cache_env):
        before = _counters()
        jax.jit(lambda x: x * 2 + 1)(np.ones((8, 8), np.float32)
                                     ).block_until_ready()
        mid = _delta(before, _counters())
        assert mid["cache_misses"] >= 1 and mid["backend_compiles"] >= 1
        assert any(f.endswith("-cache") for f in os.listdir(cache_env))
        # a FRESH function object with the same program: jit re-traces,
        # the backend compile becomes a cache retrieval
        before = _counters()
        jax.jit(lambda x: x * 2 + 1)(np.ones((8, 8), np.float32)
                                     ).block_until_ready()
        d = _delta(before, _counters())
        assert d["cache_hits"] >= 1
        assert d["backend_compiles"] == 0  # retrieval, not a compile

    def test_last_compile_verdict_tells_a_retrieval_from_a_compile(
            self, cache_env):
        """What the precompile report's ``persistent_cache`` field reads:
        per-thread, cleared by reading."""
        cc.last_compile_verdict()
        assert cc.last_compile_verdict() is None
        x = np.ones((8, 8), np.float32)
        jax.jit(lambda x: x * 3 - 7)(x).block_until_ready()
        assert cc.last_compile_verdict() == "miss"
        assert cc.last_compile_verdict() is None  # reading clears it
        jax.jit(lambda x: x * 3 - 7)(x).block_until_ready()  # fresh fn: re-trace
        assert cc.last_compile_verdict() == "hit"

    def test_real_compile_emits_loud_event(self, cache_env, tmp_path):
        tele = Telemetry(str(tmp_path / "ev.jsonl"))
        from tpuframe.track import telemetry as tmod

        old = tmod._GLOBAL
        tmod._GLOBAL = tele
        try:
            with cc.compile_label("unit-test"):
                jax.jit(lambda x: x * 5 + 3)(np.ones((4, 4), np.float32)
                                             ).block_until_ready()
        finally:
            tmod._GLOBAL = old
            tele.close()
        recs = [json.loads(l) for l in open(tmp_path / "ev.jsonl")
                if l.strip()]
        compiles = [r for r in recs
                    if r.get("name") == "compile/backend_compile"]
        assert compiles and compiles[0]["label"] == "unit-test"
        assert compiles[0]["dur_s"] > 0


# -- one span-log record a phase of every compile request ----------------------

PHASES = ("compile/jax_trace", "compile/jax_lower", "compile/jax_backend")


@pytest.fixture()
def span_tele():
    """A fresh process-wide telemetry (the listeners write to whichever
    instance is current) with the compile listeners installed."""
    from tpuframe.track import telemetry as tmod

    cc.install_listeners()
    tele = tmod.configure()
    yield tele
    tmod.reset()


def _phase_records(tele, name, fun):
    return [r for r in tele.span_log([name]) if fun in r.attrs["fun"]]


class TestCompileRecords:
    @pytest.mark.parametrize("phase", PHASES)
    def test_a_jitted_call_leaves_one_record_a_phase_under_the_open_span(
            self, span_tele, phase):
        def record_me(x):
            return x * 3 + 1

        fn = jax.jit(record_me)
        x = np.ones((8, 8), np.float32)
        with span_tele.span("x", step=4) as outer:
            fn(x).block_until_ready()
        (rec,) = _phase_records(span_tele, phase, "record_me")
        assert rec.parent_id == outer.id and rec.step == 4
        assert rec.thread == outer.thread
        assert outer.start_ns <= rec.start_ns <= rec.end_ns <= outer.end_ns
        assert 0 < rec.attrs["self_s"] <= rec.elapsed + 1e-6
        if phase == "compile/jax_backend":
            assert rec.attrs["cache"] in ("hit", "miss", "uncached")
            assert "label" in rec.attrs
        # the same numbers apart, where compile/lower_s mixed two phases
        hist = span_tele.registry.histogram(f"span/{phase}")
        assert hist.count >= 1 and hist.total >= rec.elapsed
        # a second call of the same shapes compiles nothing: no record
        fn(x).block_until_ready()
        assert len(_phase_records(span_tele, phase, "record_me")) == 1

    def test_the_two_mixed_histograms_are_gone(self, span_tele):
        jax.jit(lambda x: x - 2)(np.ones((4,), np.float32)).block_until_ready()
        snap = span_tele.registry.snapshot()
        assert not [k for k in snap if k.startswith(
            ("compile/lower_s", "compile/backend_compile_s"))]
        assert snap["span/compile/jax_backend_count"] >= 1

    def test_a_nested_trace_is_its_own_record_only_from_a_millisecond_up(
            self, span_tele):
        """A step's trace holds the trace of every jitted function it
        calls; ``self_s`` is a record's time less the records nested in
        it, so the sum over records counts every moment once."""
        @jax.jit
        def slow_inner(x):
            for _ in range(60):  # a trace of a few milliseconds
                x = x * 1.5 + 0.5
            return x

        @jax.jit
        def quick_inner(x):
            return x + 1

        def holds_both(x):
            return slow_inner(x) + quick_inner(x)

        jax.jit(holds_both)(np.ones((4,), np.float32)).block_until_ready()
        (outer,) = _phase_records(span_tele, "compile/jax_trace", "holds_both")
        (inner,) = _phase_records(span_tele, "compile/jax_trace", "slow_inner")
        assert outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns
        assert inner.elapsed >= cc._NESTED_TRACE_MIN_S
        assert outer.attrs["self_s"] == pytest.approx(
            outer.elapsed - inner.elapsed, abs=2e-4)
        quick = _phase_records(span_tele, "compile/jax_trace", "quick_inner")
        assert all(r.elapsed >= cc._NESTED_TRACE_MIN_S for r in quick)
        # nothing is lowered or loaded under a name of its own inside a trace
        assert len(_phase_records(span_tele, "compile/jax_lower", "holds_both")) == 1
        assert not _phase_records(span_tele, "compile/jax_lower", "slow_inner")

    def test_a_cache_hit_says_so_and_carries_its_retrieval(
            self, cache_env, span_tele):
        """A second process-like compile: the in-memory caches dropped,
        the same program again, into the same persistent cache."""
        def twice_compiled(x):
            return x * 7 - 5

        x = np.ones((8, 8), np.float32)
        jax.jit(twice_compiled)(x).block_until_ready()
        jax.clear_caches()
        jax.jit(twice_compiled)(x).block_until_ready()
        first, second = _phase_records(
            span_tele, "compile/jax_backend", "twice_compiled")
        assert first.attrs["cache"] == "miss" and "retrieval_s" not in first.attrs
        assert second.attrs["cache"] == "hit"
        assert 0 < second.attrs["retrieval_s"] <= second.elapsed + 1e-3

    def test_a_record_on_a_bare_thread_has_no_parent_and_a_fun(self, span_tele):
        import threading

        def on_a_thread(x):
            return x / 3

        t = threading.Thread(
            target=lambda: jax.jit(on_a_thread)(np.ones((4,), np.float32)),
            name="bare-thread")
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
        recs = [r for r in span_tele.span_log() if r.thread == "bare-thread"]
        assert {r.name for r in recs} == set(PHASES)
        assert all(r.parent_id is None and r.attrs["fun"] for r in recs)


# -- signatures + templates ---------------------------------------------------


class TestSignatures:
    def test_signature_is_order_insensitive_and_formats(self):
        a = {"image": np.zeros((4, 8, 8, 1), np.uint8),
             "label": np.zeros((4,), np.int32)}
        b = dict(reversed(list(a.items())))
        assert batch_signature(a) == batch_signature(b)
        s = format_signature(batch_signature(a))
        assert "image:(4,8,8,1):uint8" in s and "label:(4):int32" in s

    def _trainer(self, **kw):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=64, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        kw.setdefault(
            "train_dataloader",
            DataLoader(ds, batch_size=16, shuffle=True, seed=3),
        )
        kw.setdefault(
            "eval_dataloader",
            DataLoader(ds, batch_size=16, drop_last=False),
        )
        return Trainer(MnistNet(num_classes=4), max_duration="1ep",
                       eval_interval=1, log_interval=0, precompile=False,
                       **kw)

    def _actual_first_sig(self, tr, train):
        loader = tr.train_dataloader if train else tr.eval_dataloader
        it = tr._device_batches(loader, train=train)
        batch = next(iter(it))
        return batch_signature(batch)

    def test_template_matches_actual_train_batch(self):
        tr = self._trainer()
        pred = batch_signature(loader_batch_template(tr, train=True))
        assert pred == self._actual_first_sig(tr, train=True)

    def test_template_matches_actual_eval_batch_with_weight(self):
        tr = self._trainer()
        t = loader_batch_template(tr, train=False)
        assert "weight" in t  # drop_last=False: every batch masked
        assert batch_signature(t) == self._actual_first_sig(tr, train=False)

    def test_template_matches_grad_accum_reshape(self):
        tr = self._trainer(grad_accum=2)
        t = loader_batch_template(tr, train=True)
        assert t["image"].shape[:2] == (2, 8)
        assert batch_signature(t) == self._actual_first_sig(tr, train=True)

    def test_template_probes_algorithm_dtype_and_label_rank(self):
        from tpuframe.train.algorithms import MixUp

        tr = self._trainer(algorithms=[MixUp(alpha=0.2)])
        t = loader_batch_template(tr, train=True)
        # MixUp mixes images to float and labels to (N, C) soft targets
        assert np.dtype(t["image"].dtype).kind == "f"
        assert len(t["label"].shape) == 2
        assert batch_signature(t) == self._actual_first_sig(tr, train=True)

    def test_duck_typed_loader_skips_template(self):
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        tr = Trainer(MnistNet(num_classes=4), max_duration="1ba",
                     sample_input=np.zeros((1, 28, 28, 1), np.float32),
                     num_classes=4, precompile=False)
        assert loader_batch_template(tr, train=True) is None


# -- shape guard --------------------------------------------------------------


class TestShapeGuard:
    def _sig(self, n):
        return batch_signature({"image": np.zeros((n, 4, 4, 1), np.uint8),
                                "label": np.zeros((n,), np.int32)})

    def test_disarmed_guard_stays_silent(self, tmp_path):
        tele = Telemetry(str(tmp_path / "ev.jsonl"))
        g = ShapeGuard(telemetry=tele)
        assert not g.check("train", self._sig(8))  # records, no event
        tele.close()
        recs = [json.loads(l) for l in open(tmp_path / "ev.jsonl")
                if l.strip()]
        assert not any(r.get("name") == "compile/recompile" for r in recs)

    def test_armed_guard_shouts_once_per_new_signature(self, tmp_path):
        tele = Telemetry(str(tmp_path / "ev.jsonl"))
        g = ShapeGuard(telemetry=tele)
        g.expect("train", self._sig(8))
        assert g.check("train", self._sig(8))       # expected: quiet
        assert not g.check("train", self._sig(4))   # miss: one event
        assert g.check("train", self._sig(4))       # adopted: quiet
        tele.close()
        recs = [json.loads(l) for l in open(tmp_path / "ev.jsonl")
                if l.strip()]
        shouts = [r for r in recs if r.get("name") == "compile/recompile"]
        assert len(shouts) == 1
        assert "(4,4,4,1)" in shouts[0]["signature"]
        assert tele.registry.counter("compile/recompiles").value == 1


# -- Trainer AOT warm-start ---------------------------------------------------


class TestTrainerPrecompile:
    def _fit(self, precompile, **kw):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=64, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        tr = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, shuffle=True,
                                        seed=3),
            eval_dataloader=DataLoader(ds, batch_size=16, drop_last=False),
            max_duration="1ep", eval_interval=1, log_interval=0,
            precompile=precompile, **kw,
        )
        res = tr.fit()
        return tr, res

    def test_fit_precompiles_and_dispatches_same_numerics(self):
        before = _counters()
        tr, res = self._fit(True)
        d = _delta(before, _counters())
        rep = tr._precompile_report
        assert rep and all(s.get("dispatchable") for s in rep["steps"])
        assert {k for k, _ in tr._compiled} == {"train", "eval"}
        # the derived signatures matched runtime exactly: no recompile
        # events, and the executables were never dropped by a fallback
        assert d["recompiles"] == 0
        assert len(tr._compiled) == 2
        _, res2 = self._fit(False)
        for k in ("train_loss", "train_accuracy", "eval_loss",
                  "eval_accuracy"):
            assert res.metrics[k] == pytest.approx(res2.metrics[k])

    def test_precompile_method_is_sync_and_idempotent(self):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        tr = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, seed=3),
            max_duration="1ep", eval_interval=0, log_interval=0,
        )
        rep = tr.precompile()
        assert rep is tr.precompile()  # second call: same report, no redo
        assert tr._shape_guard.armed

    def test_report_says_whether_the_train_step_was_retrieved(self, cache_env):
        """Cold: the train step's AOT compile is a real backend compile
        (``miss``); the same program again, into the same cache: ``hit``."""
        first, _ = self._fit(True)
        second, _ = self._fit(True)
        for tr, want in ((first, "miss"), (second, "hit")):
            got = {s["kind"]: s["persistent_cache"]
                   for s in tr._precompile_report["steps"]}
            assert got == {"train": want, "eval": want}

    @pytest.mark.parametrize("soft_labels", [False, True])
    def test_the_precompile_span_says_whether_its_executable_was_used(
            self, span_tele, soft_labels):
        """The loader template assumes ``(N,)`` integer labels: a dataset
        with labels of another rank never matches it, its precompile is
        thrown away and the first step traces again, lazily."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=64, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        if soft_labels:
            class Soft:
                def __len__(self):
                    return len(ds)

                def __getitem__(self, i):
                    img, y = ds[i]
                    return img, np.eye(4, dtype=np.float32)[int(y)]

            data = Soft()
        else:
            data = ds
        tr = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(data, batch_size=16, seed=3),
            max_duration="3ba", eval_interval=0, log_interval=0,
        )
        tr.fit()
        log = span_tele.span_log()
        (pre,) = [r for r in log if r.name == "compile/precompile_step"]
        assert pre.attrs["kind"] == "train" and pre.attrs["signature"]
        assert pre.attrs["used"] is (not soft_labels)
        assert pre.thread == "tpuframe-precompile" and pre.parent_id is None
        # ... parent of the AOT spans that were there, themselves parents
        # of jax's own phases
        kids = {r.name: r for r in log if r.parent_id == pre.id}
        assert set(kids) == {"compile/lower", "compile/backend_compile"}
        assert [r for r in log if r.name == "compile/jax_lower"
                and r.parent_id == kids["compile/lower"].id]
        steps = sorted((r for r in log if r.name == "train/step"),
                       key=lambda r: r.step)
        assert steps[0].attrs["aot"] is (not soft_labels)
        assert all("aot" not in r.attrs for r in steps[1:])
        lazy = [r for r in log if r.name == "compile/jax_trace"
                and r.parent_id == steps[0].id]
        assert bool(lazy) is soft_labels
        # the set-up's own phases: the initialiser, and fit() entry to the
        # loop's first iteration
        (init,) = [r for r in log if r.name == "setup/state_init"]
        assert [r for r in log if r.name == "compile/jax_backend"
                and r.parent_id == init.id and "init_fn" in r.attrs["fun"]]
        (start,) = [r for r in log if r.name == "setup/fit_start"]
        first_iter = min((r for r in log if r.name == "train/iter"),
                         key=lambda r: r.start_ns)
        assert init.end_ns <= start.end_ns <= first_iter.start_ns
        assert start.start_ns <= init.start_ns  # init_state() ran inside fit()

    def test_past_the_second_window_no_step_adds_a_setup_record(self, span_tele):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=16 * 18, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        tr = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, seed=3),
            max_duration="18ba", eval_interval=0, log_interval=4,
        )
        tr.fit()
        log = span_tele.span_log()
        drains = sorted((r for r in log if r.name == "train/host_block"),
                        key=lambda r: r.end_ns)
        assert len(drains) >= 4 and tr.batches_seen == 18
        late = [r for r in log if r.name.startswith(("compile/", "setup/"))
                and r.end_ns > drains[1].end_ns]
        assert late == [], [(r.name, r.attrs.get("fun")) for r in late]
        assert [r for r in log if r.name.startswith("compile/jax_")]

    def test_opt_out_env(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PRECOMPILE", "0")
        tr, _ = self._fit(None)
        assert tr._precompile_report is None
        assert not tr._compiled


# -- warm-cache restart: the zero-recompile acceptance ------------------------


class TestWarmRestart:
    def test_in_process_restart_resumes_with_zero_backend_compiles(
        self, cache_env, tmp_path
    ):
        """Chaos kill -> supervised in-process restart: attempt 1 wrote
        every program to the persistent cache, so from attempt 2's
        fit-start (post-restore) to completion there are ZERO real
        backend compiles — every request is a retrieval."""
        from tpuframe.ckpt import Checkpointer
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.fault import ChaosPlan, RestartPolicy, Supervisor
        from tpuframe.models import MnistNet
        from tpuframe.train import Callback, Trainer

        ds = SyntheticImageDataset(n=64, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        ckpt_dir = str(tmp_path / "ck")
        snaps: list[dict] = []

        class Snap(Callback):
            def on_fit_start(self, trainer) -> None:
                snaps.append(_counters())

        def attempt():
            ck = Checkpointer(ckpt_dir)
            try:
                tr = Trainer(
                    MnistNet(num_classes=4),
                    train_dataloader=DataLoader(ds, batch_size=16,
                                                shuffle=True, seed=3),
                    max_duration="2ep", eval_interval=0, log_interval=0,
                    checkpointer=ck, checkpoint_interval_batches=2,
                    callbacks=[Snap()],
                )
                res = tr.fit()
                return tr, res
            finally:
                ck.close()

        plan = ChaosPlan.scheduled(3, sites=("loader",), min_step=5,
                                   max_step=7)
        sup = Supervisor(RestartPolicy(max_restarts=1, backoff_base_s=0.0),
                         checkpoint_dir=ckpt_dir)
        with plan.active():
            tr, res = sup.run(attempt)
        assert res.error is None and sup.retries == 1
        assert int(jax.device_get(tr.state.step)) == 8
        # attempt 1 compiled for real (cold cache)…
        end = _counters()
        assert end["cache_misses"] - snaps[0]["cache_misses"] >= 1
        # …attempt 2 (snaps[1] onward) retrieved everything: zero real
        # backend compiles, zero misses — the recompile-free restart
        assert len(snaps) == 2
        d = _delta(snaps[1], end)
        assert d["backend_compiles"] == 0
        assert d["cache_misses"] == 0
        assert end["cache_hits"] - snaps[1]["cache_hits"] >= 1


# -- analyzer: compile annotation + time_to_first_step gate -------------------


def _mklog(tmp_path, records, rank=0):
    d = tmp_path / "tele"
    d.mkdir(exist_ok=True)
    base = {"v": 1, "rank": rank, "pid": 100, "thread": "MainThread"}
    meta = {**base, "kind": "meta", "name": "telemetry/meta",
            "anchor_wall": 0.0, "anchor_mono": 0.0,
            "hostname": "h", "schema": 1}
    with open(d / f"events-rank{rank}.jsonl", "w") as f:
        f.write(json.dumps(meta) + "\n")
        for r in records:
            f.write(json.dumps({**base, **r}) + "\n")
    return str(d)


class TestAnalyzerCompile:
    def _dir(self, tmp_path):
        step = lambda b, t: {  # noqa: E731
            "ts": t, "mono": t, "kind": "span", "name": "train/step",
            "dur_s": 0.1, "ok": True,
            "attrs": {"batch": b, "data_wait_s": 0.004},
        }
        return _mklog(tmp_path, [
            {"ts": 100.0, "mono": 100.0, "kind": "event",
             "name": "fit/start"},
            {"ts": 101.2, "mono": 101.2, "kind": "span",
             "name": "compile/lower", "dur_s": 0.2, "ok": True},
            {"ts": 102.0, "mono": 102.0, "kind": "span",
             "name": "compile/backend_compile", "dur_s": 0.8, "ok": True},
            {"ts": 102.5, "mono": 102.5, "kind": "event",
             "name": "compile/backend_compile", "dur_s": 0.3,
             "label": "train"},
            step(0, 103.0), step(1, 103.2), step(2, 103.4),
        ])

    def test_report_carries_compile_wall_and_ttfs(self, tmp_path):
        from tpuframe.track import analyze as A

        rep = A.skew_report(A.load_dir(self._dir(tmp_path)))
        assert rep["compile"]["records"] == 3
        assert rep["compile"]["wall_s"] == pytest.approx(1.3)
        # first record at t=100, first step ends 103.0
        assert rep["time_to_first_step"]["s"] == pytest.approx(3.0)
        text = A.format_report(rep)
        assert "measured compile wall 1.300s" in text
        assert "time to first step: 3.000s" in text

    def test_jax_phase_records_are_the_wall_by_phase_and_by_fun(self, tmp_path):
        """Where a log holds jax's own phases they are the compile wall
        (the AOT spans and the loud event lie inside them): ``self_s``
        summed, a nested trace counted once, hits apart from compiles."""
        from tpuframe.track import analyze as A

        def phase(name, t, dur, fun, **attrs):
            return {"ts": t, "mono": t, "kind": "span", "ok": True,
                    "name": f"compile/jax_{name}", "dur_s": dur,
                    "attrs": {"fun": fun, "self_s": dur, **attrs}}

        d = _mklog(tmp_path, [
            {"ts": 100.0, "mono": 100.0, "kind": "event", "name": "fit/start"},
            phase("trace", 100.4, 0.3, "layer_norm"),
            phase("trace", 101.0, 1.0, "step", self_s=0.7),
            {"ts": 101.5, "mono": 101.5, "kind": "span", "ok": True,
             "name": "compile/lower", "dur_s": 1.5},
            phase("lower", 101.5, 0.5, "jit(step)"),
            phase("backend", 102.5, 1.0, "jit(step)", cache="hit",
                  retrieval_s=0.8),
            phase("backend", 102.9, 0.4, "jit(add)", cache="miss"),
            {"ts": 102.9, "mono": 102.9, "kind": "event",
             "name": "compile/backend_compile", "dur_s": 0.4},
            {"ts": 103.0, "mono": 103.0, "kind": "span", "name": "train/step",
             "dur_s": 0.1, "ok": True, "attrs": {"batch": 0}},
        ])
        rep = A.skew_report(A.load_dir(d))
        comp = rep["compile"]
        assert comp["records"] == 5
        assert comp["wall_s"] == pytest.approx(0.3 + 0.7 + 0.5 + 1.0 + 0.4)
        assert comp["by_phase"]["trace"] == {"s": 1.0, "records": 2}
        assert comp["by_phase"]["backend"] == {
            "s": 1.4, "records": 2, "hit": 1, "miss": 1, "retrieval_s": 0.8}
        assert [(r["fun"], r["s"]) for r in comp["by_fun"][:2]] == [
            ("jit(step)", 1.5), ("step", 0.7)]
        text = A.format_report(rep)
        assert "compile backend: 1.400s in 2 record(s): 1 cache hit(s), " \
               "retrieval 0.800s; 1 compiled" in text
        assert "compile by fun: jit(step) 1.500s (2), step 0.700s (1)" in text

    def test_ttfs_baseline_regression_gates_exit_3(self, tmp_path, capsys):
        from tpuframe.track import analyze as A

        d = self._dir(tmp_path)
        (tmp_path / "ttfs_old.json").write_text(json.dumps({
            "backend": "cpu",
            "time_to_first_step": {"s": 0.5},  # 6x faster than this run
        }))
        diff = A.baseline_diff(A.skew_report(A.load_dir(d)),
                               str(tmp_path / "ttfs_old.json"))
        assert diff["regressions"] and \
            diff["baselines"][0]["ratio_ttfs"] > 5
        rc = A.main([d, "--baseline",
                     str(tmp_path / "ttfs_old.json"), "--report"])
        assert rc == 3
        assert "REGRESSION" in capsys.readouterr().out

    def test_ttfs_baseline_ok_when_slower_baseline(self, tmp_path):
        from tpuframe.track import analyze as A

        d = self._dir(tmp_path)
        (tmp_path / "old.json").write_text(json.dumps({
            "time_to_first_step": {"s": 30.0},
        }))
        diff = A.baseline_diff(A.skew_report(A.load_dir(d)),
                               str(tmp_path / "old.json"))
        assert diff["baselines"] and not diff["regressions"]


# -- doctor + launch integration ----------------------------------------------


class TestIntegration:
    def test_doctor_compile_section(self, cache_env, monkeypatch):
        from tpuframe.doctor import compile_section

        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE_KEEP", "7")
        sec = compile_section()
        assert sec["dir"] == cache_env
        assert sec["enabled_in_process"] is True
        assert sec["keep"] == 7
        assert sec["env"]["TPUFRAME_COMPILE_CACHE"] == cache_env
        assert "entries" in sec and "total_mb" in sec

    def test_remote_ships_compile_env(self, monkeypatch):
        from tpuframe.launch.remote import RemoteDistributor

        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "/fleet/cache")
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE_MAX_MB", "256")
        rd = RemoteDistributor(["h0", "h1"])
        env = rd._worker_env(1, "h0", 1234, 1235, "tok", None)
        assert env["TPUFRAME_COMPILE_CACHE"] == "/fleet/cache"
        assert env["TPUFRAME_COMPILE_CACHE_MAX_MB"] == "256"
        # explicit env= still wins over the inherited knob
        rd2 = RemoteDistributor(["h0"],
                                env={"TPUFRAME_COMPILE_CACHE": "/custom"})
        env2 = rd2._worker_env(0, "h0", 1234, 1235, "tok", None)
        assert env2["TPUFRAME_COMPILE_CACHE"] == "/custom"
