"""Self-tuning loop (ISSUE 12): diagnosis decision table, probe guard,
winning-config persistence, serve-side derivation, trainer/serve apply
surfaces, the doctor/CLI views — and THE acceptance story: a
deliberately mis-configured CPU run converges under autotune to the
hand-tuned step wall, with zero backend compiles during the
signature-unchanged probes and the winning config re-loaded by a fresh
(supervised-restart) Trainer.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from tpuframe.autotune import probe as P
from tpuframe.autotune.config import (
    AUTOTUNE_ENV_VARS,
    TunedConfig,
    all_env_domains,
    autotune_dir,
    autotune_enabled,
    clamp,
    config_key,
    list_tuned,
    load_tuned,
    save_tuned,
)
from tpuframe.autotune.diagnosis import KnobMove, diagnose
from tpuframe.autotune.tuner import derive_serve_knobs, tune_training
from tpuframe.track import telemetry as T


@pytest.fixture(autouse=True)
def fresh_telemetry():
    T.reset()
    yield
    T.reset()


@pytest.fixture()
def store(tmp_path, monkeypatch):
    d = str(tmp_path / "autotune_store")
    monkeypatch.setenv("TPUFRAME_AUTOTUNE_DIR", d)
    return d


@pytest.fixture()
def knob_env():
    """Snapshot/restore every registered knob around a test — apply
    surfaces write ``os.environ`` directly, which monkeypatch can't see."""
    keys = tuple(all_env_domains())
    saved = {k: os.environ.get(k) for k in keys}
    yield
    for k, old in saved.items():
        if old is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = old


@pytest.fixture()
def cpu_runtime():
    from tpuframe.core import MeshSpec
    from tpuframe.core import runtime as rt

    rt.reset_runtime()
    rt.initialize(MeshSpec(data=-1))
    yield
    rt.reset_runtime()


# -- config: switch, store, clamp ---------------------------------------------


class TestConfigStore:
    def test_enabled_truthiness(self, monkeypatch):
        for v, want in (("1", True), ("true", True), ("on", True),
                        ("0", False), ("false", False), ("off", False),
                        ("", False)):
            monkeypatch.setenv("TPUFRAME_AUTOTUNE", v)
            assert autotune_enabled() is want, v
        monkeypatch.delenv("TPUFRAME_AUTOTUNE")
        assert autotune_enabled() is False

    def test_dir_resolution(self, monkeypatch, tmp_path):
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_DIR", str(tmp_path / "x"))
        assert autotune_dir() == str(tmp_path / "x")
        monkeypatch.delenv("TPUFRAME_AUTOTUNE_DIR")
        monkeypatch.setenv("TPUFRAME_LOCAL_SCRATCH", str(tmp_path / "scr"))
        assert autotune_dir() == str(tmp_path / "scr" / "autotune")

    def test_roundtrip(self, store):
        cfg = TunedConfig(host="h", topology="2x8", signature="sig",
                          env={"TPUFRAME_LOADER_WORKERS": "4"},
                          baseline_p50_s=0.2, tuned_p50_s=0.1)
        path = save_tuned(cfg)
        assert os.path.isfile(path)
        assert os.path.basename(path) == config_key("h", "2x8", "sig") + ".json"
        back = load_tuned("h", "2x8", "sig")
        assert back is not None and back.env == cfg.env
        assert back.convergence_ratio == pytest.approx(0.5)
        assert back.created_unix > 0  # stamped at save

    def test_identity_mismatch_reads_as_no_config(self, store):
        save_tuned(TunedConfig(host="h", topology="2x8", signature="sig",
                               env={}))
        assert load_tuned("h", "2x8", "other") is None
        assert load_tuned("other", "2x8", "sig") is None

    def test_corrupt_file_reads_as_no_config(self, store):
        path = save_tuned(TunedConfig(host="h", topology="1", signature="s",
                                      env={}))
        with open(path, "w") as f:
            f.write('{"half a rec')
        assert load_tuned("h", "1", "s") is None
        assert list_tuned() == []  # tolerant listing too

    def test_list_tuned(self, store):
        for sig in ("a", "b"):
            save_tuned(TunedConfig(host="h", topology="1", signature=sig,
                                   env={}))
        assert sorted(c.signature for c in list_tuned()) == ["a", "b"]

    def test_unwritable_store_degrades_silently(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_DIR",
                           "/proc/definitely/not/writable")
        save_tuned(TunedConfig(host="h", topology="1", signature="s", env={}))


class TestClamp:
    def test_int_clamps_into_range(self):
        assert clamp("TPUFRAME_LOADER_WORKERS", 999) == "64"
        assert clamp("TPUFRAME_LOADER_WORKERS", -3) == "0"
        assert clamp("TPUFRAME_LOADER_WORKERS", 4) == "4"

    def test_open_ended_range(self):
        # CKPT_INTERVAL_BATCHES has no upper bound
        assert clamp("TPUFRAME_CKPT_INTERVAL_BATCHES", 10**9) == str(10**9)
        assert clamp("TPUFRAME_CKPT_INTERVAL_BATCHES", 0) == "1"

    def test_enum_rejects_illegal_value(self):
        assert clamp("TPUFRAME_LOADER_TRANSFER_DTYPE", "uint8") == "uint8"
        assert clamp("TPUFRAME_LOADER_TRANSFER_DTYPE", "bfloat16") is None

    def test_bool_encodes_env_style(self):
        assert clamp("TPUFRAME_PRECOMPILE", True) == "1"
        assert clamp("TPUFRAME_PRECOMPILE", "off") == "0"

    def test_unknown_knob_is_never_clamped_in(self):
        assert clamp("TPUFRAME_NOT_A_KNOB", 1) is None

    def test_registry_covers_every_spine(self):
        domains = all_env_domains()
        for probe_knob in ("TPUFRAME_TELEMETRY_DIR", "TPUFRAME_COMPILE_CACHE",
                           "TPUFRAME_HEALTH_WINDOW", "TPUFRAME_SERVE_SLO_MS",
                           "TPUFRAME_LOADER_WORKERS",
                           "TPUFRAME_COMMS_COMPRESSION", "TPUFRAME_AUTOTUNE"):
            assert probe_knob in domains, probe_knob
        for knob, d in domains.items():
            assert d.get("apply") in ("live", "restart"), knob


# -- diagnosis decision table -------------------------------------------------


def _report(*, lost=None, step_mean=0.1, step_count=100, per_rank=None,
            per_step=None, comms=None, compile_s=0.0, ttfs=None, ranks=2):
    rep = {
        "ranks": ranks,
        "steps": step_count,
        "step_time": {"mean": step_mean, "count": step_count,
                      "p50": step_mean, "p95": step_mean, "p99": step_mean},
        "lost_by_bound": lost or {"input": 0.0, "compute": 0.0,
                                  "checkpoint": 0.0},
        "per_rank": per_rank or [],
        "per_step": per_step or [],
        "compile": {"wall_s": compile_s, "records": 1 if compile_s else 0},
    }
    if comms is not None:
        rep["comms"] = comms
    if ttfs is not None:
        rep["time_to_first_step"] = {"s": ttfs}
    return rep


class TestDiagnosis:
    def test_input_bound_orders_loader_moves(self):
        diag = diagnose(_report(lost={"input": 5.0, "compute": 0.1,
                                      "checkpoint": 0.0}))
        assert diag.bound == "input"
        knobs = [m.knob for m in diag.moves]
        assert knobs[0] == "TPUFRAME_LOADER_WORKERS"
        assert "TPUFRAME_LOADER_TRANSFER_DTYPE" in knobs
        assert "TPUFRAME_PREFETCH_DEPTH" in knobs

    def test_checkpoint_bound_stretches_cadence(self):
        diag = diagnose(_report(lost={"input": 0.0, "compute": 0.0,
                                      "checkpoint": 3.0}))
        assert diag.bound == "checkpoint"
        (mv,) = [m for m in diag.moves
                 if m.knob == "TPUFRAME_CKPT_INTERVAL_BATCHES"]
        assert mv.value == "200" and "checkpoint" in mv.reason

    def test_comms_bound_reads_the_percentile_block(self):
        # allreduce_s is the report's percentile dict, not a float —
        # p50 x count must clear the significance bar
        comms = {"mode": None, "allreduce_s": {"count": 100, "p50": 0.02,
                                               "p95": 0.03, "p99": 0.04}}
        diag = diagnose(_report(comms=comms))
        assert diag.bound == "comms"
        knobs = [m.knob for m in diag.moves]
        assert knobs[0] == "TPUFRAME_COMMS_COMPRESSION"
        assert "TPUFRAME_COMMS_BUCKET_MB" in knobs

    def test_comms_already_compressed_skips_the_mode_move(self):
        comms = {"mode": "int8", "allreduce_s": {"count": 100, "p50": 0.02}}
        diag = diagnose(_report(comms=comms))
        assert diag.bound == "comms"
        assert "TPUFRAME_COMMS_COMPRESSION" not in [m.knob for m in diag.moves]

    def test_single_rank_input_bound_via_data_wait(self):
        # 1 rank: lost_by_bound is zero by construction; the per-rank
        # data-wait fraction is the signal
        rep = _report(ranks=1, per_rank=[
            {"rank": 0, "data_wait_total_s": 5.0}])
        diag = diagnose(rep)
        assert diag.bound == "input"
        assert diag.detail["data_wait_fraction"] >= 0.10

    def test_healthy_run_proposes_nothing(self):
        rep = _report(per_step=[{"bound": "compute"}] * 10)
        diag = diagnose(rep)
        assert diag.bound == "compute" and diag.moves == []

    def test_empty_report_is_none_bound(self):
        diag = diagnose({})
        assert diag.bound == "none" and diag.moves == []

    def test_compile_rider_joins_any_bound(self):
        rep = _report(lost={"input": 5.0, "compute": 0.0, "checkpoint": 0.0},
                      compile_s=8.0, ttfs=10.0)
        diag = diagnose(rep)
        assert diag.moves[-1].knob == "TPUFRAME_PRECOMPILE"
        assert diag.moves[-1].value == "1"

    def test_ring_gauge_escalates_buffer_move(self):
        rep = _report(lost={"input": 5.0, "compute": 0.0, "checkpoint": 0.0})
        diag = diagnose(rep, gauges={"data/ring_allocs": 3})
        rings = [m.value for m in diag.moves
                 if m.knob == "TPUFRAME_LOADER_RING_BUFFERS"]
        assert rings == ["8", "16"]

    def test_memory_bound_trumps_every_speed_signal(self):
        # an OOM alongside a huge input-lost share: a plan that doesn't
        # fit can't be tuned faster — memory wins
        rep = _report(lost={"input": 50.0, "compute": 0.0, "checkpoint": 0.0})
        rep["memory"] = {
            "ooms": 1, "hbm_peak_util": 0.5,
            "last_oom": {"where": "step", "step": 7,
                         "suggestion": {"zero_stage": 3, "microbatches": 4,
                                        "fits": True}},
        }
        diag = diagnose(rep)
        assert diag.bound == "memory"
        moves = {m.knob: m.value for m in diag.moves}
        # the oom event's suggest_fit rung seeds the values
        assert moves["TPUFRAME_ZERO_STAGE"] == "3"
        assert moves["TPUFRAME_GRAD_ACCUM"] == "4"
        assert "TPUFRAME_OFFLOAD_OPTIMIZER" not in moves  # rung didn't ask

    def test_watermark_pressure_is_memory_bound_without_an_oom(self):
        rep = _report()
        rep["memory"] = {"ooms": 0, "hbm_peak_util": 0.95, "last_oom": None}
        diag = diagnose(rep)
        assert diag.bound == "memory"
        moves = {m.knob: m.value for m in diag.moves}
        # no suggestion to seed from: the escalation-ladder defaults
        assert moves["TPUFRAME_ZERO_STAGE"] == "3"
        assert moves["TPUFRAME_OFFLOAD_OPTIMIZER"] == "1"

    def test_healthy_watermark_is_not_memory_bound(self):
        rep = _report(lost={"input": 5.0, "compute": 0.0, "checkpoint": 0.0})
        rep["memory"] = {"ooms": 0, "hbm_peak_util": 0.6, "last_oom": None}
        assert diagnose(rep).bound == "input"

    def test_every_move_is_domain_legal(self):
        domains = all_env_domains()
        mem_rep = _report()
        mem_rep["memory"] = {"ooms": 1, "hbm_peak_util": 0.99,
                             "last_oom": None}
        for rep in (
            _report(lost={"input": 5.0, "compute": 0.0, "checkpoint": 0.0}),
            _report(lost={"input": 0.0, "compute": 0.0, "checkpoint": 5.0}),
            _report(comms={"mode": None,
                           "allreduce_s": {"count": 100, "p50": 0.02}}),
            mem_rep,
        ):
            for mv in diagnose(rep).moves:
                assert clamp(mv.knob, mv.value, domains) == mv.value


# -- the probe harness --------------------------------------------------------


class TestProbe:
    def test_faster_candidate_commits(self):
        res = P.run_probe(lambda env: [0.05] * 6, {"K": "1"}, 0.10)
        assert res.committed and res.p50_s == pytest.approx(0.05)
        assert res.ratio == pytest.approx(0.5)

    def test_guard_never_commits_slower(self):
        res = P.run_probe(lambda env: [0.20] * 6, {"K": "1"}, 0.10)
        assert not res.committed and "rolled back" in res.reason

    def test_guard_margin_blocks_a_wash(self):
        # 0.099 vs 0.10 baseline is inside the 0.97 guard margin: a wash,
        # not a win — don't churn config for noise
        res = P.run_probe(lambda env: [0.099] * 6, {"K": "1"}, 0.10)
        assert not res.committed

    def test_guard_env_is_capped_at_never_slower(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_GUARD", "1.5")
        assert P.guard_ratio() == 1.0
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_GUARD", "banana")
        assert P.guard_ratio() == pytest.approx(0.97)

    def test_warmup_prefix_is_discarded(self):
        walls = [10.0, 10.0, 0.1, 0.1, 0.1, 0.1]
        assert P.measure(lambda env: walls, {}) == pytest.approx(0.1)

    def test_env_overlaid_and_restored(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_LOADER_WORKERS", "1")
        seen = {}

        def run_fn(env):
            seen["live"] = os.environ["TPUFRAME_LOADER_WORKERS"]
            return [0.1] * 4

        P.measure(run_fn, {"TPUFRAME_LOADER_WORKERS": "8"})
        assert seen["live"] == "8"
        assert os.environ["TPUFRAME_LOADER_WORKERS"] == "1"

    def test_crashing_candidate_is_contained_and_restored(self):
        def run_fn(env):
            raise RuntimeError("loader exploded")

        before = os.environ.get("TPUFRAME_LOADER_WORKERS")
        res = P.run_probe(run_fn, {"TPUFRAME_LOADER_WORKERS": "8"}, 0.1)
        assert not res.committed and res.p50_s == float("inf")
        assert "loader exploded" in res.reason
        assert os.environ.get("TPUFRAME_LOADER_WORKERS") == before


# -- the greedy tuning loop ---------------------------------------------------


def _scripted_run_fn(table):
    """run_fn whose step wall is looked up from the committed env — a
    deterministic model of knob effects (no wall clocks in tier-1)."""

    def run_fn(env):
        wall = 0.10
        for knob, value in env.items():
            wall = table.get((knob, value), wall)
        return [wall] * 6

    return run_fn


class TestTuner:
    def test_greedy_loop_composes_winners_and_persists(self, store):
        run_fn = _scripted_run_fn({
            ("TPUFRAME_LOADER_WORKERS", "2"): 0.05,
            ("TPUFRAME_LOADER_WORKERS", "4"): 0.04,
            ("TPUFRAME_PREFETCH_DEPTH", "4"): 0.20,  # a regression
        })
        moves = [
            KnobMove("TPUFRAME_LOADER_WORKERS", "2", "probe 2 workers"),
            KnobMove("TPUFRAME_LOADER_WORKERS", "4", "probe 4 workers"),
            KnobMove("TPUFRAME_PREFETCH_DEPTH", "4", "probe deeper prefetch"),
        ]
        cfg = tune_training(run_fn, moves=moves, topology="1", signature="s")
        # winners composed; the regression was rolled back by the guard
        assert cfg.env == {"TPUFRAME_LOADER_WORKERS": "4"}
        assert cfg.tuned_p50_s == pytest.approx(0.04)
        assert cfg.convergence_ratio == pytest.approx(0.4)
        assert [p["committed"] for p in cfg.probes] == [True, True, False]
        assert all(p["knob"] and p["reason_for_move"] for p in cfg.probes)
        # persisted under the identity, reloadable
        back = load_tuned(cfg.host, "1", "s")
        assert back is not None and back.env == cfg.env

    def test_rounds_env_bounds_the_probe_budget(self, store, monkeypatch):
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_ROUNDS", "1")
        calls = []

        def run_fn(env):
            calls.append(dict(env))
            return [0.1] * 4

        moves = [KnobMove("TPUFRAME_LOADER_WORKERS", str(v), "r")
                 for v in (2, 4, 8)]
        cfg = tune_training(run_fn, moves=moves, save=False)
        # baseline + exactly one probe
        assert len(calls) == 2 and len(cfg.probes) == 1

    def test_telemetry_trail(self, store):
        tele = T.configure()
        run_fn = _scripted_run_fn({("TPUFRAME_LOADER_WORKERS", "2"): 0.05})
        tune_training(run_fn,
                      moves=[KnobMove("TPUFRAME_LOADER_WORKERS", "2", "r")],
                      topology="1", signature="s")
        names = [e["name"] for e in tele.recent_events(50)
                 if e["name"].startswith("autotune/")]
        assert names == ["autotune/start", "autotune/probe", "autotune/tuned"]
        tuned = [e for e in tele.recent_events(50)
                 if e["name"] == "autotune/tuned"][0]
        assert tuned["convergence_ratio"] == pytest.approx(0.5)

    def test_diagnosis_path_probes_the_report_bound(self, store):
        # input-bound report -> loader moves probed without a moves= list
        rep = _report(lost={"input": 5.0, "compute": 0.0, "checkpoint": 0.0})
        run_fn = _scripted_run_fn({
            ("TPUFRAME_LOADER_WORKERS", "2"): 0.05,
            ("TPUFRAME_LOADER_WORKERS", "4"): 0.03,
        })
        cfg = tune_training(run_fn, rep, topology="1", signature="d")
        assert cfg.env["TPUFRAME_LOADER_WORKERS"] == "4"


class TestDeriveServeKnobs:
    def test_buckets_follow_the_size_distribution(self):
        sizes = [1] * 50 + [3] * 40 + [13] * 9 + [30]
        out = derive_serve_knobs(sizes, slo_ms=200.0)
        assert out["TPUFRAME_SERVE_BUCKETS"] == "4,16,32"
        assert float(out["TPUFRAME_SERVE_BATCH_WAIT_MS"]) == pytest.approx(
            10.0)

    def test_wait_clamped_to_budget(self):
        assert float(derive_serve_knobs([1], slo_ms=2.0)
                     ["TPUFRAME_SERVE_BATCH_WAIT_MS"]) == 0.5
        assert float(derive_serve_knobs([1], slo_ms=10_000.0)
                     ["TPUFRAME_SERVE_BATCH_WAIT_MS"]) == 20.0

    def test_empty_observation_keeps_only_the_wait(self):
        out = derive_serve_knobs([], slo_ms=100.0)
        assert "TPUFRAME_SERVE_BUCKETS" not in out

    def test_max_bucket_caps_the_ladder(self):
        out = derive_serve_knobs([100] * 10, slo_ms=100.0, max_bucket=64)
        assert out["TPUFRAME_SERVE_BUCKETS"] == "64"

    def test_derived_knobs_are_engine_appliable(self):
        """The serve half of the loop: derived knobs flow through
        ServeEngine.apply_knobs with the live/restart split intact."""
        from tpuframe.serve.admission import ServeKnobs
        from tpuframe.serve.engine import ServeEngine

        eng = ServeEngine(lambda x: x * 2, knobs=ServeKnobs(buckets=(2, 4)),
                          item_shape=(3,), dtype=np.float32)
        out = eng.apply_knobs(derive_serve_knobs([1, 2, 7], slo_ms=100.0))
        assert "TPUFRAME_SERVE_BATCH_WAIT_MS" in out["applied"]
        assert "TPUFRAME_SERVE_BUCKETS" in out["restart_only"]
        assert eng.knobs.batch_wait_ms == pytest.approx(5.0)
        # restart-only knob did NOT touch the live bucket set
        assert eng.knobs.buckets == (2, 4)


# -- apply surfaces -----------------------------------------------------------


class TestTrainerApply:
    def _trainer(self, **kw):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        return Trainer(MnistNet(num_classes=4),
                       train_dataloader=DataLoader(ds, batch_size=16),
                       max_duration="1ba", eval_interval=0, log_interval=0,
                       **kw)

    def test_apply_tuned_splits_live_vs_restart(self, cpu_runtime, knob_env):
        tr = self._trainer()
        out = tr.apply_tuned({
            "TPUFRAME_CKPT_INTERVAL_BATCHES": "123",   # live on the loop
            "TPUFRAME_LOADER_WORKERS": "4",            # restart-only
            "TPUFRAME_NOT_A_KNOB": "1",                # not in the registry
        })
        assert out["applied"] == {"TPUFRAME_CKPT_INTERVAL_BATCHES": "123"}
        assert out["restart_only"] == {"TPUFRAME_LOADER_WORKERS": "4"}
        assert tr.checkpoint_interval_batches == 123
        # env written for later constructions; the illegal knob never was
        assert os.environ["TPUFRAME_LOADER_WORKERS"] == "4"
        assert "TPUFRAME_NOT_A_KNOB" not in os.environ

    def test_no_persisted_config_is_a_noop(self, cpu_runtime, store):
        tr = self._trainer()
        assert tr.apply_persisted_tuning() == {}

    def test_fit_applies_persisted_config_when_enabled(
        self, cpu_runtime, store, knob_env, monkeypatch
    ):
        tr = self._trainer()
        host, topology, signature = tr._autotune_identity()
        save_tuned(TunedConfig(host=host, topology=topology,
                               signature=signature,
                               env={"TPUFRAME_CKPT_INTERVAL_BATCHES": "77"}))
        monkeypatch.setenv("TPUFRAME_AUTOTUNE", "1")
        tr.fit()
        assert tr.checkpoint_interval_batches == 77

    def test_fit_ignores_store_when_disabled(self, cpu_runtime, store,
                                             knob_env, monkeypatch):
        tr = self._trainer()
        host, topology, signature = tr._autotune_identity()
        save_tuned(TunedConfig(host=host, topology=topology,
                               signature=signature,
                               env={"TPUFRAME_CKPT_INTERVAL_BATCHES": "77"}))
        monkeypatch.delenv("TPUFRAME_AUTOTUNE", raising=False)
        tr.fit()
        assert tr.checkpoint_interval_batches is None


# -- doctor + CLI views -------------------------------------------------------


class TestViews:
    def test_doctor_section_lists_this_hosts_configs(self, store, scripts_not_in_tree):
        from tpuframe.autotune.config import default_host
        from tpuframe.doctor import autotune_section

        save_tuned(TunedConfig(host=default_host(), topology="1x8",
                               signature="sig",
                               env={"TPUFRAME_LOADER_WORKERS": "4"},
                               baseline_p50_s=0.2, tuned_p50_s=0.1))
        save_tuned(TunedConfig(host="elsewhere", topology="1x8",
                               signature="sig", env={}))
        sec = autotune_section({"device_count": 8, "process_count": 1})
        assert sec["store"] == autotune_dir()
        assert "python -m tpuframe.autotune" in sec["show"]
        assert scripts_not_in_tree(sec) == []
        (row,) = sec["configs"]  # the other host's config filtered out
        assert row["matches_probed_topology"] is True
        assert row["convergence_ratio"] == pytest.approx(0.5)

    def test_cli_lookup_and_listing(self, store, capsys):
        from tpuframe.autotune.__main__ import main

        save_tuned(TunedConfig(host="h", topology="2x8", signature="sig",
                               env={"TPUFRAME_GRAD_ACCUM": "2"}))
        assert main(["--host", "h", "--topology", "2x8",
                     "--signature", "sig"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["env"] == {"TPUFRAME_GRAD_ACCUM": "2"}
        assert main(["--host", "h", "--topology", "2x8",
                     "--signature", "nope"]) == 1
        capsys.readouterr()
        assert main(["--json"]) == 0
        listing = json.loads(capsys.readouterr().out)
        assert len(listing["configs"]) == 1

    def test_knobs_ship_to_workers(self):
        from tpuframe.launch.remote import all_env_vars

        shipped = all_env_vars()
        for k in AUTOTUNE_ENV_VARS:
            assert k in shipped, k


# -- THE acceptance story -----------------------------------------------------


class _SlowDecode:
    """Dataset whose per-sample fetch carries a decode-sized sleep — the
    real mechanism the loader-worker knob exists for (sleep releases the
    GIL, so worker threads genuinely overlap it)."""

    def __init__(self, n=256, decode_s=0.004):
        from tpuframe.data import SyntheticImageDataset

        self._ds = SyntheticImageDataset(n=n, image_size=28, channels=1,
                                         num_classes=4, seed=0)
        self.decode_s = decode_s

    def __len__(self):
        return len(self._ds)

    def __getitem__(self, i):
        time.sleep(self.decode_s)
        return self._ds[i]


class TestAcceptanceStory:
    """A deliberately mis-configured run (synchronous loader against a
    decode-bound dataset) converges under the autotune loop to within
    10% of the hand-tuned step wall; the signature-unchanged probes
    trigger zero real backend compiles (persistent compile cache); the
    winning config persists and a fresh Trainer — the supervised
    restart — re-loads it."""

    @pytest.fixture()
    def compile_cache(self, tmp_path, monkeypatch):
        from tpuframe.compile import cache as cc

        prev = cc.enabled_dir()
        d = str(tmp_path / "compile_cache")
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", d)
        assert cc.enable(d) == d
        yield d
        if prev is not None:
            cc.enable(prev)
        else:
            cc.disable()

    def _run_fn(self, ds):
        """The probe workload: a fresh short fit on the real loader under
        the overlaid env, returning boundary-to-boundary batch walls —
        the number that actually contains the data wait."""
        from tpuframe.data import DataLoader
        from tpuframe.models import MnistNet
        from tpuframe.train import Callback, Trainer

        def run(env):
            walls: list[float] = []

            class Walls(Callback):
                def __init__(self):
                    self.t = None

                def on_step_end(self, trainer):
                    now = time.monotonic()
                    if self.t is not None:
                        walls.append(now - self.t)
                    self.t = now

            trainer = Trainer(
                MnistNet(num_classes=4),
                train_dataloader=DataLoader(ds, batch_size=16, shuffle=False),
                max_duration="12ba", eval_interval=0, log_interval=0,
                callbacks=[Walls()],
            )
            trainer.fit()
            return walls

        return run

    def _compile_counters(self):
        snap = T.get_telemetry().registry.snapshot()
        return {k: snap.get(f"compile/{k}", 0.0)
                for k in ("backend_compiles", "cache_misses", "recompiles")}

    def test_misconfigured_run_converges(self, cpu_runtime, compile_cache,
                                         store, knob_env, tmp_path,
                                         monkeypatch):
        from tpuframe.data import DataLoader
        from tpuframe.track import analyze as A

        # the ring pre-fills during trainer construction, so the first
        # few walls are buffer-subsidized — discard them from medians
        monkeypatch.setenv("TPUFRAME_AUTOTUNE_WARMUP_STEPS", "4")
        monkeypatch.delenv("TPUFRAME_AUTOTUNE", raising=False)
        ds = _SlowDecode()
        run_fn = self._run_fn(ds)

        # 1. the mis-configured run, captured by the telemetry spine
        tele_dir = tmp_path / "tele"
        T.configure(jsonl_dir=str(tele_dir), rank=0)
        run_fn({})  # synchronous loader: every decode serializes
        T.reset()
        report = A.skew_report(A.load_dir(str(tele_dir)))
        assert report["schema_version"] == A.SKEW_REPORT_VERSION

        # 2. the analyzer's report drives the loop (report-as-API)
        from tpuframe.autotune.diagnosis import diagnose

        diag = diagnose(report)
        assert diag.bound == "input", diag.detail

        tele = T.configure()
        before = self._compile_counters()
        cfg = tune_training(run_fn, report, topology="cpu-test",
                            signature="acceptance")
        after = self._compile_counters()

        # 3. converged: tuned beats the mis-configured baseline and lands
        # within 10% of the hand-tuned wall
        assert cfg.env.get("TPUFRAME_LOADER_WORKERS") in ("2", "4")
        assert cfg.tuned_p50_s < cfg.baseline_p50_s
        hand_tuned = P.measure(run_fn, {"TPUFRAME_LOADER_WORKERS": "4"})
        assert cfg.tuned_p50_s <= hand_tuned * 1.10

        # 4. signature-unchanged probes: zero real backend compiles —
        # every probe Trainer retrieved its programs from the persistent
        # compile cache
        assert after["backend_compiles"] == before["backend_compiles"]
        assert after["cache_misses"] == before["cache_misses"]
        assert after["recompiles"] == before["recompiles"]
        # the cache listener emits a compile/backend_compile EVENT only
        # for a real compile (a hit is a retrieval and emits nothing);
        # AOT lower/trace spans are fine — they are not compiles
        assert not [e for e in tele.recent_events(10**4)
                    if e["kind"] == "event"
                    and e["name"] in ("compile/backend_compile",
                                      "compile/recompile")]

        # 5. supervised restart: a fresh Trainer re-loads the persisted
        # config and its fresh loader picks the tuned workers up from env
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        monkeypatch.setenv("TPUFRAME_AUTOTUNE", "1")
        restarted = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, shuffle=False),
            max_duration="1ba", eval_interval=0, log_interval=0,
        )
        host, topology, signature = restarted._autotune_identity()
        # the store is keyed by the *run's* identity; re-key the config
        # onto the restarted trainer's identity the way a same-program
        # restart would share it
        cfg.topology, cfg.signature = topology, signature
        cfg.host = host
        save_tuned(cfg)
        out = restarted.apply_persisted_tuning()
        assert out["restart_only"]["TPUFRAME_LOADER_WORKERS"] == cfg.env[
            "TPUFRAME_LOADER_WORKERS"]
        fresh_loader = DataLoader(ds, batch_size=16)
        assert fresh_loader.num_workers == int(
            cfg.env["TPUFRAME_LOADER_WORKERS"])
