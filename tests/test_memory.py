"""Memory plane acceptance: the plan-level estimator agrees with XLA's
``memory_analysis()`` on composed plans, live watermarks ratchet,
executable records survive a restart, and a seeded OOM produces exactly
one ``memory/oom`` event whose suggested plan the estimator confirms
fits — with zero recompiles."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from tpuframe.fault import ChaosPlan, OomAt, OomError
from tpuframe.parallel import memory as pmem
from tpuframe.parallel import plan_memory, suggest_fit
# the submodule import, not the lazy package re-export: an earlier test
# module importing tpuframe.parallel.compose rebinds the package attr
# `compose` to the module, and the re-export stops being the function
from tpuframe.parallel.compose import compose
from tpuframe.track import memory as tmem
from tpuframe.track import telemetry as T


@pytest.fixture(autouse=True)
def _clean_memory_state():
    """Watermarks / forensics context / executable registry are
    process-wide by design — tests must not leak them into each other."""
    yield
    tmem.reset_peaks()
    tmem.clear_context()
    tmem._EXECUTABLES.clear()


# -- estimator vs compiled truth ----------------------------------------------

D, H, B = 1024, 4096, 8  # state-dominated MLP: params+opt dwarf the batch

TP_RULES = ((r"w1$", P(None, "model")), (r"w2$", P("model", None)))


def _templates(ef=False):
    params = {
        "w1": jax.ShapeDtypeStruct((D, H), jnp.float32),
        "b1": jax.ShapeDtypeStruct((H,), jnp.float32),
        "w2": jax.ShapeDtypeStruct((H, D), jnp.float32),
    }
    opt = {"mu": dict(params), "nu": dict(params)}
    batch = {
        "x": jax.ShapeDtypeStruct((B, D), jnp.float32),
        "y": jax.ShapeDtypeStruct((B, D), jnp.float32),
    }
    comms = dict(params) if ef else None
    return params, opt, batch, comms


def _step(params, opt, batch):
    def loss_fn(p):
        h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: 0.99 * v + 0.01 * g * g, opt["nu"], grads)
    new_p = jax.tree.map(
        lambda p, m, v: p - 1e-3 * m / (jnp.sqrt(v) + 1e-8), params, mu, nu
    )
    return new_p, {"mu": mu, "nu": nu}, loss


def _step_ef(params, opt, batch, comms):
    new_p, new_opt, loss = _step(params, opt, batch)
    new_c = jax.tree.map(lambda c, p: c + 0.0 * p, comms, new_p)
    return new_p, new_opt, loss, new_c


def _compiled_peak_mb(plan, ef=False):
    """Donated-state train step AOT-compiled under the plan's shardings;
    peak = arguments + temps + outputs - aliased (the same approximation
    ``record_executable_memory`` persists)."""
    params, opt, batch, comms = _templates(ef)
    p_sh = plan.param_shardings(params)
    o_sh = plan.state_shardings(opt, params, with_offload=False)
    b_sh = jax.tree.map(lambda _: plan.batch_sharding(), batch)

    def sds(t, sh):
        return jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            t, sh,
        )

    if ef:
        c_sh = plan.state_shardings(comms, params, with_offload=False)
        compiled = jax.jit(_step_ef, donate_argnums=(0, 1, 3)).lower(
            sds(params, p_sh), sds(opt, o_sh), sds(batch, b_sh),
            sds(comms, c_sh),
        ).compile()
    else:
        compiled = jax.jit(_step, donate_argnums=(0, 1)).lower(
            sds(params, p_sh), sds(opt, o_sh), sds(batch, b_sh)
        ).compile()
    st = compiled.memory_analysis()
    mb = 1024 * 1024
    return (
        st.argument_size_in_bytes + st.temp_size_in_bytes
        + st.output_size_in_bytes - st.alias_size_in_bytes
    ) / mb, compiled


#: the acceptance tolerance: the estimator must land within 15% of
#: memory_analysis() peak on every composed-plan case below.
TOLERANCE = 0.15

CASES = {
    "dp_only": (dict(), False),
    "zero1": (dict(fsdp=8, dp=1, zero_stage=1), False),
    "zero3": (dict(fsdp=8, dp=1, zero_stage=3), False),
    "tp2_pp2": (dict(tp=2, pp=2, dp=2, fsdp=1, rules=TP_RULES), False),
    "zero3_compressed_ef": (dict(fsdp=8, dp=1, zero_stage=3), True),
}


class TestEstimatorAgreement:
    @pytest.fixture(autouse=True)
    def _real_compiles(self):
        """Agreement is defined against a REAL compile: a persistent-
        cache HIT deserializes the executable without aliasing info
        (alias_size_in_bytes == 0), inflating the measured peak by the
        donated bytes — and earlier test modules enable the process-wide
        cache, whose scratch dir outlives pytest runs.  Flipping the
        flag is not enough: jax memoizes its is-the-cache-used verdict
        at the first compile of the task, so reset it on both edges
        (same dance compile.cache.enable()/disable() do)."""
        from jax._src import compilation_cache as _cc

        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        _cc.reset_cache()
        yield
        jax.config.update("jax_enable_compilation_cache", prev)
        _cc.reset_cache()

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_estimate_within_tolerance_of_memory_analysis(self, case, devices):
        kw, ef = CASES[case]
        plan = compose(**kw)
        params, opt, batch, comms = _templates(ef)
        peak_mb, _ = _compiled_peak_mb(plan, ef)
        est = plan_memory(plan, params, batch,
                          opt_template=opt, comms_template=comms)
        total = est["per_device_mb"]["total"]
        assert abs(total - peak_mb) / peak_mb <= TOLERANCE, (
            f"{case}: estimator {total:.2f} MB vs compiled {peak_mb:.2f} MB"
        )

    def test_record_executable_memory_matches_hand_computed_peak(self, devices):
        plan = compose()
        peak_mb, compiled = _compiled_peak_mb(plan)
        rec = tmem.record_executable_memory(compiled, "test/agree",
                                            persist=False)
        assert rec is not None and rec["label"] == "test/agree"
        assert rec["peak_mb"] == pytest.approx(peak_mb, abs=0.01)
        ev = [e for e in T.get_telemetry().recent_events(100)
              if e.get("name") == "memory/executable"]
        assert ev and ev[-1]["label"] == "test/agree"


class TestEstimatorUnits:
    def test_fsdp_layering_cannot_drift_from_the_plan(self):
        """`_with_fsdp` reimplements `_maybe_fsdp` in plain tuples so
        hypothetical ZeRO stages can be priced; this pins it leaf-by-leaf
        against the plan's own param_spec so the two stay identical."""
        plan = compose(fsdp=4, dp=2, zero_stage=3, rules=TP_RULES)
        shapes = {
            "w1": (D, H), "b1": (H,), "w2": (H, D),
            "tiny": (8, 8),          # below min_shard_elems: stays put
            "odd": (1023, 7),        # no dim divisible by fsdp=4
            "tall": (4096, 33),      # shards dim 0
        }
        strip = lambda t: tuple(t[: len(t) - next(  # noqa: E731
            (i for i, e in enumerate(reversed(t)) if e is not None), len(t))])
        for path, shape in shapes.items():
            want = strip(tuple(plan.param_spec(path, shape)))
            got = strip(pmem._param_entries(plan, path, shape, 3))
            assert got == want, f"{path}: {got} != {want}"

    def test_zero_stage_ladder_shrinks_the_right_components(self):
        plan = compose(fsdp=8, dp=1)  # stage 0 plan; price hypotheticals
        params, opt, batch, _ = _templates()
        kw = dict(opt_template=opt)
        s0 = plan_memory(plan, params, batch, **kw)["per_device_mb"]
        s1 = plan_memory(plan, params, batch, zero_stage=1, **kw)["per_device_mb"]
        s3 = plan_memory(plan, params, batch, zero_stage=3, **kw)["per_device_mb"]
        assert s1["params"] == s0["params"]          # stage 1: params replicated
        assert s1["opt_state"] < s0["opt_state"]     # ...but opt state sharded
        assert s3["params"] < s0["params"]           # stage 3 shards params too
        assert s3["total"] < s1["total"] < s0["total"]

    def test_offload_moves_opt_state_to_host(self):
        plan = compose(fsdp=8, dp=1, zero_stage=3)
        params, opt, batch, _ = _templates()
        on = plan_memory(plan, params, batch, opt_template=opt)
        off = plan_memory(plan, params, batch, opt_template=opt,
                          offload_optimizer=True)
        assert off["per_device_mb"]["host_total"] == pytest.approx(
            on["per_device_mb"]["opt_state"], abs=0.01
        )
        assert off["per_device_mb"]["total"] == pytest.approx(
            on["per_device_mb"]["total"] - on["per_device_mb"]["opt_state"],
            abs=0.01,
        )

    def test_microbatches_divide_activations_only(self):
        plan = compose()
        params, opt, batch, _ = _templates()
        m1 = plan_memory(plan, params, batch)["per_device_mb"]
        m4 = plan_memory(plan, params, batch, microbatches=4)["per_device_mb"]
        assert m4["activations"] == pytest.approx(m1["activations"] / 4, rel=1e-6)
        assert m4["params"] == m1["params"] and m4["batch"] == m1["batch"]

    def test_plain_shape_dtype_pairs_and_dtype_table(self):
        plan = compose()
        est = plan_memory(plan, {"w": ((1024, 1024), "bfloat16")})
        # bf16 prices at 2 bytes: 1024*1024*2 = 2 MB replicated
        assert est["per_device_mb"]["params"] == pytest.approx(2.0, abs=0.01)
        assert est["plan_signature"] == plan.signature()
        assert est["schema_version"] == pmem.PLAN_MEMORY_VERSION

    def test_top_leaves_attribute_the_biggest_buffers(self):
        plan = compose()
        params, opt, batch, _ = _templates()
        est = plan_memory(plan, params, batch, opt_template=opt, top_leaves=4)
        assert len(est["top_leaves"]) == 4
        mbs = [l["mb"] for l in est["top_leaves"]]
        assert mbs == sorted(mbs, reverse=True)
        assert est["top_leaves"][0]["component"] in ("params", "opt_state")

    def test_suggest_fit_finds_the_first_fitting_rung(self):
        plan = compose(fsdp=8, dp=1)  # stage 0: the ladder has room
        params, opt, batch, _ = _templates()
        base = plan_memory(plan, params, batch, opt_template=opt)
        total = base["per_device_mb"]["total"]
        # budget sits between stage-1 and stage-0 totals: stage 1 must win
        s1 = plan_memory(plan, params, batch, opt_template=opt, zero_stage=1)
        budget = s1["per_device_mb"]["total"] / 0.9 + 1.0
        fit = suggest_fit(plan, params, batch, opt_template=opt,
                          budget_mb=budget)
        assert not fit["base_fits"] and fit["base_total_mb"] == total
        assert fit["suggestion"] is not None
        assert fit["suggestion"]["zero_stage"] == 1
        assert fit["suggestion"]["fits"]
        # the attached estimate reprices exactly to the rung's total
        assert fit["suggestion"]["estimate"]["per_device_mb"]["total"] == (
            fit["suggestion"]["total_mb"]
        )

    def test_suggest_fit_generous_budget_means_base_fits(self):
        plan = compose()
        params, opt, batch, _ = _templates()
        fit = suggest_fit(plan, params, batch, opt_template=opt,
                          budget_mb=10**6)
        assert fit["base_fits"]


# -- knobs --------------------------------------------------------------------


class TestMemoryKnobs:
    def test_vars_and_domains_in_lockstep(self):
        assert set(tmem.MEMORY_ENV_VARS) == set(tmem.MEMORY_ENV_DOMAINS)

    def test_shipped_via_all_env_vars(self):
        from tpuframe.launch.remote import all_env_vars

        assert set(tmem.MEMORY_ENV_VARS) <= set(all_env_vars())

    def test_memory_env_defaults_and_parsing(self):
        env = tmem.memory_env({})
        assert env["TPUFRAME_MEMORY_SAMPLE_S"] == 10.0
        assert env["TPUFRAME_MEMORY_TOP_LEAVES"] == 8
        assert env["TPUFRAME_MEMORY_LIVE"] is True
        assert env["TPUFRAME_MEMORY_BUDGET_MB"] == 0.0
        assert env["errors"] == {}
        env = tmem.memory_env({
            "TPUFRAME_MEMORY_SAMPLE_S": "2.5",
            "TPUFRAME_MEMORY_TOP_LEAVES": "16",
            "TPUFRAME_MEMORY_LIVE": "off",
            "TPUFRAME_MEMORY_BUDGET_MB": "1024",
        })
        assert env["TPUFRAME_MEMORY_SAMPLE_S"] == 2.5
        assert env["TPUFRAME_MEMORY_TOP_LEAVES"] == 16
        assert env["TPUFRAME_MEMORY_LIVE"] is False
        assert env["TPUFRAME_MEMORY_BUDGET_MB"] == 1024.0

    def test_memory_env_reports_malformed_values_without_raising(self):
        env = tmem.memory_env({
            "TPUFRAME_MEMORY_SAMPLE_S": "fast",
            "TPUFRAME_MEMORY_TOP_LEAVES": "9000",
        })
        assert set(env["errors"]) == {
            "TPUFRAME_MEMORY_SAMPLE_S", "TPUFRAME_MEMORY_TOP_LEAVES"
        }
        assert env["TPUFRAME_MEMORY_SAMPLE_S"] == 10.0  # default kept
        assert env["TPUFRAME_MEMORY_TOP_LEAVES"] == 8

    def test_zero_stage_and_offload_knobs_resolve_into_compose(self, monkeypatch):
        from tpuframe.parallel.comms_env import (
            COMMS_ENV_DOMAINS,
            COMMS_ENV_VARS,
            offload_optimizer_default,
            zero_stage_default,
        )

        assert "TPUFRAME_ZERO_STAGE" in COMMS_ENV_VARS
        assert "TPUFRAME_OFFLOAD_OPTIMIZER" in COMMS_ENV_VARS
        assert set(COMMS_ENV_VARS) == set(COMMS_ENV_DOMAINS)
        assert zero_stage_default({}) == 0
        assert zero_stage_default({"TPUFRAME_ZERO_STAGE": "7"}) == 3  # clamped
        assert offload_optimizer_default({}) is False
        monkeypatch.setenv("TPUFRAME_ZERO_STAGE", "3")
        monkeypatch.setenv("TPUFRAME_OFFLOAD_OPTIMIZER", "1")
        plan = compose(fsdp=2, dp=-1)
        assert plan.zero_stage == 3 and plan.offload_optimizer is True
        # explicit argument wins over the env
        assert compose(fsdp=2, dp=-1, zero_stage=1).zero_stage == 1


# -- live watermarks ----------------------------------------------------------


class TestWatermarks:
    def _stats(self, used, util=0.5):
        return {"d0_mem_used_mb": used, "d0_mem_util": util}

    def test_peaks_ratchet_and_events_are_bounded(self):
        tele = T.configure()
        tmem.reset_peaks()
        tmem.update_watermarks(self._stats(100.0), rss_mb=50.0)
        tmem.update_watermarks(self._stats(102.0), rss_mb=60.0)  # +2%: no event
        tmem.update_watermarks(self._stats(200.0), rss_mb=55.0)  # +96%: event
        peaks = tmem.peaks()
        assert peaks["hbm_peak_mb"] == 200.0
        assert peaks["host_peak_mb"] == 60.0  # host peak ratchets too
        assert peaks["hbm_limit_mb"] == pytest.approx(400.0)  # used / util
        ev = [e for e in tele.recent_events(100)
              if e.get("name") == "memory/watermark"]
        assert len(ev) == 2  # 100 (first) and 200 (>5% growth); not 102
        assert tele.registry.gauge("memory/hbm_peak_mb").value == 200.0
        assert tele.registry.gauge("memory/host_peak_mb").value == 60.0

    def test_reset_peaks(self):
        tmem.update_watermarks(self._stats(100.0), rss_mb=50.0)
        tmem.reset_peaks()
        assert tmem.peaks() == {
            "hbm_peak_mb": 0.0, "host_peak_mb": 0.0, "hbm_limit_mb": 0.0,
        }


# -- compiled-truth persistence -----------------------------------------------


class _FakeStats:
    argument_size_in_bytes = 100 * 1024 * 1024
    output_size_in_bytes = 90 * 1024 * 1024
    temp_size_in_bytes = 30 * 1024 * 1024
    alias_size_in_bytes = 90 * 1024 * 1024
    generated_code_size_in_bytes = 1024 * 1024


class _FakeCompiled:
    def memory_analysis(self):
        return _FakeStats()


class TestExecutableRecords:
    def test_record_persists_next_to_the_compile_cache(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path))
        rec = tmem.record_executable_memory(_FakeCompiled(), "train/step")
        assert rec["peak_mb"] == pytest.approx(130.0)  # 100+30+90-90
        assert rec["host_argument_mb"] == 0.0  # absent attr -> stable schema
        files = os.listdir(tmp_path / "memory")
        assert len(files) == 1 and files[0].endswith(".json")
        with open(tmp_path / "memory" / files[0]) as f:
            assert json.load(f)["label"] == "train/step"
        # a restarted process (empty in-process registry) reads it back
        tmem._EXECUTABLES.clear()
        recs = tmem.executable_records()
        assert recs["train/step"]["peak_mb"] == pytest.approx(130.0)

    def test_records_follow_a_cache_placed_from_outside(self, tmp_path,
                                                        monkeypatch):
        """JAX_COMPILATION_CACHE_DIR places the cache, so it places the
        executable records next to it — over the knob."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path / "knob"))
        tmem.record_executable_memory(_FakeCompiled(), "train/step")
        assert os.listdir(tmp_path / "placed" / "memory")
        assert not os.path.exists(tmp_path / "knob")

    def test_cache_hit_restart_keeps_the_real_compile_record(
            self, tmp_path, monkeypatch):
        """A persistent-cache HIT deserializes the executable without
        aliasing info (alias = 0, peak inflated by the donated bytes);
        the restart must keep the real compile's persisted record
        instead of clobbering it with the degraded one."""
        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path))
        tmem.record_executable_memory(_FakeCompiled(), "train/step")
        tmem._EXECUTABLES.clear()  # the restart

        class _DeserializedStats(_FakeStats):
            alias_size_in_bytes = 0

        class _Deserialized:
            def memory_analysis(self):
                return _DeserializedStats()

        rec = tmem.record_executable_memory(_Deserialized(), "train/step")
        assert rec["alias_mb"] == pytest.approx(90.0)
        assert rec["peak_mb"] == pytest.approx(130.0)  # not 220
        assert tmem.executable_records()["train/step"]["peak_mb"] == \
            pytest.approx(130.0)
        # a genuinely alias-free program is NOT second-guessed
        rec2 = tmem.record_executable_memory(_Deserialized(), "train/other")
        assert rec2["peak_mb"] == pytest.approx(220.0)

    def test_no_analysis_no_record_no_crash(self):
        assert tmem.record_executable_memory(object(), "x") is None

        class Broken:
            def memory_analysis(self):
                raise RuntimeError("unimplemented on this backend")

        assert tmem.record_executable_memory(Broken(), "x") is None


# -- OOM classification & forensics -------------------------------------------


class TestOomClassification:
    def test_is_oom(self):
        assert tmem.is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        assert tmem.is_oom(OomError("chaos: RESOURCE_EXHAUSTED: injected"))
        assert tmem.is_oom(MemoryError("Out of memory allocating 1GB"))
        assert not tmem.is_oom(ValueError("shape mismatch"))
        assert not tmem.is_oom(RuntimeError("collective timeout"))

    def test_non_oom_and_disabled_plane_emit_nothing(self, monkeypatch):
        tele = T.configure()
        assert tmem.maybe_oom_event(ValueError("nope"), where="step") is False
        monkeypatch.setenv("TPUFRAME_MEMORY_LIVE", "0")
        assert tmem.maybe_oom_event(
            OomError("RESOURCE_EXHAUSTED"), where="step"
        ) is False
        assert not [e for e in tele.recent_events(50)
                    if e.get("name") == "memory/oom"]


class TestOomForensics:
    """The acceptance story: a seeded OomAt inside a real Trainer fit
    produces exactly one memory/oom event carrying the attribution table
    and a fit suggestion the estimator confirms, with zero recompiles
    after the crash."""

    def _fit_with_seeded_oom(self, tmp_path, monkeypatch):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "0")  # hermetic
        tele = T.configure(jsonl_dir=str(tmp_path), rank=0)
        ds = SyntheticImageDataset(n=64, image_size=28, channels=1,
                                   num_classes=4, seed=0)
        tr = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, shuffle=True,
                                        seed=3),
            max_duration="1ep",
            eval_interval=0,
            log_interval=0,
        )
        plan = ChaosPlan([OomAt("step", step=1)])
        with plan.active():
            with pytest.raises(OomError):
                tr.fit()
        return tele, tr

    def test_seeded_oom_produces_one_forensic_event(self, tmp_path,
                                                    monkeypatch):
        tele, tr = self._fit_with_seeded_oom(tmp_path, monkeypatch)
        events = tele.recent_events(500)
        ooms = [e for e in events if e.get("name") == "memory/oom"]
        assert len(ooms) == 1, "exactly one memory/oom per crash"
        ev = ooms[0]
        assert ev["where"] == "step" and ev["step"] == 1
        assert "RESOURCE_EXHAUSTED" in ev["error"]
        # attribution table: the estimator context the trainer registered
        est = ev["estimate"]
        assert est["plan_signature"] == tr.plan.signature()
        assert ev["estimate_total_mb"] == est["per_device_mb"]["total"] > 0
        assert est["top_leaves"], "attribution table must name leaves"
        assert ev["live"].keys() == {
            "hbm_peak_mb", "host_peak_mb", "hbm_limit_mb",
        }
        # and the chaos injection itself is on the record, before the oom
        names = [e.get("name") for e in events]
        assert names.index("fault/chaos_injected") < names.index("memory/oom")

    def test_suggested_plan_is_confirmed_by_the_estimator(self, tmp_path,
                                                          monkeypatch):
        tele, tr = self._fit_with_seeded_oom(tmp_path, monkeypatch)
        events = tele.recent_events(500)
        ev = [e for e in events if e.get("name") == "memory/oom"][0]
        fit = ev["fit"]
        assert fit["base_total_mb"] > 0
        sug = fit["suggestion"]
        assert sug is not None and sug["fits"]
        # re-run the estimator under the suggested knobs: it must verify
        # the rung fits (here: no budget -> >=20% under the base total)
        from tpuframe.compile import loader_batch_template

        est2 = plan_memory(
            tr.plan, tr.state.params,
            loader_batch_template(tr, train=True),
            opt_template=tr.state.opt_state,
            comms_template=tr.state.comms,
            zero_stage=sug.get("zero_stage"),
            microbatches=sug.get("microbatches"),
            offload_optimizer=sug.get("offload_optimizer"),
        )
        assert est2["per_device_mb"]["total"] == pytest.approx(
            sug["total_mb"], abs=0.02
        )
        assert sug["total_mb"] <= 0.8 * fit["base_total_mb"]
        # zero recompiles: forensics is stdlib math, so nothing compiles
        # after the crash
        names = [e.get("name") for e in events]
        oom_at = names.index("memory/oom")
        assert "compile/backend_compile" not in names[oom_at:]

    def test_precompile_seam_classifies_oom(self, monkeypatch):
        tele = T.configure()
        params, opt, batch, _ = _templates()
        plan = compose()
        tmem.set_context(plan=plan, model_template=params, batch_spec=batch,
                         opt_template=opt)
        assert tmem.maybe_oom_event(
            RuntimeError("RESOURCE_EXHAUSTED: while allocating"),
            where="precompile",
        )
        ev = [e for e in tele.recent_events(50)
              if e.get("name") == "memory/oom"]
        assert len(ev) == 1 and ev[0]["where"] == "precompile"
        assert ev[0]["estimate"]["plan_signature"] == plan.signature()

    def test_budget_env_gates_the_fit_verdict(self, monkeypatch):
        tele = T.configure()
        params, opt, batch, _ = _templates()
        plan = compose(fsdp=8, dp=1)
        tmem.set_context(plan=plan, model_template=params, batch_spec=batch,
                         opt_template=opt)
        s1_total = plan_memory(plan, params, batch, opt_template=opt,
                               zero_stage=1)["per_device_mb"]["total"]
        monkeypatch.setenv("TPUFRAME_MEMORY_BUDGET_MB",
                           str(s1_total / 0.9 + 1.0))
        assert tmem.maybe_oom_event(OomError("RESOURCE_EXHAUSTED"),
                                    where="step", step=7)
        ev = [e for e in tele.recent_events(50)
              if e.get("name") == "memory/oom"][-1]
        assert ev["budget_mb"] == pytest.approx(s1_total / 0.9 + 1.0)
        assert ev["fit"]["suggestion"]["zero_stage"] == 1
