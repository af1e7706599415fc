"""Bucket-group scheduled collectives (the overlapped gradient sync):
grouped sync bit-exact against the single shot in every wire mode, the
schedule as a first-class ParallelPlan artifact, exact bytes-on-wire
accounting under any grouping, the TPUFRAME_COMMS_GROUPS/ASYNC knobs,
zero-recompile AOT dispatch of the overlapped step, and EF residuals
riding checkpoints/reshards with grouped layouts."""

import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import MeshSpec
from tpuframe.parallel import ParallelPlan
from tpuframe.parallel.comms_env import COMMS_ENV_DOMAINS
from tpuframe.parallel.compression import (
    COMMS_ENV_VARS,
    CommsConfig,
    comms_template,
    grad_layout,
    init_comms_state,
    make_compressed_pmean,
    sync_gradients,
    wire_plan,
)
from tpuframe.track.telemetry import get_telemetry
from tpuframe.train import create_train_state, make_train_step
from tpuframe.train.step import make_grad_accum_step

_MARKS = itertools.count()


def _mark() -> str:
    token = f"overlap-test-{next(_MARKS)}"
    get_telemetry().event("test/mark", token=token)
    return token


def _events_since(token: str, name: str | None = None) -> list:
    ev = get_telemetry().recent_events(10**6)
    idx = max(
        i for i, e in enumerate(ev)
        if e.get("name") == "test/mark" and e.get("token") == token
    )
    out = ev[idx + 1:]
    return [e for e in out if name is None or e.get("name") == name]


def _mesh(dp: int, **axes):
    devs = jax.devices()
    spec = MeshSpec(data=dp, **axes)
    n = int(np.prod([max(s, 1) for s in spec.sizes().values()]))
    return spec.build(devs[:n])


def _host(tree):
    return jax.tree.map(lambda x: np.array(jax.device_get(x), copy=True), tree)


def _bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(
        a.view(np.uint8), b.view(np.uint8)
    )


def _grad_tree(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "deep/w": jnp.asarray(
            rng.standard_normal((8, 40, 17)) * scale, jnp.float32),
        "mid/b": jnp.asarray(
            rng.standard_normal((8, 300)) * 3e-4, jnp.float32),
        "top/w": jnp.asarray(
            rng.standard_normal((8, 61)) * 40, jnp.float32),
        "steps": jnp.ones((8,), jnp.int32),
    }


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Dense(16)(x.reshape((x.shape[0], -1)))
        return nn.Dense(4)(nn.relu(x))


def _state(plan, config=None, seed=0, tx=None):
    s = create_train_state(
        Tiny(), jax.random.PRNGKey(seed),
        jnp.ones((1, 6, 6, 1), jnp.float32), tx or optax.adam(1e-2),
        plan=plan,
    )
    if config is not None:
        s = s.replace(comms=init_comms_state(s.params, plan, config))
    return s


_W_TRUE = np.random.default_rng(7).standard_normal((36, 4)).astype(np.float32)


def _batches(plan, n=4, b=16, seed=3, accum=None):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        img = rng.standard_normal((b, 6, 6, 1)).astype(np.float32)
        lab = np.argmax(img.reshape(b, -1) @ _W_TRUE, axis=1).astype(np.int32)
        batch = {"image": img, "label": lab}
        if accum:
            batch = {
                k: v.reshape((accum, b // accum) + v.shape[1:])
                for k, v in batch.items()
            }
        yield plan.shard_batch(batch, leading_microbatch=bool(accum))


# -- bit-exactness of the grouped schedule ------------------------------------


class TestGroupedBitExact:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    @pytest.mark.parametrize("ef", [True, False])
    def test_grouped_matches_single_shot(self, mode, ef):
        """The tentpole contract: partitioning the bucketed sync into
        scheduled groups changes the schedule, never the arithmetic —
        synced gradients AND the EF residual are bit-identical to the
        single-shot reference, both payload formats, EF on and off."""
        config = CommsConfig(mode=mode, bucket_mb=0.001, error_feedback=ef)
        tree = _grad_tree()
        outs, resids = [], []
        for groups in (None, 3):
            plan = ParallelPlan(mesh=_mesh(8), comms_groups=groups)
            fn = make_compressed_pmean(plan, config)
            resid = (
                {k: jnp.zeros(s, jnp.float32)
                 for k, s in comms_template(tree, config, plan).items()}
                if ef else {}
            )
            out, new_resid = fn(tree, resid)
            outs.append(_host(out))
            resids.append(_host(new_resid))
        layout = grad_layout(
            tree, config, ParallelPlan(mesh=_mesh(8), comms_groups=3))
        assert layout.n_groups == 3 and layout.n_buckets >= 3
        for k in outs[0]:
            assert _bits_equal(outs[0][k], outs[1][k]), k
        if ef:
            assert _bits_equal(resids[0]["flat"], resids[1]["flat"])
            assert float(np.abs(resids[1]["flat"]).max()) > 0

    def test_grouped_stochastic_rounding_bit_exact(self):
        """Stochastic rounding draws ONE full-shape uniform and slices
        it per group, so even the random grid is schedule-invariant."""
        config = CommsConfig(
            mode="int8", bucket_mb=0.001, stochastic_rounding=True)
        tree = {"w": _grad_tree()["deep/w"]}  # (world, 40, 17), shard-varying
        template = {
            k: jax.ShapeDtypeStruct(v.shape[1:], jnp.float32)
            for k, v in tree.items()
        }
        key = jax.random.PRNGKey(11)
        outs = []
        for groups in (1, 4):
            plan = ParallelPlan(mesh=_mesh(8))
            layout = grad_layout(template, config, plan, group_buckets=groups)

            def run(t):
                out, _ = sync_gradients(
                    {k: v[0] for k, v in t.items()}, {}, layout, config,
                    rng=key,
                )
                return {k: v[None] for k, v in out.items()}

            outs.append(_host(shard_map(
                run, mesh=plan.mesh, in_specs=P("data"),
                out_specs=P("data"), check_vma=False,
            )(tree)))
        assert _bits_equal(outs[0]["w"], outs[1]["w"])

    def test_zero1_grouped_matches_single_shot(self):
        """The sliced (ZeRO-1 reduce-scatter) leaves fire in reverse
        path order under a grouped schedule but keep their NATURAL
        rng tags — owned update slices stay bit-identical, stochastic
        rounding included."""
        config = CommsConfig(
            mode="int8", stochastic_rounding=True, bucket_mb=0.001)
        plan = ParallelPlan(
            mesh=_mesh(2, fsdp=4), zero_stage=1, min_shard_elems=32)
        rng = np.random.default_rng(5)
        tree = {
            "a/kernel": jnp.asarray(
                rng.standard_normal((8, 64, 16)), jnp.float32),
            "b/kernel": jnp.asarray(
                rng.standard_normal((8, 48, 8)) * 7, jnp.float32),
            "c/bias": jnp.asarray(
                rng.standard_normal((8, 30)) * 1e-3, jnp.float32),
        }
        template = {
            k: jax.ShapeDtypeStruct(v.shape[1:], jnp.float32)
            for k, v in tree.items()
        }
        key = jax.random.PRNGKey(3)
        outs = []
        for groups in (1, 2):
            layout = grad_layout(template, config, plan, group_buckets=groups)

            def run(t):
                out, _ = sync_gradients(
                    {k: v[0] for k, v in t.items()}, {}, layout, config,
                    rng=key,
                )
                return {k: v[None] for k, v in out.items()}

            outs.append(_host(shard_map(
                run, mesh=plan.mesh,
                in_specs=P(layout.axes), out_specs=P(layout.axes),
                check_vma=False,
            )(tree)))
        assert grad_layout(template, config, plan, group_buckets=2).sliced
        for k in outs[0]:
            assert _bits_equal(outs[0][k], outs[1][k]), k

    def test_accum_peel_matches_unpeeled(self):
        """The grouped grad-accum step peels the last microbatch out of
        the scan (same addition order, open tail backward): one step
        from the same init lands where the single-shot accum step does."""
        config_1 = CommsConfig(mode="int8", bucket_mb=0.001)
        plan_1 = ParallelPlan(mesh=_mesh(8))
        plan_g = ParallelPlan(mesh=_mesh(8), comms_groups=3)
        batch = next(iter(_batches(plan_1, n=1, b=16, accum=2)))
        results = []
        for plan in (plan_1, plan_g):
            step = make_grad_accum_step(
                2, plan=plan, grad_compression=config_1)
            s = _state(plan, config_1, tx=optax.sgd(1e-2))
            s, m = step(s, dict(batch))
            results.append((_host(s.params), _host(s.comms), _host(m)))
        (p1, c1, m1), (pg, cg, mg) = results
        assert float(m1["count"]) == float(mg["count"]) == 16.0
        np.testing.assert_allclose(
            float(m1["loss_sum"]), float(mg["loss_sum"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pg)):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)
        # the peel re-fuses the tail microbatch's backward, so the
        # accumulated grads entering the sync can differ by float
        # association ulps (the SYNC itself is bit-exact on identical
        # inputs — TestGroupedBitExact above); the residual tracks those
        # ulps, nothing more
        assert c1["flat"].shape == cg["flat"].shape
        np.testing.assert_allclose(c1["flat"], cg["flat"], rtol=0, atol=1e-6)


# -- the schedule as a plan artifact ------------------------------------------


class TestScheduleArtifact:
    def test_plan_signature_backward_compat(self):
        """Pre-existing plan signatures — autotune store keys, topology
        manifests, compile labels — must not change just because the
        field exists: None and 1 are both the single-shot identity."""
        mesh = _mesh(8)
        base = ParallelPlan(mesh=mesh).signature()
        assert ParallelPlan(mesh=mesh, comms_groups=None).signature() == base
        assert ParallelPlan(mesh=mesh, comms_groups=1).signature() == base
        assert ParallelPlan(mesh=mesh, comms_groups=4).signature() != base

    def test_comms_schedule_resolution(self):
        mesh = _mesh(8)
        sched = ParallelPlan(mesh=mesh).comms_schedule()
        assert sched == {
            "groups": 1, "order": "reverse_backward", "pinned": False,
            "fused": False, "fused_pinned": False,
            "pp_schedule": "interleaved", "pp_pinned": False}
        # env/config default fills in when the plan doesn't pin...
        sched = ParallelPlan(mesh=mesh).comms_schedule(
            CommsConfig(mode="int8", groups=3))
        assert sched["groups"] == 3 and not sched["pinned"]
        # ...and the pinned plan wins over the config
        sched = ParallelPlan(mesh=mesh, comms_groups=4).comms_schedule(
            CommsConfig(mode="int8", groups=3))
        assert sched["groups"] == 4 and sched["pinned"]
        with pytest.raises(ValueError, match="comms_groups"):
            ParallelPlan(mesh=mesh, comms_groups=0)

    def test_group_bounds_cover_reversed_and_clamp(self):
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        tree = _grad_tree()
        plan = ParallelPlan(mesh=_mesh(8))
        layout = grad_layout(tree, config, plan, group_buckets=3)
        bounds = layout.group_bounds
        assert layout.n_groups == 3
        # bounds partition [0, n_buckets) exactly, fire order reversed:
        # the LAST bucket range (deepest layers, backward's first
        # gradients) goes on the wire first
        assert sorted(bounds) == sorted(set(bounds))
        assert sum(e - s for s, e in bounds) == layout.n_buckets
        assert bounds[0][1] == layout.n_buckets and bounds[-1][0] == 0
        assert list(bounds) == sorted(bounds, reverse=True)
        # more groups than buckets clamps to one bucket per group
        tiny = grad_layout(
            {"w": jnp.zeros((4,), jnp.float32)}, config, plan,
            group_buckets=64)
        assert tiny.n_groups == tiny.n_buckets


# -- exact wire accounting under any schedule ---------------------------------


class TestWireAccounting:
    def test_group_bytes_sum_to_single_shot(self):
        """comms/bytes_on_wire stays exact under grouping: the per-group
        payload+scale bytes sum to the single-shot flat contribution and
        the metered total is schedule-invariant."""
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        tree = _grad_tree()
        plan = ParallelPlan(mesh=_mesh(8))
        single = wire_plan(grad_layout(tree, config, plan), config)
        grouped = wire_plan(
            grad_layout(tree, config, plan, group_buckets=3), config)
        assert single["overlap_groups"] == 1
        assert grouped["overlap_groups"] == 3
        assert len(grouped["groups"]) == 3
        assert grouped["bytes_per_step"] == single["bytes_per_step"]
        assert grouped["reduction_x"] == single["reduction_x"]
        assert sum(
            g["payload_bytes"] + g["scale_bytes"] for g in grouped["groups"]
        ) == pytest.approx(single["bytes_per_step"], abs=len(
            grouped["groups"]) + 1)  # per-group int rounding only
        assert sum(g["buckets"] for g in grouped["groups"]) \
            == grouped["n_buckets"]


# -- knobs --------------------------------------------------------------------


class TestOverlapKnobs:
    def test_groups_knob_parses_and_has_domain(self, monkeypatch):
        for var in ("TPUFRAME_COMMS_GROUPS", "TPUFRAME_COMMS_ASYNC"):
            assert var in COMMS_ENV_VARS and var in COMMS_ENV_DOMAINS
            assert COMMS_ENV_DOMAINS[var]["apply"] == "restart"
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "int8")
        monkeypatch.setenv("TPUFRAME_COMMS_GROUPS", "4")
        assert CommsConfig.from_env().groups == 4
        monkeypatch.setenv("TPUFRAME_COMMS_GROUPS", "banana")
        assert CommsConfig.from_env().groups == 1  # malformed falls back
        with pytest.raises(ValueError, match="groups"):
            CommsConfig(mode="int8", groups=0)

    def test_async_flag_resolver_platform_gated(self, monkeypatch):
        from tpuframe.parallel.comms_env import (
            comms_async_enabled, comms_async_flags)

        monkeypatch.delenv("TPUFRAME_COMMS_ASYNC", raising=False)
        assert not comms_async_enabled()
        assert comms_async_flags("tpu") == ()
        monkeypatch.setenv("TPUFRAME_COMMS_ASYNC", "1")
        assert comms_async_enabled()
        tpu = comms_async_flags("tpu")
        assert any("latency_hiding_scheduler" in f for f in tpu)
        # CPU has no safe flag set: the knob degrades to a no-op rather
        # than aborting the compiler
        assert comms_async_flags("cpu") == ()

    def test_initialize_merges_flags_idempotently(self, monkeypatch):
        from tpuframe.core.runtime import _apply_comms_async_flags

        monkeypatch.setenv("TPUFRAME_COMMS_ASYNC", "1")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("XLA_FLAGS", "--xla_something=1")
        _apply_comms_async_flags()
        flags = os.environ["XLA_FLAGS"]
        assert "--xla_something=1" in flags
        assert "--xla_tpu_enable_latency_hiding_scheduler=true" in flags
        _apply_comms_async_flags()  # second apply adds nothing
        assert os.environ["XLA_FLAGS"] == flags

    def test_doctor_prints_resolved_flag_set(self, monkeypatch):
        from tpuframe.doctor import comms_section

        monkeypatch.setenv("TPUFRAME_COMMS_ASYNC", "1")
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        sec = comms_section()
        assert sec["async"]["enabled"] is True
        assert sec["async"]["platform"] == "cpu"
        assert sec["async"]["flags"] == []


# -- compile spine ------------------------------------------------------------


class TestOverlappedStepCompileSpine:
    def test_zero_recompiles_with_grouped_schedule(self):
        """The overlapped step is a first-class compile-spine citizen:
        precompile AOT-lowers the grouped program, the fit dispatches
        straight to the executable, zero compile/recompile and zero
        compile/aot_fallback — and the wire plan the trainer meters
        names the schedule it compiled."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=48, image_size=8, num_classes=4, seed=0)
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=8, shuffle=True, seed=0),
            max_duration="2ep",
            optimizer="adam",
            num_classes=4,
            plan=ParallelPlan(mesh=_mesh(8), comms_groups=2),
            # small buckets so the tiny model spans several (a 4 MiB
            # bucket would swallow it whole and clamp the schedule to 1)
            grad_compression=CommsConfig(mode="int8", bucket_mb=0.001),
            eval_interval=0,
            log_interval=0,
        )
        report = trainer.precompile(wait=True)
        assert report["steps"]
        assert any(k[0] == "train" for k in trainer._compiled)  # AOT armed
        n0 = _mark()
        trainer.fit()
        assert _events_since(n0, "compile/recompile") == []
        assert _events_since(n0, "compile/aot_fallback") == []
        wire = trainer._train_step.wire
        assert wire["overlap_groups"] == 2 and len(wire["groups"]) == 2
        tele = get_telemetry()
        assert tele.registry.gauge("comms/overlap_groups").value == 2


# -- EF residual portability with grouped layouts -----------------------------


class TestGroupedResidualCheckpointing:
    def _fit_some(self, plan, config, steps=4):
        step = make_train_step(plan=plan, grad_compression=config)
        s = _state(plan, config)
        for batch in _batches(plan, n=steps):
            s, _ = step(s, dict(batch))
        return s

    def test_roundtrip_bit_exact_with_groups(self, tmp_path):
        from tpuframe.ckpt import Checkpointer

        plan = ParallelPlan(mesh=_mesh(4), comms_groups=2)
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        s = self._fit_some(plan, config)
        ref = _host(s.comms)
        assert float(np.abs(ref["flat"]).max()) > 0
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s, step=4, plan=plan)
            ck.wait()
            restored, _ = ck.restore(_state(plan, config, seed=9))
        np.testing.assert_array_equal(
            np.asarray(restored.comms["flat"]), ref["flat"])

    def test_shrink_fold_with_groups(self, tmp_path):
        """The PR-6 reshard path with a grouped schedule: save at dp=4,
        restore at dp=2 — the rebind carries comms_groups, and the
        folded residual is the world-ratio-scaled group sum regardless
        of the bucket-group partition (folding is over the WORLD dim,
        orthogonal to the schedule's bucket dim)."""
        from tpuframe.ckpt import Checkpointer

        plan4 = ParallelPlan(mesh=_mesh(4), comms_groups=3)
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        s = self._fit_some(plan4, config)
        ref = _host(s.comms)["flat"]
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s, step=4, plan=plan4)
            ck.wait()
            plan2 = plan4.rebind(_mesh(2))
            assert plan2.comms_groups == 3  # the schedule rides the rebind
            n0 = _mark()
            restored, _ = ck.restore(
                _state(plan2, config, seed=9), plan=plan2)
        folded = np.asarray(restored.comms["flat"])
        np.testing.assert_allclose(
            folded, ref.reshape(2, 2, *ref.shape[1:]).sum(axis=1) * 0.5,
            rtol=1e-6, atol=1e-7)
        assert len(_events_since(n0, "comms/ef_reshard")) == 1


# -- device-time attribution on the CPU backend -------------------------------


class TestCpuExecTracks:
    def test_eigen_pool_counts_as_device_time(self):
        """XLA:CPU runs the thunk runtime's named HLO ops — including
        every collective — on the tf_XLAEigen intra-op pool; the merged
        host timeline must count it, or simulated-CPU captures report
        zero collectives and the exposed-comms A/B is blind."""
        from tpuframe.track import device_time as DT

        rep = DT.device_time_report({"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "python"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "tf_XLATfrtCpuClient/1"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
             "args": {"name": "tf_XLAEigen/2"}},
            {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
             "args": {"name": "python"}},
            {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
             "ts": 0, "dur": 100},
            {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.1",
             "ts": 100, "dur": 50},
            {"ph": "X", "pid": 1, "tid": 3, "name": "host_thing",
             "ts": 0, "dur": 500},
        ]})
        assert rep["classes"]["collective"]["events"] == 1
        assert rep["classes"]["compute"]["events"] == 1
        # the python thread's host bookkeeping is NOT device time
        assert rep["window_s"] == pytest.approx(150e-6)
        assert rep["exposed_comms_s"] == pytest.approx(50e-6)
