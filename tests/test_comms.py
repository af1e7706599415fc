"""Wire-level compressed collectives (tpuframe.parallel.compression):
bucketed transport, error feedback, plan-derived update sharding,
checkpoint-portable residuals, bytes-on-wire telemetry, and the
analyzer's wire regression gate."""

import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuframe.core.runtime import MeshSpec
from tpuframe.parallel import ParallelPlan
from tpuframe.parallel.compression import (
    COMMS_ENV_VARS,
    CommsConfig,
    comms_template,
    grad_layout,
    init_comms_state,
    make_compressed_pmean,
    wire_plan,
)
from tpuframe.track.telemetry import get_telemetry
from tpuframe.train import create_train_state, make_train_step
from tpuframe.train.step import make_grad_accum_step

_MARKS = itertools.count()


def _mark() -> str:
    token = f"comms-test-{next(_MARKS)}"
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


def _batches(plan, n=40, b=16, seed=3, accum=None):
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


# -- EF parity ---------------------------------------------------------------


class TestErrorFeedbackParity:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_ef_fit_tracks_f32(self, mode):
        """The acceptance parity bar: a seeded fit through the
        compressed wire (EF on) lands within a few percent of the exact
        f32 trajectory, for both payload formats."""
        plan = ParallelPlan(mesh=_mesh(8))
        config = CommsConfig(mode=mode)
        exact_step = make_train_step(plan=plan)
        comp_step = make_train_step(plan=plan, grad_compression=config)
        se, sc = _state(plan), _state(plan, config)
        le, lc = [], []
        for batch in _batches(plan):
            se, me = exact_step(se, dict(batch))
            sc, mc = comp_step(sc, dict(batch))
            le.append(float(me["loss_sum"] / me["count"]))
            lc.append(float(mc["loss_sum"] / mc["count"]))
        assert np.isfinite(lc).all()
        assert lc[-1] < lc[0] * 0.7, lc  # it learns
        # loss-ratio tolerance vs f32 at the end of the fit
        assert abs(lc[-1] / le[-1] - 1.0) < 0.05, (lc[-1], le[-1])
        # the residual carries real deferred mass
        assert float(jnp.abs(sc.comms["flat"]).max()) > 0

    def test_ef_residual_telescopes(self):
        """One-shard sanity of the EF contract: applied updates +
        residual drift == the exact gradient sum (telescoping)."""
        plan = ParallelPlan(mesh=_mesh(1))
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        fn = make_compressed_pmean(plan, config)
        tree = {"g": jnp.asarray(
            np.random.default_rng(0).standard_normal(65), jnp.float32
        ) * 0.02}
        residual = {
            k: jnp.zeros(s, jnp.float32)
            for k, s in comms_template(tree, config, plan).items()
        }
        applied_sum = np.zeros(65, np.float32)
        for _ in range(20):
            out, residual = fn(tree, residual)
            applied_sum += np.asarray(out["g"])
        # sum(applied) == sum(g) - residual_end  (residual_0 = 0)
        drift = np.asarray(residual["flat"]).ravel()[:65]
        np.testing.assert_allclose(
            applied_sum + drift, 20 * np.asarray(tree["g"]),
            rtol=1e-4, atol=1e-5,
        )


# -- bucketing ----------------------------------------------------------------


class TestBucketedTransport:
    def test_bucketing_bit_stable_across_leaf_orderings(self):
        plan = ParallelPlan(mesh=_mesh(8))
        config = CommsConfig(mode="int8", bucket_mb=0.001)
        rng = np.random.default_rng(2)
        leaves = {
            "zeta": rng.standard_normal((8, 40)).astype(np.float32),
            "alpha": rng.standard_normal((8, 17)).astype(np.float32) * 9,
            "b10": rng.standard_normal((8, 5)).astype(np.float32) * 1e-3,
            "b2": rng.standard_normal((8, 31)).astype(np.float32),
        }
        fn = make_compressed_pmean(plan, config)
        t1 = {k: jnp.asarray(leaves[k]) for k in ["zeta", "alpha", "b10", "b2"]}
        t2 = {k: jnp.asarray(leaves[k]) for k in ["b2", "b10", "alpha", "zeta"]}
        o1, _ = fn(t1, {})
        o2, _ = fn(t2, {})
        for k in leaves:
            np.testing.assert_array_equal(np.asarray(o1[k]), np.asarray(o2[k]))
        # offsets follow SORTED path order, not insertion/flatten order
        layout = grad_layout(
            {k: jax.ShapeDtypeStruct(v.shape[1:], jnp.float32)
             for k, v in leaves.items()},
            config, plan,
        )
        assert [p for p, _, _, _ in layout.flat] == sorted(leaves)
        offs = [o for _, _, _, o in layout.flat]
        assert offs == sorted(offs)

    def test_fixed_size_buckets_and_padding(self):
        config = CommsConfig(mode="int8", bucket_mb=4.0)
        plan = ParallelPlan(mesh=_mesh(8))
        big = {"w": jax.ShapeDtypeStruct((3 * (1 << 20),), jnp.float32)}
        layout = grad_layout(big, config, plan)
        # 12 MiB of f32 -> 3 buckets of 4 MiB
        assert layout.n_buckets == 3
        assert layout.padded_elems >= layout.flat_elems
        assert layout.padded_elems - layout.flat_elems < layout.n_buckets * 64

    def test_wire_plan_reduction_and_world1(self):
        config = CommsConfig(mode="int8")
        plan = ParallelPlan(mesh=_mesh(8))
        big = {"w": jax.ShapeDtypeStruct((1 << 20,), jnp.float32)}
        wp = wire_plan(grad_layout(big, config, plan), config)
        assert wp["reduction_x"] >= 3.5  # the committed acceptance bar
        lone = ParallelPlan(mesh=_mesh(1))
        wp1 = wire_plan(grad_layout(big, config, lone), config)
        assert wp1["bytes_per_step"] == 0  # no wire, no bytes

    def test_stochastic_rounding_changes_grid_not_trajectory(self):
        plan = ParallelPlan(mesh=_mesh(8))
        det = CommsConfig(mode="int8", stochastic_rounding=False)
        sto = CommsConfig(mode="int8", stochastic_rounding=True)
        batch = next(iter(_batches(plan, n=1)))
        sd = _state(plan, det)
        ss = _state(plan, sto)
        sd, _ = make_train_step(plan=plan, grad_compression=det)(sd, dict(batch))
        ss, _ = make_train_step(plan=plan, grad_compression=sto)(ss, dict(batch))
        pd, ps = _host(sd.params), _host(ss.params)
        # different rounding -> different grids...
        assert any(
            not np.array_equal(a, b)
            for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(ps))
        )
        # ...but the same step to quantization tolerance
        for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(ps)):
            np.testing.assert_allclose(a, b, atol=5e-2)


# -- composition --------------------------------------------------------------


class TestComposition:
    def test_grad_accum_matches_flat_batch(self):
        """Compress-once-per-super-batch: one accumulated+compressed
        step over (2, 8, ...) microbatches lands where the flat 16-row
        compressed step does (same samples, no dropout/BN in the model).
        SGD, so the update is proportional to the synced gradient — an
        fp-association jitter that flips one int8 grid point costs at
        most lr * one grid step, not an adam-style sign flip."""
        plan = ParallelPlan(mesh=_mesh(8))
        config = CommsConfig(mode="int8")
        flat_step = make_train_step(plan=plan, grad_compression=config)
        acc_step = make_grad_accum_step(2, plan=plan, grad_compression=config)
        sgd = lambda: optax.sgd(1e-2)  # noqa: E731
        s_flat = _state(plan, config, tx=sgd())
        s_acc = _state(plan, config, tx=sgd())
        flat_b = next(iter(_batches(plan, n=1, b=16)))
        acc_b = next(iter(_batches(plan, n=1, b=16, accum=2)))
        s_flat, m_flat = flat_step(s_flat, dict(flat_b))
        s_acc, m_acc = acc_step(s_acc, dict(acc_b))
        assert float(m_flat["count"]) == float(m_acc["count"]) == 16.0
        for a, b in zip(
            jax.tree.leaves(_host(s_flat.params)),
            jax.tree.leaves(_host(s_acc.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=0, atol=3e-4)

    def test_zero1_compressed_tracks_exact(self):
        """ZeRO-1 + compression: the plan-derived reduce-scatter ->
        sharded update -> all-gather pipeline trains to the same place
        as the exact ZeRO-1 step."""
        plan = ParallelPlan(
            mesh=_mesh(2, fsdp=4), zero_stage=1, min_shard_elems=32
        )
        config = CommsConfig(mode="int8")
        exact_step = make_train_step(plan=plan)
        comp_step = make_train_step(plan=plan, grad_compression=config)
        se, sc = _state(plan), _state(plan, config)
        assert any(k.startswith("leaf.") for k in sc.comms)  # sliced leaves
        le, lc = [], []
        for batch in _batches(plan):
            se, me = exact_step(se, dict(batch))
            sc, mc = comp_step(sc, dict(batch))
            le.append(float(me["loss_sum"] / me["count"]))
            lc.append(float(mc["loss_sum"] / mc["count"]))
        assert np.isfinite(lc).all()
        assert lc[-1] < lc[0] * 0.7, lc
        assert abs(lc[-1] / le[-1] - 1.0) < 0.06, (lc[-1], le[-1])
        # replicated params identical across shards and finite
        for leaf in jax.tree.leaves(sc.params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_tp_rules_still_refuse(self):
        """ZeRO-3 composes now (gather-on-use, the test below); TP and
        pipeline rules keep the loud refusal — their shard_map cannot
        nest inside the compressed step's — with the exact message."""
        step = make_train_step(
            plan=ParallelPlan(mesh=_mesh(4, fsdp=2), zero_stage=3),
            grad_compression="int8",
        )
        assert step is not None  # ZeRO-3 refusal retired
        with pytest.raises(
            ValueError,
            match=r"TP/pipeline rules re-shard params inside the model",
        ):
            make_train_step(
                plan=ParallelPlan(
                    mesh=_mesh(4, model=2),
                    rules=((".*kernel", P(None, "model")),),
                ),
                grad_compression="int8",
            )

    def test_zero3_compressed_matches_zero2_bit_exact(self):
        """Stage 3 is stage 2 plus a different resting layout: same
        wire, same sliced update — gather-on-use must not change a
        single bit of the params (global view), while the stage-3
        params actually REST fsdp-sharded between steps."""
        import optax

        from tpuframe.parallel.comms_env import CommsConfig
        from tpuframe.parallel.compression import init_comms_state
        from tpuframe.train.state import create_train_state

        cfg = CommsConfig.from_env("int8")
        mesh = _mesh(2, fsdp=4)
        plan2 = ParallelPlan(mesh=mesh, zero_stage=2, min_shard_elems=128)
        plan3 = ParallelPlan(mesh=mesh, zero_stage=3, min_shard_elems=128)
        x = jnp.zeros((4, 8, 8, 3))
        s2 = create_train_state(
            Tiny(), jax.random.PRNGKey(0), x, optax.sgd(0.1), plan=plan2
        )
        s2 = s2.replace(comms=init_comms_state(s2.params, plan2, cfg))
        s3 = create_train_state(
            Tiny(), jax.random.PRNGKey(0), x, optax.sgd(0.1), plan=plan3
        )
        # one init for both arms (sharded-init RNG draws differ by
        # design — threefry under sharded out_shardings)
        s3 = s3.replace(
            params=jax.device_put(s2.params, plan3.param_shardings(s2.params)),
            comms=init_comms_state(s2.params, plan3, cfg),
        )
        fsdp_specs = {str(l.sharding.spec) for l in jax.tree.leaves(s3.params)}
        assert any("fsdp" in s for s in fsdp_specs), fsdp_specs
        step2 = make_train_step(
            plan=plan2, grad_compression="int8", grad_clip=1.0, donate=False
        )
        step3 = make_train_step(
            plan=plan3, grad_compression="int8", grad_clip=1.0, donate=False
        )
        batch = {
            "image": jax.random.normal(jax.random.PRNGKey(1), (16, 8, 8, 3)),
            "label": jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 4),
        }
        for _ in range(3):
            s2, m2 = step2(s2, batch)
            s3, m3 = step3(s3, batch)
        for a, b in zip(jax.tree.leaves(s2.params), jax.tree.leaves(s3.params)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        # stage 3 keeps its resting shard layout through the step
        out_specs = {
            str(l.sharding.spec) for l in jax.tree.leaves(s3.params)
        }
        assert any("fsdp" in s for s in out_specs), out_specs

    def test_trainer_grad_clip_zero_compression_composes(self):
        """The grad_clip × ZeRO × compression refusal is retired: the
        clip moves inside the compressed step (plan-global norm), the
        optax chain is skipped, and training proceeds."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=16, image_size=8, num_classes=4, seed=0)
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=8),
            plan=ParallelPlan(mesh=_mesh(4, fsdp=2), zero_stage=1),
            grad_clip=1.0,
            grad_compression="int8",
            num_classes=4,
            max_duration="1ep",
            eval_interval=0,
            log_interval=0,
        )
        assert trainer._step_grad_clip == 1.0
        result = trainer.fit()
        assert np.isfinite(result.metrics["train_loss"])

    def test_trainer_grad_accum_composes(self):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=8, num_classes=4, seed=0)
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=16, shuffle=True, seed=0),
            max_duration="2ep",
            optimizer="adam",
            lr=1e-2,
            num_classes=4,
            grad_accum=2,
            grad_compression="int8",
            eval_interval=0,
            log_interval=0,
        )
        result = trainer.fit()
        assert np.isfinite(result.metrics["train_loss"])
        # the EF residual rode along
        assert trainer.state.comms and "flat" in trainer.state.comms


# -- checkpoint portability ---------------------------------------------------


class TestResidualCheckpointing:
    def _fit_some(self, plan, config, steps=4):
        step = make_train_step(plan=plan, grad_compression=config)
        s = _state(plan, config)
        for batch in _batches(plan, n=steps):
            s, _ = step(s, dict(batch))
        return s

    def test_same_topology_roundtrip_bit_exact(self, tmp_path):
        from tpuframe.ckpt import Checkpointer

        plan = ParallelPlan(mesh=_mesh(4))
        config = CommsConfig(mode="int8")
        s = self._fit_some(plan, config)
        ref = _host(s.comms)
        assert float(np.abs(ref["flat"]).max()) > 0
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s, step=4, plan=plan)
            ck.wait()
            # the manifest carries the residual leaves
            man = ck.manifest_for()
            assert any(k.startswith("comms/") for k in man["leaves"])
            restored, _ = ck.restore(_state(plan, config, seed=9))
        np.testing.assert_array_equal(
            np.asarray(restored.comms["flat"]), ref["flat"]
        )

    def test_residual_survives_shrink_to_survivors(self, tmp_path):
        """Save at dp=4, restore at dp=2 (the PR-6 reshard path): the
        folded residual is the group-sum scaled by to/from world — what
        EF owes the trajectory is the MEAN correction (1/W)*sum(resid),
        and the next step divides by the NEW world, so the totals must
        shrink with W (= the per-group mean on an even shrink).  One
        comms/ef_reshard event."""
        from tpuframe.ckpt import Checkpointer

        plan4 = ParallelPlan(mesh=_mesh(4))
        config = CommsConfig(mode="int8")
        s = self._fit_some(plan4, config)
        ref = _host(s.comms)["flat"]  # (4, nb, be)
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s, step=4, plan=plan4)
            ck.wait()
            plan2 = plan4.rebind(_mesh(2))
            template = _state(plan2, config, seed=9)
            assert template.comms["flat"].shape[0] == 2
            n0 = _mark()
            restored, _ = ck.restore(template, plan=plan2)
        folded = np.asarray(restored.comms["flat"])
        # contiguous groups (new shard 0 <- old {0,1}, 1 <- {2,3}),
        # scaled by 2/4: the mean deficit (1/W)*sum(resid) is invariant
        np.testing.assert_allclose(
            folded, ref.reshape(2, 2, *ref.shape[1:]).sum(axis=1) * 0.5,
            rtol=1e-6, atol=1e-7,
        )
        assert np.asarray(folded).sum() == pytest.approx(
            ref.sum() * 0.5, rel=1e-5
        )
        ev = _events_since(n0, "comms/ef_reshard")
        assert len(ev) == 1
        assert ev[0]["from_world"] == 4 and ev[0]["to_world"] == 2
        # ...and the params still restored bit-exact through the reshard
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(restored.params)[0]),
            np.asarray(jax.tree.leaves(s.params)[0]),
        )

    def test_precompression_checkpoint_resets_residual_loudly(self, tmp_path):
        """An f32-era checkpoint restores into a compressed trainer:
        params load, the residual stays zero, one comms/ef_reset
        event."""
        from tpuframe.ckpt import Checkpointer

        plan = ParallelPlan(mesh=_mesh(4))
        s_f32 = _state(plan)  # no comms
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s_f32, step=1, plan=plan)
            ck.wait()
            config = CommsConfig(mode="int8")
            n0 = _mark()
            restored, _ = ck.restore(_state(plan, config, seed=9))
        assert len(_events_since(n0, "comms/ef_reset")) == 1
        assert float(np.abs(np.asarray(restored.comms["flat"])).max()) == 0.0
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(restored.params)[0]),
            np.asarray(jax.tree.leaves(s_f32.params)[0]),
        )


# -- telemetry / knobs / doctor ----------------------------------------------


class TestTelemetryAndKnobs:
    def test_trainer_meters_bytes_on_wire(self):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=8, num_classes=4, seed=0)
        tele = get_telemetry()
        before = tele.registry.counter("comms/bytes_on_wire").value
        n0 = _mark()
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=8, shuffle=True, seed=0),
            max_duration="1ep",
            optimizer="adam",
            num_classes=4,
            grad_compression="int8",
            eval_interval=0,
            log_interval=0,
        )
        trainer.fit()
        wire = trainer._train_step.wire
        assert wire and wire["bytes_per_step"] > 0
        ev = _events_since(n0, "comms/wire_plan")
        assert ev and ev[-1]["mode"] == "int8" and ev[-1]["error_feedback"]
        counted = tele.registry.counter("comms/bytes_on_wire").value - before
        assert counted == wire["bytes_per_step"] * trainer.batches_seen

    def test_zero_recompiles_with_compression_on(self):
        """The compressed step is a first-class compile-spine citizen:
        precompile AOT-lowers it, fit dispatches straight to the
        executable, and no compile/recompile event fires."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=48, image_size=8, num_classes=4, seed=0)
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=8, shuffle=True, seed=0),
            max_duration="2ep",
            optimizer="adam",
            num_classes=4,
            grad_compression="int8",
            eval_interval=0,
            log_interval=0,
        )
        report = trainer.precompile(wait=True)
        assert report["steps"] and "signature" in report["steps"][0]
        assert any(k[0] == "train" for k in trainer._compiled)  # AOT armed
        n0 = _mark()
        trainer.fit()
        assert _events_since(n0, "compile/recompile") == []
        assert _events_since(n0, "compile/aot_fallback") == []

    def test_comms_knobs_ship_and_parse(self, monkeypatch):
        from tpuframe.launch.remote import all_env_vars

        registry = all_env_vars()
        for var in COMMS_ENV_VARS:
            assert var in registry
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "fp8")
        monkeypatch.setenv("TPUFRAME_COMMS_BUCKET_MB", "2.5")
        monkeypatch.setenv("TPUFRAME_COMMS_STOCHASTIC", "1")
        monkeypatch.setenv("TPUFRAME_COMMS_EF", "0")
        config = CommsConfig.from_env()
        assert config == CommsConfig(
            mode="fp8", bucket_mb=2.5, stochastic_rounding=True,
            error_feedback=False,
        )
        # explicit param beats env; malformed numerics fall back
        assert CommsConfig.from_env("int8").mode == "int8"
        monkeypatch.setenv("TPUFRAME_COMMS_BUCKET_MB", "banana")
        assert CommsConfig.from_env().bucket_mb == 4.0
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "")
        assert CommsConfig.from_env() is None
        # a typo'd MODE is the one loud failure
        with pytest.raises(ValueError, match="unknown grad_compression"):
            CommsConfig.from_env("int7")

    def test_doctor_comms_section(self, monkeypatch, scripts_not_in_tree):
        from tpuframe.doctor import comms_section

        monkeypatch.delenv("TPUFRAME_COMMS_COMPRESSION", raising=False)
        sec = comms_section()
        assert sec["enabled"] is False and scripts_not_in_tree(sec) == []
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "int8")
        sec = comms_section()
        assert sec["enabled"] and sec["config"]["mode"] == "int8"
        assert sec["env"] == {"TPUFRAME_COMMS_COMPRESSION": "int8"}
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "int7")
        assert "error" in comms_section()  # typo reported, not crashed


# -- analyzer gate ------------------------------------------------------------


class TestAnalyzerCommsGate:
    def _log(self, tmp_path, bytes_per_step=1000):
        base = {"v": 1, "rank": 0, "pid": 10, "thread": "MainThread"}
        recs = [
            {**base, "kind": "meta", "name": "telemetry/meta", "schema": 1,
             "anchor_wall": 100.0, "anchor_mono": 50.0},
            {**base, "kind": "event", "name": "comms/wire_plan", "ts": 100.1,
             "mono": 50.1, "mode": "int8", "world": 8, "error_feedback": True,
             "bytes_per_step": bytes_per_step, "f32_bytes_per_step": 4000,
             "reduction_x": 4.0},
        ]
        t = 101.0
        for b in range(4):
            recs.append({**base, "kind": "span", "name": "train/step",
                         "ts": t, "mono": t - 50.0, "dur_s": 0.01,
                         "attrs": {"batch": b, "data_wait_s": 0.0}})
            t += 0.02
        for d in (0.004, 0.005, 0.006):
            recs.append({**base, "kind": "span", "name": "comms/allreduce",
                         "ts": t, "mono": t - 50.0, "dur_s": d})
            t += 0.01
        p = tmp_path / "events-rank0.jsonl"
        p.write_text("".join(json.dumps(r) + "\n" for r in recs))
        return str(tmp_path)

    def test_skew_report_comms_block(self, tmp_path):
        from tpuframe.track import analyze as A

        ranks = A.load_dir(self._log(tmp_path))
        rep = A.skew_report(ranks)
        cm = rep["comms"]
        assert cm["mode"] == "int8" and cm["bytes_per_step"] == 1000
        assert cm["steps"] == 4 and cm["bytes_on_wire"] == 4000
        assert cm["allreduce_s"]["p50"] == pytest.approx(0.005)
        assert "comms:" in A.format_report(rep)

    def test_baseline_gate_exit3_on_wire_regression(self, tmp_path):
        from tpuframe.track import analyze as A

        ranks = A.load_dir(self._log(tmp_path, bytes_per_step=4000))
        rep = A.skew_report(ranks)
        # committed baseline: int8 wire at 1000 B/step
        baseline = tmp_path / "bench_collectives_cpu.json"
        baseline.write_text(json.dumps({
            "backend": "cpu",
            "comms": {"mode": "int8", "bytes_per_step": 1000,
                      "allreduce_s": {"p50": 0.005}},
        }))
        diff = A.baseline_diff(rep, str(baseline), threshold=1.25)
        assert diff["regressions"], diff
        reg = diff["regressions"][0]
        assert reg["ratio_bytes_on_wire"] == 4.0
        # the allreduce wall itself sits under threshold — the BYTES
        # ratio alone is what trips the gate here
        assert reg["ratio_allreduce_p50"] <= 1.25
        # compression back at parity -> no regression
        ok = A.baseline_diff(
            A.skew_report(A.load_dir(self._log(tmp_path, bytes_per_step=1000))),
            str(baseline), threshold=1.25,
        )
        assert not ok["regressions"]


# -- convergence gate ---------------------------------------------------------


def test_digits_convergence_gate_compressed_matches_f32(tmp_path):
    """THE acceptance story: the real-data digits recipe clears the SAME
    --min-accuracy gate with the compressed wire as with f32 — run both
    arms through examples/08 at an identical threshold."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    script = os.path.join(
        os.path.dirname(__file__), os.pardir, "examples",
        "08_real_data_convergence.py",
    )
    for arm, extra in (("f32", []), ("int8", ["--grad-compression", "int8"])):
        proc = subprocess.run(
            [sys.executable, script, "--dataset", "digits", "--epochs", "6",
             "--eval-interval", "3", "--min-accuracy", "0.84",
             "--workdir", str(tmp_path / arm)] + extra,
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, (
            f"[{arm}] --- stdout ---\n{proc.stdout[-2000:]}\n--- stderr ---\n"
            f"{proc.stderr[-3000:]}"
        )
        assert "ACCEPTED" in proc.stdout, (arm, proc.stdout[-500:])
