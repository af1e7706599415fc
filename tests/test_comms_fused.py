"""In-collective compression (the fused quantized wire): the ring
transport bit-exact against the staged psum in every mode, the fused
failure edges (non-finite propagation, W=1 identity, fp8 world bound,
multi-axis fallback), EF residuals riding the PR-6 shrink restore with
fused on, zero-recompile AOT dispatch of the fused step, the plan pin
in the signature, the quant_wire kernel parity contract, and the
diagnosis move that flips the knob off the top-op table."""

import dataclasses
import itertools
import types

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
from tpuframe.parallel.compression import (
    CommsConfig,
    comms_template,
    fused_active,
    grad_layout,
    init_comms_state,
    make_compressed_pmean,
    resolve_fused,
    sync_gradients,
    wire_plan,
)
from tpuframe.track.telemetry import get_telemetry
from tpuframe.train import create_train_state, make_train_step

_MARKS = itertools.count()


def _mark() -> str:
    token = f"fused-test-{next(_MARKS)}"
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


def _batches(plan, n=4, b=16, seed=3):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        img = rng.standard_normal((b, 6, 6, 1)).astype(np.float32)
        lab = np.argmax(img.reshape(b, -1) @ _W_TRUE, axis=1).astype(np.int32)
        yield plan.shard_batch({"image": img, "label": lab})


# -- the tentpole contract: fused transport == staged transport, bit for bit --


class TestFusedBitExact:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    @pytest.mark.parametrize("ef", [True, False])
    @pytest.mark.parametrize("sr", [True, False])
    def test_fused_matches_staged_flat(self, mode, ef, sr):
        """Routing the encoded buckets through the ring reduce-
        scatter/all-gather instead of one psum changes the transport,
        never the arithmetic: synced gradients AND the EF residual are
        bit-identical, every payload format, stochastic rounding and
        error feedback on or off."""
        base = CommsConfig(
            mode=mode, bucket_mb=0.001, error_feedback=ef,
            stochastic_rounding=sr,
        )
        tree = _grad_tree()
        plan = ParallelPlan(mesh=_mesh(8))
        outs, resids = [], []
        for fused in (False, True):
            config = dataclasses.replace(base, fused=fused)
            fn = make_compressed_pmean(plan, config)
            resid = (
                {k: jnp.zeros(s, jnp.float32)
                 for k, s in comms_template(tree, config, plan).items()}
                if ef else {}
            )
            out, new_resid = fn(tree, resid)
            outs.append(_host(out))
            resids.append(_host(new_resid))
        layout = grad_layout(tree, base, plan)
        assert fused_active(layout, dataclasses.replace(base, fused=True))
        for k in outs[0]:
            assert _bits_equal(outs[0][k], outs[1][k]), k
        if ef:
            assert _bits_equal(resids[0]["flat"], resids[1]["flat"])
            assert float(np.abs(resids[1]["flat"]).max()) > 0

    def test_both_transport_forms_match_staged_psum(self):
        """The transport has three backend-dispatched forms — the
        hop-pipelined ring (TPU), the concurrent all-to-all + local
        grid sum (GPU), and the single fused all-reduce thunk (CPU) —
        and ALL are bit-identical to ``psum`` on the same encoded
        payload, signed zeros included (an all-(-0.0) chunk must land
        +0.0 exactly like psum's identity accumulator)."""
        from tpuframe.parallel.compression import _fused_allreduce

        plan = ParallelPlan(mesh=_mesh(8))
        rng = np.random.default_rng(4)
        q_int = jnp.asarray(rng.integers(-127, 128, (8, 1000)), jnp.int32)
        # fp8 payloads exactly as _encode ships them: f32 values ON the
        # e4m3 grid (the wire narrows back to that container), one
        # column pinned to -0.0 on every shard
        q_fp8 = (jnp.asarray(rng.standard_normal((8, 1000)) * 40,
                             jnp.float32)
                 .astype(jnp.float8_e4m3fn).astype(jnp.float32))
        q_fp8 = q_fp8.at[:, 0].set(-0.0)
        for q in (q_int, q_fp8):
            want = _host(shard_map(
                lambda t: jax.lax.psum(t[0], ("data",))[None],
                mesh=plan.mesh, in_specs=P("data"), out_specs=P("data"),
                check_vma=False,
            )(q))
            for form in ("ring", "concurrent", "single"):
                got = _host(shard_map(
                    lambda t, f=form: _fused_allreduce(
                        t[0], "data", 8, form=f)[None],
                    mesh=plan.mesh, in_specs=P("data"), out_specs=P("data"),
                    check_vma=False,
                )(q))
                assert _bits_equal(got, want), (str(q.dtype), form)

    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_fused_zero1_sliced_matches_staged(self, mode):
        """The ZeRO-1 sliced leaves ride the fused ring reduce-scatter
        (each shard keeps its owned chunk) — owned update slices stay
        bit-identical to the staged psum_scatter, stochastic rounding
        included."""
        base = CommsConfig(
            mode=mode, stochastic_rounding=True, bucket_mb=0.001)
        plan = ParallelPlan(
            mesh=_mesh(8), zero_stage=1, min_shard_elems=32)
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
        for fused in (False, True):
            config = dataclasses.replace(base, fused=fused)
            layout = grad_layout(template, config, plan)

            def run(t, layout=layout, config=config):
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
        layout = grad_layout(
            template, dataclasses.replace(base, fused=True), plan)
        assert layout.sliced
        assert fused_active(layout, dataclasses.replace(base, fused=True))
        for k in outs[0]:
            assert _bits_equal(outs[0][k], outs[1][k]), k


# -- failure edges ------------------------------------------------------------


class TestFusedFailureEdges:
    def test_nonfinite_gradient_decodes_nan_like_staged(self):
        """A non-finite gradient poisons its bucket's agreed amax, and
        the fused wire must propagate the same all-NaN verdict the
        staged psum does — divergence may not hide inside the ring."""
        plan = ParallelPlan(mesh=_mesh(8))
        tree = _grad_tree()
        tree["deep/w"] = tree["deep/w"].at[0, 0, 0].set(jnp.inf)
        outs = []
        for fused in (False, True):
            config = CommsConfig(mode="int8", bucket_mb=0.001, fused=fused)
            out, _ = make_compressed_pmean(plan, config)(tree, {})
            outs.append(_host(out))
        # the poisoned BUCKET decodes to NaN (per-bucket scales mean
        # per-bucket blast radius), identically on both transports
        assert np.isnan(outs[1]["deep/w"]).any()
        for k in outs[0]:
            assert _bits_equal(outs[0][k], outs[1][k]), k

    def test_world1_is_no_wire_identity(self):
        """W=1 means no wire either way: the fused knob resolves to the
        same no-collective program as staged (bit-identical output) and
        the wire plan reports no hops and no bytes."""
        plan = ParallelPlan(mesh=_mesh(1))
        tree = {"w": jnp.asarray(
            np.random.default_rng(0).standard_normal((64, 3)), jnp.float32)}
        outs = []
        for fused in (False, True):
            config = CommsConfig(mode="int8", bucket_mb=0.001, fused=fused)
            wire = wire_plan(grad_layout(tree, config, plan), config)
            assert wire["fused"] is False
            assert wire["fused_hops"] == 0
            assert wire["bytes_per_step"] == 0
            out, _ = make_compressed_pmean(plan, config)(tree, {})
            outs.append(_host(out))
        assert _bits_equal(outs[0]["w"], outs[1]["w"])

    def test_fp8_world_bound_falls_back_to_staged(self):
        """fp8 grid partial sums are exact in f32 only while
        W * 448 * 512 <= 2^24 (W <= 73): past the bound the fused path
        must refuse rather than drift from bit-exactness."""
        config = CommsConfig(mode="fp8", fused=True)
        inside = types.SimpleNamespace(axes=("data",), world=73)
        beyond = types.SimpleNamespace(axes=("data",), world=74)
        assert fused_active(inside, config)
        assert not fused_active(beyond, config)
        # int8 accumulates in int32 — exact at any world size
        assert fused_active(
            beyond, dataclasses.replace(config, mode="int8"))

    def test_multi_axis_layout_falls_back_to_staged(self):
        """The manual ring is written over ONE named axis; a layout
        syncing over two (data x fsdp) keeps the staged psum."""
        config = CommsConfig(mode="int8", fused=True)
        multi = types.SimpleNamespace(axes=("data", "fsdp"), world=8)
        assert not fused_active(multi, config)
        assert not fused_active(
            types.SimpleNamespace(axes=("data",), world=1), config)


# -- wire accounting: bytes are invariant under fusion ------------------------


class TestFusedWireAccounting:
    def test_bytes_invariant_fused_vs_staged(self):
        """Fusing moves WHERE the payloads cross the wire (hop-sized
        chunks instead of one rendezvous), never how many bytes: the
        wire plan's byte accounting is identical, only the transport
        fields flip."""
        plan = ParallelPlan(mesh=_mesh(8))
        tree = _grad_tree()
        staged = CommsConfig(mode="int8", bucket_mb=0.001)
        fused = dataclasses.replace(staged, fused=True)
        ws = wire_plan(grad_layout(tree, staged, plan), staged)
        wf = wire_plan(grad_layout(tree, fused, plan), fused)
        assert ws["bytes_per_step"] == wf["bytes_per_step"]
        assert ws["f32_bytes_per_step"] == wf["f32_bytes_per_step"]
        assert ws["fused"] is False and ws["fused_hops"] == 0
        assert wf["fused"] is True
        assert wf["fused_hops"] == 2 * (wf["world"] - 1) == 14

    def test_fused_hop_span_and_step_counter(self):
        """One ``comms/fused_hop`` span per fused sync (hop count as an
        attr — the hops live inside one jitted program), none on the
        staged path."""
        plan = ParallelPlan(mesh=_mesh(8))
        tree = _grad_tree()
        config = CommsConfig(mode="int8", bucket_mb=0.001, fused=True)
        n0 = _mark()
        make_compressed_pmean(plan, config)(tree, {})
        spans = [e for e in _events_since(n0)
                 if e.get("name") == "comms/fused_hop"]
        assert spans and spans[-1].get("attrs", {}).get("hops") == 14
        n1 = _mark()
        make_compressed_pmean(
            plan, dataclasses.replace(config, fused=False))(tree, {})
        assert not [e for e in _events_since(n1)
                    if e.get("name") == "comms/fused_hop"]


# -- the plan pin + knob registry ---------------------------------------------


class TestFusedPlanArtifact:
    def test_signature_includes_fused_pin(self):
        """Only a pinned fused=True changes the plan identity — older
        signatures (and unpinned plans) stay byte-stable, the PR 15
        omit-default rule."""
        base = ParallelPlan(mesh=_mesh(2)).signature()
        assert ParallelPlan(
            mesh=_mesh(2), comms_fused=None).signature() == base
        assert ParallelPlan(
            mesh=_mesh(2), comms_fused=False).signature() == base
        assert ParallelPlan(
            mesh=_mesh(2), comms_fused=True).signature() != base
        with pytest.raises(ValueError):
            ParallelPlan(mesh=_mesh(2), comms_fused="yes")

    def test_comms_schedule_reports_fused_resolution(self):
        plan = ParallelPlan(mesh=_mesh(2))
        config = CommsConfig(mode="int8", fused=True)
        sched = plan.comms_schedule(config)
        assert sched["fused"] is True and sched["fused_pinned"] is False
        pinned = ParallelPlan(mesh=_mesh(2), comms_fused=False)
        sched = pinned.comms_schedule(config)
        assert sched["fused"] is False and sched["fused_pinned"] is True

    def test_resolve_fused_plan_wins_over_env(self):
        config = CommsConfig(mode="int8", fused=False)
        pinned = ParallelPlan(mesh=_mesh(2), comms_fused=True)
        assert resolve_fused(pinned, config).fused is True
        unpinned = ParallelPlan(mesh=_mesh(2))
        assert resolve_fused(unpinned, config).fused is False
        assert resolve_fused(pinned, None) is None

    def test_knobs_declared_and_clamped(self, monkeypatch):
        from tpuframe.parallel.comms_env import (
            COMMS_ENV_DOMAINS,
            COMMS_ENV_VARS,
            comms_fused_block,
        )

        assert "TPUFRAME_COMMS_FUSED" in COMMS_ENV_VARS
        assert COMMS_ENV_DOMAINS["TPUFRAME_COMMS_FUSED"]["type"] == "bool"
        assert COMMS_ENV_DOMAINS["TPUFRAME_COMMS_FUSED_BLOCK"]["type"] == "int"
        assert comms_fused_block({}) == 2048
        # clamps into the declared domain, then quantizes to lane width
        assert comms_fused_block(
            {"TPUFRAME_COMMS_FUSED_BLOCK": "1000"}) == 896
        assert comms_fused_block(
            {"TPUFRAME_COMMS_FUSED_BLOCK": "1"}) == 128
        monkeypatch.setenv("TPUFRAME_COMMS_COMPRESSION", "int8")
        monkeypatch.setenv("TPUFRAME_COMMS_FUSED", "1")
        assert CommsConfig.from_env().fused is True


# -- EF residual portability with the fused wire ------------------------------


class TestFusedResidualShrinkFold:
    def test_shrink_fold_mean_correct_with_fused(self, tmp_path):
        """The PR-6 reshard path with the fused transport on: save at
        dp=4, restore at dp=2 — the folded residual is the world-ratio-
        scaled group sum, exactly as with the staged wire (folding is
        over the WORLD dim; the transport never touches it)."""
        from tpuframe.ckpt import Checkpointer

        config = CommsConfig(mode="int8", bucket_mb=0.001, fused=True)
        plan4 = ParallelPlan(mesh=_mesh(4))
        assert wire_plan(
            grad_layout(_state(plan4).params, config, plan4), config
        )["fused"] is True
        step = make_train_step(plan=plan4, grad_compression=config)
        s = _state(plan4, config)
        for batch in _batches(plan4, n=4):
            s, _ = step(s, dict(batch))
        ref = _host(s.comms)["flat"]
        assert float(np.abs(ref).max()) > 0
        with Checkpointer(str(tmp_path / "ck")) as ck:
            ck.save(s, step=4, plan=plan4)
            ck.wait()
            plan2 = plan4.rebind(_mesh(2))
            n0 = _mark()
            restored, _ = ck.restore(
                _state(plan2, config, seed=9), plan=plan2)
        folded = np.asarray(restored.comms["flat"])
        np.testing.assert_allclose(
            folded, ref.reshape(2, 2, *ref.shape[1:]).sum(axis=1) * 0.5,
            rtol=1e-6, atol=1e-7)
        assert len(_events_since(n0, "comms/ef_reshard")) == 1


# -- compile spine ------------------------------------------------------------


class TestFusedCompileSpine:
    def test_zero_recompiles_with_fused_wire(self):
        """The fused step is a first-class compile-spine citizen:
        precompile AOT-lowers the ring program, the fit dispatches
        straight to the executable, zero compile/recompile and zero
        compile/aot_fallback — and the wire plan names the fused
        transport it compiled."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=48, image_size=8, num_classes=4, seed=0)
        trainer = Trainer(
            Tiny(),
            train_dataloader=DataLoader(ds, batch_size=8, shuffle=True, seed=0),
            max_duration="2ep",
            optimizer="adam",
            num_classes=4,
            plan=ParallelPlan(mesh=_mesh(8), comms_fused=True),
            grad_compression=CommsConfig(mode="int8", bucket_mb=0.001),
            eval_interval=0,
            log_interval=0,
        )
        report = trainer.precompile(wait=True)
        assert report["steps"]
        assert any(k[0] == "train" for k in trainer._compiled)  # AOT armed
        tele = get_telemetry()
        fused0 = tele.registry.counter("comms/fused_steps").value
        n0 = _mark()
        trainer.fit()
        assert _events_since(n0, "compile/recompile") == []
        assert _events_since(n0, "compile/aot_fallback") == []
        wire = trainer._train_step.wire
        assert wire["fused"] is True and wire["fused_hops"] == 14
        assert tele.registry.counter("comms/fused_steps").value > fused0


# -- quant_wire kernel parity (interpret mode) --------------------------------


class TestQuantWireKernels:
    SHAPES = ((1, 64), (3, 130), (8, 2048))

    def test_amax_and_encode_bit_exact(self):
        """The kernels reproduce the staged wire's arithmetic bit for
        bit (amax + both encode grids, stochastic noise included) —
        the dispatch path may never decide the wire's bits."""
        from tpuframe.ops.quant_wire import (
            bucket_abs_max,
            bucket_abs_max_reference,
            quant_encode,
            quant_encode_reference,
        )

        rng = np.random.default_rng(0)
        for shape in self.SHAPES:
            v = jnp.asarray(rng.standard_normal(shape) * 9, jnp.float32)
            assert _bits_equal(
                bucket_abs_max(v, interpret=True),
                bucket_abs_max_reference(v))
            amax = bucket_abs_max_reference(v)
            noise = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
            for mode, nz in (("int8", None), ("int8", noise), ("fp8", None)):
                qk, dk = quant_encode(v, amax, mode, noise=nz, interpret=True)
                qr, dr = quant_encode_reference(v, amax, mode, noise=nz)
                assert _bits_equal(qk, qr), (shape, mode, nz is not None)
                assert _bits_equal(dk, dr), (shape, mode)

    def test_decode_matches_reference_and_propagates_nan(self):
        """Decode runs fused mul chains whose rounding XLA may schedule
        differently inside the kernel (1-ulp class) — close, not
        bit-pinned; the non-finite-amax -> NaN contract IS pinned."""
        from tpuframe.ops.quant_wire import (
            quant_decode,
            quant_decode_reference,
        )

        rng = np.random.default_rng(1)
        total = jnp.asarray(
            rng.integers(-1016, 1016, (5, 256)), jnp.int32)
        amax = jnp.asarray(
            np.abs(rng.standard_normal((5, 1))) * 20, jnp.float32)
        amax = amax.at[2, 0].set(jnp.inf)
        got = quant_decode(total, amax, "int8", 8, interpret=True)
        want = quant_decode_reference(total, amax, "int8", 8)
        assert np.isnan(np.asarray(got)[2]).all()
        np.testing.assert_allclose(
            np.where(np.isnan(want), 0, np.asarray(got)),
            np.where(np.isnan(want), 0, np.asarray(want)),
            rtol=1e-6, atol=1e-6)

    def test_cpu_default_dispatch_is_reference(self):
        """No env knobs, CPU backend: the dispatchers take the jnp
        reference path — existing CPU callers see identical bits with
        zero Pallas in the program."""
        from tpuframe.ops.dispatch import pallas_mode
        from tpuframe.ops.quant_wire import (
            bucket_abs_max,
            bucket_abs_max_reference,
        )

        assert pallas_mode() is None
        v = jnp.asarray(
            np.random.default_rng(2).standard_normal((4, 96)), jnp.float32)
        assert _bits_equal(bucket_abs_max(v), bucket_abs_max_reference(v))

    def test_ops_package_lazy_exports(self):
        import tpuframe.ops as ops

        for name in ("bucket_abs_max", "quant_encode", "quant_decode"):
            assert name in ops.__all__
            assert callable(getattr(ops, name))


# -- diagnosis: the top-op table's first consumer -----------------------------


class TestDiagnosisFusedMove:
    def _report(self, top_ops, mode="int8"):
        return {
            "step_time": {"mean": 1.0, "count": 10},
            "per_step": [{"bound": "compute"}] * 10,
            "per_rank": [],
            "comms": {"mode": mode},
            "device_time": {"top_ops": top_ops},
        }

    def test_compute_bound_wire_math_flips_fused(self):
        """Staged encode/decode math surfacing in top_ops while the
        wire is compressed -> propose TPUFRAME_COMMS_FUSED=1 (and keep
        the Pallas paths engaged for fusable compute)."""
        from tpuframe.autotune.diagnosis import diagnose

        d = diagnose(self._report([
            {"name": "convert.42", "class": "compute",
             "count": 900, "total_s": 2.0, "pct": 14.0},
            {"name": "round-nearest.7", "class": "compute",
             "count": 900, "total_s": 1.5, "pct": 11.0},
            {"name": "fusion.3", "class": "compute",
             "count": 900, "total_s": 1.0, "pct": 8.0},
        ]))
        assert d.bound == "compute"
        assert d.detail["top_ops"]
        knobs = {m.knob: m.value for m in d.moves}
        assert knobs.get("TPUFRAME_COMMS_FUSED") == "1"
        assert knobs.get("TPUFRAME_DISABLE_PALLAS") == "0"

    def test_wire_off_means_no_fused_move(self):
        """The same top-op shape at mode none proposes nothing fused —
        there is no staged wire to fuse."""
        from tpuframe.autotune.diagnosis import diagnose

        d = diagnose(self._report([
            {"name": "convert.42", "class": "compute",
             "count": 900, "total_s": 2.0, "pct": 14.0},
        ], mode="none"))
        assert d.bound == "compute"
        assert "TPUFRAME_COMMS_FUSED" not in {m.knob for m in d.moves}
