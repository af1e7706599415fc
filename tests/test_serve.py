"""Serving: export artifacts + the deadline-aware dynamic-batching spine.

Export half: StableHLO artifacts round-trip without the model code (the
deployable side of the reference's C19 inference demo,
`/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py:370-387`).

Serving half: admission control verdicts, door-side poison validation,
bucketed AOT-batching correctness, and the seeded chaos acceptance
stories — `QueueFlood` overload (sheds fire, admitted p99 holds the
SLO), `PoisonRequest` (rejected at the door, batch-mates unaffected),
SIGTERM drain (zero dropped in-flight) — all on CPU with zero
`compile/recompile` events (SERVE.md).
"""

import json
import os
import signal as _signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models import MnistNet, ResNet18
from tpuframe.serve import export_model, load_model

HERE = os.path.dirname(os.path.abspath(__file__))


def small_model_and_vars(rng_seed=0):
    model = MnistNet(num_classes=4)
    variables = model.init(
        jax.random.PRNGKey(rng_seed), np.zeros((1, 28, 28, 1), np.float32),
        train=False,
    )
    return model, variables


class TestExportRoundTrip:
    def test_logits_match_direct_apply(self, tmp_path):
        model, variables = small_model_and_vars()
        x = np.random.RandomState(0).rand(3, 28, 28, 1).astype(np.float32)
        path = export_model(model, variables, x, tmp_path / "m.shlo")
        loaded = load_model(path)
        np.testing.assert_allclose(
            np.asarray(loaded(x)),
            np.asarray(model.apply(variables, x, train=False)),
            rtol=1e-5, atol=1e-5,
        )

    def test_batch_polymorphic_serves_any_batch(self, tmp_path):
        model, variables = small_model_and_vars()
        sample = np.zeros((2, 28, 28, 1), np.float32)
        loaded = load_model(
            export_model(model, variables, sample, tmp_path / "m.shlo")
        )
        for b in (1, 5, 16):
            out = loaded(np.zeros((b, 28, 28, 1), np.float32))
            assert out.shape == (b, 4)

    def test_fixed_shape_when_not_polymorphic(self, tmp_path):
        model, variables = small_model_and_vars()
        sample = np.zeros((2, 28, 28, 1), np.float32)
        loaded = load_model(
            export_model(model, variables, sample, tmp_path / "m.shlo",
                         batch_polymorphic=False)
        )
        assert loaded(sample).shape == (2, 4)
        with pytest.raises(ValueError):
            loaded(np.zeros((3, 28, 28, 1), np.float32))

    def test_fused_preprocess_takes_raw_uint8(self, tmp_path):
        """The artifact owns normalization: callers send raw bytes."""
        from tpuframe.ops import normalize_images

        model, variables = small_model_and_vars()

        def pre(x):
            return normalize_images(x, (0.5,), (0.25,))

        sample = np.zeros((2, 28, 28, 1), np.uint8)
        loaded = load_model(
            export_model(model, variables, sample, tmp_path / "m.shlo",
                         preprocess=pre)
        )
        raw = np.random.RandomState(1).randint(
            0, 255, (4, 28, 28, 1)
        ).astype(np.uint8)
        expect = model.apply(
            variables, np.asarray(pre(raw)), train=False
        )
        np.testing.assert_allclose(
            np.asarray(loaded(raw)), np.asarray(expect), rtol=1e-5, atol=1e-5
        )

    def test_meta_and_bad_file_rejected(self, tmp_path):
        model, variables = small_model_and_vars()
        path = export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        )
        loaded = load_model(path)
        assert loaded.meta["model"] == "MnistNet"
        assert loaded.meta["param_bytes"] > 0
        bad = tmp_path / "bad.shlo"
        bad.write_bytes(b"\x10\x00\x00\x00\x00\x00\x00\x00" + b"{}" * 8)
        with pytest.raises(ValueError):
            load_model(bad)

    @pytest.mark.parametrize(
        "payload",
        [
            b"PK\x03\x04" + b"\x00" * 64,  # zip magic: huge header_len
            b"\xff" * 128,  # header_len beyond file size
            b"\x08\x00\x00\x00\x00\x00\x00\x00" + b"\xfe\xed" * 32,  # non-utf8
            b"",  # empty file
        ],
    )
    def test_arbitrary_binaries_raise_valueerror(self, tmp_path, payload):
        bad = tmp_path / "garbage.bin"
        bad.write_bytes(payload)
        with pytest.raises(ValueError):
            load_model(bad)


class TestTrainerExport:
    def test_trained_model_exports_with_normalize_baked_in(self, tmp_path):
        """Trainer.export: the serving artifact owns the trainer's own
        normalize= constants, so it consumes the same raw batches
        training did and reproduces Trainer.predict."""
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=28, channels=1,
                                   num_classes=4)
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=DataLoader(ds, batch_size=16, shuffle=True,
                                        process_index=0, process_count=1),
            max_duration="1ep",
            num_classes=4,
            log_interval=0,
            normalize=((0.5,), (0.25,)),
        )
        trainer.fit()
        path = trainer.export(tmp_path / "trained.shlo")
        served = load_model(path)
        # raw batches in the dataset's own dtype (uint8 pixels) — the
        # artifact's input spec comes from the trainer's init sample
        raw = np.random.RandomState(0).randint(
            0, 255, (5, 28, 28, 1)
        ).astype(served.meta["input_dtype"])
        np.testing.assert_allclose(
            np.asarray(served(raw)), trainer.predict(raw),
            rtol=2e-5, atol=2e-5,
        )


class TestShardedTrainerExport:
    def test_mesh_sharded_params_export_as_single_device_artifact(
        self, tmp_path
    ):
        """A multi-chip trainer's params are sharded jax Arrays; the
        artifact must NOT remember the training mesh (it serves on one
        device)."""
        from tpuframe.core import MeshSpec
        from tpuframe.core import runtime as rt
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.parallel import ParallelPlan
        from tpuframe.train import Trainer

        rt.reset_runtime()
        try:
            rt.initialize(MeshSpec(data=-1))  # all 8 simulated devices
            plan = ParallelPlan(mesh=rt.current_runtime().mesh)
            ds = SyntheticImageDataset(n=32, image_size=28, channels=1,
                                       num_classes=4)
            trainer = Trainer(
                MnistNet(num_classes=4),
                train_dataloader=DataLoader(ds, batch_size=16, shuffle=True,
                                            process_index=0, process_count=1),
                max_duration="1ep",
                num_classes=4,
                log_interval=0,
                plan=plan,
            )
            trainer.fit()
            served = load_model(trainer.export(tmp_path / "sharded.shlo"))
            assert served._exported.nr_devices == 1
            out = served(
                np.zeros((3, 28, 28, 1), served.meta["input_dtype"])
            )
            assert out.shape == (3, 4)
        finally:
            rt.reset_runtime()


class TestTorchCheckpointToArtifact:
    def test_imported_torchvision_weights_export_and_serve(self, tmp_path):
        """The full migration path: torch .pt file -> flax -> portable
        serving artifact reproducing the torch model's golden logits."""
        torch = pytest.importorskip("torch")
        from tpuframe.models.interop import import_torch_resnet

        sd = torch.load(
            os.path.join(HERE, "fixtures", "resnet18_tv_w4.pt"),
            map_location="cpu", weights_only=True,
        )
        golden = np.load(
            os.path.join(HERE, "fixtures", "resnet18_tv_w4_golden.npz")
        )
        model = ResNet18(num_filters=4, num_classes=10)
        variables = import_torch_resnet(sd)
        loaded = load_model(
            export_model(model, variables, golden["x"], tmp_path / "r18.shlo")
        )
        np.testing.assert_allclose(
            np.asarray(loaded(golden["x"])), golden["logits"],
            atol=2e-4, rtol=1e-3,
        )


# ===========================================================================
# the serving spine (PR 8): admission, validation, engine, chaos, drain
# ===========================================================================


def _linear_model(item_shape=(4, 3), classes=3, seed=0):
    """Tiny jit-able stand-in for an export: instant compile, exact
    reference values on the host."""
    n = int(np.prod(item_shape))
    W = np.random.RandomState(seed).rand(n, classes).astype(np.float32)

    def fn(x):
        return jnp.asarray(x).reshape(x.shape[0], -1) @ W

    return fn, W


def _engine(**over):
    from tpuframe.serve import ServeEngine, ServeKnobs

    fn, W = _linear_model()
    kn = dict(buckets=(1, 4), slo_ms=5000, queue_cap=16, batch_wait_ms=1.0)
    kn.update(over)
    eng = ServeEngine(fn, knobs=ServeKnobs(**kn),
                      item_shape=(4, 3), dtype="float32")
    return eng, W


class TestExportedModelValidation:
    """Satellite: wrong dtype/shape fails with a message naming the
    exported signature, not an opaque XLA error; version checks are
    direction-aware."""

    def test_wrong_dtype_names_expected_signature(self, tmp_path):
        model, variables = small_model_and_vars()
        loaded = load_model(export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        ))
        with pytest.raises(ValueError, match=r"float32.*cast"):
            loaded(np.zeros((2, 28, 28, 1), np.float64))

    def test_wrong_trailing_shape_names_expected_signature(self, tmp_path):
        model, variables = small_model_and_vars()
        loaded = load_model(export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        ))
        with pytest.raises(ValueError, match=r"\(b, 28, 28, 1\)"):
            loaded(np.zeros((2, 32, 32, 1), np.float32))
        with pytest.raises(ValueError, match="expected an array"):
            loaded("not an array")

    def test_newer_version_blob_says_upgrade(self, tmp_path):
        model, variables = small_model_and_vars()
        path = export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        )
        raw = open(path, "rb").read()
        hlen = int.from_bytes(raw[:8], "little")
        meta = json.loads(raw[8:8 + hlen])
        meta["version"] = 99
        header = json.dumps(meta).encode()
        newer = tmp_path / "newer.shlo"
        newer.write_bytes(
            len(header).to_bytes(8, "little") + header + raw[8 + hlen:]
        )
        with pytest.raises(ValueError, match="newer tpuframe.*upgrade"):
            load_model(newer)

    def test_read_export_meta_is_stdlib_and_matches(self, tmp_path):
        from tpuframe.serve import read_export_meta

        model, variables = small_model_and_vars()
        path = export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        )
        meta = read_export_meta(path)
        assert meta["model"] == "MnistNet"
        assert meta["input_shape"] == [1, 28, 28, 1]
        with pytest.raises(ValueError):
            read_export_meta(__file__)  # a .py file is not an artifact


class TestServeKnobs:
    def test_env_overrides_and_tolerant_parsing(self, monkeypatch):
        from tpuframe.serve import ServeKnobs

        monkeypatch.setenv("TPUFRAME_SERVE_BUCKETS", "8,2,2")
        monkeypatch.setenv("TPUFRAME_SERVE_SLO_MS", "250")
        monkeypatch.setenv("TPUFRAME_SERVE_QUEUE_CAP", "32")
        monkeypatch.setenv("TPUFRAME_SERVE_SHED_POLICY", "shed-oldest")
        kn = ServeKnobs.from_env()
        assert kn.buckets == (2, 8)
        assert kn.slo_ms == 250 and kn.queue_cap == 32
        assert kn.shed_policy == "shed-oldest"

    def test_malformed_env_reads_as_default(self, monkeypatch):
        from tpuframe.serve import ServeKnobs

        monkeypatch.setenv("TPUFRAME_SERVE_BUCKETS", "a,b")
        monkeypatch.setenv("TPUFRAME_SERVE_SLO_MS", "garbage")
        monkeypatch.setenv("TPUFRAME_SERVE_SHED_POLICY", "panic")
        kn = ServeKnobs.from_env()
        d = ServeKnobs()
        assert kn.buckets == d.buckets
        assert kn.slo_ms == d.slo_ms
        assert kn.shed_policy == d.shed_policy


class TestAdmission:
    def _req(self):
        return object()

    def test_reject_new_when_full(self):
        from tpuframe.serve import AdmissionController

        ac = AdmissionController(cap=2, policy="reject-new")
        assert ac.offer(self._req()) == ("admitted", None)
        assert ac.offer(self._req()) == ("admitted", None)
        verdict, shed = ac.offer(self._req())
        assert verdict == "rejected-queue-full" and shed is None
        assert ac.depth() == 2

    def test_shed_oldest_evicts_head(self):
        from tpuframe.serve import AdmissionController

        ac = AdmissionController(cap=2, policy="shed-oldest")
        r1, r2, r3 = self._req(), self._req(), self._req()
        ac.offer(r1), ac.offer(r2)
        verdict, shed = ac.offer(r3)
        assert verdict == "admitted" and shed is r1
        assert ac.pop_nowait() is r2 and ac.pop_nowait() is r3

    def test_draining_rejects_new_pops_old(self):
        from tpuframe.serve import AdmissionController

        ac = AdmissionController(cap=4)
        r = self._req()
        ac.offer(r)
        ac.start_drain()
        assert ac.offer(self._req()) == ("rejected-draining", None)
        assert ac.pop(timeout=0.1) is r
        assert ac.pop(timeout=0.1) is None  # drained + empty: no block

    def test_queue_depth_gauge_tracks(self):
        from tpuframe.serve import AdmissionController
        from tpuframe.track.telemetry import get_telemetry

        g = get_telemetry().registry.gauge("serve/queue_depth")
        ac = AdmissionController(cap=4)
        ac.offer(self._req()), ac.offer(self._req())
        assert g.value == 2.0
        ac.pop_nowait()
        assert g.value == 1.0


class TestValidation:
    def test_shape_dtype_pixels_nan(self):
        from tpuframe.serve import InvalidRequest, validate_payload

        ok = np.zeros((4, 3), np.float32)
        validate_payload(ok, item_shape=(4, 3), dtype="float32")
        with pytest.raises(InvalidRequest, match="shape"):
            validate_payload(np.zeros((5, 3), np.float32),
                             item_shape=(4, 3), dtype="float32")
        with pytest.raises(InvalidRequest, match="dtype"):
            validate_payload(np.zeros((4, 3), np.float64),
                             item_shape=(4, 3), dtype="float32")
        with pytest.raises(InvalidRequest, match="budget"):
            validate_payload(ok, item_shape=(4, 3), dtype="float32",
                             max_pixels=4)
        bad = ok.copy()
        bad[1, 2] = np.inf
        with pytest.raises(InvalidRequest, match="non-finite"):
            validate_payload(bad, item_shape=(4, 3), dtype="float32")
        with pytest.raises(InvalidRequest, match="array"):
            validate_payload([1, 2, 3], item_shape=(4, 3), dtype="float32")

    def test_uint8_payload_skips_finiteness(self):
        from tpuframe.serve import validate_payload

        validate_payload(np.zeros((2, 2), np.uint8),
                         item_shape=(2, 2), dtype="uint8")


class TestEngine:
    def test_roundtrip_matches_reference_across_buckets(self):
        eng, W = _engine()
        with eng:
            xs = [np.random.RandomState(i).rand(4, 3).astype(np.float32)
                  for i in range(7)]
            futs = [eng.submit(x) for x in xs]
            for x, f in zip(xs, futs):
                np.testing.assert_allclose(
                    f.result(timeout=10), x.reshape(-1) @ W, rtol=1e-5
                )
                assert f.verdict == "ok" and f.latency_s > 0

    def test_zero_recompiles_and_occupancy(self):
        from tpuframe.track.telemetry import get_telemetry

        reg = get_telemetry().registry
        rc0 = reg.counter("compile/recompiles").value
        eng, W = _engine(queue_cap=64)
        with eng:
            futs = [eng.submit(np.random.RandomState(i).rand(4, 3)
                               .astype(np.float32)) for i in range(24)]
            for f in futs:
                f.result(timeout=10)
        assert reg.counter("compile/recompiles").value == rc0
        assert reg.histogram("serve/batch_occupancy").window()

    def test_backend_error_fails_only_that_batch(self):
        from tpuframe.fault.chaos import ChaosPlan, RaiseAt

        eng, W = _engine(buckets=(1,), batch_wait_ms=0.0)
        plan = ChaosPlan([RaiseAt("serve/infer", step=0)])
        with eng, plan.active():
            x = np.random.RandomState(0).rand(4, 3).astype(np.float32)
            f1 = eng.submit(x)
            with pytest.raises(OSError, match="chaos"):
                f1.result(timeout=10)
            f2 = eng.submit(x)  # the loop survived the failed batch
            np.testing.assert_allclose(
                f2.result(timeout=10), x.reshape(-1) @ W, rtol=1e-5
            )

    def test_expired_deadline_sheds_before_batch_slot(self):
        from tpuframe.fault.chaos import ChaosPlan, SlowConsumer
        from tpuframe.serve import RequestShed

        eng, _ = _engine(buckets=(1,), batch_wait_ms=0.0)
        plan = ChaosPlan([SlowConsumer(step=0, stall_s=0.4)])
        with eng, plan.active():
            x = np.zeros((4, 3), np.float32)
            f1 = eng.submit(x)              # batch 0: wedged 0.4s
            f2 = eng.submit(x, deadline_ms=50)  # expires in the queue
            f1.result(timeout=10)
            with pytest.raises(RequestShed, match="shed-deadline"):
                f2.result(timeout=10)
            assert f2.verdict == "shed-deadline"

    def test_exported_model_through_engine(self, tmp_path):
        from tpuframe.serve import ServeEngine, ServeKnobs

        model, variables = small_model_and_vars()
        served = load_model(export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        ))
        eng = ServeEngine(
            served, knobs=ServeKnobs(buckets=(1, 2), slo_ms=10_000)
        ).start()
        try:
            x = np.random.RandomState(0).rand(28, 28, 1).astype(np.float32)
            out = eng.submit(x).result(timeout=30)
            np.testing.assert_allclose(
                out, np.asarray(model.apply(
                    variables, x[None], train=False))[0],
                rtol=1e-4, atol=1e-5,
            )
        finally:
            eng.drain(timeout=10)

    def test_plain_callable_requires_signature(self):
        from tpuframe.serve import ServeEngine

        with pytest.raises(ValueError, match="item_shape"):
            ServeEngine(lambda x: x)


class TestChaosAcceptance:
    """The ISSUE's seeded acceptance stories, all CPU."""

    def test_queue_flood_sheds_and_p99_holds_slo(self):
        """QueueFlood overload => shed verdicts fire AND the p99 of
        admitted (served) requests stays under the configured SLO —
        bounded degradation, not queue-wait meltdown."""
        from tpuframe.fault.chaos import ChaosPlan, QueueFlood
        from tpuframe.serve import RequestRejected, RequestShed
        from tpuframe.track.telemetry import get_telemetry

        reg = get_telemetry().registry
        slo_ms = 2000.0
        eng, W = _engine(queue_cap=8, shed_policy="shed-oldest",
                         slo_ms=slo_ms)
        shed0 = reg.counter("serve/shed").value
        rc0 = reg.counter("compile/recompiles").value
        plan = ChaosPlan([QueueFlood(120, step=3)])
        lats = []
        with eng, plan.active():
            for i in range(40):
                x = np.random.RandomState(i).rand(4, 3).astype(np.float32)
                try:
                    f = eng.submit(x)
                    f.result(timeout=20)
                except (RequestRejected, RequestShed):
                    continue
                lats.append(f.latency_s)
        assert plan.fired_count() == 1
        assert reg.counter("serve/shed").value > shed0  # sheds fired
        assert lats, "every client request was lost under overload"
        lats.sort()
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))]
        assert p99 * 1e3 <= slo_ms, f"admitted p99 {p99*1e3:.0f}ms > SLO"
        # the overload never pushed the backend off a precompiled shape
        assert reg.counter("compile/recompiles").value == rc0
        names = [e.get("name") for e in get_telemetry().recent_events(500)]
        assert "serve/shed" in names and "serve/flood" in names

    def test_poison_request_rejected_batchmates_unaffected(self):
        """PoisonRequest => InvalidRequest at the door; requests that
        would have shared its batch serve bit-exact results."""
        from tpuframe.fault.chaos import ChaosPlan, PoisonRequest
        from tpuframe.serve import InvalidRequest

        eng, W = _engine(batch_wait_ms=5.0)  # wide window: batches form
        plan = ChaosPlan([PoisonRequest(step=2)])
        xs = [np.random.RandomState(i).rand(4, 3).astype(np.float32)
              for i in range(6)]
        results: dict[int, object] = {}
        poisoned: list[int] = []

        def client(i):
            try:
                results[i] = eng.submit(xs[i]).result(timeout=20)
            except InvalidRequest:
                poisoned.append(i)

        with eng, plan.active():
            # serialized submits so the seeded step (2) hits exactly one
            # request; threads would race the submit counter
            threads = []
            for i in range(6):
                t = threading.Thread(target=client, args=(i,))
                t.start()
                t.join(timeout=0.02)  # overlap completion, order submits
                threads.append(t)
            for t in threads:
                t.join(timeout=20)
        assert poisoned == [2]
        assert sorted(results) == [0, 1, 3, 4, 5]
        for i, out in results.items():
            np.testing.assert_allclose(
                out, xs[i].reshape(-1) @ W, rtol=1e-5,
                err_msg=f"batch-mate {i} corrupted by the poison request",
            )

    def test_sigterm_drains_with_zero_dropped_inflight(self):
        """SIGTERM mid-load => in-flight requests all complete, new
        requests get draining verdicts, the engine exits cleanly with
        telemetry flushed."""
        from tpuframe.fault import preempt
        from tpuframe.fault.chaos import ChaosPlan, SlowConsumer
        from tpuframe.serve import RequestRejected
        from tpuframe.track.telemetry import get_telemetry

        preempt.uninstall()
        watcher = preempt.install(signals=(_signal.SIGUSR1,))
        eng, W = _engine(buckets=(1,), batch_wait_ms=0.0)
        try:
            with eng:
                plan = ChaosPlan([SlowConsumer(step=0, stall_s=0.2)])
                with plan.active():
                    xs = [np.random.RandomState(i).rand(4, 3)
                          .astype(np.float32) for i in range(6)]
                    futs = [eng.submit(x) for x in xs]
                    # the platform reclaims the machine mid-load
                    os.kill(os.getpid(), _signal.SIGUSR1)
                    assert eng.drain(timeout=20), "drain did not complete"
                    for x, f in zip(xs, futs):  # zero dropped in-flight
                        np.testing.assert_allclose(
                            f.result(timeout=1), x.reshape(-1) @ W,
                            rtol=1e-5,
                        )
                    with pytest.raises(RequestRejected,
                                       match="rejected-draining"):
                        eng.submit(xs[0])
            events = get_telemetry().recent_events(500)
            drained = [e for e in events if e.get("name") == "serve/drained"]
            assert drained and drained[-1]["served"] >= 6
        finally:
            preempt.uninstall()


class TestServeWatchdog:
    def test_wedged_backend_produces_stall_report(self, tmp_path):
        """SlowConsumer past the serve/infer deadline => the watchdog
        dumps an attributed stall report instead of a silent hang."""
        from tpuframe.fault.chaos import ChaosPlan, SlowConsumer
        from tpuframe.track import telemetry as T
        from tpuframe.track.watchdog import Watchdog

        wd = Watchdog(deadlines={"serve/infer": 0.1}, poll_interval_s=0.05)
        T.configure(jsonl_dir=str(tmp_path), watchdog=wd)
        try:
            eng, _ = _engine(buckets=(1,), batch_wait_ms=0.0,
                             watchdog_s=0.1)
            plan = ChaosPlan([SlowConsumer(step=0, stall_s=0.5)])
            with eng, plan.active():
                f = eng.submit(np.zeros((4, 3), np.float32))
                f.result(timeout=10)
            assert any(r["name"] == "serve/infer" for r in wd.reports)
        finally:
            T.reset()


class TestServingServer:
    def test_http_predict_health_metrics_and_drain(self):
        import io
        import urllib.error
        import urllib.request

        from tpuframe.serve import ServingServer

        eng, W = _engine()
        srv = None
        with eng:
            srv = ServingServer(eng)
            try:
                x = np.random.RandomState(3).rand(4, 3).astype(np.float32)
                buf = io.BytesIO()
                np.save(buf, x)
                req = urllib.request.Request(
                    srv.url + "/predict", data=buf.getvalue(), method="POST",
                    headers={"X-Deadline-Ms": "5000"},
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    body = json.loads(resp.read())
                np.testing.assert_allclose(
                    np.asarray(body["output"], np.float32),
                    x.reshape(-1) @ W, rtol=1e-4,
                )
                assert body["verdict"] == "ok" and body["latency_ms"] > 0
                with urllib.request.urlopen(srv.url + "/healthz",
                                            timeout=10) as resp:
                    h = json.loads(resp.read())
                assert h["status"] == "ok"
                with urllib.request.urlopen(srv.url + "/metrics",
                                            timeout=10) as resp:
                    text = resp.read().decode()
                assert "tpuframe_serve_requests_served" in text
                # malformed body: 400 with the verdict, not a wedge
                bad = urllib.request.Request(
                    srv.url + "/predict", data=b"not-npy", method="POST"
                )
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(bad, timeout=10)
                assert ei.value.code == 400
                # draining replica: 503 so the balancer rotates away
                eng.drain(timeout=10)
                buf2 = io.BytesIO()
                np.save(buf2, x)
                req2 = urllib.request.Request(
                    srv.url + "/predict", data=buf2.getvalue(), method="POST"
                )
                with pytest.raises(urllib.error.HTTPError) as ei2:
                    urllib.request.urlopen(req2, timeout=10)
                assert ei2.value.code == 503
                with urllib.request.urlopen(srv.url + "/healthz",
                                            timeout=10) as resp:
                    assert json.loads(resp.read())["status"] == "draining"
            finally:
                srv.close()


class TestKnobRegistry:
    def test_all_env_vars_aggregates_every_spine(self):
        from tpuframe.compile.cache import COMPILE_ENV_VARS
        from tpuframe.fault.health import HEALTH_ENV_VARS
        from tpuframe.launch.remote import all_env_vars
        from tpuframe.serve import SERVE_ENV_VARS
        from tpuframe.track.telemetry import OBSERVABILITY_ENV_VARS

        agg = all_env_vars()
        for lst in (OBSERVABILITY_ENV_VARS, COMPILE_ENV_VARS,
                    HEALTH_ENV_VARS, SERVE_ENV_VARS):
            assert set(lst) <= set(agg)

    def test_remote_ships_serve_env(self, monkeypatch):
        from tpuframe.launch.remote import RemoteDistributor

        monkeypatch.setenv("TPUFRAME_SERVE_SLO_MS", "250")
        monkeypatch.setenv("TPUFRAME_SERVE_SHED_POLICY", "shed-oldest")
        rd = RemoteDistributor(["h0", "h1"])
        env = rd._worker_env(1, "h0", 1234, 1235, "tok", None)
        assert env["TPUFRAME_SERVE_SLO_MS"] == "250"
        assert env["TPUFRAME_SERVE_SHED_POLICY"] == "shed-oldest"


class TestDoctorServeSection:
    def test_section_with_export(self, tmp_path, scripts_not_in_tree):
        from tpuframe.doctor import serve_section

        model, variables = small_model_and_vars()
        path = export_model(
            model, variables, np.zeros((1, 28, 28, 1), np.float32),
            tmp_path / "m.shlo",
        )
        sec = serve_section(str(path))
        assert sec["export"]["model"] == "MnistNet"
        assert [1, 28, 28, 1] in sec["export"]["bucket_shapes"]
        assert scripts_not_in_tree(sec) == []
        assert sec["knobs"]["slo_ms"] > 0

    def test_section_with_bad_artifact_reports_not_crashes(self, tmp_path):
        from tpuframe.doctor import serve_section

        bad = tmp_path / "junk.bin"
        bad.write_bytes(b"\xff" * 64)
        sec = serve_section(str(bad))
        assert "error" in sec["export"]

    def test_section_without_export_still_has_knobs(self, scripts_not_in_tree):
        from tpuframe.doctor import serve_section

        sec = serve_section(None)
        assert "export" not in sec
        assert sec["knobs"]["slo_ms"] > 0
        assert scripts_not_in_tree(sec) == []


class TestAnalyzeServeLatency:
    def _run_logged_engine(self, tmp_path):
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            eng, _ = _engine()
            with eng:
                for i in range(20):
                    eng.submit(np.random.RandomState(i).rand(4, 3)
                               .astype(np.float32)).result(timeout=10)
        finally:
            T.reset()

    def test_skew_report_builds_serve_latency_block(self, tmp_path):
        from tpuframe.track.analyze import load_dir, skew_report

        self._run_logged_engine(tmp_path)
        report = skew_report(load_dir(str(tmp_path)))
        sv = report["serve_latency"]
        assert sv and sv["count"] == 20
        assert 0 < sv["p50"] <= sv["p99"]

    def test_baseline_gates_serve_p99_regression(self, tmp_path):
        from tpuframe.track.analyze import (
            baseline_diff,
            format_report,
            load_dir,
            skew_report,
        )

        self._run_logged_engine(tmp_path)
        report = skew_report(load_dir(str(tmp_path)))
        # a committed baseline 100x faster than this run: regression
        fast = tmp_path / "baseline_fast.json"
        fast.write_text(json.dumps({
            "backend": "cpu",
            "serve_latency": {"p50": 1e-7, "p95": 1e-7, "p99": 1e-7},
        }))
        diff = baseline_diff(report, str(fast), threshold=1.25,
                             backend="cpu")
        assert diff["regressions"] and \
            diff["regressions"][0]["ratio_serve_p99"] > 1.25
        assert "serve_p99" in format_report(report, diff)
        # vs an equal baseline: no regression
        same = tmp_path / "baseline_same.json"
        same.write_text(json.dumps({
            "backend": "cpu", "serve_latency": dict(report["serve_latency"]),
        }))
        ok = baseline_diff(report, str(same), threshold=1.25, backend="cpu")
        assert not ok["regressions"]


class TestReviewHardening:
    """Regression pins for the review findings: transport-level body cap,
    stop() shedding the queued remainder, watchdog_s=0 as a real
    disable, construction-time pixel budget, in-place poison on any
    memory layout, fixed-batch leading-dim validation."""

    def test_http_oversized_body_rejected_before_parse(self):
        import urllib.error
        import urllib.request

        from tpuframe.serve import ServingServer

        eng, _ = _engine()
        with eng:
            srv = ServingServer(eng)
            try:
                big = b"\x00" * (srv.max_body_bytes + 1)
                req = urllib.request.Request(
                    srv.url + "/predict", data=big, method="POST"
                )
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=10)
                assert ei.value.code == 413
            finally:
                srv.close()

    def test_stop_sheds_queued_requests_promptly(self):
        from tpuframe.fault.chaos import ChaosPlan, SlowConsumer
        from tpuframe.serve import RequestShed

        eng, W = _engine(buckets=(1,), batch_wait_ms=0.0)
        plan = ChaosPlan([SlowConsumer(step=0, stall_s=0.3)])
        with plan.active():
            eng.start()
            x = np.random.RandomState(0).rand(4, 3).astype(np.float32)
            f1 = eng.submit(x)          # batch 0: wedged 0.3s
            queued = [eng.submit(x) for _ in range(4)]
            eng.stop()                   # hard stop, not drain
            # the in-flight batch finishes either way; the QUEUED ones
            # must be shed with a verdict, not served or dropped
            for f in queued:
                with pytest.raises(RequestShed, match="shed-stopped"):
                    f.result(timeout=5)
                assert f.verdict == "shed-stopped"
            f1.result(timeout=5)

    def test_serve_watchdog_zero_disables_despite_global_default(
        self, tmp_path
    ):
        from tpuframe.fault.chaos import ChaosPlan, SlowConsumer
        from tpuframe.track import telemetry as T
        from tpuframe.track.watchdog import Watchdog

        wd = Watchdog(default_deadline_s=0.05, poll_interval_s=0.02)
        T.configure(jsonl_dir=str(tmp_path), watchdog=wd)
        try:
            eng, _ = _engine(buckets=(1,), batch_wait_ms=0.0, watchdog_s=0.0)
            plan = ChaosPlan([SlowConsumer(step=0, stall_s=0.3)])
            with eng, plan.active():
                eng.submit(np.zeros((4, 3), np.float32)).result(timeout=10)
            assert not any(r["name"] == "serve/infer" for r in wd.reports), \
                "watchdog_s=0 must disable the serve guard entirely"
        finally:
            T.reset()

    def test_pixel_budget_checked_at_construction(self):
        from tpuframe.serve import ServeEngine, ServeKnobs

        with pytest.raises(ValueError, match="element budget"):
            ServeEngine(lambda x: x, knobs=ServeKnobs(max_pixels=4),
                        item_shape=(4, 3), dtype="float32")

    def test_poison_fires_in_place_on_noncontiguous_payload(self):
        from tpuframe.fault.chaos import PoisonRequest

        base = np.ones((3, 4), np.float32)
        view = base.T  # non-contiguous: reshape(-1) would copy
        PoisonRequest().fire({"payload": view})
        assert np.isnan(view).any() and np.isnan(base).any()

    def test_fixed_batch_leading_dim_validated_at_the_door(self, tmp_path):
        model, variables = small_model_and_vars()
        loaded = load_model(export_model(
            model, variables, np.zeros((2, 28, 28, 1), np.float32),
            tmp_path / "m.shlo", batch_polymorphic=False,
        ))
        with pytest.raises(ValueError, match="exported signature"):
            loaded(np.zeros((3, 28, 28, 1), np.float32))
