"""Train-engine tests: jitted steps, algorithms, durations, Trainer loop.

Covers the reference's de-facto validation strategy (SURVEY.md §4): local
smoke run, 1-epoch cheap run, loss-falls regression signal, post-train
inference spot check — on the 8-device simulated mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe.core import MeshSpec
from tpuframe.core import runtime as rt
from tpuframe.data import DataLoader, SyntheticImageDataset
from tpuframe.models import MnistNet, ResNet18
from tpuframe.parallel import ParallelPlan
from tpuframe.train import (
    CutMix,
    Duration,
    EarlyStopping,
    LabelSmoothing,
    MixUp,
    Trainer,
    create_train_state,
    cross_entropy,
    make_eval_step,
    make_grad_accum_step,
    make_train_step,
    param_count,
)


@pytest.fixture(autouse=True)
def fresh_runtime():
    rt.reset_runtime()
    rt.initialize(MeshSpec(data=-1))
    yield
    rt.reset_runtime()


def small_state(num_classes=10, image=28, channels=1, plan=None):
    model = MnistNet(num_classes=num_classes)
    return model, create_train_state(
        model,
        jax.random.PRNGKey(0),
        jnp.zeros((1, image, image, channels)),
        optax.adam(1e-3),
        plan=plan,
        init_kwargs={"train": False},
    )


class TestDuration:
    def test_parse(self):
        assert Duration.parse("2ep") == Duration(2, "ep")
        assert Duration.parse("500ba").unit == "ba"
        assert Duration.parse(3) == Duration(3, "ep")

    def test_reached(self):
        d = Duration.parse("2ep")
        assert not d.reached(epoch=1, batch=999, samples=0)
        assert d.reached(epoch=2, batch=0, samples=0)

    def test_bad(self):
        with pytest.raises(ValueError):
            Duration.parse("2 epochs")


class TestSteps:
    @pytest.mark.parametrize("flavor", ["plain", "health", "grad_accum"])
    def test_lowered_step_carries_the_device_side_names(self, flavor):
        """``tpuframe/forward``, ``tpuframe/optimizer`` and
        ``tpuframe/input_normalize`` are in the lowered step's op metadata
        (the backward pass under ``transpose(jvp(tpuframe/forward))``):
        what a trace's reduction finds the phases by after a refactor."""
        from tpuframe.fault.health import HealthPolicy

        _, state = small_state()

        def transform(batch):
            batch["image"] = batch["image"] * 2.0 - 1.0
            return batch

        shape = (32, 28, 28, 1)
        if flavor == "grad_accum":
            step = make_grad_accum_step(2, batch_transform=transform)
            shape = (2, 16, 28, 28, 1)
        else:
            step = make_train_step(
                batch_transform=transform,
                health=HealthPolicy() if flavor == "health" else None)
        batch = {"image": jnp.zeros(shape), "label": jnp.zeros(shape[:-3], jnp.int32)}
        text = step.lower(state, batch).as_text(debug_info=True)
        for name in ("tpuframe/forward", "transpose(jvp(tpuframe/forward))",
                     "tpuframe/optimizer", "tpuframe/input_normalize"):
            assert name in text, name

    def test_train_step_reduces_loss(self):
        _, state = small_state()
        step = make_train_step()
        rng = np.random.RandomState(0)
        x = rng.rand(32, 28, 28, 1).astype(np.float32)
        y = (x.mean((1, 2, 3)) > 0.5).astype(np.int32)  # learnable from pixels
        batch = {"image": x, "label": y}
        first = None
        for i in range(20):
            state, metrics = step(state, batch)
            if first is None:
                first = float(metrics["loss_sum"])
        assert float(metrics["loss_sum"]) < first

    def test_eval_step_weight_mask(self):
        _, state = small_state()
        estep = make_eval_step()
        x = np.random.RandomState(0).rand(8, 28, 28, 1).astype(np.float32)
        y = np.zeros(8, np.int32)
        full = estep(state, {"image": x, "label": y})
        half = estep(
            state,
            {
                "image": x,
                "label": y,
                "weight": np.array([1, 1, 1, 1, 0, 0, 0, 0], np.float32),
            },
        )
        assert float(full["count"]) == 8.0
        assert float(half["count"]) == 4.0

    def test_soft_labels(self):
        logits = jnp.array([[2.0, 0.0], [0.0, 2.0]])
        hard = cross_entropy(logits, jnp.array([0, 1]))
        soft = cross_entropy(logits, jnp.array([[1.0, 0.0], [0.0, 1.0]]))
        np.testing.assert_allclose(np.asarray(hard), np.asarray(soft), rtol=1e-6)

    def test_grad_accum_matches_large_batch(self):
        """2 microbatches of 16 must equal one batch of 32 — requires a
        deterministic model (no dropout/BN noise between the two paths)."""
        import flax.linen as nn

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                return nn.Dense(10)(x.reshape((x.shape[0], -1)))

        def mk():
            return create_train_state(
                Tiny(),
                jax.random.PRNGKey(0),
                jnp.zeros((1, 28, 28, 1)),
                optax.adam(1e-3),
                init_kwargs={"train": False},
            )

        state_a, state_b = mk(), mk()
        rng = np.random.RandomState(1)
        x = rng.rand(32, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, 32).astype(np.int32)

        big = make_train_step(donate=False)
        accum = make_grad_accum_step(2, donate=False)
        state_a, ma = big(state_a, {"image": x, "label": y})
        state_b, mb = accum(
            state_b, {"image": x.reshape(2, 16, 28, 28, 1), "label": y.reshape(2, 16)}
        )
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(state_a.params)[0]),
            np.asarray(jax.tree.leaves(state_b.params)[0]),
            atol=1e-6,
        )
        assert float(mb["count"]) == 32.0

    def test_param_count(self):
        _, state = small_state()
        assert param_count(state) > 10_000


class TestAlgorithms:
    def _batch(self):
        rng = np.random.RandomState(0)
        return rng.rand(16, 32, 32, 3).astype(np.float32), rng.randint(
            0, 10, 16
        ).astype(np.int32)

    def test_label_smoothing(self):
        x, y = self._batch()
        xs, ys = LabelSmoothing(0.1, num_classes=10).apply(
            x, y, np.random.default_rng(0)
        )
        assert ys.shape == (16, 10)
        np.testing.assert_allclose(ys.sum(-1), 1.0, rtol=1e-6)
        assert ys.max() <= 0.91

    def test_cutmix_preserves_label_mass(self):
        x, y = self._batch()
        xs, ys = CutMix(1.0, num_classes=10).apply(x, y, np.random.default_rng(0))
        assert xs.shape == x.shape
        np.testing.assert_allclose(ys.sum(-1), 1.0, rtol=1e-5)

    def test_mixup(self):
        x, y = self._batch()
        xs, ys = MixUp(0.2, num_classes=10).apply(x, y, np.random.default_rng(0))
        np.testing.assert_allclose(ys.sum(-1), 1.0, rtol=1e-5)


class TestTrainerLoop:
    def _loaders(self, n=64, classes=4, size=28):
        train = SyntheticImageDataset(
            n=n, num_classes=classes, image_size=size, channels=1
        )
        evald = SyntheticImageDataset(
            n=32, num_classes=classes, image_size=size, channels=1, seed=9
        )
        lt = DataLoader(train, batch_size=16, shuffle=True,
                        process_index=0, process_count=1)
        le = DataLoader(evald, batch_size=16, drop_last=False,
                        process_index=0, process_count=1)
        return lt, le

    def test_one_epoch_fit(self):
        lt, le = self._loaders()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            eval_dataloader=le,
            max_duration="1ep",
            lr=1e-3,
            num_classes=4,
        )
        result = trainer.fit()
        assert "train_loss" in result.metrics
        assert "eval_accuracy" in result.metrics
        assert len(result.history) == 1
        assert trainer.batches_seen == 4  # 64 / 16

    @pytest.mark.slow
    def test_duration_in_batches(self):
        lt, _ = self._loaders()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            max_duration="2ba",
            num_classes=4,
        )
        trainer.fit()
        assert trainer.batches_seen == 2

    @pytest.mark.slow
    def test_loss_falls_over_epochs(self):
        lt, _ = self._loaders(n=128)
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            max_duration="4ep",
            lr=3e-3,
            num_classes=4,
            log_interval=0,
        )
        result = trainer.fit()
        assert result.history[-1]["train_loss"] < result.history[0]["train_loss"]

    @pytest.mark.slow
    def test_algorithms_in_loop(self):
        lt, le = self._loaders()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            eval_dataloader=le,
            max_duration="1ep",
            algorithms=[LabelSmoothing(0.1), CutMix(1.0)],
            num_classes=4,
        )
        result = trainer.fit()
        assert np.isfinite(result.metrics["train_loss"])

    @pytest.mark.slow
    def test_early_stopping(self):
        lt, le = self._loaders()
        stopper = EarlyStopping(monitor="eval_loss", patience=1)
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            eval_dataloader=le,
            max_duration="50ep",
            lr=0.0,  # loss can never improve -> must stop early
            callbacks=[stopper],
            num_classes=4,
        )
        result = trainer.fit()
        assert result.stopped_reason is not None
        assert trainer.epoch < 50

    def test_grad_accum_knob_matches_plain(self):
        # Trainer(grad_accum=4) must train identically to the plain step on
        # the same batches: grads average over microbatches, loss_sum/count
        # aggregate exactly.  Deterministic model (no dropout/BN) so the
        # comparison is tight.
        from flax import linen as nn

        class Lin(nn.Module):
            num_classes: int = 4

            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(self.num_classes)(x.reshape((x.shape[0], -1)))

        def loader():
            ds = SyntheticImageDataset(
                n=64, num_classes=4, image_size=8, channels=1
            )
            return DataLoader(
                ds, batch_size=16, shuffle=False, process_index=0, process_count=1
            )

        results = []
        finals = []
        for accum in (1, 2):  # micro 8 still divides the 8-way data mesh
            trainer = Trainer(
                Lin(),
                train_dataloader=loader(),
                max_duration="2ep",
                optimizer="sgd",
                lr=1e-2,
                num_classes=4,
                log_interval=0,
                grad_accum=accum,
            )
            results.append(trainer.fit())
            finals.append(trainer.state.params)
        assert results[0].metrics["train_loss"] == pytest.approx(
            results[1].metrics["train_loss"], rel=1e-4
        )
        for a, b in zip(jax.tree.leaves(finals[0]), jax.tree.leaves(finals[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_grad_accum_indivisible_batch_raises(self):
        lt, _ = self._loaders()
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            max_duration="1ep",
            num_classes=4,
            grad_accum=5,  # 16 % 5 != 0
        )
        with pytest.raises(ValueError, match="not divisible"):
            trainer.fit()

    @pytest.mark.slow
    def test_logger_receives_metrics(self):
        class Capture:
            def __init__(self):
                self.metrics, self.params = [], []

            def log_metrics(self, m, step):
                self.metrics.append((step, m))

            def log_params(self, p):
                self.params.append(p)

        cap = Capture()
        lt, _ = self._loaders()
        Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            max_duration="1ep",
            loggers=[cap],
            num_classes=4,
            log_interval=2,
        ).fit()
        assert cap.params and cap.metrics

    @pytest.mark.slow
    def test_predict_spot_check(self):
        lt, _ = self._loaders()
        trainer = Trainer(
            MnistNet(num_classes=4), train_dataloader=lt, max_duration="1ep",
            num_classes=4,
        )
        trainer.fit()
        img, _ = lt.dataset[0]
        logits = trainer.predict(np.asarray(img)[None])
        assert logits.shape == (1, 4)


@pytest.mark.slow
class TestTrainerSharded:
    def test_zero3_resnet_epoch(self):
        """Full Trainer epoch with ZeRO-3 params over a dp2 x fsdp4 mesh."""
        rt.reset_runtime()
        runtime = rt.initialize(MeshSpec(data=2, fsdp=4))
        plan = ParallelPlan(mesh=runtime.mesh, zero_stage=3, min_shard_elems=128)
        train = SyntheticImageDataset(n=32, num_classes=4, image_size=32, channels=3)
        lt = DataLoader(train, batch_size=16, process_index=0, process_count=1)
        trainer = Trainer(
            ResNet18(num_classes=4, stem="cifar"),
            train_dataloader=lt,
            max_duration="1ep",
            plan=plan,
            precision="bf16",
            num_classes=4,
        )
        result = trainer.fit()
        assert np.isfinite(result.metrics["train_loss"])
        # ZeRO-3: at least one large param is genuinely sharded over fsdp
        specs = jax.tree.leaves(
            jax.tree.map(
                lambda x: x.sharding.spec,
                trainer.state.params,
                is_leaf=lambda x: hasattr(x, "sharding"),
            ),
            is_leaf=lambda s: True,
        )
        assert any("fsdp" in tuple(jax.tree.leaves(list(s), is_leaf=lambda e: True)) or "fsdp" in str(s) for s in specs)


class TestDeviceNormalize:
    def test_uint8_on_device_normalize_matches_host_prenormalized(self):
        # normalize=(mean,std): uint8 crosses to the device raw and is
        # normalized inside the jitted step — must train identically to
        # feeding host-prenormalized floats.
        from flax import linen as nn

        class Lin(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(4)(x.reshape((x.shape[0], -1)))

        mean, std = (0.4, 0.45, 0.5), (0.2, 0.25, 0.3)
        rng = np.random.default_rng(11)
        raw = rng.integers(0, 256, (32, 8, 8, 3), dtype=np.uint8)
        labels = rng.integers(0, 4, (32,)).astype(np.int32)
        floats = (raw.astype(np.float32) / 255.0 - np.asarray(mean)) / np.asarray(std)

        class Arrays:
            def __init__(self, images):
                self.images = images

            def __len__(self):
                return len(self.images)

            def __getitem__(self, i):
                return self.images[i], int(labels[i])

        finals = []
        trainers = []
        for images, norm in ((raw, (mean, std)), (floats.astype(np.float32), None)):
            loader = DataLoader(
                Arrays(images), 16, shuffle=False, process_index=0, process_count=1
            )
            trainer = Trainer(
                Lin(),
                train_dataloader=loader,
                max_duration="1ep",
                optimizer="sgd",
                lr=1e-2,
                num_classes=4,
                log_interval=0,
                normalize=norm,
                sample_input=floats[:1].astype(np.float32),
            )
            result = trainer.fit()
            trainers.append(trainer)
            finals.append((result.metrics["train_loss"], trainer.state.params))
        assert finals[0][0] == pytest.approx(finals[1][0], rel=1e-4)
        for a, b in zip(jax.tree.leaves(finals[0][1]), jax.tree.leaves(finals[1][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        # predict() must apply the same normalization: raw uint8 into the
        # normalize trainer == prenormalized floats into the plain one
        p_raw = trainers[0].predict(raw[:4])
        p_float = trainers[1].predict(floats[:4].astype(np.float32))
        np.testing.assert_allclose(p_raw, p_float, atol=1e-3)

    def test_lowered_image_step_holds_no_normalize_custom_call(self, request):
        """An image Trainer's train step, lowered for the TPU with the
        backend's answer pinned to "compiled": the kernels that stay are
        there under their names, the normalize is plain ops under
        ``tpuframe/input_normalize`` and no custom call (PR 25: the layout
        changes a custom call forces around an NHWC batch cost seventy
        times the kernel)."""
        trainer = Trainer(
            MnistNet(num_classes=4), max_duration="1ba", num_classes=4,
            log_interval=0, normalize=((0.1307,), (0.3081,)),
            sample_input=np.zeros((1, 28, 28, 1), np.float32),
        )
        state = trainer.init_state()  # runs on this backend: pin after it
        request.getfixturevalue("compiled_backend")
        step = getattr(trainer._train_step, "_inner_jit", trainer._train_step)
        batch = {"image": jax.ShapeDtypeStruct((16, 28, 28, 1), np.uint8),
                 "label": jax.ShapeDtypeStruct((16,), np.int32)}
        text = step.trace(state, batch).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        assert 'kernel_name = "tpuframe_ce_fwd"' in text
        assert "tpuframe/input_normalize" in text
        assert "tpuframe_normalize" not in text

    def test_normalize_with_grad_accum(self):
        from flax import linen as nn

        class Lin(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(4)(x.reshape((x.shape[0], -1)))

        rng = np.random.default_rng(12)
        raw = rng.integers(0, 256, (32, 8, 8, 3), dtype=np.uint8)
        labels = rng.integers(0, 4, (32,)).astype(np.int32)

        class Arrays:
            def __len__(self):
                return 32

            def __getitem__(self, i):
                return raw[i], int(labels[i])

        loader = DataLoader(Arrays(), 16, process_index=0, process_count=1)
        trainer = Trainer(
            Lin(),
            train_dataloader=loader,
            max_duration="1ep",
            num_classes=4,
            log_interval=0,
            grad_accum=2,
            normalize=((0.5, 0.5, 0.5), (0.25, 0.25, 0.25)),
            sample_input=np.zeros((1, 8, 8, 3), np.float32),
        )
        result = trainer.fit()
        assert np.isfinite(result.metrics["train_loss"])


    def test_train_accum_and_eval_steps_normalize_alike(self):
        """One transform feeds the train step, the grad-accum scan and the
        eval step: the same raw batch reaches the model bit-equal through
        all three, (B, ...) and (n_micro, micro, ...) alike."""
        from flax import linen as nn

        from tpuframe.ops import normalize_images_reference

        seen: dict[str, list] = {}

        class Probe(nn.Module):
            tag: str

            @nn.compact
            def __call__(self, x, train: bool = False):
                kind = f"{self.tag}/{'train' if train else 'eval'}"
                if not self.is_initializing():
                    jax.debug.callback(
                        lambda a: seen.setdefault(kind, []).append(np.asarray(a)), x)
                return nn.Dense(4)(x.reshape((x.shape[0], -1)))

        mean, std = (0.4, 0.45, 0.5), (0.2, 0.25, 0.3)
        rng = np.random.default_rng(14)
        raw = rng.integers(0, 256, (16, 8, 8, 3), dtype=np.uint8)
        labels = rng.integers(0, 4, (16,)).astype(np.int32)

        class Arrays:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                return raw[i], int(labels[i])

        for tag, grad_accum in (("plain", 1), ("accum", 2)):
            def loader():
                return DataLoader(Arrays(), 16, shuffle=False,
                                  process_index=0, process_count=1)

            Trainer(
                Probe(tag), train_dataloader=loader(), eval_dataloader=loader(),
                max_duration="1ep", num_classes=4, log_interval=0,
                grad_accum=grad_accum, normalize=(mean, std),
                sample_input=np.zeros((1, 8, 8, 3), np.float32),
            ).fit()
            jax.effects_barrier()

        def whole(kind):
            # the scan hands the model one microbatch at a time, in order
            got = np.concatenate(seen[kind])
            assert got.shape == raw.shape and got.dtype == np.float32, kind
            return got

        plain = whole("plain/train")
        for kind in ("plain/eval", "accum/train", "accum/eval"):
            np.testing.assert_array_equal(whole(kind), plain, err_msg=kind)
        assert len(seen["accum/train"]) == 2
        np.testing.assert_allclose(
            plain, np.asarray(normalize_images_reference(raw, mean, std)),
            atol=8 * float(np.finfo(np.float32).eps), rtol=0)


class TestMidEpochResume:
    @pytest.mark.slow
    def test_crash_resumes_with_next_batch_not_replay(self, tmp_path):
        """checkpoint_interval_batches bundles the consumer-true loader
        position; a fresh Trainer over the same checkpointer continues the
        epoch from that batch (batches_seen ends at exactly one epoch's
        worth, which is impossible if the epoch restarted from batch 0)."""
        from tpuframe.ckpt import Checkpointer

        def make():
            ds = SyntheticImageDataset(n=128, image_size=28, channels=1,
                                       num_classes=4)
            lt = DataLoader(ds, batch_size=16, shuffle=True, seed=5,
                            process_index=0, process_count=1)
            return Trainer(
                MnistNet(num_classes=4),
                train_dataloader=lt,
                max_duration="8ba",  # one full epoch is 8 batches
                lr=1e-3,
                num_classes=4,
                log_interval=0,
                checkpointer=Checkpointer(tmp_path / "ck"),
                checkpoint_interval_batches=3,
            )

        from tpuframe.train.callbacks import Callback

        class Bomb(Callback):
            """Simulate a hard crash mid-epoch (a duration-stop would
            legitimately write an epoch-end checkpoint; a crash must not)."""

            def __init__(self):
                self.n = 0

            def on_step_end(self, trainer, *a):
                self.n += 1
                if self.n >= 5:
                    raise RuntimeError("boom")

        first = make()
        first.callbacks = list(first.callbacks) + [Bomb()]
        with pytest.raises(RuntimeError, match="boom"):
            first.fit()
        assert first.batches_seen == 5  # crashed; last save was batch 3

        resumed = make()
        result = resumed.fit()
        # restored at batches_seen=3, trained batches 4..8 of the SAME epoch
        assert resumed.batches_seen == 8
        assert resumed.epoch == 1
        # the resumed run made 5 optimizer steps on top of the restored 3
        assert int(resumed.state.step) == 8
        assert result.error is None

    @pytest.mark.slow
    def test_snapshots_isolated_from_epoch_checkpoints(self, tmp_path):
        """Mid-epoch snapshots live in a sibling dir with max_to_keep=1:
        they never collide with or evict epoch-end checkpoints, the
        epoch-final batch is not snapshotted (the epoch-end save follows
        immediately), and a stale snapshot is deleted once a newer
        epoch-end checkpoint supersedes it (r3 advisor: it would
        otherwise linger on disk forever)."""
        from tpuframe.ckpt import Checkpointer

        ds = SyntheticImageDataset(n=128, image_size=28, channels=1,
                                   num_classes=4)
        lt = DataLoader(ds, batch_size=16, shuffle=True, seed=5,
                        process_index=0, process_count=1)
        ck = Checkpointer(tmp_path / "ck2")
        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=lt,
            max_duration="1ep",  # 8 batches
            lr=1e-3,
            num_classes=4,
            log_interval=0,
            checkpointer=ck,
            checkpoint_interval_batches=2,  # batches 2, 4, 6 (8 skipped)
        )
        trainer.fit()
        assert ck.all_steps() == [8]  # epoch-end only; no snapshot pollution
        _, meta = ck.restore(trainer.state)
        assert meta["epoch"] == 1 and "loader_state" not in meta
        # snapshots 2 and 4 were superseded mid-epoch (max_to_keep=1),
        # batch 8's was skipped (epoch-final), and batch 6's was deleted
        # by the newer epoch-end save at step 8
        intra = Checkpointer(str(tmp_path / "ck2") + "_intra")
        assert intra.all_steps() == []

    @pytest.mark.slow
    def test_leftover_snapshot_resumes_even_with_feature_off(self, tmp_path):
        """A crash mid-epoch leaves an _intra snapshot; a restart that
        DISABLES checkpoint_interval_batches must still auto-resume from
        it (r3 advisor: the old gate silently replayed from the older
        epoch-end checkpoint)."""
        from tpuframe.ckpt import Checkpointer
        from tpuframe.train.callbacks import Callback

        def make(interval):
            ds = SyntheticImageDataset(n=128, image_size=28, channels=1,
                                       num_classes=4)
            lt = DataLoader(ds, batch_size=16, shuffle=True, seed=5,
                            process_index=0, process_count=1)
            return Trainer(
                MnistNet(num_classes=4),
                train_dataloader=lt,
                max_duration="8ba",
                lr=1e-3,
                num_classes=4,
                log_interval=0,
                checkpointer=Checkpointer(tmp_path / "ck3"),
                checkpoint_interval_batches=interval,
            )

        class Bomb(Callback):
            def on_step_end(self, trainer, *a):
                if trainer.batches_seen >= 5:
                    raise RuntimeError("boom")

        first = make(interval=3)
        first.callbacks = [Bomb()]
        with pytest.raises(RuntimeError, match="boom"):
            first.fit()

        resumed = make(interval=None)  # feature off on the restart
        resumed.fit()
        # restored at batches_seen=3 (the snapshot), not 0: only batches
        # 4..8 were retrained
        assert resumed.batches_seen == 8
        assert int(resumed.state.step) == 8

    def test_untrackable_loader_with_mid_epoch_ckpt_is_a_clear_error(
        self, tmp_path
    ):
        """checkpoint_interval_batches + a duck-typed iterable without
        state_dict() must raise a curated error, not AttributeError deep
        in the prefetcher (r3 advisor, medium)."""
        from tpuframe.ckpt import Checkpointer

        class Duck:
            global_batch_size = 16
            process_count = 1

            def set_epoch(self, e):
                pass

            def __iter__(self):
                rng = np.random.default_rng(0)
                for _ in range(4):
                    yield (rng.standard_normal((16, 28, 28, 1)).astype(np.float32),
                           rng.integers(0, 4, (16,)).astype(np.int32))

        trainer = Trainer(
            MnistNet(num_classes=4),
            train_dataloader=Duck(),
            max_duration="1ep",
            num_classes=4,
            log_interval=0,
            sample_input=np.zeros((1, 28, 28, 1), np.float32),
            checkpointer=Checkpointer(tmp_path / "ck4"),
            checkpoint_interval_batches=2,
        )
        with pytest.raises(ValueError, match="checkpoint_interval_batches"):
            trainer.fit()
