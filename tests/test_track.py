"""MLflow-compatible tracking: file-store layout, metric history, artifacts,
model logging, logger plugin, run-id broadcast (single-process degenerate)."""

import os

import pytest
import jax.numpy as jnp
import numpy as np
import yaml

from tpuframe.track import (
    ExperimentTracker,
    MLflowLogger,
    SystemMetricsMonitor,
    broadcast_run_id,
)


def test_experiment_get_or_create(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    eid = tracker.set_experiment("/Users/me/experiments/cifar")
    assert tracker.set_experiment("/Users/me/experiments/cifar") == eid
    assert tracker.set_experiment("other") != eid
    meta = yaml.safe_load((tmp_path / "mlruns" / eid / "meta.yaml").read_text())
    assert meta["name"] == "/Users/me/experiments/cifar"
    assert meta["lifecycle_stage"] == "active"


def test_run_params_metrics_layout(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    tracker.set_experiment("exp")
    with tracker.start_run(run_name="baseline") as run:
        run.log_params({"lr": 1e-3, "batch_size": 128})
        for epoch, loss in enumerate([0.9, 0.5, 0.3]):
            run.log_metric("train_loss", loss, step=epoch)

    assert run.get_param("lr") == "0.001"
    hist = run.get_metric_history("train_loss")
    assert [(v, s) for _, v, s in hist] == [(0.9, 0), (0.5, 1), (0.3, 2)]
    # mlflow file-store layout: metrics/<key> lines "<ts> <val> <step>"
    run_dir = tmp_path / "mlruns" / tracker.experiment_id / run.run_id
    assert (run_dir / "params" / "lr").read_text() == "0.001"
    assert len((run_dir / "metrics" / "train_loss").read_text().splitlines()) == 3
    meta = yaml.safe_load((run_dir / "meta.yaml").read_text())
    assert meta["status"] == 3 and meta["end_time"] is not None  # RunStatus.FINISHED
    assert tracker.runs() == [run.run_id]


def test_artifacts_and_model(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    tracker.set_experiment("exp")
    run = tracker.start_run()
    src = tmp_path / "note.txt"
    src.write_text("hello")
    dest = run.log_artifact(str(src), "notes")
    assert open(dest).read() == "hello"
    run.log_dict({"epoch": 3, "acc": 0.9}, "meta/summary.json")
    assert os.path.exists(run.artifact_path("meta", "summary.json"))

    class FakeState:
        params = {"w": jnp.ones((2, 2))}
        batch_stats = {}

    model_dir = run.log_model(FakeState(), "model")
    mlmodel = yaml.safe_load(open(os.path.join(model_dir, "MLmodel")))
    assert mlmodel["flavors"]["tpuframe"]["data"] == "model.msgpack"
    assert os.path.exists(os.path.join(model_dir, "model.msgpack"))

    from tpuframe.ckpt import load_pytree

    out = load_pytree(
        os.path.join(model_dir, "model.msgpack"),
        {"params": {"w": jnp.zeros((2, 2))}, "batch_stats": {}},
    )
    np.testing.assert_array_equal(out["params"]["w"], np.ones((2, 2)))


def test_mlflow_logger_plugin(tmp_path):
    logger = MLflowLogger("exp", tracking_uri=str(tmp_path / "mlruns"))
    logger.log_params({"optimizer": "adam"})
    logger.log_metrics({"train_loss": 0.7}, step=0)
    run = logger.run
    logger.flush()
    assert run.get_param("optimizer") == "adam"
    assert run.get_metric_history("train_loss")[0][1:] == (0.7, 0)


def test_run_failed_status_and_nested_keys(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    tracker.set_experiment("exp")
    with pytest.raises(RuntimeError):
        with tracker.start_run() as run:
            run.log_metric("system/cpu_utilization", 0.5, step=0)
            raise RuntimeError("boom")
    run_dir = tmp_path / "mlruns" / tracker.experiment_id / run.run_id
    meta = yaml.safe_load((run_dir / "meta.yaml").read_text())
    assert meta["status"] == 4  # RunStatus.FAILED
    # slash keys become nested file-store dirs, and read back unchanged
    assert (run_dir / "metrics" / "system" / "cpu_utilization").exists()
    assert run.get_metric_history("system/cpu_utilization")[0][1:] == (0.5, 0)


def test_broadcast_run_id_single_process():
    assert broadcast_run_id("abc123") == "abc123"


def test_system_metrics_monitor(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    tracker.set_experiment("exp")
    run = tracker.start_run()
    mon = SystemMetricsMonitor(run, interval_s=60.0)
    mon.start()
    mon.stop()  # final sample logs at least one point
    hist = run.get_metric_history("system/memory_rss_mb")
    assert len(hist) >= 1 and hist[0][1] > 0


def test_metric_key_prefix_collision_both_orders(tmp_path):
    tracker = ExperimentTracker(str(tmp_path / "mlruns"))
    tracker.set_experiment("exp")
    with tracker.start_run() as run:
        # flat first, then nested under the same prefix (SystemMetricsMonitor
        # key shapes) -- and the reverse -- must both survive and read back.
        run.log_metric("system", 1.0, step=0)
        run.log_metric("system/cpu", 2.0, step=1)
        run.log_metric("nested/deep", 3.0, step=0)
        run.log_metric("nested", 4.0, step=1)
    assert run.get_metric_history("system")[0][1:] == (1.0, 0)
    assert run.get_metric_history("system/cpu")[0][1:] == (2.0, 1)
    assert run.get_metric_history("nested/deep")[0][1:] == (3.0, 0)
    assert run.get_metric_history("nested")[0][1:] == (4.0, 1)


def test_trace_context_manager_captures(tmp_path):
    # jax.profiler on CPU still emits a trace directory structure.
    import jax
    import jax.numpy as jnp2

    from tpuframe.track import trace

    logdir = tmp_path / "trace"
    with trace(str(logdir)):
        y = jnp2.ones((8, 8)) @ jnp2.ones((8, 8))
        jax.block_until_ready(y)
    # plugins/profile/<ts>/*.xplane.pb is the TB layout
    found = list(logdir.rglob("*.xplane.pb"))
    assert found, f"no xplane captured under {logdir}"


@pytest.mark.slow
def test_profiler_callback_in_trainer(tmp_path):
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.models import MnistNet
    from tpuframe.track import MLflowLogger, ProfilerCallback
    from tpuframe.train import Trainer

    ds = SyntheticImageDataset(n=64, num_classes=4, image_size=28, channels=1)
    loader = DataLoader(ds, batch_size=16, process_index=0, process_count=1)
    logger = MLflowLogger("prof-exp", tracking_uri=str(tmp_path / "mlruns"))
    prof = ProfilerCallback(skip_steps=1, num_steps=2)
    trainer = Trainer(
        MnistNet(num_classes=4),
        train_dataloader=loader,
        max_duration="1ep",
        num_classes=4,
        callbacks=[prof],
        loggers=[logger],
        log_interval=2,
    )
    result = trainer.fit()
    # breakdown lands in the epoch summary
    for key in ("data_wait_s", "dispatch_s", "host_block_s"):
        assert key in result.metrics and result.metrics[key] >= 0
    # the trace was captured and logged as a run artifact
    assert prof.artifact is not None and prof.artifact.endswith(".zip")
    assert os.path.exists(prof.artifact)


@pytest.mark.slow
def test_profiler_callback_closes_trace_on_early_end(tmp_path):
    # duration reached mid-capture: on_fit_end must stop the profiler so a
    # following fit can start its own trace.
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.models import MnistNet
    from tpuframe.track import ProfilerCallback
    from tpuframe.train import Trainer

    ds = SyntheticImageDataset(n=64, num_classes=4, image_size=28, channels=1)
    loader = DataLoader(ds, batch_size=16, process_index=0, process_count=1)
    prof = ProfilerCallback(skip_steps=0, num_steps=100, logdir=str(tmp_path / "t"))
    trainer = Trainer(
        MnistNet(num_classes=4),
        train_dataloader=loader,
        max_duration="2ba",
        num_classes=4,
        callbacks=[prof],
    )
    trainer.fit()
    assert not prof._active
    # a fresh capture works afterwards (profiler not wedged)
    from tpuframe.track import trace
    import jax, jax.numpy as jnp2

    with trace(str(tmp_path / "t2")):
        jax.block_until_ready(jnp2.ones(4) + 1)
