"""Experiment tracking: MLflow-compatible params/metrics/artifacts/models.

TPU-native replacement for the reference's MLflow wiring (SURVEY.md §5
"Metrics / logging"): experiment-per-notebook setup
(`/root/reference/setup/00_setup.py:96-101`), per-epoch ``log_metric(step=)``
(`/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py:258-260`),
param logging (`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:275-276`),
state-dict/model artifacts (`/root/reference/04_accelerate/01_cifar_accelerate.ipynb:cell-18`),
system metrics (`02_cifar_torch_distributor_resnet.py:186`), and the rank-0 +
run-id-broadcast discipline for multi-process logging (`cell-18`'s char-tensor
hack becomes :func:`broadcast_run_id` on the control plane).

Backend-neutral: writes the MLflow ``mlruns/`` file-store layout natively, so
runs and artifacts are readable by any stock MLflow UI/client pointed at the
same directory — no mlflow package required.

Exports resolve lazily (PEP 562): the telemetry spine (``telemetry``,
``watchdog`` — stdlib-only, usable while jax is wedged) must be importable
without dragging in the profiler's train-package (and therefore jax)
imports.  ``from tpuframe.track import X`` works exactly as before.
"""

# tpuframe-lint: stdlib-only

import importlib

# name -> submodule it lives in (all under tpuframe.track)
_EXPORTS = {
    "RankLog": "analyze",
    "StragglerMonitor": "analyze",
    "baseline_diff": "analyze",
    "build_trace": "analyze",
    "load_trace_dir": "analyze",
    "skew_report": "analyze",
    "PROFILE_ENV_VARS": "device_time",
    "PROFILE_ENV_DOMAINS": "device_time",
    "classify_op": "device_time",
    "device_time_report": "device_time",
    "device_trace_events": "device_time",
    "profile_env": "device_time",
    "MEMORY_ENV_VARS": "memory",
    "MEMORY_ENV_DOMAINS": "memory",
    "memory_env": "memory",
    "record_executable_memory": "memory",
    "executable_records": "memory",
    "update_watermarks": "memory",
    "maybe_oom_event": "memory",
    "is_oom": "memory",
    "ExperimentTracker": "mlflow_store",
    "MLflowLogger": "mlflow_store",
    "Run": "mlflow_store",
    "broadcast_run_id": "mlflow_store",
    "set_experiment": "mlflow_store",
    "start_run": "mlflow_store",
    "HttpExperimentTracker": "http_store",
    "HttpRun": "http_store",
    "MetricsServer": "http_store",
    "make_tracker": "http_store",
    "ProfilerCallback": "profiler",
    "trace": "profiler",
    "trace_step_window": "profiler",
    "HttpModelRegistry": "registry",
    "ModelRegistry": "registry",
    "ModelVersion": "registry",
    "load_model": "registry",
    "TensorBoardLogger": "tensorboard",
    "SystemMetricsMonitor": "system_metrics",
    "MetricsExportCallback": "telemetry",
    "MetricsRegistry": "telemetry",
    "Telemetry": "telemetry",
    "configure_telemetry": "telemetry",
    "get_telemetry": "telemetry",
    "publish_to_loggers": "telemetry",
    "start_metrics_server": "telemetry",
    "Watchdog": "watchdog",
}

# a few exports carry a different name in their home module
_ALIASES = {"configure_telemetry": "configure", "load_trace_dir": "load_dir"}

_SUBMODULES = (
    "analyze",
    "device_time",
    "http_store",
    "memory",
    "mlflow_store",
    "profiler",
    "registry",
    "system_metrics",
    "telemetry",
    "tensorboard",
    "watchdog",
)

__all__ = sorted(_EXPORTS) + list(_SUBMODULES)


def __getattr__(name):
    if name in _EXPORTS:
        mod = importlib.import_module(f"tpuframe.track.{_EXPORTS[name]}")
        value = getattr(mod, _ALIASES.get(name, name))
        globals()[name] = value  # cache: resolve once
        return value
    if name in _SUBMODULES:
        return importlib.import_module(f"tpuframe.track.{name}")
    raise AttributeError(f"module 'tpuframe.track' has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + __all__))
