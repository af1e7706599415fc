"""System-metrics monitor: host + device utilization sampled in background.

Replaces the reference's ``MLFLOW_ENABLE_SYSTEM_METRICS_LOGGING=true`` env
(`/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py:186`)
and its ``nvidia-smi`` notebook cells (SURVEY.md §5 "Tracing / profiling"):
a daemon thread samples /proc (CPU, RSS) and jax device memory stats (TPU HBM
in-use) and appends them to the run's metrics with a monotonically increasing
step, no external agents.

Every sample is also mirrored into the telemetry registry as gauges
(``system/cpu_util``, ``system/rss_mb``, ``system/device<i>_mem_used_mb``,
``system/device<i>_mem_util``), so the Prometheus ``/metrics`` endpoint
(``telemetry.start_metrics_server``) exposes host and HBM utilization —
not just the Run logger path.  ``run=None`` runs the monitor registry-only.
"""

from __future__ import annotations

import os
import resource
import threading
import time


def _cpu_times() -> tuple[float, float]:
    """(process_cpu_seconds, wall_seconds)."""
    t = os.times()
    return (t.user + t.system), time.monotonic()


def _rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def machine_counters() -> dict[str, float]:
    """What the machine has done to this process so far, for deltas over a
    window (the Trainer's drain): involuntary and voluntary context
    switches, major faults and CPU seconds of all its threads, the
    runtime's included (one ``getrusage``), and ``psi_cpu_some_us``, the
    microseconds some task on the host stood runnable without a CPU
    (``/proc/pressure/cpu``; left out where the file cannot be read)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"nivcsw": ru.ru_nivcsw, "nvcsw": ru.ru_nvcsw,
           "majflt": ru.ru_majflt, "cpu_s": ru.ru_utime + ru.ru_stime}
    try:
        with open("/proc/pressure/cpu") as f:
            some = f.readline()
        out["psi_cpu_some_us"] = int(some.rsplit("total=", 1)[1])
    except (OSError, IndexError, ValueError):
        pass
    return out


def device_memory_stats() -> dict[str, float]:
    """Per-device HBM usage in MB (empty on backends without stats, e.g. CPU)."""
    import jax

    out: dict[str, float] = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            used = stats.get("bytes_in_use", 0) / 2**20
            limit = stats.get("bytes_limit", 0) / 2**20
            out[f"device{d.id}_mem_used_mb"] = used
            if limit:
                out[f"device{d.id}_mem_util"] = used / limit
    return out


class SystemMetricsMonitor:
    """Daemon thread logging system metrics every ``interval_s``.

    Args:
      run: a tracker Run with ``log_metrics(dict, step=)``; None samples
        into the telemetry registry only (the Prometheus path).
      registry: MetricsRegistry to mirror gauges into (default: the
        process-wide telemetry's).
    """

    def __init__(self, run=None, interval_s: float | None = None,
                 prefix: str = "system/", registry=None):
        self.run = run
        if interval_s is None:
            # TPUFRAME_MEMORY_SAMPLE_S: the memory plane's watermark
            # cadence doubles as the monitor default (one sampler)
            from tpuframe.track.memory import memory_env

            interval_s = memory_env()["TPUFRAME_MEMORY_SAMPLE_S"]
        self.interval_s = interval_s
        self.prefix = prefix
        self.registry = registry
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._step = 0
        self._lock = threading.Lock()  # serializes thread vs stop() final sample

    def _registry(self):
        if self.registry is not None:
            return self.registry
        from tpuframe.track.telemetry import get_telemetry

        return get_telemetry().registry

    def sample(self) -> dict[str, float]:
        cpu, wall = _cpu_times()
        if not hasattr(self, "_last"):
            self._last = (cpu, wall)
        dcpu = cpu - self._last[0]
        dwall = max(wall - self._last[1], 1e-9)
        self._last = (cpu, wall)
        cpu_util = min(dcpu / dwall, float(os.cpu_count() or 1))
        rss = _rss_mb()
        metrics = {
            f"{self.prefix}cpu_utilization": cpu_util,
            f"{self.prefix}memory_rss_mb": rss,
        }
        devices = device_memory_stats()
        for k, v in devices.items():
            metrics[f"{self.prefix}{k}"] = v
        # registry mirror: the gauge names are fixed (OBSERVABILITY.md),
        # independent of the Run-path prefix, so dashboards scraping
        # /metrics see the same series whatever the run is called
        reg = self._registry()
        reg.gauge("system/cpu_util").set(cpu_util)
        reg.gauge("system/rss_mb").set(rss)
        for k, v in devices.items():
            reg.gauge(f"system/{k}").set(v)
        # memory plane: fold this sample into the process-wide HBM/host
        # watermarks (memory/hbm_peak_mb, memory/host_peak_mb + the
        # ratcheted memory/watermark event) — same sample, no second
        # device poll
        from tpuframe.track.memory import memory_env, update_watermarks

        if memory_env()["TPUFRAME_MEMORY_LIVE"]:
            update_watermarks(devices, rss, registry=reg)
        return metrics

    def _publish(self) -> None:
        with self._lock:
            metrics = self.sample()
            if self.run is not None:
                self.run.log_metrics(metrics, step=self._step)
            self._step += 1

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._publish()

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        # final sample so short runs record at least one point
        self._publish()
