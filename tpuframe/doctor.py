"""Environment doctor: one-command report of what this install can do.

``python -m tpuframe`` is the CLI face of the reference's setup cell —
`/root/reference/setup/00_setup.py:105-123` prints worker counts, GPU
topology and debug-env state at bootstrap; this prints the tpuframe
equivalents (backend, devices, mesh hint, native extensions, codecs,
compile cache) as one JSON report a user can paste into a bug report.

The device probe runs in a TIMEOUT-BOUNDED subprocess: on a wedged
backend ``jax.devices()`` can hang rather than error, and a diagnostics
tool that hangs on exactly the environment it should diagnose is
useless.  It also keeps this process off the accelerator: a chip belongs
to one process at a time, and the child that probed it has exited (and
released it) by the time the report prints.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import importlib
import json
import shlex
import shutil
import os
import subprocess
import sys

_PROBE_SRC = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'backend': jax.default_backend(), "
    "'device_count': jax.device_count(), "
    "'local_device_count': jax.local_device_count(), "
    "'process_index': jax.process_index(), "
    "'process_count': jax.process_count(), "
    "'device_kinds': sorted({dev.device_kind for dev in d}), "
    "'jax_version': jax.__version__}))"
)


def _module_version(name: str) -> str | None:
    try:
        mod = importlib.import_module(name)
        return getattr(mod, "__version__", "installed")
    except Exception:
        return None


def probe_devices(timeout_s: float = 30.0) -> dict:
    """Backend/topology via a bounded child (never hangs the doctor).

    The probe runs under a telemetry span, and every outcome — including
    the wedged-timeout path — carries ``probe_wall_s``: a wedged-probe
    report should say how long the hang was given, not just that it hung.
    """
    from tpuframe.track.telemetry import get_telemetry

    with get_telemetry().span("doctor/device_probe", timeout_s=timeout_s) as sp:
        rec = _probe_devices(timeout_s)
    rec["probe_wall_s"] = round(sp.elapsed, 3)
    return rec


def _probe_devices(timeout_s: float) -> dict:
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_SRC],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return {
            "error": f"device probe hung > {timeout_s:.0f}s — backend "
            "wedged, or another process holds the chip"
        }
    if proc.returncode != 0:
        detail = (proc.stderr or proc.stdout).strip()[-500:]
        # never an empty/falsy error: a silently-killed child (OOM,
        # segfault) must still read as a failed probe
        return {"error": f"probe exited rc={proc.returncode}: "
                         f"{detail or '(no output)'}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"unparseable probe output: {proc.stdout[-200:]}"}


def _telemetry_env_vars() -> tuple[str, ...]:
    from tpuframe.track.telemetry import OBSERVABILITY_ENV_VARS

    return OBSERVABILITY_ENV_VARS


def telemetry_section() -> dict:
    """State of the telemetry spine (`tpuframe.track.telemetry`): where the
    event log goes, whether a stall watchdog is armed, which exporters are
    live — pasted into bug reports next to the device probe so a "wedged"
    report also says what diagnostics were (or weren't) running."""
    from tpuframe.track.telemetry import get_telemetry

    tele = get_telemetry()
    wd = tele.watchdog
    exporters = ["memory_ring"]
    if tele.jsonl_path:
        exporters.append("jsonl")
    return {
        "event_log": tele.jsonl_path,
        # the fleet-analysis one-liner for THIS run's telemetry dir —
        # paste-ready next to the bug report (track/analyze.py), so it
        # must survive pasting: quote the dir, '.' when path-less
        "analyze": (
            "python -m tpuframe.track analyze "
            f"{shlex.quote(os.path.dirname(tele.jsonl_path) or '.')} --report"
            if tele.jsonl_path else
            "set TPUFRAME_TELEMETRY_DIR, then: "
            "python -m tpuframe.track analyze <dir> --report"
        ),
        "events_buffered": len(tele.recent_events(10**9)),
        "exporters": exporters,
        "watchdog": {
            "active": wd is not None,
            "default_deadline_s": getattr(wd, "default_deadline_s", None),
            "deadlines": dict(getattr(wd, "deadlines", {}) or {}),
            "stalls_reported": len(getattr(wd, "reports", ())),
        },
        "env": {
            k: os.environ[k]
            for k in _telemetry_env_vars()
            if k in os.environ
        },
    }


def compile_section() -> dict:
    """State of the compile spine (`tpuframe.compile`): where the
    persistent compilation cache lives (or would, were it enabled), how
    many entries / MB it holds, the eviction knobs bounding it, and the
    ``TPUFRAME_COMPILE_*`` env — so a "slow cold start / slow recovery"
    report says up front whether warm-start was even on."""
    from tpuframe.compile.cache import COMPILE_ENV_VARS, cache_info

    info = cache_info()
    info["env"] = {
        k: os.environ[k] for k in COMPILE_ENV_VARS if k in os.environ
    }
    return info


def ckpt_section(directory: str | None = None,
                 device_count: int | None = None) -> dict | None:
    """State of a checkpoint directory (``--ckpt-dir`` /
    ``TPUFRAME_CKPT_DIR``): committed steps, quarantined torn steps, and
    the latest committed step's **topology manifest** — the mesh shape
    the checkpoint was saved under.  When the manifest's world size
    disagrees with the probed backend, the section carries a warning
    with the reshard-restore one-liner: the checkpoint is still usable,
    it just restores onto a rebound plan (FAULT.md "Elastic recovery").
    Stdlib-only reads — works against a wedged backend."""
    directory = directory or os.environ.get("TPUFRAME_CKPT_DIR")
    if not directory:
        return None
    from tpuframe.ckpt.meta import read_manifest, valid_steps

    steps = valid_steps(directory)
    qdir = os.path.join(directory, "_quarantine")
    try:
        quarantined = sorted(os.listdir(qdir))
    except (FileNotFoundError, NotADirectoryError):
        quarantined = []
    out: dict = {
        "directory": os.path.abspath(directory),
        "committed_steps": steps[-5:],
        "latest_step": steps[-1] if steps else None,
        "quarantined": quarantined,
    }
    manifest = read_manifest(directory, steps[-1]) if steps else None
    if manifest is not None:
        out["topology"] = {
            "mesh_axes": manifest.get("mesh_axes"),
            "world_size": manifest.get("world_size"),
            "process_count": manifest.get("process_count"),
            "plan_signature": manifest.get("plan_signature"),
            "zero_stage": manifest.get("zero_stage"),
            "leaves": len(manifest.get("leaves") or {}),
        }
        saved_world = manifest.get("world_size")
        if (
            isinstance(device_count, int)
            and isinstance(saved_world, int)
            and device_count != saved_world
        ):
            out["warning"] = (
                f"checkpoint topology (world={saved_world}, mesh="
                f"{manifest.get('mesh_axes')}) != current backend "
                f"({device_count} device(s)): restore reshards at load — "
                "build the survivor mesh, plan = old_plan.rebind(mesh), "
                "then Checkpointer.restore(template, plan=plan) (or "
                "launch.run_elastic, which does all three)"
            )
    elif steps:
        out["topology"] = None  # pre-manifest checkpoint (or host-numpy state)
    return out


def health_section(directory: str | None = None) -> dict:
    """State of the training-health sentinel (``tpuframe.fault.health``):
    whether it is on, the live thresholds (env overrides applied), the
    ``TPUFRAME_HEALTH_*`` env, and — when a checkpoint directory is
    known — the newest committed step's health stamp plus the rollback
    target, so a "my run diverged" report says up front what the
    sentinel would do about it.  Stdlib-only reads, like
    ``read_manifest``."""
    import dataclasses

    from tpuframe.fault.health import (
        HEALTH_ENV_VARS,
        HealthPolicy,
        enabled_by_env,
    )

    # malformed env (TPUFRAME_HEALTH_WINDOW=0, ...) must not crash the
    # report that exists to surface it: show the error WITH the env
    try:
        thresholds = dataclasses.asdict(HealthPolicy.from_env())
    except ValueError as e:
        thresholds = {"error": str(e)}
    out: dict = {
        "enabled": enabled_by_env(),
        "thresholds": thresholds,
        "env": {
            k: os.environ[k] for k in HEALTH_ENV_VARS if k in os.environ
        },
    }
    directory = directory or os.environ.get("TPUFRAME_CKPT_DIR")
    if directory:
        from tpuframe.ckpt.meta import (
            latest_healthy_step,
            latest_step,
            read_health,
        )

        latest = latest_step(directory)
        healthy = latest_healthy_step(directory)
        out["latest_checkpoint"] = {
            "step": latest,
            "health": read_health(directory, latest) if latest is not None
            else None,
            "latest_healthy_step": healthy,
        }
        if latest is not None and healthy != latest:
            out["latest_checkpoint"]["warning"] = (
                f"newest committed step {latest} is stamped unhealthy; a "
                f"divergence rollback would resume at {healthy} "
                "(fault.Supervisor does this automatically; by hand: "
                "tpuframe.ckpt.rollback_to_last_healthy(dir))"
            )
    return out


def serve_section(export_path: str | None = None) -> dict:
    """State of the serving spine (``tpuframe.serve``): the live SLO /
    queue / shed-policy knobs (env overrides applied), the
    ``TPUFRAME_SERVE_*`` env, and — given an export artifact
    (``--export`` / ``TPUFRAME_SERVE_EXPORT``) — its meta plus the
    padded bucket shapes the engine would AOT-precompile for it.
    Stdlib-only reads (:func:`~tpuframe.serve.admission.read_export_meta`)
    — works against a wedged backend, like the ckpt/health sections."""
    import dataclasses

    from tpuframe.serve.admission import SERVE_ENV_VARS, ServeKnobs

    knobs = ServeKnobs.from_env()
    out: dict = {
        "knobs": dataclasses.asdict(knobs),
        "env": {
            k: os.environ[k] for k in SERVE_ENV_VARS if k in os.environ
        },
    }
    export_path = export_path or os.environ.get("TPUFRAME_SERVE_EXPORT")
    if export_path:
        from tpuframe.serve.admission import read_export_meta

        try:
            meta = read_export_meta(export_path)
        except (OSError, ValueError) as e:
            out["export"] = {"path": export_path, "error": str(e)}
        else:
            trailing = list(meta.get("input_shape") or [])[1:]
            out["export"] = {
                "path": os.path.abspath(export_path),
                "model": meta.get("model"),
                "version": meta.get("version"),
                "input_shape": meta.get("input_shape"),
                "input_dtype": meta.get("input_dtype"),
                "batch_polymorphic": meta.get("batch_polymorphic"),
                "platforms": meta.get("platforms"),
                # the closed shape set the engine precompiles at start();
                # anything else at runtime is one loud compile/recompile
                "bucket_shapes": [[b] + trailing for b in knobs.buckets],
                "aot_precompile": (
                    "armed at ServeEngine.start() via compile.precompile "
                    "(persistent cache warm; ShapeGuard loud on stray "
                    "shapes)"
                ),
            }
    return out


def fleet_section() -> dict:
    """State of the fleet layer (``tpuframe.serve.fleet``): the
    router/replica-set knobs (env overrides applied), the
    ``TPUFRAME_ROUTER_*``/``TPUFRAME_FLEET_*`` env subset and the bounded
    detection window those knobs imply.  Stdlib-only
    (:class:`~tpuframe.serve.router.FleetKnobs` never touches jax), like
    the serve section."""
    import dataclasses

    from tpuframe.serve.admission import SERVE_ENV_VARS
    from tpuframe.serve.router import FleetKnobs

    knobs = FleetKnobs.from_env()
    return {
        "knobs": dataclasses.asdict(knobs),
        "env": {
            k: os.environ[k] for k in SERVE_ENV_VARS
            if k.startswith(("TPUFRAME_ROUTER_", "TPUFRAME_FLEET_"))
            and k in os.environ
        },
        # worst-case probe-driven rotation delay; in-band forwarding
        # failures rotate a replica out immediately, ahead of this
        "detection_window_ms": knobs.probe_ms,
    }


def slo_section() -> dict:
    """State of the serving SLO plane (``tpuframe.serve.slo``): the
    declared objectives (strict env parse — a malformed
    ``TPUFRAME_SLO_*`` is *reported*, not crashed on, mirroring the
    health section's threshold idiom), the live burn-rate/error-budget
    gauges off this process's registry, the ``TPUFRAME_SLO_*`` env
    subset, and the paste-ready analyze one-liner whose ``serve_trace``
    block scores a telemetry dir against the objectives that were in
    force.  Stdlib-only, like the serve/fleet sections."""
    import dataclasses

    from tpuframe.serve.admission import SERVE_ENV_VARS
    from tpuframe.serve.slo import SloObjectives
    from tpuframe.track.telemetry import get_telemetry

    try:
        objectives = dataclasses.asdict(SloObjectives.from_env(strict=True))
    except ValueError as e:
        objectives = {"error": str(e)}
    reg = get_telemetry().registry
    return {
        "objectives": objectives,
        # live window state — 0.0 until something observes outcomes
        "burn_rate": reg.gauge("slo/burn_rate").value,
        "error_budget_remaining": reg.gauge("slo/error_budget").value,
        "env": {
            k: os.environ[k] for k in SERVE_ENV_VARS
            if k.startswith("TPUFRAME_SLO_") and k in os.environ
        },
        "analyze": ("python -m tpuframe.track analyze "
                    "$TPUFRAME_TELEMETRY_DIR --report"),
    }


def comms_section() -> dict:
    """State of the wire-compression spine
    (``tpuframe.parallel.compression``): the resolved compression config
    (env knobs applied — mode/buckets/stochastic/EF) and the
    ``TPUFRAME_COMMS_*`` env that is set.  Stdlib-only reads
    (``parallel.comms_env``) — works against a wedged backend, like the
    serve/ckpt sections."""
    import dataclasses

    from tpuframe.parallel.comms_env import (
        COMMS_ENV_VARS,
        CommsConfig,
        comms_async_enabled,
        comms_async_flags,
        comms_async_platform,
    )

    out: dict = {
        "env": {
            k: os.environ[k] for k in COMMS_ENV_VARS if k in os.environ
        },
    }
    # the async-scheduler knob resolves per-platform (restart-only):
    # print exactly the XLA flag set initialize() would merge, so "why
    # is my overlap not overlapping" is answerable from the report
    plat = comms_async_platform()
    out["async"] = {
        "enabled": comms_async_enabled(),
        "platform": plat,
        "flags": list(comms_async_flags(plat)),
    }
    try:
        config = CommsConfig.from_env()
    except ValueError as e:  # typo'd mode: report it, don't crash the doctor
        out["error"] = str(e)
        return out
    out["enabled"] = config is not None
    if config is not None:
        out["config"] = dataclasses.asdict(config)
    # in-collective wire: fused is resolved off the same config (it is
    # a no-op without a compressed mode)
    out["fused"] = {"enabled": bool(config is not None and config.fused)}
    return out


def parallel_section() -> dict:
    """State of the composed-parallelism knobs (``parallel.compose``):
    the resolved pipeline/TP env (``TPUFRAME_PP_*``/``TPUFRAME_TP_SIZE``)
    and the legal schedules.  Stdlib-only reads (``parallel.comms_env``)
    — works against a wedged backend; what mesh the plan actually
    composed is a runtime question the ``pp/schedule`` event answers."""
    from tpuframe.parallel.comms_env import (
        PP_SCHEDULE_CHOICES,
        pp_microbatches,
        pp_schedule,
        tp_size,
    )

    return {
        "pp_microbatches": pp_microbatches() or None,
        "pp_schedule": pp_schedule(),
        "tp_size": tp_size(),
        "schedules": list(PP_SCHEDULE_CHOICES),
        "env": {
            k: os.environ[k]
            for k in ("TPUFRAME_PP_MICROBATCHES", "TPUFRAME_PP_SCHEDULE",
                      "TPUFRAME_TP_SIZE")
            if k in os.environ
        },
    }


def profile_section() -> dict:
    """State of the device-time capture path (`track/profiler.py` +
    `track/device_time.py`): the ``TPUFRAME_PROFILE_*`` knobs (malformed
    values reported, not crashed on), the newest surviving capture dir
    with its parsed ``device_time`` summary (stdlib gzip+json — works
    against a wedged backend), and the paste-ready analyze one-liner —
    so a "my step is slow" report says up front whether on-device
    evidence exists and what it already attributes."""
    from tpuframe.track.device_time import (
        PROFILE_ENV_VARS,
        device_time_report,
        list_captures,
        profile_env,
    )

    env = profile_env()
    errors = env.pop("errors")
    out: dict = {
        "armed": bool(env["TPUFRAME_PROFILE_STEPS"]),
        "knobs": env,
        "env": {
            k: os.environ[k] for k in PROFILE_ENV_VARS if k in os.environ
        },
        "analyze": (
            "python -m tpuframe.track analyze "
            "$TPUFRAME_TELEMETRY_DIR --report"
        ),
    }
    if errors:
        out["errors"] = errors
    profile_dir = env["TPUFRAME_PROFILE_DIR"]
    captures = list_captures(profile_dir) if profile_dir else []
    out["captures"] = len(captures)
    if captures:
        newest = captures[-1]
        out["newest_capture"] = newest
        try:
            summary = device_time_report(newest)
        except (OSError, ValueError) as e:  # torn capture ≠ doctor crash
            out["parse_error"] = f"{type(e).__name__}: {e}"
            summary = None
        if summary is not None:
            # the headline numbers, not the whole record (top-op table
            # and per-class breakdown come from the analyze one-liner)
            out["device_time"] = {
                "window_s": summary["window_s"],
                "exposed_comms_s": summary["exposed_comms_s"],
                "overlap_efficiency": summary["overlap_efficiency"],
                "device_tracks": summary["device_tracks"],
                "top_op": (
                    summary["top_ops"][0]["name"]
                    if summary["top_ops"] else None
                ),
            }
    return out


def memory_section() -> dict:
    """State of the memory plane (`track/memory.py` +
    `parallel/memory.py`): the ``TPUFRAME_MEMORY_*`` knobs (malformed
    values reported, not crashed on), the persisted executable-memory
    records next to the compile cache (stdlib json — works against a
    wedged backend), the process-wide watermarks, and a fits /
    doesn't-fit verdict of the known peak against the resolved budget —
    plus the paste-ready estimator one-liner, so a "will it fit" report
    starts from numbers, not a recompile."""
    from tpuframe.track.memory import (
        MEMORY_ENV_VARS,
        executable_records,
        memory_env,
        peaks,
    )

    env = memory_env()
    errors = env.pop("errors")
    out: dict = {
        "knobs": env,
        "env": {
            k: os.environ[k] for k in MEMORY_ENV_VARS if k in os.environ
        },
        # the paste-ready capacity check: price the composed plan's
        # budget before anything compiles
        "estimate": (
            "python -c \"from tpuframe.parallel import compose, plan_memory; "
            "print(plan_memory(compose(), "
            "{'w': ((4096, 4096), 'float32')})['per_device_mb'])\""
        ),
    }
    if errors:
        out["errors"] = errors
    recs = executable_records()
    live = peaks()
    out["executables"] = len(recs)
    out["watermarks"] = {k: round(v, 3) for k, v in live.items() if v}
    # best known per-device peak: live watermark when the backend
    # reports device stats, else the biggest compiled executable
    peak = max(
        (float(r.get("peak_mb") or 0.0) for r in recs.values()), default=0.0
    )
    peak = max(peak, float(live.get("hbm_peak_mb") or 0.0))
    budget = (
        float(env["TPUFRAME_MEMORY_BUDGET_MB"])
        or float(live.get("hbm_limit_mb") or 0.0)
    )
    out["peak_known_mb"] = round(peak, 3) or None
    out["budget_mb"] = round(budget, 3) or None
    if peak and budget:
        # 10% headroom for allocator fragmentation, same margin as
        # suggest_fit
        out["verdict"] = (
            "fits" if peak <= 0.9 * budget
            else "tight" if peak <= budget
            else "does-not-fit"
        )
    else:
        out["verdict"] = "unknown (no budget or no recorded peak — run " \
                         "the estimator one-liner)"
    return out


def autotune_section(devices: dict | None = None) -> dict:
    """State of the self-tuning loop (``tpuframe.autotune``): whether it
    is armed, where the per-``(host, topology, signature)`` configs
    persist, every config stored for THIS host (the plan signature is
    run-scoped, so the doctor lists all of the host's entries and marks
    which match the probed topology), and the paste-ready one-liner —
    so a "my run is slow" report says up front whether a tuned config
    exists and what it would set.  Stdlib-only reads — works against a
    wedged backend, like the serve/ckpt sections."""
    from tpuframe.autotune.config import (
        AUTOTUNE_ENV_VARS,
        autotune_dir,
        autotune_enabled,
        default_host,
        list_tuned,
    )

    host = default_host()
    topology = None
    if devices and isinstance(devices.get("device_count"), int):
        topology = (f"{devices.get('process_count', 1)}x"
                    f"{devices['device_count']}")
    out: dict = {
        "enabled": autotune_enabled(),
        "store": autotune_dir(),
        "host": host,
        "topology": topology,
        "env": {
            k: os.environ[k] for k in AUTOTUNE_ENV_VARS if k in os.environ
        },
        # paste-ready, consistent with the other sections: what is persisted
        "show": "python -m tpuframe.autotune --json",
    }
    configs = []
    for cfg in list_tuned():
        if cfg.host != host:
            continue
        configs.append({
            "topology": cfg.topology,
            "signature": cfg.signature,
            "source": cfg.source,
            "env": dict(cfg.env),
            "convergence_ratio": cfg.convergence_ratio,
            "matches_probed_topology": (
                None if topology is None else cfg.topology == topology
            ),
        })
    out["configs"] = configs
    return out


def kernels_section(devices: dict | None = None) -> dict:
    """State of the kernel dispatch plane (``tpuframe.ops``): which
    Pallas execution mode the env + probed backend would pick, and every
    registered dispatchable op.  Stdlib-only reads (the registry module
    never imports jax); the mode is recomputed from env + the subprocess
    probe's backend rather than calling ``ops.dispatch.pallas_mode()``,
    which needs jax."""
    from tpuframe.ops.registry import OPS_REGISTRY

    falsy = {"", "0", "false", "no", "off"}
    disabled = os.environ.get(
        "TPUFRAME_DISABLE_PALLAS", "").strip().lower() not in falsy
    interpret = os.environ.get(
        "TPUFRAME_PALLAS_INTERPRET", "").strip().lower() not in falsy
    backend = (devices or {}).get("backend")
    if disabled:
        mode = None
    elif interpret:
        mode = "interpret"
    elif backend is None:
        mode = "unprobed"  # backend probe failed; can't tell
    else:
        mode = "compiled" if backend == "tpu" else None
    return {
        "mode": mode,
        "registry": sorted(OPS_REGISTRY),
        "attention": "python benchmarks/bench_attention.py --standalone B,L,H,D",
    }


def lint_section() -> dict:
    """State of the invariant linter (``tpuframe.lint``): the full pass
    run in-process over the installed tree — finding count per rule and
    the paste-ready one-liner, consistent with the compile/serve/ckpt
    sections.  A bug report whose ``lint`` section is dirty says up
    front that the tree's own contracts (jax-free modules, knob
    shipping, telemetry schema) were already broken before whatever is
    being reported.  Stdlib-only like the pass itself."""
    from tpuframe.lint import run_lint

    try:
        result = run_lint()
    except (OSError, SyntaxError, ValueError) as e:  # unreadable tree ≠
        # doctor crash (ValueError covers UnicodeDecodeError/null bytes)
        return {"error": f"{type(e).__name__}: {e}", "cmd": "python -m tpuframe.lint --json"}
    return {
        "findings": len(result.findings),
        "clean": not result.findings,
        "by_rule": result.rule_counts(),
        "files_scanned": result.files_scanned,
        "rules_run": result.rules_run,
        # the paste-ready reproduction next to the verdict, like the
        # telemetry section's analyze one-liner
        "cmd": "python -m tpuframe.lint --json",
    }


def report(probe_timeout_s: float = 30.0, ckpt_dir: str | None = None,
           export_path: str | None = None) -> dict:
    """Collect the full environment report (pure data; printing is main's)."""
    import tpuframe

    from tpuframe.core import native

    devices = probe_devices(probe_timeout_s)
    built = []
    build_dir = os.path.join(os.path.dirname(native.__file__), os.pardir,
                             "_native", "build")
    if os.path.isdir(build_dir):
        built = sorted(f for f in os.listdir(build_dir) if f.endswith(".so"))
    mesh_hint = None
    n = devices.get("device_count")
    if isinstance(n, int) and n > 0:
        mesh_hint = (f"MeshSpec(data=-1) -> {n}-way DP; "
                     f"MeshSpec(data={max(1, n // 8)}, fsdp=8) for ZeRO" if n >= 8
                     else f"MeshSpec(data=-1) -> {n}-way DP")
    return {
        "tpuframe": tpuframe.__version__,
        "python": sys.version.split()[0],
        "devices": devices,
        "mesh_hint": mesh_hint,
        "native_extensions": {
            # toolchain probed independently of the codecs so "g++ there
            # but libzstd/libjpeg missing" reads as exactly that
            "toolchain_available": shutil.which("g++") is not None,
            "zstd_codec": native.native_available(),
            "jpeg_decoder": native.jpeg_native_available(),
            "built": built,
        },
        "optional_deps": {
            name: _module_version(name)
            for name in ("zstandard", "PIL", "torch", "orbax.checkpoint",
                         "cloudpickle", "msgpack")
        },
        "telemetry": telemetry_section(),
        # the compile section's "dir" supersedes the old env-sourced
        # compile_cache_dir key: the spine enables the cache via
        # jax.config, so the env var being unset says nothing
        "compile": compile_section(),
        "ckpt": ckpt_section(ckpt_dir, devices.get("device_count")),
        "health": health_section(ckpt_dir),
        "serve": serve_section(export_path),
        "fleet": fleet_section(),
        "slo": slo_section(),
        "comms": comms_section(),
        "parallel": parallel_section(),
        "profile": profile_section(),
        "memory": memory_section(),
        "autotune": autotune_section(devices),
        "kernels": kernels_section(devices),
        "lint": lint_section(),
        "env": {
            k: os.environ[k]
            for k in ("JAX_PLATFORMS", "XLA_FLAGS",
                      "JAX_COMPILATION_CACHE_DIR", "TPUFRAME_DEBUG")
            if k in os.environ
        },
        # every spine knob that is actually set, off the one aggregated
        # registry (launch.remote.all_env_vars — the same list shipped
        # to remote workers), so a bug report carries the full config
        "knobs_set": _knobs_set(),
    }


def _knobs_set() -> dict:
    from tpuframe.launch.remote import all_env_vars

    return {k: os.environ[k] for k in all_env_vars() if k in os.environ}


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tpuframe",
        description="tpuframe environment doctor (one JSON report)",
    )
    ap.add_argument("--probe-timeout", type=float, default=30.0,
                    help="seconds before declaring the backend wedged")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory to report on (committed "
                         "steps + the latest step's topology manifest; "
                         "default: TPUFRAME_CKPT_DIR)")
    ap.add_argument("--export", default=None, dest="export_path",
                    help="serve export artifact to report on (meta + "
                         "AOT bucket shapes; "
                         "default: TPUFRAME_SERVE_EXPORT)")
    args = ap.parse_args(argv)
    rec = report(args.probe_timeout, args.ckpt_dir, args.export_path)
    print(json.dumps(rec, indent=2))
    return 1 if "error" in rec["devices"] else 0


if __name__ == "__main__":  # pragma: no cover - exercised via __main__
    raise SystemExit(main())
