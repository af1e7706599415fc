"""Autotune configuration: the knob registry slice it owns, the legal
search space, and the persisted winning-config store.

The store lives next to the compile cache (same ``TPUFRAME_LOCAL_SCRATCH``
root) and is keyed ``(host, topology, plan.signature())`` — the same
identity the compile spine uses to tell "same program, rebound" from
"different program".  A supervised restart on the same host, and every
other rank on that host, loads the persisted config and starts tuned
instead of re-probing; a different topology or plan signature misses the
key and tunes fresh.  Writes are atomic (tmp + ``os.replace``) and reads
are tolerant (corrupt/partial JSON loads as "no config"), like every
other scratch artifact in the tree.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import socket
import tempfile
import time
from typing import Any

__all__ = [
    "AUTOTUNE_ENV_VARS",
    "AUTOTUNE_ENV_DOMAINS",
    "TunedConfig",
    "all_env_domains",
    "autotune_dir",
    "autotune_enabled",
    "clamp",
    "config_key",
    "default_host",
    "list_tuned",
    "load_tuned",
    "save_tuned",
]

#: every env knob the autotune spine reads — THE list, aggregated by
#: ``launch.remote.all_env_vars()`` and printed by the doctor's
#: ``autotune`` section.  Add new knobs here, not in the consumers.
AUTOTUNE_ENV_VARS = (
    "TPUFRAME_AUTOTUNE",
    "TPUFRAME_AUTOTUNE_DIR",
    "TPUFRAME_AUTOTUNE_PROBE_STEPS",
    "TPUFRAME_AUTOTUNE_WARMUP_STEPS",
    "TPUFRAME_AUTOTUNE_GUARD",
    "TPUFRAME_AUTOTUNE_ROUNDS",
)

#: value domains for the knobs above (KN007).  The probe-shape knobs are
#: re-read per ``tune_training`` call -> "live"; the master switch and
#: the store location are consulted where components are built ->
#: "restart".
AUTOTUNE_ENV_DOMAINS = {
    "TPUFRAME_AUTOTUNE": {"type": "bool", "apply": "restart"},
    "TPUFRAME_AUTOTUNE_DIR": {"type": "path", "apply": "restart"},
    "TPUFRAME_AUTOTUNE_PROBE_STEPS": {
        "type": "int", "range": (2, 10000), "apply": "live"},
    "TPUFRAME_AUTOTUNE_WARMUP_STEPS": {
        "type": "int", "range": (0, 1000), "apply": "live"},
    "TPUFRAME_AUTOTUNE_GUARD": {
        "type": "float", "range": (0.5, 1.0), "apply": "live"},
    "TPUFRAME_AUTOTUNE_ROUNDS": {
        "type": "int", "range": (1, 64), "apply": "live"},
}

_FALSY = ("", "0", "false", "no", "off", "disabled")


def autotune_enabled() -> bool:
    """The master switch: ``TPUFRAME_AUTOTUNE`` truthy."""
    return os.environ.get("TPUFRAME_AUTOTUNE", "").strip().lower() not in _FALSY


def autotune_dir() -> str:
    """Where winning configs persist: ``TPUFRAME_AUTOTUNE_DIR``, else an
    ``autotune/`` sibling of the compile cache under the host-shared
    scratch root (every rank on a host shares one store, which is the
    point — same-host ranks start tuned)."""
    v = os.environ.get("TPUFRAME_AUTOTUNE_DIR", "").strip()
    if v:
        return v
    base = os.environ.get("TPUFRAME_LOCAL_SCRATCH") or os.path.join(
        tempfile.gettempdir(), "tpuframe_scratch"
    )
    return os.path.join(base, "autotune")


def default_host() -> str:
    return socket.gethostname()


def config_key(host: str, topology: str, signature: str) -> str:
    """Filename-stable digest of the persistence identity."""
    blob = json.dumps([host, topology, signature]).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


@dataclasses.dataclass
class TunedConfig:
    """One winning configuration: the env overrides that beat the
    baseline, plus enough provenance to audit how they won.

    ``env`` maps knob name -> string value (env-var encoding: this is
    exactly what a supervised restart exports).  ``probes`` records each
    A/B probe's (knob, value, p50, committed) so the doctor can show the
    decision trail.
    """

    host: str
    topology: str
    signature: str
    env: dict[str, str]
    source: str = "train"  # "train" | "serve"
    baseline_p50_s: float | None = None
    tuned_p50_s: float | None = None
    probes: list[dict] = dataclasses.field(default_factory=list)
    created_unix: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @property
    def convergence_ratio(self) -> float | None:
        """tuned p50 / baseline p50 (< 1.0 means the loop won)."""
        if self.baseline_p50_s and self.tuned_p50_s:
            return self.tuned_p50_s / self.baseline_p50_s
        return None


def _path_for(host: str, topology: str, signature: str,
              store_dir: str | None = None) -> str:
    d = store_dir or autotune_dir()
    return os.path.join(d, config_key(host, topology, signature) + ".json")


def save_tuned(cfg: TunedConfig, store_dir: str | None = None) -> str:
    """Atomically persist ``cfg``; returns the path.  A store that can't
    be written degrades to un-tuned restarts, never takes training down."""
    path = _path_for(cfg.host, cfg.topology, cfg.signature, store_dir)
    if not cfg.created_unix:
        cfg.created_unix = time.time()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(cfg.to_dict(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return path
    return path


def load_tuned(host: str, topology: str, signature: str,
               store_dir: str | None = None) -> TunedConfig | None:
    """The persisted config for this identity, or None (missing store,
    corrupt JSON, wrong shape — all read as "tune fresh")."""
    path = _path_for(host, topology, signature, store_dir)
    try:
        with open(path) as f:
            d = json.load(f)
        cfg = TunedConfig.from_dict(d)
    except (OSError, ValueError, TypeError):
        return None
    if (cfg.host, cfg.topology, cfg.signature) != (host, topology, signature):
        return None  # hash collision or hand-edited file: don't trust it
    return cfg


def list_tuned(store_dir: str | None = None) -> list[TunedConfig]:
    """Every readable persisted config in the store (doctor/CLI view)."""
    d = store_dir or autotune_dir()
    out: list[TunedConfig] = []
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(d, name)) as f:
                out.append(TunedConfig.from_dict(json.load(f)))
        except (OSError, ValueError, TypeError):
            continue
    return out


def all_env_domains() -> dict[str, dict]:
    """Every spine's knob value-domains, aggregated — the runtime mirror
    of ``launch.remote.all_env_vars()`` and the autotuner's legal search
    space.  Same stdlib-only import set, same reason: this must resolve
    on a wedged-backend process (the doctor prints it)."""
    from tpuframe.compile.cache import COMPILE_ENV_DOMAINS
    from tpuframe.core.workspace import PERF_ENV_DOMAINS
    from tpuframe.fault.health import HEALTH_ENV_DOMAINS
    from tpuframe.parallel.comms_env import COMMS_ENV_DOMAINS
    from tpuframe.serve.admission import SERVE_ENV_DOMAINS
    from tpuframe.track.telemetry import OBSERVABILITY_ENV_DOMAINS

    out: dict[str, dict] = {}
    for d in (OBSERVABILITY_ENV_DOMAINS, COMPILE_ENV_DOMAINS,
              HEALTH_ENV_DOMAINS, SERVE_ENV_DOMAINS, PERF_ENV_DOMAINS,
              COMMS_ENV_DOMAINS, AUTOTUNE_ENV_DOMAINS):
        out.update(d)
    return out


def clamp(knob: str, value: Any,
          domains: dict[str, dict] | None = None) -> str | None:
    """``value`` coerced into ``knob``'s legal domain as an env string,
    or None when the knob has no domain / the value can't be made legal.
    This is the single gate between a diagnosis and the environment: a
    move the registry doesn't sanction never reaches a probe."""
    d = (domains if domains is not None else all_env_domains()).get(knob)
    if d is None:
        return None
    t = d.get("type")
    try:
        if t == "int" or t == "float":
            num = int(value) if t == "int" else float(value)
            lo, hi = d.get("range", (None, None))
            if lo is not None and num < lo:
                num = int(lo) if t == "int" else float(lo)
            if hi is not None and num > hi:
                num = int(hi) if t == "int" else float(hi)
            return str(num)
        if t == "bool":
            if isinstance(value, str):
                return "0" if value.strip().lower() in _FALSY else "1"
            return "1" if value else "0"
        if t == "enum":
            s = str(value)
            return s if s in tuple(d.get("choices", ())) else None
        if t in ("str", "path"):
            return str(value)
    except (TypeError, ValueError):
        return None
    return None
