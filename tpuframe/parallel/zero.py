"""ZeRO-style presets: DeepSpeed's stage ladder as sharding plans.

The reference authors four ZeRO configs but never engages them
(`/root/reference/02_deepspeed/deepspeed_config.py:52-105`; the distributor
call comments the config out at `/root/reference/02_deepspeed/
01_cifar_deepspeed_resnet.py:108`).  Here the ladder is real and declarative:
each stage is just a :class:`~tpuframe.parallel.sharding.ParallelPlan` with a
different sharding assignment, and the buckets/overlap/prefetch knobs from the
DeepSpeed dicts disappear — XLA schedules and overlaps its own collectives.

Stage-3's CPU offload (`deepspeed_config.py:87-105`, ``offload_optimizer/
offload_param -> cpu``) maps to JAX memory kinds: optimizer state pinned in
host memory (``pinned_host``) and streamed to HBM inside the update.  That is
only supported on real TPU backends, so it is a flag the Trainer applies when
the platform allows.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh

from tpuframe.parallel.sharding import ParallelPlan, Rule, host_memory_available


@dataclasses.dataclass(frozen=True)
class ZeroConfig:
    """Config-file-friendly description of a ZeRO stage (what
    ``deepspeed_config.deepspeed_zero_N`` described, minus the dead knobs)."""

    stage: int = 0
    offload_optimizer: bool = False  # stage-3 'offload_optimizer.device: cpu'
    min_shard_elems: int = 2**14

    @classmethod
    def from_dict(cls, cfg: Mapping[str, Any]) -> "ZeroConfig":
        """Accept a DeepSpeed-shaped dict: ``{"zero_optimization": {"stage": N,
        "offload_optimizer": {"device": "cpu"}}}`` or the flat form."""
        zo = cfg.get("zero_optimization", cfg)
        offload = zo.get("offload_optimizer")
        if isinstance(offload, Mapping):
            offload = offload.get("device") not in (None, "none")
        return cls(
            stage=int(zo.get("stage", 0)),
            offload_optimizer=bool(offload),
            min_shard_elems=int(zo.get("min_shard_elems", 2**14)),
        )

    def plan(self, mesh: Mesh, rules: Sequence[Rule] = ()) -> ParallelPlan:
        return ParallelPlan(
            mesh=mesh,
            zero_stage=self.stage,
            rules=tuple(rules),
            min_shard_elems=self.min_shard_elems,
            offload_optimizer=self.offload_optimizer,
        )


def zero_0(mesh: Mesh, **kw) -> ParallelPlan:
    """Pure DP (DDP semantics: replicate everything, all-reduce grads)."""
    return ZeroConfig(stage=0).plan(mesh, **kw)


def zero_1(mesh: Mesh, **kw) -> ParallelPlan:
    """Optimizer-state sharding (`deepspeed_config.py:53-63`)."""
    return ZeroConfig(stage=1).plan(mesh, **kw)


def zero_2(mesh: Mesh, **kw) -> ParallelPlan:
    """Grad+optimizer sharding (`deepspeed_config.py:66-71`); identical plan to
    stage 1 under XLA — gradient lifetime is the compiler's to schedule."""
    return ZeroConfig(stage=2).plan(mesh, **kw)


def zero_3(mesh: Mesh, **kw) -> ParallelPlan:
    """Fully-sharded params, all-gather on use (`deepspeed_config.py:74-84`)."""
    return ZeroConfig(stage=3).plan(mesh, **kw)


def zero_3_offload(mesh: Mesh, **kw) -> ParallelPlan:
    """Stage 3 + optimizer state in pinned host memory
    (`deepspeed_config.py:87-105`).  EXPERIMENTAL: downgrades to plain
    stage 3 — with a loud ``UserWarning`` — on backends without a usable
    host memory space; never run on a chip, no record exists: validate
    with ``benchmarks/check_offload_tpu.py`` before relying on the
    HBM savings on a given backend."""
    return ZeroConfig(stage=3, offload_optimizer=True).plan(mesh, **kw)


def host_offload_sharding(sharding: jax.sharding.Sharding) -> jax.sharding.Sharding:
    """The same sharding, placed in pinned host memory (stage-3 offload).

    Raises if the backend has no host memory space (CPU simulation).
    """
    return sharding.with_memory_kind("pinned_host")


def supports_host_offload() -> bool:
    return host_memory_available()
