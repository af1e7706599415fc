"""Sharding planner: one object that decides where every tensor lives.

TPU-native replacement for the reference's parallelism stack (SURVEY.md §2.2):
DDP's replicate-and-allreduce (`/root/reference/01_torch_distributor/
01_basic_torch_distributor.py:285-291`) and DeepSpeed's ZeRO stage dicts
(`/root/reference/02_deepspeed/deepspeed_config.py:52-105`) both collapse into
*sharding assignments* here — XLA inserts the collectives (reduce-scatter,
all-gather, all-reduce over ICI) that DDP/ZeRO perform imperatively with NCCL.

The planner answers three questions for a train step:

1. Where do **params** live?  Replicated (DDP), sharded over ``fsdp``
   (ZeRO-3 / FSDP), and/or split by tensor-parallel rules on ``model``.
2. Where does **optimizer state** live?  With the params (stage 0/3) or
   sharded over ``fsdp`` even while params stay replicated (stage 1/2 —
   DeepSpeed's optimizer/gradient partitioning ≈ XLA weight-update sharding).
3. Where do **batches** live?  Split over every data-ish axis.

Everything is declarative: the plan produces ``NamedSharding`` pytrees that
are handed to ``jax.jit(in_shardings=..., out_shardings=...)``; no imperative
hooks, no bucketing, no ``overlap_comm`` knobs — XLA's scheduler overlaps the
collectives with compute on its own.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
import warnings
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS

#: A tensor-parallel rule: (regex over the param path, PartitionSpec).
Rule = tuple[str, P]


def spec_to_json(spec: P) -> list:
    """A PartitionSpec as plain JSON: each entry None, a str, or a list
    of strs — the form checkpoint topology manifests store per leaf."""
    out: list = []
    for entry in spec:
        if entry is None or isinstance(entry, str):
            out.append(entry)
        else:  # tuple of axis names
            out.append(list(entry))
    return out


def spec_from_json(entries: Sequence) -> P:
    """Inverse of :func:`spec_to_json`."""
    return P(*(tuple(e) if isinstance(e, list) else e for e in entries))


def mesh_axes(mesh: Mesh) -> dict[str, int]:
    """``{axis_name: size}`` for a mesh — the manifest's topology key."""
    return {str(name): int(size) for name, size in mesh.shape.items()}


def host_memory_available(mesh: Mesh | None = None) -> bool:
    """True when host-offloaded placement actually works: a real TPU
    backend whose devices expose a ``pinned_host`` memory space.

    The CPU simulation backend *lists* pinned_host but cannot compile
    SPMD programs with host-placement annotations ("side-effect ops
    cannot be replicated"), so CPU always returns False — offload plans
    downgrade gracefully in tests/dryruns."""
    if jax.default_backend() != "tpu":
        return False
    try:
        devs = mesh.devices.flat if mesh is not None else jax.devices()
        dev = next(iter(devs))
        return any(m.kind == "pinned_host" for m in dev.addressable_memories())
    except Exception:  # pragma: no cover - backend-dependent
        return False


def path_str(path: tuple) -> str:
    """Render a jax tree path as ``a/b/c`` (DictKey/SequenceKey/attr agnostic)."""
    parts = []
    for key in path:
        if hasattr(key, "key"):
            parts.append(str(key.key))
        elif hasattr(key, "idx"):
            parts.append(str(key.idx))
        elif hasattr(key, "name"):
            parts.append(str(key.name))
        else:
            parts.append(str(key))
    return "/".join(parts)


def infer_shard_dim(shape: Sequence[int], axis_size: int, taken: Sequence[int] = ()) -> int | None:
    """Pick the dimension to shard ``axis_size``-ways: the largest divisible
    dim not already taken by another mesh axis.  None if nothing divides."""
    best = None
    for dim, size in enumerate(shape):
        if dim in taken or size % axis_size or size < axis_size:
            continue
        if best is None or size > shape[best]:
            best = dim
    return best


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Declarative parallelism policy over a named mesh.

    ``zero_stage`` maps DeepSpeed's ladder onto XLA sharding:

    - 0: pure DP — params+opt state replicated, grads all-reduced (DDP).
    - 1/2: params replicated, **optimizer state sharded** over ``fsdp``;
      XLA turns the update into reduce-scatter(grads) -> sharded update ->
      all-gather(params), i.e. DeepSpeed's stage-1/2 comm pattern
      (`deepspeed_config.py:53-71`).  1 and 2 are one stage here because
      gradient lifetime is XLA's to schedule, not ours.
    - 3: **params sharded** over ``fsdp`` (all-gather on use), optimizer
      state sharded to match (`deepspeed_config.py:74-84`).

    ``rules`` add tensor parallelism: first regex matching a param's path
    assigns an explicit PartitionSpec (axes it names are layered on top of
    any fsdp sharding).  ``min_shard_elems`` keeps small tensors (biases, BN
    scales) replicated — sharding them costs more latency than HBM.
    """

    mesh: Mesh
    zero_stage: int = 0
    rules: Sequence[Rule] = ()
    min_shard_elems: int = 2**14
    fsdp_axis: str = FSDP_AXIS
    data_axes: Sequence[str] = (DATA_AXIS, FSDP_AXIS)
    #: DeepSpeed stage-3 CPU offload (`deepspeed_config.py:87-105`):
    #: optimizer-state leaves live in pinned host memory and stream to HBM
    #: inside the update.  EXPERIMENTAL: applied only when the backend has
    #: a usable ``pinned_host`` memory space (real TPUs — CPU simulation
    #: downgrades with a warning), and the pinned-host path has not yet
    #: been executed on real TPU hardware in this repo (never run on a
    #: chip; no record exists) — ``benchmarks/check_offload_tpu.py`` is
    #: the acceptance harness, and a passing run of it on a backend is
    #: the proof of support there.
    offload_optimizer: bool = False
    #: bucket-group count for the scheduled compressed gradient sync
    #: (see ``parallel.compression.sync_gradients``): None defers to
    #: ``CommsConfig.groups`` (the ``TPUFRAME_COMMS_GROUPS`` env knob);
    #: an explicit value pins the schedule on the plan so it rides the
    #: plan signature, the topology manifest, and the compile labels.
    comms_groups: int | None = None
    #: in-collective compressed transport (see
    #: ``parallel.compression.fused_active``): None defers to
    #: ``CommsConfig.fused`` (the ``TPUFRAME_COMMS_FUSED`` env knob);
    #: an explicit bool pins the transport on the plan so it rides the
    #: plan signature and the AOT compile labels — a fused and a staged
    #: program are different programs.
    comms_fused: bool | None = None
    #: microbatch count for the pipeline schedule (``parallel.pipeline``):
    #: None defers to the model/``TPUFRAME_PP_MICROBATCHES`` env knob; an
    #: explicit value pins the schedule depth on the plan so it rides the
    #: plan signature and the AOT compile labels — a different microbatch
    #: count is a different scanned program.
    pp_microbatches: int | None = None
    #: pipeline hop/compute interleave policy (``parallel.pipeline``
    #: schedules): None defers to ``TPUFRAME_PP_SCHEDULE`` (default
    #: ``interleaved``); an explicit value pins it on the plan.
    #: ``interleaved`` lets the scheduler slot ``ppermute`` hops between
    #: stage compute, ``1f1b`` adds remat-bounded stage stashes, and
    #: ``barriered`` serializes hop-then-compute (the A/B baseline arm).
    pp_schedule: str | None = None

    def __post_init__(self):
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0..3, got {self.zero_stage}")
        if self.comms_groups is not None and self.comms_groups < 1:
            raise ValueError(
                f"comms_groups must be >= 1 (or None), got {self.comms_groups}"
            )
        if self.comms_fused not in (None, True, False):
            raise ValueError(
                f"comms_fused must be a bool or None, got {self.comms_fused!r}"
            )
        if self.pp_microbatches is not None and self.pp_microbatches < 1:
            raise ValueError(
                f"pp_microbatches must be >= 1 (or None), got {self.pp_microbatches}"
            )
        from tpuframe.parallel.pipeline import PP_SCHEDULES

        if self.pp_schedule is not None and self.pp_schedule not in PP_SCHEDULES:
            raise ValueError(
                f"pp_schedule must be one of {PP_SCHEDULES} (or None), "
                f"got {self.pp_schedule!r}"
            )
        if self.offload_optimizer and not host_memory_available(self.mesh):
            # loud, not silent: a user who asked for DeepSpeed-style CPU
            # offload must know their optimizer state is staying in HBM
            warnings.warn(
                "offload_optimizer=True requested but backend "
                f"{jax.default_backend()!r} has no usable pinned_host memory "
                f"space; downgrading to plain ZeRO-{self.zero_stage} "
                "(optimizer state stays in device HBM). Host offload is "
                "EXPERIMENTAL: run benchmarks/check_offload_tpu.py on the "
                "target backend to validate it before relying on the "
                "memory savings.",
                stacklevel=3,
            )

    def _offload_active(self) -> bool:
        return self.offload_optimizer and host_memory_available(self.mesh)

    # -- identity / topology ----------------------------------------------
    def signature(self) -> str:
        """Stable short digest of the plan's *policy + topology*: mesh
        axis names/sizes, ZeRO stage, TP rules, thresholds.  Two plans
        with equal signatures lower the same step program for the same
        batch signature, so this is the key the compile spine (and the
        checkpoint topology manifest) uses to tell "same plan, rebound"
        from "different plan".  Deliberately excludes device identities:
        the same logical shape on different physical chips is the same
        program."""
        payload = {
            "mesh": sorted(mesh_axes(self.mesh).items()),
            "zero_stage": self.zero_stage,
            "rules": [[pat, spec_to_json(spec)] for pat, spec in self.rules],
            "min_shard_elems": self.min_shard_elems,
            "fsdp_axis": self.fsdp_axis,
            "data_axes": list(self.data_axes),
            "offload": bool(self.offload_optimizer),
        }
        # schedule-bearing plans key their own programs; the default
        # (None / 1 = single-shot) is OMITTED so every pre-existing plan
        # signature — autotune store keys, topology manifests, compile
        # labels — is unchanged by the field's existence
        if self.comms_groups is not None and self.comms_groups != 1:
            payload["comms_groups"] = int(self.comms_groups)
        # same omit-the-default rule for the fused transport: only a
        # pinned True changes the program identity (pinned False is the
        # staged program every pre-existing signature already names)
        if self.comms_fused:
            payload["comms_fused"] = True
        # pipeline-schedule pins are program identity too (a different
        # microbatch count or interleave policy lowers a different scanned
        # program), but the defaults are omitted so pre-existing plan
        # signatures stay byte-stable
        if self.pp_microbatches is not None:
            payload["pp_microbatches"] = int(self.pp_microbatches)
        if self.pp_schedule is not None and self.pp_schedule != "interleaved":
            payload["pp_schedule"] = str(self.pp_schedule)
        blob = json.dumps(payload, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:12]

    def comms_schedule(self, config: Any = None) -> dict:
        """The plan's collective schedule as a first-class artifact:
        how many bucket groups the compressed gradient sync fires, and
        in what order.  ``config`` (a ``CommsConfig``) supplies the env
        default when the plan itself doesn't pin ``comms_groups``.
        ``order`` is fixed: groups fire in reverse path-sorted bucket
        order — the reverse-backward leaf order, so the group covering
        the gradients backward produces *first* goes on the wire first
        and hides behind the rest of the backward."""
        groups = self.comms_groups
        if groups is None:
            groups = int(getattr(config, "groups", 1) or 1)
        fused = self.comms_fused
        if fused is None:
            fused = bool(getattr(config, "fused", False))
        return {
            "groups": int(groups),
            "order": "reverse_backward",
            "pinned": self.comms_groups is not None,
            "fused": bool(fused),
            "fused_pinned": self.comms_fused is not None,
            "pp_schedule": self.pp_schedule or "interleaved",
            "pp_pinned": self.pp_schedule is not None,
        }

    def describe_topology(self) -> dict:
        """The plan's topology as manifest-shaped JSON (mesh axes, world
        size, signature) — what ``fault/world_resized`` events carry.
        The ``pipeline_stages``/``tp_size`` breakout names the composed
        N-D split explicitly so a plan-change restore (TP=4 saved,
        TP=2×PP=2 target) reads as a *plan* move, not just a mesh diff."""
        axes = mesh_axes(self.mesh)
        return {
            "mesh_axes": axes,
            "world_size": int(self.mesh.devices.size),
            "plan_signature": self.signature(),
            "zero_stage": self.zero_stage,
            "pipeline_stages": int(axes.get("pipe", 1)),
            "tp_size": int(axes.get("model", 1)),
        }

    def rebind(self, mesh: Mesh) -> "ParallelPlan":
        """Re-derive an equivalent plan over a different mesh (the elastic
        shrink/grow path): every policy knob — ZeRO stage, TP rules,
        thresholds — carries over; only the topology changes.  Axis
        *collapses* (an axis that was >1 now 1: ZeRO sharding vanishing
        when ``fsdp`` collapses, TP rules going inert when ``model``
        does) are loud — one ``parallel/plan_rebind`` event with the
        old/new axes plus a warning, because the memory/layout contract
        the old plan bought silently disappears otherwise."""
        from tpuframe.track.telemetry import get_telemetry

        old_axes, new_axes = mesh_axes(self.mesh), mesh_axes(mesh)
        new = dataclasses.replace(self, mesh=mesh)
        collapsed = sorted(
            a for a in old_axes
            if old_axes.get(a, 1) > 1 and new_axes.get(a, 1) == 1
        )
        get_telemetry().event(
            "parallel/plan_rebind",
            from_axes=old_axes,
            to_axes=new_axes,
            from_world=int(self.mesh.devices.size),
            to_world=int(mesh.devices.size),
            collapsed=collapsed,
            signature=new.signature(),
        )
        if collapsed:
            warnings.warn(
                f"plan rebind collapsed mesh axis(es) {collapsed} to size 1 "
                f"({old_axes} -> {new_axes}): sharding over those axes is "
                "now inert (ZeRO partitions gather to every replica when "
                "fsdp collapses; TP rules naming a collapsed axis "
                "replicate).  Expected when shrinking to survivors — but "
                "re-check the memory budget fits the new world.",
                stacklevel=2,
            )
        return new

    # -- axis helpers ------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name] if name in self.mesh.shape else 1

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.axis_size(a) for a in self.data_axes]))

    # -- batch -------------------------------------------------------------
    def batch_spec(self) -> P:
        axes = tuple(a for a in self.data_axes if self.axis_size(a) > 1)
        return P(axes) if axes else P()

    def batch_sharding(self, leading_microbatch: bool = False) -> NamedSharding:
        """``leading_microbatch=True`` for (n_micro, micro, ...) grad-accum
        batches: the microbatch dim leads, the batch axes shard dim 1."""
        spec = self.batch_spec()
        if leading_microbatch:
            spec = P(None, *spec)
        return NamedSharding(self.mesh, spec)

    # -- params ------------------------------------------------------------
    def _rule_spec(self, path: str) -> P | None:
        for pattern, spec in self.rules:
            if re.search(pattern, path):
                return spec
        return None

    def _maybe_fsdp(self, shape: Sequence[int], base: P) -> P:
        """Layer fsdp sharding onto ``base`` if the plan shards params."""
        size = self.axis_size(self.fsdp_axis)
        if size <= 1 or int(np.prod(shape)) < self.min_shard_elems:
            return base
        # a TP rule may already place fsdp; a duplicate axis is illegal
        named = {
            a for e in base if e is not None
            for a in (e if isinstance(e, tuple) else (e,))
        }
        if self.fsdp_axis in named:
            return base
        entries = list(base) + [None] * (len(shape) - len(base))
        taken = [i for i, e in enumerate(entries) if e is not None]
        dim = infer_shard_dim(shape, size, taken)
        if dim is None:
            return base
        entries[dim] = self.fsdp_axis
        return P(*entries)

    def param_spec(self, path: str, shape: Sequence[int]) -> P:
        spec = self._rule_spec(path) or P()
        if self.zero_stage == 3:
            spec = self._maybe_fsdp(shape, spec)
        return spec

    def _state_spec(self, path: str, shape: Sequence[int]) -> P:
        """Optimizer-state leaves: follow params, plus fsdp for stage>=1.

        A state leaf can have lower rank than the param it mirrors (e.g.
        adafactor's row/col factors); the param's TP rule spec is then
        meaningless for it, so it falls back to plain fsdp inference.
        """
        spec = self._rule_spec(path) or P()
        if len(spec) > len(shape):
            spec = P()
        if self.zero_stage >= 1:
            spec = self._maybe_fsdp(shape, spec)
        return spec

    def update_shard_specs(self, params: Any) -> dict[str, tuple]:
        """The plan-derived weight-update sharding (arXiv:2004.13336,
        mechanically from the data-parallel graph): for ZeRO stage 1/2/3,
        every param leaf big enough to shard (``min_shard_elems``) with
        a dimension divisible by the *combined* data-parallel world is
        assigned ``{path: (dim, axes)}`` — the compressed train step
        reduce-scatters its gradient along ``dim`` over ``axes``, runs
        the optimizer on the owned slice against the plan's sharded
        state, and all-gathers the update.  Leaves that don't qualify
        (small, or no divisible dim) stay replicated and travel in the
        shared transport buckets instead.
        """
        axes = tuple(a for a in self.data_axes if self.axis_size(a) > 1)
        world = int(np.prod([self.axis_size(a) for a in axes])) if axes else 1
        out: dict[str, tuple] = {}
        if world <= 1 or self.zero_stage not in (1, 2, 3):
            return out
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            if not shape or int(np.prod(shape)) < self.min_shard_elems:
                continue
            dim = infer_shard_dim(shape, world)
            if dim is not None:
                out[path_str(path)] = (dim, axes)
        return out

    def param_shardings(self, params: Any) -> Any:
        """Pytree of NamedSharding matching ``params`` (arrays or ShapeDtypeStructs)."""

        def assign(path, leaf):
            if not hasattr(leaf, "shape") or leaf.shape == ():
                return self.replicated()
            return NamedSharding(self.mesh, self.param_spec(path_str(path), leaf.shape))

        return jax.tree_util.tree_map_with_path(assign, params)

    def state_shardings(self, state: Any, params: Any, with_offload: bool = True) -> Any:
        """Pytree of NamedSharding for an optax state mirroring ``params``.

        Param-shaped leaves inside the state (``mu``/``nu``/trace buffers —
        optax builds them with the params' own tree structure, so their tree
        paths end with the param's path) get the param-aligned spec with the
        ZeRO-stage fsdp sharding layered on; scalars (step counts) replicate.

        ``with_offload=False`` suppresses the pinned-host memory kind even
        when offload is active — used for shardings that must be legal
        inside a jit's ``out_shardings`` (XLA rejects memory-kind
        annotations there); the caller then ``device_put``s to the
        offloaded shardings afterwards.
        """
        param_paths = {
            path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        }
        offload = with_offload and self._offload_active()

        def place(sharding: NamedSharding) -> NamedSharding:
            # Scalars (step counts) stay on device: they gate control flow.
            return sharding.with_memory_kind("pinned_host") if offload else sharding

        def assign(path, leaf):
            if not hasattr(leaf, "shape") or leaf.shape == ():
                return self.replicated()
            full = path_str(path)
            # longest param-path suffix match identifies param-mirroring leaves
            parts = full.split("/")
            for start in range(len(parts)):
                if "/".join(parts[start:]) in param_paths:
                    return place(NamedSharding(
                        self.mesh, self._state_spec("/".join(parts[start:]), leaf.shape)
                    ))
            # non-param-mirroring leaves (EMA buffers etc.) follow the stage
            # gate too: stage 0 means *everything* in the state is replicated
            spec = self._maybe_fsdp(leaf.shape, P()) if self.zero_stage >= 1 else P()
            return place(NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(assign, state)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- application -------------------------------------------------------
    def shard_params(self, params: Any) -> Any:
        """Place a live param pytree according to the plan (host -> devices)."""
        return jax.device_put(params, self.param_shardings(params))

    def shard_batch(self, batch: Any, leading_microbatch: bool = False) -> Any:
        """Host batch (this process's shard) -> global sharded Arrays.

        Multi-process runs assemble the global array from per-process
        locals via ``jax.make_array_from_process_local_data`` (each
        process passes *different* rows — a plain device_put would
        reject that); single-process is a straight device_put.
        """
        sharding = self.batch_sharding(leading_microbatch)
        if jax.process_count() > 1:
            put = lambda x: jax.make_array_from_process_local_data(  # noqa: E731
                sharding, np.asarray(x)
            )
        else:
            put = lambda x: jax.device_put(x, sharding)  # noqa: E731
        return jax.tree.map(put, batch)

    def describe(self, params: Any) -> dict[str, str]:
        """Human-readable spec per param path (for logging/debugging)."""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            p = path_str(path)
            shape = getattr(leaf, "shape", ())
            out[p] = f"{tuple(shape)} -> {self.param_spec(p, shape)}"
        return out
