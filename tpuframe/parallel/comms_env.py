"""Comms knob registry — the stdlib-only half of the compression spine.

The wire-level collective configuration (``tpuframe.parallel.compression``)
is env-tunable per fleet: every knob here ships to remote workers through
``launch.remote.all_env_vars()`` and prints in the doctor's ``comms``
section.  Kept jax-free (like ``serve.admission`` / ``core.workspace``)
so the aggregator and the doctor can read the registry from a
wedged-backend or jax-less process.

Knob semantics (the one table, mirrored in OBSERVABILITY.md):

- ``TPUFRAME_COMMS_COMPRESSION`` — gradient wire format: ``int8`` /
  ``fp8`` (e4m3) / empty = off.  The ``Trainer(grad_compression=...)``
  parameter overrides the env.
- ``TPUFRAME_COMMS_BUCKET_MB`` — transport bucket size in MiB of f32
  payload (default 4.0).  Leaves are flattened into a small number of
  fixed-size buckets, each with its own quantization scale.
- ``TPUFRAME_COMMS_STOCHASTIC`` — ``1`` enables stochastic rounding on
  the int8 grid (unbiased; fp8 uses round-to-nearest-even in hardware,
  the knob does not apply there).
- ``TPUFRAME_COMMS_EF`` — error feedback on/off (default on): the
  quantization residual is carried as a ``TrainState.comms`` leaf and
  re-injected next step, so the compressed trajectory tracks f32.
- ``TPUFRAME_COMMS_GROUPS`` — bucket-group count for the scheduled
  sync (default 1 = the single-shot collective).  Groups fire in
  reverse path-sorted order (the reverse-backward leaf order: the
  deepest layers' gradients are produced first), so group *i*'s
  quantized collective is dataflow-independent of group *i+1*'s
  quantization and can hide behind it.  Bit-exact against the
  single-shot reference — per-bucket scales/EF/non-finite handling are
  elementwise over the bucket dimension, so partitioning changes the
  schedule, never the arithmetic.  A ``ParallelPlan.comms_groups``
  override wins over the env (the plan is the first-class schedule
  artifact).
- ``TPUFRAME_COMMS_FUSED`` — ``1`` fuses the quantized wire *into* the
  collective: the staged single-``psum`` transport is replaced by a
  manual ring reduce-scatter / all-gather over the data axes whose hops
  carry the 8-bit payloads directly (per-bucket scales agreed once up
  front, partial sums accumulated exactly on arrival), so quantized
  bytes — not f32 — are what cross the wire on every hop.  Bit-exact
  against the staged path in every mode: int8 partials are integer
  sums, fp8-e4m3 grid values are multiples of 2^-9 bounded by 448 so
  f32 partial sums stay exact through world sizes <= 73 (beyond that
  the fp8 wire falls back to staged rather than drift).  Requires a
  single data axis; multi-axis meshes and world size 1 fall back to
  the staged path.  A ``ParallelPlan.comms_fused`` override wins over
  the env (same plan-first rule as ``comms_groups``).
- ``TPUFRAME_COMMS_FUSED_BLOCK`` — column-block element count for the
  ``ops.quant_wire`` Pallas encode/decode kernels (default 2048, lane
  multiple).  Larger blocks amortize grid overhead; smaller ones fit
  tighter VMEM budgets next to the ring buffers.
- ``TPUFRAME_COMMS_ASYNC`` — ``1`` turns on the backend's
  latency-hiding-scheduler / async-collective-fusion XLA flags at
  ``core.runtime.initialize`` (:func:`comms_async_flags` is the one
  resolver; the doctor prints the resolved set).  Restart-only: XLA
  reads the flags at backend init.  No-op on CPU — the CPU compiler
  rejects the TPU/GPU scheduler flags, so the resolver returns an
  empty set there rather than aborting the process.
- ``TPUFRAME_PP_MICROBATCHES`` — microbatches per pipeline step
  (default 0 = unset: the model's ``n_microbatches`` default applies).
  More microbatches shrink the GPipe bubble ``(S-1)/(M+S-1)``.  A
  composed ``ParallelPlan.pp_microbatches`` pin (or an explicit model
  field) wins over the env and rides the plan signature.
- ``TPUFRAME_PP_SCHEDULE`` — pipeline hop/compute interleave policy:
  ``interleaved`` (default; ``ppermute`` hops slot behind stage
  compute), ``1f1b`` (interleaved + remat-bounded backward stash), or
  ``barriered`` (hop-then-compute serialized — the baseline arm of an
  A/B against ``interleaved``, not a production schedule).  A
  ``ParallelPlan.pp_schedule`` pin wins over the env.
- ``TPUFRAME_TP_SIZE`` — tensor-parallel (``model`` axis) size
  ``parallel.compose.compose`` builds its mesh with when the caller
  doesn't pass ``tp=`` (default 1 = no TP).  Restart-only: the mesh is
  laid out at ``initialize``.
- ``TPUFRAME_ZERO_STAGE`` — ZeRO stage [0, 3] ``compose`` uses when the
  caller doesn't pass ``zero_stage=`` (default 0 = pure DP).  The
  memory-bound autotune branch proposes stage moves through this knob;
  restart-only because the state shardings are laid out at plan build.
- ``TPUFRAME_OFFLOAD_OPTIMIZER`` — ``1`` defaults ``compose`` to
  host-offloaded optimizer state (the plan still downgrades loudly on
  backends without an addressable host space).  The estimator prices
  the offloaded bytes as ``host_total`` instead of HBM.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import dataclasses
import os

__all__ = [
    "COMMS_ENV_VARS",
    "CommsConfig",
    "COMPRESSION_MODES",
    "PP_SCHEDULE_CHOICES",
    "comms_async_enabled",
    "comms_async_flags",
    "comms_async_platform",
    "comms_fused_block",
    "offload_optimizer_default",
    "pp_microbatches",
    "pp_schedule",
    "tp_size",
    "zero_stage_default",
]

#: the comms spine's env knobs — aggregated by
#: ``launch.remote.all_env_vars()`` and printed by the doctor
COMMS_ENV_VARS = (
    "TPUFRAME_COMMS_COMPRESSION",
    "TPUFRAME_COMMS_BUCKET_MB",
    "TPUFRAME_COMMS_STOCHASTIC",
    "TPUFRAME_COMMS_EF",
    "TPUFRAME_COMMS_GROUPS",
    "TPUFRAME_COMMS_FUSED",
    "TPUFRAME_COMMS_FUSED_BLOCK",
    "TPUFRAME_COMMS_ASYNC",
    "TPUFRAME_PP_MICROBATCHES",
    "TPUFRAME_PP_SCHEDULE",
    "TPUFRAME_TP_SIZE",
    "TPUFRAME_ZERO_STAGE",
    "TPUFRAME_OFFLOAD_OPTIMIZER",
)

#: value domains for the knobs above (KN007).  All "restart":
#: ``CommsConfig.from_env`` is snapshotted when the train step is
#: built, and changing the wire format retraces the step anyway.
COMMS_ENV_DOMAINS = {
    "TPUFRAME_COMMS_COMPRESSION": {
        "type": "enum", "choices": ("", "int8", "fp8"), "apply": "restart"},
    "TPUFRAME_COMMS_BUCKET_MB": {
        "type": "float", "range": (0.25, 1024.0), "apply": "restart"},
    "TPUFRAME_COMMS_STOCHASTIC": {"type": "bool", "apply": "restart"},
    "TPUFRAME_COMMS_EF": {"type": "bool", "apply": "restart"},
    "TPUFRAME_COMMS_GROUPS": {
        "type": "int", "range": (1, 64), "apply": "restart"},
    "TPUFRAME_COMMS_FUSED": {"type": "bool", "apply": "restart"},
    "TPUFRAME_COMMS_FUSED_BLOCK": {
        "type": "int", "range": (128, 65536), "apply": "restart"},
    "TPUFRAME_COMMS_ASYNC": {"type": "bool", "apply": "restart"},
    "TPUFRAME_PP_MICROBATCHES": {
        "type": "int", "range": (0, 4096), "apply": "restart"},
    "TPUFRAME_PP_SCHEDULE": {
        "type": "enum",
        "choices": ("", "interleaved", "barriered", "1f1b"),
        "apply": "restart"},
    "TPUFRAME_TP_SIZE": {
        "type": "int", "range": (1, 64), "apply": "restart"},
    "TPUFRAME_ZERO_STAGE": {
        "type": "int", "range": (0, 3), "apply": "restart"},
    "TPUFRAME_OFFLOAD_OPTIMIZER": {"type": "bool", "apply": "restart"},
}

#: wire formats the compressed collectives understand
COMPRESSION_MODES = ("int8", "fp8")

#: pipeline schedules the env knob accepts — the one source of truth
#: (``parallel.pipeline.PP_SCHEDULES`` re-exports it); lives here,
#: stdlib-only, so the registry stays importable from a jax-less process
PP_SCHEDULE_CHOICES = ("interleaved", "barriered", "1f1b")

_FALSY = {"0", "false", "off", "no", ""}


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _env_bool(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _FALSY


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


# -- TPUFRAME_COMMS_ASYNC: the XLA scheduler flag resolver --------------------

#: per-platform flag sets the async knob turns on.  TPU: the
#: latency-hiding scheduler (orders independent collectives into
#: compute gaps) + async-collective fusion (keeps the DMA in flight
#: across the fused region).  GPU: the LHS has its own flag name.
#: CPU has neither pass and the compiler aborts on unknown flags, so
#: its entry is the empty set — the knob degrades to a no-op there.
_ASYNC_FLAGS = {
    "tpu": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
    ),
    "gpu": ("--xla_gpu_enable_latency_hiding_scheduler=true",),
    "cuda": ("--xla_gpu_enable_latency_hiding_scheduler=true",),
}


def comms_async_enabled(environ: dict | None = None) -> bool:
    """Is ``TPUFRAME_COMMS_ASYNC`` requested? (Whether it resolves to
    any flags is the platform's call — :func:`comms_async_flags`.)"""
    env = os.environ if environ is None else environ
    raw = env.get("TPUFRAME_COMMS_ASYNC")
    if raw is None:
        return False
    return raw.strip().lower() not in _FALSY


def comms_async_platform(environ: dict | None = None) -> str:
    """Best-effort backend guess WITHOUT importing jax (asking jax for
    its backend would initialize it — exactly what must not happen
    before the flags are merged into ``XLA_FLAGS``): the first
    ``JAX_PLATFORMS`` token when set, else "tpu" when libtpu is
    importable, else "cpu"."""
    env = os.environ if environ is None else environ
    plats = env.get("JAX_PLATFORMS", "").strip().lower()
    if plats:
        return plats.split(",")[0].strip() or "cpu"
    try:
        import importlib.util

        if importlib.util.find_spec("libtpu") is not None:
            return "tpu"
    except (ImportError, ValueError):
        pass
    return "cpu"


def comms_async_flags(platform: str | None = None,
                      environ: dict | None = None) -> tuple[str, ...]:
    """The resolved XLA flag set ``TPUFRAME_COMMS_ASYNC`` adds for
    ``platform`` (default: :func:`comms_async_platform`), or ``()``
    when the knob is off or the platform has no safe flags.  One
    resolver for ``core.runtime.initialize`` (applies it) and the
    doctor (prints it)."""
    if not comms_async_enabled(environ):
        return ()
    plat = platform if platform is not None else comms_async_platform(environ)
    return _ASYNC_FLAGS.get(plat, ())


@dataclasses.dataclass(frozen=True)
class CommsConfig:
    """Resolved wire-compression policy for the gradient collectives.

    ``mode`` is one of :data:`COMPRESSION_MODES`; construction validates
    it so a typo'd env/param fails at build time, not mid-step.
    """

    mode: str = "int8"
    bucket_mb: float = 4.0
    stochastic_rounding: bool = False
    error_feedback: bool = True
    #: bucket-group count for the scheduled sync (1 = single shot).
    #: More groups than buckets clamps down at layout build.
    groups: int = 1
    #: in-collective transport: ring reduce-scatter/all-gather whose
    #: hops carry the 8-bit payloads (False = staged psum around one
    #: encode/decode).  Falls back to staged on multi-axis meshes,
    #: world size 1, and fp8 beyond the exact-sum world bound.
    fused: bool = False

    def __post_init__(self):
        if self.mode not in COMPRESSION_MODES:
            raise ValueError(
                f"unknown grad_compression {self.mode!r}; known: "
                + "/".join(COMPRESSION_MODES)
            )
        if self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {self.bucket_mb}")
        if self.groups < 1:
            raise ValueError(f"groups must be >= 1, got {self.groups}")

    @property
    def bucket_elems(self) -> int:
        """Max f32 elements per transport bucket."""
        return max(64, int(self.bucket_mb * (1 << 20) / 4))

    @property
    def wire_bytes_per_elem(self) -> int:
        """Payload bytes per element on the wire (int8 and fp8-e4m3 are
        both one byte)."""
        return 1

    @classmethod
    def from_env(cls, mode: str | None = None) -> "CommsConfig | None":
        """The env-resolved config; ``mode`` (a Trainer/step parameter)
        overrides ``TPUFRAME_COMMS_COMPRESSION``.  None = compression
        off (no mode requested anywhere).  Malformed numeric/boolean
        knobs fall back to defaults (tolerant, like ``ServeKnobs``); an
        unknown *mode* still raises — silently training uncompressed
        when compression was asked for is the one failure that must be
        loud."""
        if mode is None:
            mode = os.environ.get("TPUFRAME_COMMS_COMPRESSION", "").strip()
        if isinstance(mode, CommsConfig):
            return mode
        if not mode:
            return None
        return cls(
            mode=str(mode).lower(),
            bucket_mb=_env_float("TPUFRAME_COMMS_BUCKET_MB", 4.0),
            stochastic_rounding=_env_bool("TPUFRAME_COMMS_STOCHASTIC", False),
            error_feedback=_env_bool("TPUFRAME_COMMS_EF", True),
            groups=max(1, _env_int("TPUFRAME_COMMS_GROUPS", 1)),
            fused=_env_bool("TPUFRAME_COMMS_FUSED", False),
        )


def comms_fused_block(environ: dict | None = None) -> int:
    """Column-block element count for the ``ops.quant_wire`` kernels
    (``TPUFRAME_COMMS_FUSED_BLOCK``), clamped to the declared domain and
    rounded down to a lane multiple.  Lives here — not in ops/ — so the
    knob's one read site sits next to its registry row."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_COMMS_FUSED_BLOCK", "") or "").strip()
    try:
        val = int(raw) if raw else 2048
    except ValueError:
        val = 2048
    val = max(128, min(65536, val))
    return (val // 128) * 128


def pp_microbatches(environ: dict | None = None) -> int:
    """``TPUFRAME_PP_MICROBATCHES`` resolved and clamped to its declared
    domain; 0 = unset (the model's ``n_microbatches`` default applies).
    A composed plan's ``pp_microbatches`` pin wins over this env value."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_PP_MICROBATCHES", "") or "").strip()
    try:
        val = int(raw) if raw else 0
    except ValueError:
        val = 0
    return max(0, min(4096, val))


def pp_schedule(environ: dict | None = None) -> str:
    """``TPUFRAME_PP_SCHEDULE`` resolved against
    :data:`PP_SCHEDULE_CHOICES`; unset/unknown values fall back to
    ``interleaved`` (tolerant like the other comms knobs — the pipeline
    primitive itself is the loud validator for programmatic schedules).
    A ``ParallelPlan.pp_schedule`` pin wins over this env value."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_PP_SCHEDULE", "") or "").strip().lower()
    return raw if raw in PP_SCHEDULE_CHOICES else "interleaved"


def tp_size(environ: dict | None = None) -> int:
    """``TPUFRAME_TP_SIZE`` resolved and clamped to its declared domain
    (default 1 = no tensor parallelism); ``parallel.compose.compose``
    reads it when the caller doesn't pass ``tp=`` explicitly."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_TP_SIZE", "") or "").strip()
    try:
        val = int(raw) if raw else 1
    except ValueError:
        val = 1
    return max(1, min(64, val))


def zero_stage_default(environ: dict | None = None) -> int:
    """``TPUFRAME_ZERO_STAGE`` resolved and clamped to [0, 3] (default 0
    = pure DP); ``parallel.compose.compose`` reads it when the caller
    doesn't pass ``zero_stage=`` explicitly — the memory-bound autotune
    branch proposes its moves through this knob."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_ZERO_STAGE", "") or "").strip()
    try:
        val = int(raw) if raw else 0
    except ValueError:
        val = 0
    return max(0, min(3, val))


def offload_optimizer_default(environ: dict | None = None) -> bool:
    """``TPUFRAME_OFFLOAD_OPTIMIZER`` as a bool (default off); the
    ``compose(offload_optimizer=...)`` parameter wins when passed
    explicitly.  The plan still downgrades loudly when the backend has
    no addressable host memory space."""
    env = os.environ if environ is None else environ
    raw = str(env.get("TPUFRAME_OFFLOAD_OPTIMIZER", "") or "").strip().lower()
    return raw not in _FALSY
