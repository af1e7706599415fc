"""High-level Trainer: the Composer-shaped engine on a jitted TPU step.

Capability parity with the reference's four L4 engines (SURVEY.md §1):

- Composer ``Trainer(model, optimizers, loaders, max_duration, algorithms,
  loggers)`` + ``.fit()`` (`/root/reference/03_composer/
  01_cifar_composer_resnet.ipynb:cell-16`) — same constructor shape, same
  duration grammar, same algorithm/callback/logger registries.
- The DDP epoch loop with rank-0 eval/checkpoint discipline
  (`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:293-323`).
- Ray Train's per-epoch "report metrics + checkpoint bundle" contract via
  the ``report`` hook -> :class:`FitResult` (`/root/reference/05_ray/
  01_fashion_mnist_pytorch_ray.ipynb:cell-6,cell-8`).
- Early stopping / eval cadence from the DeepSpeed TinyImageNet example
  (`/root/reference/02_deepspeed/02_tiny_imagenet_deepspeed_resnet.py:219-297`).

TPU-first: the loop body is ONE donated jitted step on global arrays; host
work (algorithms, metric sums, logging) overlaps device compute through the
DevicePrefetcher pipeline.  Metrics cross host<->device once per logging
interval, not per batch.
"""

from __future__ import annotations

import collections
import os
import statistics
import threading
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpuframe.compile.cache import compile_label, last_compile_verdict
from tpuframe.compile.precompile import (
    ShapeGuard,
    batch_signature,
    format_signature,
    loader_batch_template,
    precompile_step,
)
from tpuframe.core import runtime as rt
from tpuframe.data.loader import DataLoader, DevicePrefetcher
from tpuframe.fault import chaos
from tpuframe.fault import health as _health
from tpuframe.fault import preempt as _preempt
from tpuframe.fault.health import Divergence
from tpuframe.fault.preempt import Preempted
from tpuframe.track import memory as _memory
# importing the profiler module is what shows the loop's spans in any
# jax.profiler trace (it installs the spans' TraceAnnotation factory)
from tpuframe.track import profiler as _profiler
from tpuframe.track.analyze import StragglerMonitor
from tpuframe.track.system_metrics import machine_counters
from tpuframe.track.telemetry import get_telemetry
from tpuframe.parallel.precision import Policy, align_model_dtype, get_policy
from tpuframe.parallel.sharding import ParallelPlan
from tpuframe.train.algorithms import Algorithm, apply_algorithms, resolve_algorithms
from tpuframe.train.callbacks import Callback
from tpuframe.train.duration import Duration
from tpuframe.train.schedules import resolve_schedule
from tpuframe.train.state import TrainState, create_train_state
from tpuframe.train.step import (
    cross_entropy,
    make_eval_step,
    make_grad_accum_step,
    make_predict_fn,
    make_train_step,
    merge_metrics,
    model_objective,
    summarize_metrics,
)


class FitResult:
    """Ray-style structured result: metrics + checkpoint path + error
    (`05_ray/01_fashion_mnist_pytorch_ray.ipynb:cell-8`: ``result.metrics``,
    ``result.checkpoint``, ``result.error``)."""

    def __init__(self):
        self.metrics: dict[str, float] = {}
        self.history: list[dict[str, float]] = []
        self.checkpoint: str | None = None
        self.error: BaseException | None = None
        self.stopped_reason: str | None = None

    def __repr__(self):
        return (
            f"FitResult(metrics={self.metrics}, checkpoint={self.checkpoint!r}, "
            f"error={self.error!r}, stopped={self.stopped_reason!r})"
        )


class Trainer:
    """Train a flax model over a mesh with algorithms/callbacks/loggers.

    Args:
      model: flax module with ``__call__(x, train: bool)``.
      tx: optax transform (or use ``optimizer=`` name + ``lr=``; ``lr``
        also takes an optax schedule or a DeepSpeed-shaped scheduler dict,
        see ``tpuframe.train.schedules``).
      train_dataloader / eval_dataloader: tpuframe DataLoaders.
      max_duration: ``"2ep"`` / ``"500ba"`` / int epochs.
      algorithms: batch algorithms (LabelSmoothing, CutMix, ...).
      callbacks: event hooks (EarlyStopping, ProgressLogger, ...).
      loggers: objects with ``log_params(dict)`` / ``log_metrics(dict, step)``
        (tpuframe.track trackers fit; anything duck-typed works).  Rank-0
        discipline is enforced *here*, not by each logger.
      plan: ParallelPlan (default: pure DP over the current runtime mesh).
      precision: policy name or Policy ("bf16" recommended on TPU).  When
        given, it is the source of truth: the model is cloned so its
        compute dtype matches.  When omitted, the policy follows the
        model's own ``dtype`` knob (explicitly-bf16 models keep bf16
        compute with f32 master params).
      checkpointer: tpuframe.ckpt.Checkpointer (optional; saved per
        ``checkpoint_interval`` epochs + best tracking).
      ema_decay: maintain an exponential moving average of the params
        inside the optimizer state (fused into the train step,
        ZeRO-sharded, checkpointed for free); evaluate/predict/export
        then use the averaged weights.  Typical: 0.999.
      checkpoint_interval_batches: additionally save every N global
        batches *inside* an epoch, bundling the consumer-true loader
        position — a crash then auto-resumes with the very next batch
        (deterministic mid-epoch resume) instead of replaying the epoch.
      eval_interval: run eval every N epochs (0 = never).
      preemption: preemption handling (``tpuframe.fault.preempt``).
        None (default) uses the process-wide watcher when one is
        installed (launch workers install it during bootstrap); True
        installs the process-wide watcher at ``fit()``; False disables;
        a :class:`~tpuframe.fault.PreemptionWatcher` instance is
        installed at ``fit()`` and used directly.  On notice, the
        Trainer finishes the in-flight step, writes a last-chance
        synchronous snapshot (model/opt state + loader position, into
        the ``_intra`` sibling directory) and raises
        :class:`~tpuframe.fault.Preempted` — the supervisor restarts
        the run on a fresh machine from exactly that step.
      preempt_sync_steps: multi-host cadence (in steps) of the
        preemption agreement collective — every host must save the same
        step, so the flag check is an all-gather at a fixed step cadence
        (single-process checks locally every step; the collective only
        exists on pods).
      straggler_sync_steps / straggler_factor: live slow-rank detection
        (``tpuframe.track.analyze.StragglerMonitor``).  Every rank keeps
        a rolling step-time EWMA (``train/step_ewma_s`` gauge); every
        ``straggler_sync_steps`` steps the EWMAs cross ranks through a
        tiny all-gather (degraded to a self-baseline off-pod) and a rank
        exceeding the fleet median by ``straggler_factor`` emits a
        ``train/straggler`` event + the ``train/skew_ratio`` gauge.
        Defaults come from ``TPUFRAME_STRAGGLER_STEPS`` (0 disables;
        else 32) and ``TPUFRAME_STRAGGLER_FACTOR`` (2.0), which launch
        propagates to every worker.
      precompile: AOT warm-start (``tpuframe.compile``).  ``fit()``
        derives the train/eval step signatures from the loader specs and
        lowers+compiles them in a background thread *overlapped with the
        DataLoader / ring-buffer spin-up*, so first-batch latency is
        ``max(compile, loader warmup)`` instead of their sum; the hot
        loop then dispatches straight to the compiled executables (no
        per-first-step re-trace), and the armed shape guard turns any
        runtime signature miss into a loud ``compile/recompile`` event.
        Default None follows ``TPUFRAME_PRECOMPILE`` (on unless set
        falsy); False opts out.  :meth:`precompile` runs the same thing
        synchronously on demand.
      grad_compression: gradient wire format (``"int8"`` / ``"fp8"`` /
        a :class:`~tpuframe.parallel.comms_env.CommsConfig`).  The DP
        allreduce then moves as bucketed quantized payloads with
        per-bucket scales and EF-SGD error feedback (residual carried
        as a checkpointed ``TrainState.comms`` leaf — ~4x fewer sync
        bytes where DCN bandwidth bounds scaling; see
        ``tpuframe.parallel.compression`` and PERF.md round 10).
        Composes with ``grad_accum`` (compress once per super-batch)
        and ZeRO-1/2/3 plans (plan-derived compressed reduce-scatter →
        sharded update → all-gather; stage 3 adds gather-on-use over
        the fsdp-resident params) and with ``grad_clip`` (the clip
        moves inside the compressed step as a plan-global-norm scale);
        refuses TP/pipeline rules.  Default None
        follows ``TPUFRAME_COMMS_COMPRESSION`` (off unless set); the
        per-step wire bytes are metered as ``comms/bytes_on_wire``.
      health: training-health sentinel (``tpuframe.fault.health``).
        The jitted step computes global grad-norm + loss/grad
        finiteness (one fused reduction) and an EWMA loss-spike test on
        device; a bad step applies NO update (branch-free ``jnp.where``
        skip) and its verdict rides the step's metrics pytree — the
        Trainer reads it every ``window`` steps (one tiny device fetch,
        not per-step sync), emits ``health/bad_step`` + counters, and
        raises :class:`~tpuframe.fault.health.Divergence` when
        ``max_bad`` bad steps land inside a window — the supervisor's
        DIVERGENCE class then rolls back to the last *healthy*
        committed checkpoint and re-enters with the configured LR
        backoff / data-order skip.  Every save is stamped with the
        sentinel state (loss EWMA, grad norm, bad-step count) next to
        the topology manifest.  Default None follows ``TPUFRAME_HEALTH``
        (on unless set falsy); False disables; a
        :class:`~tpuframe.fault.health.HealthPolicy` sets thresholds.
    """

    def __init__(
        self,
        model: Any,
        tx: optax.GradientTransformation | None = None,
        train_dataloader: DataLoader | None = None,
        eval_dataloader: DataLoader | None = None,
        *,
        optimizer: str = "adam",
        lr: float | Mapping[str, Any] | optax.Schedule = 1e-3,
        max_duration: str | int = "1ep",
        algorithms: Sequence[Algorithm] = (),
        callbacks: Sequence[Callback] = (),
        loggers: Sequence[Any] = (),
        plan: ParallelPlan | None = None,
        precision: str | Policy | None = None,
        loss_fn: Callable | None = None,
        seed: int = 0,
        num_classes: int | None = None,
        sample_input: np.ndarray | None = None,
        checkpointer: Any = None,
        checkpoint_interval: int = 1,
        checkpoint_interval_batches: int | None = None,
        eval_interval: int = 1,
        log_interval: int = 10,
        report: Callable[[dict, str | None], None] | None = None,
        grad_accum: int | None = None,
        grad_clip: float | None = None,
        grad_compression: str | None = None,
        normalize: tuple | None = None,
        ema_decay: float | None = None,
        preemption: Any = None,
        preempt_sync_steps: int = 16,
        straggler_sync_steps: int | None = None,
        straggler_factor: float | None = None,
        precompile: bool | None = None,
        health: Any = None,
    ):
        if precision is None:
            # follow the model: an explicitly-bf16 model keeps bf16 compute
            # (f32 masters); an f32 model gets the plain f32 policy
            self.policy = Policy(compute_dtype=getattr(model, "dtype", jnp.float32))
            self.model = model
        else:
            # an explicit policy is the source of truth: align the model to
            # it (an f32 model under a bf16 policy would silently up-cast
            # inside every layer and double the HBM traffic)
            self.policy = get_policy(precision)
            self.model = align_model_dtype(model, self.policy)
        self.train_dataloader = train_dataloader
        self.eval_dataloader = eval_dataloader
        self.max_duration = Duration.parse(max_duration)
        self.callbacks = list(callbacks)
        # env-armed sampled profiler capture: a launch that ships
        # TPUFRAME_PROFILE_* gets bounded device-time evidence with no
        # code change; an explicitly-passed ProfilerCallback keeps
        # authority over its own cadence
        if os.environ.get("TPUFRAME_PROFILE_STEPS", "").strip():
            if not any(
                isinstance(cb, _profiler.ProfilerCallback)
                for cb in self.callbacks
            ):
                env_profiler = _profiler.ProfilerCallback.from_env()
                if env_profiler is not None:
                    self.callbacks.append(env_profiler)
        self.loggers = list(loggers)
        # None: the objective the model brings, if it brings one
        # (``model.objective(output, batch)``), else cross entropy
        loss_fn = loss_fn or model_objective(model) or cross_entropy
        self.loss_fn = loss_fn
        self.seed = seed
        self.checkpointer = checkpointer
        self.checkpoint_interval = checkpoint_interval
        if checkpoint_interval_batches is None:
            # env-defaulted (tolerant): the cadence half of the autotune
            # config; also live-appliable later via apply_tuned() — the
            # step loop re-reads the attribute every batch
            env_ckpt = _health._env_int("TPUFRAME_CKPT_INTERVAL_BATCHES", 0)
            checkpoint_interval_batches = env_ckpt if env_ckpt > 0 else None
        self.checkpoint_interval_batches = checkpoint_interval_batches
        self.eval_interval = eval_interval
        self.log_interval = log_interval
        self.report = report
        if preempt_sync_steps < 1:
            raise ValueError(
                f"preempt_sync_steps must be >= 1, got {preempt_sync_steps}"
            )
        if (
            preemption is not None
            and not isinstance(preemption, bool)
            and not hasattr(preemption, "requested")
        ):
            raise ValueError(
                "preemption must be None (auto), True (install the "
                "process-wide watcher), False (disable), or a "
                f"PreemptionWatcher; got {type(preemption).__name__}"
            )
        self.preemption = preemption
        self.preempt_sync_steps = preempt_sync_steps
        # live slow-rank detection: persists across epochs (the EWMA and
        # the self-baseline window are run-scoped, not epoch-scoped)
        self._straggler = StragglerMonitor(
            sync_steps=straggler_sync_steps, factor=straggler_factor
        )
        # training-health sentinel: the per-window buffer of the step's
        # on-device bad-step flags (run-scoped like the straggler)
        self.health = _health.resolve_policy(health)
        self._health_flags: list = []
        # seconds a step of the windows drained so far, newest last: what a
        # window is held against before it is called slow (run-scoped)
        self._window_step_s: collections.deque = collections.deque(maxlen=64)
        self._comms_gauge_set = False
        self._pp_gauge_set = False

        if plan is None:
            plan = ParallelPlan(mesh=rt.current_runtime().mesh)
        self.plan = plan
        # per-replica BN ("local") needs to know the data shard count; the
        # model can't see the mesh, so fill it from the plan here
        if (
            getattr(self.model, "bn_stats", None) == "local"
            and not getattr(self.model, "bn_groups", 1)
            and hasattr(self.model, "clone")
        ):
            self.model = self.model.clone(bn_groups=plan.dp_size)

        # wire compression (tpuframe.parallel.compression): the explicit
        # param wins; with grad_compression=None the fleet knob
        # TPUFRAME_COMMS_COMPRESSION decides (off unless set).  Resolved
        # BEFORE the optimizer chain — where the clip lives depends on it.
        from tpuframe.parallel.compression import CommsConfig

        self.comms_config = CommsConfig.from_env(grad_compression)
        # DeepSpeed's gradient_clipping knob (`deepspeed_config.py:18`):
        # global-norm clip.  With a ZeRO-sharded compressed wire the
        # optimizer sees only each shard's update slice, so an optax
        # chain clip would use a shard-local (silently wrong) norm — the
        # clip moves INSIDE the compressed step instead, scaled by the
        # plan-global synced norm (see _make_compressed_train_step).
        self._step_grad_clip: float | None = None
        if tx is None:
            tx = _make_optimizer(optimizer, self._resolve_lr(lr))
            if grad_clip:
                if self.comms_config is not None and plan.zero_stage >= 1:
                    self._step_grad_clip = float(grad_clip)
                else:
                    tx = optax.chain(
                        optax.clip_by_global_norm(float(grad_clip)), tx
                    )
        elif grad_clip:
            raise ValueError(
                "grad_clip only applies when the Trainer builds the optimizer "
                "(tx=None); chain optax.clip_by_global_norm into your tx instead"
            )
        self.ema_decay = ema_decay
        if ema_decay is not None:
            # outermost wrapper: the averaged weights live in opt_state
            # (ZeRO-sharded + checkpointed for free); evaluate/predict/
            # export then use them via _serving_state()
            from tpuframe.train.ema import with_ema

            tx = with_ema(tx, float(ema_decay))
        self.tx = tx

        if num_classes is None:
            num_classes = getattr(
                getattr(train_dataloader, "dataset", None), "num_classes", None
            )
        self.num_classes = num_classes
        self.algorithms = (
            resolve_algorithms(algorithms, num_classes) if algorithms else []
        )
        if sample_input is None and train_dataloader is not None:
            img, _ = train_dataloader.dataset[0]
            sample_input = np.asarray(img)[None]
        self.sample_input = sample_input

        if precompile is None:
            from tpuframe.compile.cache import _FALSY

            v = os.environ.get("TPUFRAME_PRECOMPILE", "").strip().lower()
            precompile = not v or v not in _FALSY
        self.precompile_enabled = bool(precompile)
        # AOT executables keyed by (step kind, batch signature); the
        # shape guard is armed by precompile with the expected set
        self._compiled: dict[tuple, Any] = {}
        self._shape_guard = ShapeGuard()
        self._precompile_thread: threading.Thread | None = None
        self._precompile_report: dict | None = None
        # step kind -> its ``compile/precompile_step`` span, until that
        # kind's first call says whether the executable was the one used
        self._precompile_spans: dict[str, Any] = {}
        # fit()'s entry on perf_counter_ns, until ``setup/fit_start`` is
        # recorded where the loop's first iteration opens
        self._fit_start_ns: int | None = None

        # live loop state
        self.state: TrainState | None = None
        self.epoch = 0
        self.batches_seen = 0
        self.samples_seen = 0
        self._stop_reason: str | None = None
        # mid-epoch resume: loader position restored from a checkpoint,
        # applied at the next epoch start (after its set_epoch rewind)
        self._pending_loader_state: dict | None = None
        self._train_prefetcher: DevicePrefetcher | None = None
        self._intra_ck: Any = None  # lazy sibling checkpointer (snapshots)

        if grad_accum is None:
            # env default (tolerant, restart-apply — the accum factor is
            # baked into the compiled step below)
            grad_accum = max(1, _health._env_int("TPUFRAME_GRAD_ACCUM", 1))
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.grad_accum = grad_accum
        # ``normalize=(mean, std[, scale])``: images cross host->HBM raw
        # (uint8 = 4x less PCIe traffic than f32) and are normalized
        # *inside* the jitted step by ``ops.normalize_images``, which
        # keeps an image batch in its own layout (one XLA fusion that
        # feeds the first convolution; no kernel) — the
        # reference's host-side ToTensor+Normalize
        # (`utils/hf_dataset_utilities.py:70-80`) with the same
        # convention: inputs in 0-255 (uint8 or float — algorithms like
        # MixUp emit 0-255 floats), mean/std in [0, 1] units.  Pass an
        # explicit third element to override the 1/255 scale.
        self.normalize = normalize
        # The ONE place the normalize tuple is interpreted — training,
        # eval, and the serving-artifact export all read these, so the
        # preprocessing convention cannot skew between them.
        if normalize is not None:
            _mean, _std, *_rest = normalize
            self._norm_args = (_mean, _std, _rest[0] if _rest else 1.0 / 255.0)
        else:
            self._norm_args = None

        # one transform for the train step, the grad-accum scan (per
        # (micro, ...) microbatch), the eval step and predict(): elementwise
        # jnp that GSPMD shards natively, whatever the leading dimensions
        image_transform = batch_transform = None
        if normalize is not None:
            def image_transform(img):
                from tpuframe.ops import normalize_images

                mean, std, scale = self._norm_args
                return normalize_images(
                    img, mean, std, scale=scale,
                    out_dtype=self.policy.compute_dtype,
                )

            def batch_transform(batch: dict) -> dict:
                batch["image"] = image_transform(batch["image"])
                return batch

        if grad_accum > 1:
            # DeepSpeed's gradient_accumulation_steps
            # (`deepspeed_config.py:17`): host batches are reshaped to
            # (n_micro, micro, ...) in _device_batches.  Compression
            # composes: the scan accumulates the super-batch gradient
            # and the compressed sync runs once per optimizer step.
            self._train_step = make_grad_accum_step(
                grad_accum, self.policy, loss_fn, plan=self.plan,
                batch_transform=batch_transform,
                health=self.health,
                grad_compression=self.comms_config,
                grad_clip=self._step_grad_clip,
            )
        else:
            self._train_step = make_train_step(
                self.policy, loss_fn, plan=self.plan,
                batch_transform=batch_transform,
                grad_compression=self.comms_config,
                health=self.health,
                grad_clip=self._step_grad_clip,
            )
        self._eval_step = make_eval_step(
            self.policy, loss_fn, plan=self.plan, batch_transform=batch_transform
        )
        self._predict = make_predict_fn(
            self.policy, input_transform=image_transform)

    # -- wiring ------------------------------------------------------------
    def _resolve_lr(self, lr):
        """Accept a float, an optax schedule, or a DeepSpeed-shaped
        scheduler dict (``{"type": "WarmupLR", "params": {...}}`` or a full
        config carrying a ``"scheduler"`` key — `deepspeed_config.py:33-40`);
        ``total_num_steps: "auto"`` resolves against max_duration and the
        train dataloader.

        A divergence-recovery directive (``fault.health``: the
        supervisor escalates one per rollback) scales the resolved
        schedule by its compounded LR backoff — the perturbation that
        keeps a deterministic replay from re-hitting the same spike.
        Wrapping the *schedule* (not the optimizer chain) keeps the
        opt_state structure identical, so the rolled-back checkpoint
        restores cleanly."""
        schedule = resolve_schedule(
            lr,
            total_steps=_planned_total_steps(self.max_duration, self.train_dataloader),
        )
        scale = _health.recovery_directive().lr_scale
        if scale == 1.0:
            return schedule
        get_telemetry().event("health/lr_backoff", lr_scale=round(scale, 6))
        if callable(schedule):
            return lambda step: schedule(step) * scale
        return schedule * scale

    @property
    def is_main(self) -> bool:
        return rt.is_main_process()

    def request_stop(self, reason: str) -> None:
        """Callbacks call this to end fit() after the current epoch."""
        self._stop_reason = reason

    def _intra_checkpointer(self, create: bool = False):
        """Sibling checkpointer for mid-epoch snapshots, ``max_to_keep=1``.

        A SEPARATE directory keeps snapshots out of the main
        checkpointer's retention (frequent snapshots would evict real
        epoch-end checkpoints mid-epoch) and out of its step namespace
        (an epoch-end save landing on a snapshot's optimizer step would
        otherwise collide).  Only the most recent snapshot matters for
        crash-resume, so one is kept.
        """
        if self._intra_ck is None and self.checkpointer is not None:
            from tpuframe.ckpt import Checkpointer
            from tpuframe.ckpt.meta import latest_step

            intra_dir = str(self.checkpointer.directory) + "_intra"
            # Construct when the feature is on, OR when a previous run
            # (that had it on) left a snapshot behind — auto-resume must
            # see that snapshot even if this run disabled the feature,
            # else a restart silently replays from an older epoch-end
            # checkpoint.  The path probe avoids creating the directory
            # just to look.  ``create`` forces construction (the
            # preemption last-chance save needs a snapshot home even
            # with interval snapshots off).
            if (
                create
                or self.checkpoint_interval_batches
                or latest_step(intra_dir) is not None
            ):
                self._intra_ck = Checkpointer(intra_dir, max_to_keep=1)
        return self._intra_ck

    def _emit(self, hook: str, *args) -> None:
        if not self.callbacks:
            return
        with get_telemetry().span("train/callbacks", emit=False, hook=hook):
            for cb in self.callbacks:
                getattr(cb, hook)(self, *args)

    def _meter_comms(self, tele) -> None:
        """Per-step bytes-on-wire accounting: the compressed step's wire
        plan is static per signature, so the meter is one host add per
        step (no device sync).  f32 runs meter nothing."""
        wire = getattr(self._train_step, "wire", None)
        if not wire or not wire.get("bytes_per_step"):
            return
        if not self._comms_gauge_set:
            tele.registry.gauge("comms/bytes_per_step").set(
                wire["bytes_per_step"]
            )
            # the declared collective schedule: >1 means the sync fires
            # as that many bucket groups in reverse-backward order (bytes
            # are invariant under grouping; exposed-comms is what moves)
            tele.registry.gauge("comms/overlap_groups").set(
                wire.get("overlap_groups") or 1
            )
            self._comms_gauge_set = True
        tele.registry.counter("comms/bytes_on_wire").inc(
            wire["bytes_per_step"]
        )
        if wire.get("fused"):
            # steps whose sync rode the in-collective (fused ring)
            # transport — bytes are invariant under fusion, so this
            # counter is how dashboards tell the transports apart
            tele.registry.counter("comms/fused_steps").inc()

    def _meter_pp(self, tele) -> None:
        """Pipeline-plan accounting, same shape as the comms meter: the
        schedule is static per plan signature, so the first step emits
        one ``pp/schedule`` event + sets the gauges, and every pipelined
        step is one host counter add.  Non-pipeline plans meter nothing."""
        stages = self.plan.axis_size("pipe")
        if stages <= 1:
            return
        sched = self.plan.comms_schedule()
        if not self._pp_gauge_set:
            tele.event(
                "pp/schedule",
                schedule=sched["pp_schedule"],
                pinned=sched["pp_pinned"],
                stages=stages,
                microbatches=self.plan.pp_microbatches,
                signature=self.plan.signature(),
            )
            tele.registry.gauge("pp/stages").set(stages)
            tele.registry.gauge("pp/microbatches").set(
                self.plan.pp_microbatches or 0
            )
            self._pp_gauge_set = True
        tele.registry.counter("pp/steps").inc()

    # -- preemption ----------------------------------------------------------
    def _preempt_watcher(self):
        if self.preemption is False:
            return None
        if self.preemption is None:
            return _preempt.active_watcher()
        return self.preemption

    def _maybe_preempt_exit(self) -> None:
        """Step-boundary preemption exit (``tpuframe.fault.preempt``).

        Single-process: the local flag is checked every step.  Multi-host:
        hosts learn of the notice at different times, but all must save
        the SAME step — so the flag crosses hosts through a tiny
        all-gather at a fixed step cadence (``preempt_sync_steps``),
        entered by every host at the same step boundary (the loop is
        synchronous).  On agreement: one synchronous snapshot (state +
        consumer-true loader position, into the ``_intra`` sibling dir,
        so auto-resume continues from this very step), then
        :class:`Preempted` propagates out with the checkpoint path.
        """
        watcher = self._preempt_watcher()
        multi_host = rt.process_count() > 1
        if watcher is None and not multi_host:
            return
        local = watcher is not None and watcher.requested
        if multi_host:
            if self.batches_seen % self.preempt_sync_steps:
                return
            flagged = _preempt.agree(local)
        else:
            flagged = local
        if not flagged:
            return
        reason = (watcher.reason if watcher is not None and watcher.reason
                  else "peer-host")
        tele = get_telemetry()
        path = None
        if self.checkpointer is not None:
            intra = self._intra_checkpointer(create=True)
            cur_step = int(jax.device_get(self.state.step))
            if intra.latest_step() == cur_step:
                # an interval snapshot already captured this exact step
                path = str(intra.directory) + f"/{cur_step}"
            else:
                meta = {
                    "epoch": self.epoch,
                    "batches_seen": self.batches_seen,
                    "samples_seen": self.samples_seen,
                    "preempted": True,
                    "global_batch": self.train_dataloader.global_batch_size,
                }
                if (
                    self._train_prefetcher is not None
                    and hasattr(self.train_dataloader, "state_dict")
                ):
                    meta["loader_state"] = self._train_prefetcher.state_dict()
                elif self._train_prefetcher is not None:
                    # mid-epoch with an untrackable loader: the snapshot
                    # still beats losing the step, but resume replays
                    # this epoch from its first batch.  Warn (raising
                    # here would forfeit the last-chance save entirely —
                    # unlike opt-in interval snapshots, which reject
                    # untrackable loaders up front).
                    import warnings

                    warnings.warn(
                        "preemption snapshot taken mid-epoch but the "
                        f"train_dataloader ({type(self.train_dataloader).__name__}) "
                        "has no state_dict(): resume will replay this "
                        "epoch's already-trained batches",
                        stacklevel=2,
                    )
                    meta["loader_state_missing"] = True
                with tele.span(
                    "fault/preempt_checkpoint", step=self.batches_seen
                ), tele.guard("ckpt/save"):
                    path = intra.save(self.state, meta=meta, plan=self.plan,
                                      health=self._health_stamp())
                    intra.wait()  # synchronous: the machine is going away
        # no counter here: fault/preempt_notices counted at the watcher,
        # fault/preemptions at the supervisor's restart — incrementing a
        # third time per event would double-read on dashboards
        tele.event(
            "fault/preempted",
            reason=reason,
            batch=self.batches_seen,
            checkpoint=path,
        )
        self._stop_reason = f"preempted: {reason}"
        if watcher is not None:
            # the notice is fully acted on (checkpoint written): consume
            # the flag HERE, on the watcher that was actually checked —
            # an in-process supervised restart of a Trainer holding an
            # explicit watcher must not re-preempt at its first boundary
            # (a real preemption replaces the process; clearing is moot)
            watcher.clear()
        raise Preempted(reason, step=self.batches_seen, checkpoint=path)

    # -- training health -----------------------------------------------------
    def _health_step(self, metrics: Mapping[str, Any]) -> None:
        """Buffer the step's on-device bad flag; check per window.

        The buffer holds the scalar flag arrays un-fetched (a list
        append — zero dispatch, zero sync on the hot path); the only
        host sync is the once-per-window fused fetch in
        :meth:`_health_check`, so the sentinel costs the hot loop
        nothing between checks."""
        if self.health is None:
            return
        stats = metrics.get("health_stats")
        if stats is None:
            return
        self._health_flags.append(stats)
        if len(self._health_flags) >= self.health.window:
            self._health_check()

    def _health_check(self) -> None:
        """Materialize the window's verdict: gauges + ``health/bad_step``
        events, and the escalation — ``max_bad`` bad steps inside the
        window raises :class:`Divergence` for the supervisor's rollback
        ladder."""
        import math

        if self.health is None or not self._health_flags:
            return
        tele = get_telemetry()
        # the loop's one host sync besides the window drain
        with tele.span("train/health_fetch", emit=False,
                       step=self.batches_seen):
            stats, hs = jax.device_get((self._health_flags, self.state.health))
            hs = {k: float(v) for k, v in hs.items()}
        n_bad = int(round(sum(float(s[0]) for s in stats)))
        window_steps = len(stats)
        self._health_flags = []
        for key, name in (("loss_ewma", "health/loss_ewma"),
                          ("grad_norm", "health/grad_norm")):
            if math.isfinite(hs.get(key, float("nan"))):
                tele.registry.gauge(name).set(hs[key])
        if not n_bad:
            return
        tele.registry.counter("health/bad_steps").inc(n_bad)
        tele.event(
            "health/bad_step",
            batch=self.batches_seen,
            bad_in_window=n_bad,
            window_steps=window_steps,
            bad_steps_total=int(hs.get("bad_steps", 0.0)),
            loss_ewma=hs["loss_ewma"] if math.isfinite(hs["loss_ewma"]) else None,
            grad_norm=hs["grad_norm"] if math.isfinite(hs["grad_norm"]) else None,
        )
        if n_bad >= self.health.max_bad:
            tele.registry.counter("health/divergences").inc()
            tele.event(
                "health/divergence",
                batch=self.batches_seen,
                bad_in_window=n_bad,
                window_steps=window_steps,
                max_bad=self.health.max_bad,
            )
            raise Divergence(
                f"{n_bad} bad step(s) inside a {window_steps}-step health "
                f"window (max_bad={self.health.max_bad}) at batch "
                f"{self.batches_seen}: skip-step is no longer converging",
                step=self.batches_seen,
                bad_in_window=n_bad,
                window=window_steps,
                loss_ewma=hs.get("loss_ewma"),
                policy=self.health,
            )

    def _slow_window(self, first_step: int, steps: int, step_s: float,
                     median_s: float, record: Mapping[str, float]) -> None:
        """One ``train/slow_window`` event for a window that took more than
        1.5x the median of those before it, with what tells its causes
        apart: the longest pull, assembly and copy in it (input), the
        machine's ``record`` over it (host, machine), the shallowest
        queue a dispatch found after the window's first (which follows a
        drain and finds none); none of them, and the device or its
        runtime held the step.  Evidence only: no metric leaves the
        window out."""
        tele = get_telemetry()
        last = first_step + steps - 1
        longest = dict.fromkeys(
            ("train/data_wait", "data/assemble", "data/h2d"), 0.0)
        depths = []
        for r in tele.span_log((*longest, "train/step")):
            if r.step is None or not first_step <= r.step <= last:
                continue
            if r.name != "train/step":
                longest[r.name] = max(longest[r.name], r.elapsed)
            elif r.step > first_step:
                depths.append(r.attrs["steps_in_flight"])
        tele.event(
            "train/slow_window", first_step=first_step, step=last,
            steps=steps, window_s=round(step_s * steps, 6),
            median_s=round(median_s * steps, 6),
            **{f"{name.split('/')[1]}_max_s": round(s, 6)
               for name, s in longest.items()},
            steps_in_flight_min=min(depths, default=None), **record)

    def _health_stamp(self) -> dict | None:
        """The health record stamped into every save's meta JSON (next
        to the topology manifest): loss EWMA, grad norm, bad-step count,
        and the ``healthy`` verdict rollback selects on."""
        if self.health is None or self.state is None:
            return None
        hs = jax.device_get(self.state.health)
        if not hs:
            return None
        return _health.health_stamp(
            hs, int(jax.device_get(self.state.step)), self.health
        )

    def _log_metrics(self, metrics: Mapping[str, float], step: int) -> None:
        if not self.is_main or not self.loggers:
            return
        with get_telemetry().span("train/log", emit=False):
            for lg in self.loggers:
                lg.log_metrics(dict(metrics), step=step)

    def _log_params(self, params: Mapping[str, Any]) -> None:
        if not self.is_main:
            return
        for lg in self.loggers:
            if hasattr(lg, "log_params"):
                lg.log_params(dict(params))

    # -- state -------------------------------------------------------------
    def init_state(self) -> TrainState:
        if self.state is None:
            if self.sample_input is None:
                raise ValueError("need sample_input or a train_dataloader to init")
            self.state = create_train_state(
                self.model,
                jax.random.PRNGKey(self.seed),
                self.sample_input,
                self.tx,
                plan=self.plan,
                init_kwargs={"train": False},
            )
            if self.comms_config is not None:
                # EF residuals for the compressed wire (zeros; a restore
                # overwrites them — the residual is checkpoint state)
                from tpuframe.parallel.compression import init_comms_state

                self.state = self.state.replace(
                    comms=init_comms_state(
                        self.state.params, self.plan, self.comms_config
                    )
                )
        return self.state

    # -- compile warm-start ------------------------------------------------
    def precompile(self, wait: bool = True) -> dict | None:
        """AOT-compile the train/eval steps from the loader specs
        (``tpuframe.compile``): derive each step's full batch signature
        up front (ragged-tail padding and the grad-accum reshape
        included), ``lower().compile()`` it under ``compile/lower`` /
        ``compile/backend_compile`` spans, arm the shape guard with the
        expected set, and stash the executables for direct dispatch.

        ``fit()`` auto-invokes this with ``wait=False`` so the compile
        overlaps DataLoader/ring-buffer spin-up; the first step joins.
        Idempotent; returns the precompile report (signatures + walls).
        """
        if self._precompile_thread is None:
            self.init_state()  # model init on the caller's thread
            t = threading.Thread(
                target=self._precompile_run,
                name="tpuframe-precompile",
                daemon=True,
            )
            self._precompile_thread = t
            t.start()
        if wait:
            self._precompile_thread.join()
        return self._precompile_report

    def _precompile_run(self) -> None:
        """Background body: a failed precompile must degrade to today's
        lazy-compile behavior, never take the fit down."""
        tele = get_telemetry()
        # precompiles are keyed on the plan: after an elastic shrink the
        # same batch signature lowers a DIFFERENT program (survivor mesh,
        # rebound shardings), and the label must attribute those compiles
        # to the rebound plan rather than look like cache misses of the
        # old one
        plan_sig = self.plan.signature()
        report: dict[str, Any] = {
            "steps": [], "wall_s": 0.0, "plan_signature": plan_sig,
        }
        t0 = time.perf_counter()
        targets = [("train", self._train_step, True)]
        if self.eval_dataloader is not None:
            targets.append(("eval", self._eval_step, False))
        for kind, fn, train in targets:
            entry: dict[str, Any] = {"kind": kind}
            report["steps"].append(entry)
            # one span a target, parent of the compile/lower and
            # compile/backend_compile spans; ``used`` turns true where the
            # kind's first real batch dispatches the executable kept here
            # (never, where the signature does not match or no template
            # could be built: the span's seconds were then for nothing)
            with tele.span("compile/precompile_step", kind=kind,
                           used=False) as sp:
                self._precompile_spans[kind] = sp
                try:
                    template = loader_batch_template(self, train=train)
                    if template is None:
                        entry["skipped"] = "no derivable loader signature"
                        continue
                    sig = batch_signature(template)
                    entry["signature"] = format_signature(sig)
                    sp.attrs["signature"] = entry["signature"]
                    t1 = time.perf_counter()
                    compiled = precompile_step(
                        fn, self.state, template,
                        label=f"precompile/{kind}@{plan_sig}",
                    )
                    entry["wall_s"] = round(time.perf_counter() - t1, 6)
                    # hit = retrieved from the persistent cache, no backend
                    # compile ran (what a warm restart should report)
                    entry["persistent_cache"] = last_compile_verdict()
                    # arm the guard even when direct dispatch isn't possible
                    # (offload wrapper): the signature is still the contract,
                    # and the persistent cache is warm for the jit path
                    self._shape_guard.expect(kind, sig)
                    if compiled is not None:
                        self._compiled[(kind, sig)] = compiled
                    entry["dispatchable"] = compiled is not None
                except Exception as e:
                    # an OOM during AOT compile gets the forensics event
                    # (estimate vs compiled vs live + fit suggestion); the
                    # precompile itself still degrades to lazy-compile
                    _memory.maybe_oom_event(e, where="precompile")
                    entry["error"] = f"{type(e).__name__}: {e}"[:300]
                    tele.event(
                        "compile/precompile_error", step_kind=kind,
                        error=entry["error"],
                    )
        report["wall_s"] = round(time.perf_counter() - t0, 6)
        self._precompile_report = report
        tele.event("compile/precompile", **{
            "wall_s": report["wall_s"],
            "compiled": sum(
                1 for s in report["steps"] if s.get("signature")
            ),
            "dispatchable": sum(
                1 for s in report["steps"] if s.get("dispatchable")
            ),
        })

    def _step_call(self, kind: str, fn, state, batch, span=None):
        """One step through the compile spine: join an in-flight
        precompile (first step = ``max(compile, loader warmup)``),
        dispatch straight to the AOT executable on a signature match,
        else fall back to the jitted fn with the shape guard shouting
        about unexpected signatures and the compile label attributing
        whatever backend compile follows.  The kind's first call since
        its precompile writes the verdict: ``used`` on the
        ``compile/precompile_step`` span and ``aot`` on ``span`` (the
        caller's ``train/step``)."""
        tele = get_telemetry()
        t = self._precompile_thread
        if t is not None and t.is_alive():
            with tele.span("compile/wait"):
                t.join()
        sig = batch_signature(batch)
        compiled = self._compiled.get((kind, sig))
        pre = (self._precompile_spans.pop(kind, None)
               if self._precompile_spans else None)
        if pre is not None:
            verdict = span.attrs if span is not None else {}
            verdict["aot"] = False  # until the kept executable runs
        if compiled is not None:
            try:
                out = compiled(state, batch)
                if pre is not None:
                    pre.attrs["used"] = verdict["aot"] = True
                return out
            except Exception as e:
                # sharding/layout drift: drop the executable, shout once,
                # let the jit path (below) own the call
                self._compiled.pop((kind, sig), None)
                tele.event(
                    "compile/aot_fallback",
                    step_kind=kind,
                    signature=format_signature(sig),
                    error=f"{type(e).__name__}: {e}"[:300],
                )
                # the train executable donates state: an error raised
                # AFTER execution launched (OOM, runtime fault) has
                # already invalidated those buffers, and "retrying" on
                # deleted arrays would mask the real failure — only
                # pre-execution rejections (aval/sharding mismatch,
                # buffers intact) may fall through to the jit path
                if any(
                    getattr(x, "is_deleted", lambda: False)()
                    for x in jax.tree.leaves(state)
                    if isinstance(x, jax.Array)
                ):
                    raise
        else:
            self._shape_guard.check(kind, sig)
        with compile_label(f"{kind} {format_signature(sig)}"):
            return fn(state, batch)

    # -- data --------------------------------------------------------------
    def _device_batches(self, loader: DataLoader, train: bool):
        """Host pipeline: algorithms -> dict batches -> prefetched global arrays."""
        algs = self.algorithms if train else []
        accum = self.grad_accum if train else 1
        run_key = (self.seed * 1_000_003 + self.epoch) * 2 + int(train)

        fallback_pos = iter(range(1, 1 << 62))

        def batch_rng() -> np.random.Generator:
            """Augmentation rng keyed by (run, absolute batch position) —
            stateless, so a mid-epoch resume applies the SAME augmentation
            draws to batch k as the uninterrupted run would (a single
            sequential rng would hand the skipped batches' draws to the
            resumed ones).  Duck-typed iterables without a position
            counter fall back to a local sequence (distinct draws per
            batch; mid-epoch resume isn't supported for those anyway)."""
            pos = getattr(loader, "_batches_yielded", None)
            if pos is None:
                pos = next(fallback_pos)
            return np.random.default_rng(run_key * 1_000_003 + pos)

        def split_micro(x: np.ndarray) -> np.ndarray:
            if x.shape[0] % accum:
                raise ValueError(
                    f"batch size {x.shape[0]} not divisible by "
                    f"grad_accum={accum}"
                )
            micro = x.shape[0] // accum
            # x holds this process's rows; the dp check is on the *global*
            # microbatch assembled across processes.
            global_micro = micro * loader.process_count
            if global_micro % self.plan.dp_size:
                raise ValueError(
                    f"global microbatch size {global_micro} (global batch "
                    f"{x.shape[0] * loader.process_count} / grad_accum="
                    f"{accum}) not divisible by the mesh's "
                    f"{self.plan.dp_size} data-parallel shards"
                )
            return x.reshape((accum, micro) + x.shape[1:])

        def host_iter():
            # consumption index of this epoch's first yielded batch —
            # the prefetcher runs this generator ahead of training, but
            # batch i of the epoch is consumed at step base+i, so chaos
            # scheduled by step fires on exactly the batch that step eats
            base = self.batches_seen
            for pos, batch in enumerate(loader):
                images, labels = np.asarray(batch[0]), np.asarray(batch[1])
                if algs:
                    images, labels = apply_algorithms(
                        algs, images, labels, batch_rng()
                    )
                # chaos site: poison the HOST batch in place (NaNAt /
                # SpikeAt) exactly where a corrupt record or a broken
                # augmentation would land — upstream of the device copy,
                # so the jitted step's sentinel sees it like the real thing
                if train:
                    chaos.maybe_fire("batch", step=base + pos, images=images)
                out = {"image": images, "label": labels}
                if len(batch) > 2:
                    out["weight"] = np.asarray(batch[2], np.float32)
                if accum > 1:
                    out = {k: split_micro(v) for k, v in out.items()}
                yield out

        # consumer-true resume position for mid-epoch checkpoints (the
        # loader's own counter runs `depth` batches ahead).  Duck-typed
        # train iterables without state_dict() are fine — they just can't
        # be position-tracked, so mid-epoch checkpointing must be off.
        trackable = hasattr(loader, "state_dict")
        if (
            train
            and self.checkpointer is not None
            and self.checkpoint_interval_batches
            and not trackable
        ):
            raise ValueError(
                "checkpoint_interval_batches (mid-epoch snapshots) requires "
                "a train_dataloader with state_dict()/load_state_dict() "
                f"(got {type(loader).__name__}); use tpuframe.data.DataLoader "
                "or disable checkpoint_interval_batches"
            )
        pf = DevicePrefetcher(
            host_iter(),
            # env-defaulted pipeline depth (tolerant read): how many
            # batches the H2D copy runs ahead of the consuming step
            depth=max(1, _health._env_int("TPUFRAME_PREFETCH_DEPTH", 2)),
            sharding=self.plan.batch_sharding(leading_microbatch=accum > 1),
            track_loader=loader if train and trackable else None,
            # ring-buffer recycling: host_iter yields exactly one dict per
            # loader batch (grad-accum reshapes within a batch), so the
            # prefetcher's release-after-H2D stays FIFO-aligned with the
            # loader's lease order
            recycler=loader if hasattr(loader, "release_oldest") else None,
            # the train step this epoch's first batch feeds: the producer's
            # spans then carry the same step id as the loop's
            first_step=self.batches_seen + 1 if train else None,
        )
        if train:
            self._train_prefetcher = pf
        yield from pf

    # -- autotune ----------------------------------------------------------
    def _autotune_identity(self) -> tuple[str, str, str]:
        """The persistence key the autotune store uses for this run:
        (host, topology, plan signature) — same-host ranks and a
        supervised restart of the same program share it; a different
        world shape or plan misses and tunes fresh."""
        from tpuframe.autotune.config import default_host

        topology = f"{rt.process_count()}x{rt.current_runtime().device_count}"
        return default_host(), topology, self.plan.signature()

    def apply_tuned(self, env: Mapping[str, str]) -> dict:
        """Apply a tuned config's env to this process: every knob is
        written to ``os.environ`` (so per-use readers and anything
        constructed later — eval loaders, a supervisor's next attempt —
        see it), and the domain registry's ``apply`` field classifies
        each into ``applied`` (live effect now; the mid-epoch snapshot
        cadence is additionally pushed onto the running loop) vs
        ``restart_only`` (takes effect at the next construction).
        Returns ``{"applied": {...}, "restart_only": {...}}``.
        """
        from tpuframe.autotune.config import all_env_domains

        domains = all_env_domains()
        applied: dict[str, str] = {}
        restart_only: dict[str, str] = {}
        for knob, value in env.items():
            d = domains.get(knob)
            if d is None:
                continue  # not in the legal registry: never apply
            os.environ[knob] = str(value)
            if d.get("apply") == "live":
                applied[knob] = str(value)
            else:
                restart_only[knob] = str(value)
        if "TPUFRAME_CKPT_INTERVAL_BATCHES" in applied:
            # the one live knob the Trainer itself re-reads per step
            iv = _health._env_int("TPUFRAME_CKPT_INTERVAL_BATCHES", 0)
            self.checkpoint_interval_batches = iv if iv > 0 else None
        if applied or restart_only:
            get_telemetry().event(
                "autotune/apply", applied=len(applied),
                restart_only=len(restart_only), side="train",
            )
        return {"applied": applied, "restart_only": restart_only}

    def apply_persisted_tuning(self) -> dict:
        """Load the persisted winning config for this run's identity and
        :meth:`apply_tuned` it.  Called from :meth:`fit` when
        ``TPUFRAME_AUTOTUNE`` is truthy — the supervised-restart half of
        the loop: the restarting attempt (and every same-host rank)
        starts tuned without re-probing.  No config is a no-op."""
        from tpuframe.autotune.config import load_tuned

        host, topology, signature = self._autotune_identity()
        cfg = load_tuned(host, topology, signature)
        if cfg is None:
            return {}
        return self.apply_tuned(cfg.env)

    # -- the loop ----------------------------------------------------------
    def fit(self) -> FitResult:
        """Run to max_duration; returns the Ray-style FitResult."""
        from tpuframe.autotune.config import autotune_enabled

        self._fit_start_ns = time.perf_counter_ns()
        if autotune_enabled():
            self.apply_persisted_tuning()
        result = FitResult()
        state = self.init_state()
        if self.preemption is True:
            # enable: ensure the process-wide watcher exists and use it
            self.preemption = _preempt.install()
        elif self.preemption is not None and self.preemption is not False:
            # an explicitly-passed watcher: make sure its signal handlers
            # / poll thread are live for the duration of the fit
            self.preemption.install()
        if self.checkpointer is not None:
            # auto-resume from whichever is newer: the last epoch-end
            # checkpoint or a mid-epoch snapshot (crash inside an epoch)
            source = self.checkpointer
            intra = self._intra_checkpointer()
            if intra is not None:
                main_step = self.checkpointer.latest_step()
                intra_step = intra.latest_step()
                if intra_step is not None and (
                    main_step is None or intra_step > main_step
                ):
                    source = intra
            state, restored_meta = source.maybe_restore(state, plan=self.plan)
            self.state = state
            if restored_meta:
                self.epoch = int(restored_meta.get("epoch", 0))
                self.batches_seen = int(restored_meta.get("batches_seen", 0))
                self.samples_seen = int(restored_meta.get("samples_seen", 0))
                # a mid-epoch snapshot carries the loader position;
                # applied after _run_epoch's set_epoch rewind
                self._pending_loader_state = restored_meta.get("loader_state")
                # the data-order contract across an elastic resize: the
                # loader position above counts GLOBAL batches, so the
                # global batch must survive the shrink unchanged — a
                # resized world re-splits it (per-process batch x
                # processes x grad-accum), never changes the product.
                # Misconfiguration is FATAL (ValueError): retrying would
                # replay/skip samples on every attempt.
                saved_gb = restored_meta.get("global_batch")
                cur_gb = getattr(self.train_dataloader, "global_batch_size", None)
                if saved_gb and cur_gb and int(saved_gb) != int(cur_gb):
                    raise ValueError(
                        f"restored checkpoint was trained at global batch "
                        f"{saved_gb} but this loader produces {cur_gb}: a "
                        "world resize must preserve the global batch to "
                        "keep the checkpointed loader position meaningful "
                        "— re-derive the per-process split with "
                        "tpuframe.launch.rederive_batch_split(global_batch="
                        f"{saved_gb}, dp_size={self.plan.dp_size})"
                    )
        # memory-forensics context: register the plan + the live state's
        # shape/dtype trees (the walker only reads attrs — nothing
        # materializes) so an OOM anywhere in this fit can attribute
        # bytes and suggest the nearest-fitting plan without recompiling
        try:
            batch_template = loader_batch_template(self, train=True)
        except Exception:
            batch_template = None
        _memory.set_context(
            plan=self.plan,
            model_template=self.state.params,
            batch_spec=batch_template,
            opt_template=self.state.opt_state,
            comms_template=self.state.comms,
            microbatches=self.grad_accum,
        )
        # divergence-recovery data-order skip: after a rollback the
        # supervisor may direct this attempt to re-enter PAST the poison
        # window instead of deterministically replaying into it.
        # Applied on top of whatever loader position the restore carried
        # — INCLUDING a restore-less fresh start (every step quarantined,
        # or no checkpointer at all: the perturbation half of recovery
        # must not depend on there being something to roll back to).
        # One-shot: consumed here so a later unrelated restart in the
        # same run doesn't re-skip healthy batches.
        skip = (
            _health.consume_skip_batches()
            if self.health is not None
            and hasattr(self.train_dataloader, "load_state_dict")
            else 0
        )
        if skip:
            ls = self._pending_loader_state
            if ls is None:
                ls = self.train_dataloader.state_dict()
                ls["epoch"] = self.epoch
                ls["batches_yielded"] = 0
            ls = dict(ls)
            try:
                epoch_len = len(self.train_dataloader)
            except TypeError:
                epoch_len = int(ls["batches_yielded"]) + skip
            ls["batches_yielded"] = min(
                int(ls["batches_yielded"]) + skip, epoch_len
            )
            self._pending_loader_state = ls
            get_telemetry().event(
                "health/skip_batches",
                skip=skip,
                batches_yielded=ls["batches_yielded"],
                epoch=int(ls.get("epoch", self.epoch)),
            )

        if self.precompile_enabled:
            # background AOT warm-start, overlapped with the epoch's
            # loader/ring-buffer spin-up; the first _step_call joins.
            # Started AFTER restore so the lowered programs see the
            # restored state's exact shardings.
            self.precompile(wait=False)
        self._log_params(
            {
                "max_duration": str(self.max_duration),
                "optimizer": type(self.tx).__name__,
                "precision": str(self.policy.compute_dtype.__name__)
                if hasattr(self.policy.compute_dtype, "__name__")
                else str(self.policy.compute_dtype),
                "devices": rt.current_runtime().device_count,
                "zero_stage": self.plan.zero_stage,
                "algorithms": ",".join(type(a).__name__ for a in self.algorithms),
            }
        )
        self._emit("on_fit_start")
        try:
            while not self._done() and self._stop_reason is None:
                with get_telemetry().span("train/epoch", epoch=self.epoch):
                    epoch_metrics = self._run_epoch()
                eval_metrics: dict[str, float] = {}
                if (
                    self.eval_dataloader is not None
                    and self.eval_interval
                    and (self.epoch + 1) % self.eval_interval == 0
                ):
                    eval_metrics = self.evaluate()
                    self._emit("on_eval_end", self.epoch, eval_metrics)
                epoch_summary = {**epoch_metrics, **eval_metrics}
                result.history.append(epoch_summary)
                result.metrics = epoch_summary
                self._log_metrics(epoch_summary, step=self.epoch)
                self._emit("on_epoch_end", self.epoch, epoch_summary)

                ckpt_path = None
                # Every process participates: orbax sharded saves are
                # collective (rank-0-only discipline applies to *logging*,
                # not checkpoint writes).
                if self.checkpointer is not None and (
                    (self.epoch + 1) % self.checkpoint_interval == 0
                ):
                    ckpt_path = self.checkpointer.save(
                        self.state,
                        metrics=epoch_summary,
                        meta={
                            "epoch": self.epoch + 1,
                            "batches_seen": self.batches_seen,
                            "samples_seen": self.samples_seen,
                            "global_batch": self.train_dataloader.global_batch_size,
                        },
                        plan=self.plan,
                        health=self._health_stamp(),
                    )
                    result.checkpoint = str(ckpt_path)
                    # An epoch-end save supersedes any mid-epoch snapshot
                    # at an earlier-or-equal optimizer step: drop it so it
                    # neither lingers on disk nor wins a later auto-resume
                    # it no longer should.
                    intra = self._intra_checkpointer()
                    if intra is not None:
                        saved = self.checkpointer.latest_step()
                        stale = intra.latest_step()
                        if (
                            saved is not None
                            and stale is not None
                            and stale <= saved
                        ):
                            intra.delete(stale)
                if self.report is not None:
                    self.report(epoch_summary, result.checkpoint)
                self.epoch += 1
        except BaseException as e:  # Ray-style: surface, don't swallow rank-0 state
            result.error = e
            raise
        finally:
            result.stopped_reason = self._stop_reason
            self._emit("on_fit_end")
            for lg in self.loggers:
                # finish(error=) lets status-aware loggers record FAILED for a
                # crashed fit instead of a blanket flush-as-success.
                if hasattr(lg, "finish"):
                    lg.finish(error=result.error)
                elif hasattr(lg, "flush"):
                    lg.flush()
        return result

    def _done(self) -> bool:
        return self.max_duration.reached(
            epoch=self.epoch, batch=self.batches_seen, samples=self.samples_seen
        )

    def _run_epoch(self) -> dict[str, float]:
        self._emit("on_epoch_start", self.epoch)
        self.train_dataloader.set_epoch(self.epoch)
        if self._pending_loader_state is not None:
            # resume mid-epoch: skip the already-trained batches of this
            # epoch (this epoch's summary then covers only the remainder)
            if not hasattr(self.train_dataloader, "load_state_dict"):
                # a leftover snapshot from a previous run can reach here
                # even with checkpoint_interval_batches off; silently
                # dropping the position would replay trained batches
                raise ValueError(
                    "resuming a mid-epoch snapshot requires a "
                    "train_dataloader with load_state_dict() (got "
                    f"{type(self.train_dataloader).__name__}); restore "
                    "with a tpuframe.data.DataLoader or delete the "
                    "*_intra snapshot directory"
                )
            self.train_dataloader.load_state_dict(self._pending_loader_state)
            self._pending_loader_state = None
        acc = None
        window = None  # device-side metric pytree, materialized per interval
        t0 = time.perf_counter()
        # DeepSpeed-style wall-clock breakdown (`deepspeed_config.py:47-48`):
        # where host time goes per epoch — now measured by telemetry spans
        # at the SAME points the old perf_counter pairs sat, so the epoch
        # summary keys keep their values while per-step distributions
        # (span/train/* histograms) and the watchdog's live position come
        # free.  Inner per-batch spans use emit=False: one JSONL event per
        # *step* (train/step), not three.
        tele = get_telemetry()
        data_wait = dispatch = host_block = 0.0
        # producer-side costs (assembly in the loader, H2D in the
        # prefetcher thread) accrue in their span histograms; the delta
        # over this epoch lands in the summary next to data_wait_s —
        # together they attribute an input stall to production vs
        # transfer vs consumption.
        _h_assemble = tele.registry.histogram("span/data/assemble")
        _h_h2d = tele.registry.histogram("span/data/h2d")
        assemble0, h2d0 = _h_assemble.total, _h_h2d.total
        _epoch_end = object()

        def drain(window, first_step):
            """Materialize the device-side window (the only host sync)."""
            nonlocal host_block, machine0, window_t0
            # the drained window: first_step .. step
            with tele.span("train/host_block", emit=False,
                           step=self.batches_seen, first_step=first_step) as sp:
                # every leaf in one fetch: the device idles from the end of
                # the window's last step until the next dispatch lands, so a
                # transfer a leaf (and an eager slice a health field) would
                # be the device's time, not only the host's
                window = jax.device_get(window)
                out = {
                    k: float(v) for k, v in window.items()
                    if k not in ("health_stats", "model_stats")
                }
                # what the model's layers counted (step._model_stats),
                # summed over the window's steps: counters advance by
                # the sum, gauges show the mean step
                stats = window.get("model_stats", {})
                steps = self.batches_seen - first_step + 1
                for name, v in stats.get("counters", {}).items():
                    tele.registry.counter(name).inc(float(v))
                for name, v in stats.get("gauges", {}).items():
                    tele.registry.gauge(name).set(float(v) / steps)
                # the sentinel's packed vector splits into its named
                # scalar sums (one device leaf on the hot path, five
                # host columns in the summary)
                if "health_stats" in window:
                    out.update(
                        _health.unpack_health_stats(window["health_stats"])
                    )
                # what the machine did since the drain before: the record
                # that tells a descheduled thread from a device that paused
                machine = machine_counters()
                record = {k: round(v - machine0[k], 6)
                          for k, v in machine.items() if k in machine0}
                sp.attrs.update(record)
            host_block += sp.elapsed
            step_s = (sp.end_ns - window_t0) / 1e9 / steps
            seen = self._window_step_s
            median_s = statistics.median(seen) if len(seen) >= 3 else step_s
            if step_s > 1.5 * median_s:
                self._slow_window(first_step, steps, step_s, median_s, record)
            seen.append(step_s)
            machine0, window_t0 = machine, sp.end_ns
            return out

        batches = iter(self._device_batches(self.train_dataloader, train=True))
        empty_queue = tele.registry.counter("train/empty_queue_dispatches")
        depth_hist = tele.registry.histogram("train/steps_in_flight")
        # one output leaf a dispatched step, oldest first: the device's queue
        in_flight: collections.deque = collections.deque()
        window_first = 0  # the first step summed into ``window``
        # straggler boundary: the gap back to the previous epoch (eval,
        # epoch-end checkpoint) must not read as one slow step
        self._straggler.mark()
        # a window runs from the close of the drain before it (here: from
        # the epoch's start) to the close of its own
        machine0, window_t0 = machine_counters(), time.perf_counter_ns()
        if self._fit_start_ns is not None:
            # fit()'s entry to the loop's first iteration: resume lookup,
            # the precompile thread's start, on_fit_start, loader spin-up
            tele.record_span("setup/fit_start", self._fit_start_ns, window_t0)
            self._fit_start_ns = None
        while True:
            # chaos site: a scheduled loader fault raises here, exactly
            # where a real worker-pool / shard-fetch failure surfaces
            chaos.maybe_fire("loader", step=self.batches_seen)
            # one parent per iteration, tagged with the step it feeds (its
            # children inherit the tag): what no child covers is the
            # loop's own time
            with tele.span("train/iter", emit=False, cpu=True,
                           step=self.batches_seen + 1) as it:
                with tele.span("train/data_wait", emit=False) as sp:
                    batch = next(batches, _epoch_end)
                # the exhausted final pull never counts toward data_wait
                stop = batch is _epoch_end
                if not stop:
                    wait_s = sp.elapsed
                    data_wait += wait_s
                    stop = self._done() or self._stop_reason is not None
                if stop:
                    it.step = sp.step = None  # this iteration feeds no step
                    break
                self._emit("on_step_start")
                try:
                    chaos.maybe_fire("step", step=self.batches_seen)
                    # the guard turns a wedged dispatch (first-step compile,
                    # stuck collective) into an attributed watchdog report
                    # instead of a silent hang; unmonitored unless a watchdog
                    # is configured.  data_wait_s rides as a span attr so the
                    # fleet analyzer can classify this step input-bound
                    # without a second JSONL line.
                    with tele.span("train/step", batch=self.batches_seen,
                                   data_wait_s=round(wait_s, 6)) as sp, \
                            tele.guard("train/step"):
                        # steps dispatched and not yet complete (no sync,
                        # no dispatch); none means nothing is queued: the
                        # device sits idle until this dispatch lands
                        complete = 0
                        for leaf in in_flight:
                            if not leaf.is_ready():
                                break
                            complete += 1
                        depth = len(in_flight) - complete
                        sp.attrs["steps_in_flight"] = depth
                        sp.attrs["device_idle_at_dispatch"] = depth == 0
                        depth_hist.observe(depth)
                        if depth == 0:
                            empty_queue.inc()
                        self.state, metrics = self._step_call(
                            "train", self._train_step, self.state, batch,
                            span=sp,
                        )
                except Exception as e:
                    # OOM forensics: a RESOURCE_EXHAUSTED here (the chaos
                    # OomAt fires inside this block too) becomes one
                    # memory/oom event with the attribution table + fit
                    # suggestion; everything re-raises untouched
                    _memory.maybe_oom_event(e, where="step",
                                            step=self.batches_seen)
                    raise
                dispatch += sp.elapsed
                in_flight.append(jax.tree.leaves(metrics)[0])
                # dropped after the dispatch, not before it: each frees a
                # device buffer (3-4 us), and after a drain the device
                # waits meanwhile
                for _ in range(complete):
                    in_flight.popleft()
                self.batches_seen += 1
                self.samples_seen += self.train_dataloader.global_batch_size
                self._meter_comms(tele)
                self._meter_pp(tele)
                # boundary-to-boundary step time: charges whatever actually
                # slowed this rank (wait, dispatch, snapshot, callback)
                self._straggler.observe()
                # Accumulate on device (async) — floating every step would
                # block the host on each step's completion and serialize the
                # pipeline.  Before the health sentinel, whose check waits
                # for this step: the adds then queue behind the step, and
                # the device runs them while the host is still waiting
                if window is None:
                    window, window_first = metrics, self.batches_seen
                else:
                    # one eager add a leaf: each a dispatch, and the place
                    # the loop waits where the device's queue is full
                    with tele.span("train/metrics_window", emit=False,
                                   leaves=len(jax.tree.leaves(metrics))):
                        window = jax.tree.map(jnp.add, window, metrics)
                # health sentinel: accumulate the step's bad-flag on device
                # (async, like the metrics window) and check once per window
                # — may raise Divergence, BEFORE this step's interval
                # snapshot would write yet another doomed checkpoint
                self._health_step(metrics)
                if (
                    self.checkpointer is not None
                    and self.checkpoint_interval_batches
                    and self.batches_seen % self.checkpoint_interval_batches == 0
                ):
                    try:
                        epoch_len = len(self.train_dataloader) or 1
                    except TypeError:  # duck-typed iterable without __len__
                        epoch_len = 1 << 62
                    snap = self._train_prefetcher.state_dict()
                    # the epoch-final batch is followed immediately by the
                    # epoch-end save — a snapshot there would be a throwaway
                    # full serialization of the same state.  The WITHIN-epoch
                    # position decides (cumulative batches_seen desyncs from
                    # epoch boundaries after any mid-epoch stop).
                    if snap["batches_yielded"] < epoch_len:
                        # mid-epoch snapshot (sibling checkpointer): model/opt
                        # state + the consumer-true loader position, so a
                        # crash resumes with the very next batch (no replayed
                        # or skipped samples)
                        # (the health stamp is a device_get: a wait on the
                        # step just dispatched, named with the save it is for)
                        with tele.span("train/snapshot", emit=False):
                            self._intra_checkpointer().save(
                                self.state,
                                meta={
                                    "epoch": self.epoch,
                                    "batches_seen": self.batches_seen,
                                    "samples_seen": self.samples_seen,
                                    "loader_state": snap,
                                    "global_batch": self.train_dataloader.global_batch_size,
                                },
                                plan=self.plan,
                                health=self._health_stamp(),
                            )
                # step boundary = the preemption exit point: the step is the
                # atomic unit of progress, so a SIGTERM/maintenance notice is
                # acted on here — last-chance checkpoint, then Preempted out
                self._maybe_preempt_exit()
                self._emit("on_step_end")
                if self.log_interval and self.batches_seen % self.log_interval == 0:
                    w = drain(window, window_first)
                    acc = merge_metrics(acc, w)
                    self._emit("on_batch_end", w)
                    self._log_metrics(
                        summarize_metrics(w, prefix="train_batch_"),
                        step=self.batches_seen,
                    )
                    window = None
        if window is not None:
            w = drain(window, window_first)
            acc = merge_metrics(acc, w)
            self._emit("on_batch_end", w)
        # flush the partial health window: max_bad bad steps are max_bad
        # bad steps whether or not the window filled before epoch end
        self._health_check()
        elapsed = time.perf_counter() - t0
        summary = summarize_metrics(acc or {}, prefix="train_")
        if acc:
            # ``count`` comes from the jitted step over *global* arrays, so
            # it is already the global sample count — no process factor
            # (multiplying by process_count over-reported N x on pods).
            summary["train_samples_per_sec"] = acc.get("count", 0.0) / max(elapsed, 1e-9)
        if self.health is not None and acc:
            summary["health_bad_steps"] = acc.get("health_bad", 0.0)
            # mean over FINITE steps only: grad_norm_sum zeroes the
            # non-finite ones, so they must leave the denominator too
            finite_steps = (
                acc.get("health_steps", 0.0)
                - acc.get("health_nonfinite", 0.0)
            )
            if finite_steps > 0:
                summary["grad_norm"] = (
                    acc.get("grad_norm_sum", 0.0) / finite_steps
                )
        summary["epoch_time_s"] = elapsed
        summary["data_wait_s"] = data_wait
        summary["dispatch_s"] = dispatch
        summary["host_block_s"] = host_block
        summary["assemble_s"] = _h_assemble.total - assemble0
        summary["h2d_s"] = _h_h2d.total - h2d0
        return summary

    def evaluate(self) -> dict[str, float]:
        """Global, mask-correct eval over the eval dataloader."""
        if self.eval_dataloader is None:
            raise ValueError("no eval_dataloader")
        if getattr(self.eval_dataloader, "drop_last", False) and not getattr(
            self, "_warned_eval_drop", False
        ):
            # eval counts silently lose the ragged tail with drop_last=True;
            # the mask contract (DataLoader(drop_last=False) third element)
            # exists precisely so eval never miscounts
            import warnings

            warnings.warn(
                "eval_dataloader has drop_last=True: the final ragged batch "
                "is skipped and eval metrics undercount; use "
                "drop_last=False (yields a validity mask) for exact eval",
                stacklevel=2,
            )
            self._warned_eval_drop = True
        state = self._serving_state()
        self.eval_dataloader.set_epoch(0)
        acc = None
        with get_telemetry().span("train/eval", epoch=self.epoch):
            for batch in self._device_batches(self.eval_dataloader, train=False):
                metrics = self._step_call("eval", self._eval_step, state, batch)
                acc = merge_metrics(acc, metrics)
        return summarize_metrics(acc or {}, prefix="eval_")

    def _serving_state(self) -> TrainState:
        """The state evaluate/predict/export should read weights from:
        the live params, or the EMA average when ``ema_decay`` is on
        (the whole point of maintaining the average)."""
        state = self.init_state()
        if self.ema_decay is None:
            return state
        from tpuframe.train.ema import ema_params

        return state.replace(params=ema_params(state))

    def predict(self, images: np.ndarray) -> np.ndarray:
        """Logits for a (N, H, W, C) image batch (the reference's
        single-image demo path adds the batch dim itself)."""
        state = self._serving_state()
        return np.asarray(self._predict(state, np.asarray(images)))

    def export(
        self,
        path: str,
        sample_input: np.ndarray | None = None,
        batch_polymorphic: bool = True,
        platforms: tuple[str, ...] | None = None,
    ) -> str:
        """Freeze the trained model into a portable serving artifact.

        Bundles the current params/batch_stats AND the trainer's
        ``normalize=`` preprocessing into one StableHLO blob via
        :func:`tpuframe.serve.export_model` — callers of the artifact
        send the same raw batches training consumed.  Portability over
        performance, deliberately: params are gathered to host numpy
        (the artifact must not remember the training mesh's device
        count) and the normalize runs the plain-jnp reference path (the
        compiled Pallas kernel would pin the artifact to TPU).
        ``sample_input`` defaults to the trainer's own init sample;
        ``platforms=("cpu", "tpu")`` lowers for both targets.
        """
        from tpuframe.serve import export_model

        state = self._serving_state()
        variables = {"params": state.params}
        if jax.tree.leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        # host-gathered constants: a multi-chip trainer's params are
        # sharded Arrays, and closing over those would bake the training
        # mesh's device count into the artifact.  Across processes a
        # plain device_get cannot read non-addressable shards, so gather
        # collectively first.
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            variables = multihost_utils.process_allgather(variables)
        variables = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)), variables
        )
        if sample_input is None:
            if self.sample_input is None:
                raise ValueError("pass sample_input= (none known to the trainer)")
            sample_input = self.sample_input
        preprocess = None
        if self._norm_args is not None:
            from tpuframe.ops.normalize import normalize_images_reference

            mean, std, scale = self._norm_args
            out_dtype = self.policy.compute_dtype

            def preprocess(x):
                return normalize_images_reference(
                    x, mean, std, scale, out_dtype
                )

        return export_model(
            self.model,
            variables,
            sample_input,
            path,
            preprocess=preprocess,
            batch_polymorphic=batch_polymorphic,
            platforms=platforms,
        )


def _planned_total_steps(duration, dataloader) -> int | None:
    """Best-effort optimizer-step count for schedule resolution (the
    DeepSpeed ``total_num_steps: "auto"`` pattern,
    `deepspeed_config.py:16` style deferred values)."""
    if duration.unit == "ba":
        return duration.value
    if dataloader is None:
        return None
    if duration.unit == "ep":
        try:
            return duration.value * len(dataloader)
        except TypeError:
            return None
    # "sp": samples -> batches at the loader's global batch size.  The loop
    # stops when samples_seen >= value, i.e. after ceil(value/gbs) steps.
    gbs = getattr(dataloader, "global_batch_size", None)
    return max(-(-duration.value // gbs), 1) if gbs else None


def _make_optimizer(name: str, lr: float | optax.Schedule) -> optax.GradientTransformation:
    """Named optimizers matching the reference examples' choices (Adam
    everywhere except MNIST's momentum SGD, `01_basic_torch_distributor.py:283`,
    and DeepSpeed's AdamW+warmup config, `deepspeed_config.py:28-40`)."""
    table = {
        "adam": optax.adam,
        "adamw": optax.adamw,
        "sgd": lambda lr: optax.sgd(lr, momentum=0.9),
        "lamb": optax.lamb,
        "lion": optax.lion,
        "adafactor": optax.adafactor,
    }
    try:
        return table[name.lower()](lr)
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}; known: {sorted(table)}") from None
