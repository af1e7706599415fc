"""Jitted train/eval step factories — the framework's hot loop.

The reference's per-batch body (H2D copy -> forward -> loss -> backward ->
allreduce -> optimizer.step, `/root/reference/01_torch_distributor/
01_basic_torch_distributor.py:224-230`) compiles here into ONE XLA program:
forward+backward+update fused, gradients all-reduced (or reduce-scattered
under ZeRO) by the partitioner over ICI, input batch donated, bf16 on the MXU.

Factories return plain jitted callables — the high-level Trainer wraps them,
but they are equally the "Accelerate-style" low-level API (SURVEY.md §7:
train/ exposes both levels).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import optax
from jax import shard_map

from tpuframe.parallel.precision import Policy, full_precision
from tpuframe.parallel.sharding import ParallelPlan
from tpuframe.train.state import TrainState

#: loss_fn(logits, labels) -> per-example losses, pluggable.
LossFn = Callable[[jax.Array, jax.Array], jax.Array]


class ModelObjective:
    """An objective a model brings with it, as a ``loss_fn``: called with
    the model's output and the whole batch (not the labels alone), it
    returns one loss a row.  What the labels cannot score the step does
    not score: ``correct`` stays 0, and ``count`` is the rows, so
    ``loss_sum / count`` is the mean objective a row."""

    def __init__(self, fn: Callable[[jax.Array, Mapping[str, jax.Array]], jax.Array]):
        self.fn = fn

    def __call__(self, output, batch):
        return self.fn(output, batch)


def model_objective(model) -> ModelObjective | None:
    """The objective ``model`` brings (a method ``objective(output, batch)``
    -> per-row losses), or None: then the caller's ``loss_fn`` stands,
    `cross_entropy` by default."""
    fn = getattr(model, "objective", None)
    return ModelObjective(fn) if callable(fn) else None


def cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    mesh=None,
    batch_axes: tuple | None = None,
) -> jax.Array:
    """Integer-label softmax cross entropy (≈ reference's ``nll_loss`` after
    log_softmax, `01_basic_torch_distributor.py:90-92,226`).  Supports soft
    labels (N, C) for CutMix/LabelSmoothing mixtures.

    (B,) integer labels route through the fused Pallas kernel on TPU
    (recompute backward, no HBM softmax materialization) — per batch
    shard under ``shard_map`` when ``mesh`` is given (the step factories
    pass it from their ``plan``), single-chip directly.  Higher-rank
    integer labels keep the optax path."""
    if labels.ndim == logits.ndim:
        return optax.softmax_cross_entropy(logits, labels)
    if labels.ndim == 1 and logits.ndim == 2:
        from tpuframe.ops import fused_cross_entropy

        return fused_cross_entropy(
            logits, labels, mesh=mesh, batch_axes=batch_axes
        )
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


@functools.lru_cache(maxsize=64)
def _supports_mutable(apply_fn) -> bool:
    """True when ``apply_fn`` takes flax's ``mutable=`` kwarg."""
    import inspect

    try:
        return "mutable" in inspect.signature(apply_fn).parameters
    except (TypeError, ValueError):  # builtins / C callables
        return False


# Stable names on the device side: ``jax.named_scope`` writes them into the
# metadata of every op traced under it (and ``transpose(jvp(<name>))`` into
# the backward pass's), which is where a profiler trace's reduction finds
# forward, backward, optimizer and the input transform after any refactor.
# Metadata only: the compiled program's memory and code do not change.


def _named_transform(batch_transform: Callable[[dict], dict] | None):
    """The on-device input transform (the Trainer's: the image normalize,
    one fusion in front of the first convolution) under
    ``tpuframe/input_normalize``."""
    if batch_transform is None:
        return None
    return jax.named_scope("tpuframe/input_normalize")(
        lambda batch: batch_transform(dict(batch))
    )


@jax.named_scope("tpuframe/optimizer")
def _apply_gradients(state: TrainState, grads: Any, **changes) -> TrainState:
    return state.apply_gradients(grads, **changes)


@jax.named_scope("tpuframe/forward")
def _forward(state: TrainState, params: Any, batch: Mapping[str, jax.Array],
             policy: Policy, train: bool, rng: jax.Array | None,
             loss_fn: LossFn):
    """Shared forward: handles batch_stats mutability, dropout rngs, and
    auxiliary losses (``aux_loss`` collection — MoE load balancing).

    A `ModelObjective` takes the model's output and the whole batch, and
    its output is then scored no further (``logits`` comes back None).

    Returns (losses, logits, new_stats, aux, model_stats) where ``aux`` is
    the summed auxiliary loss (0.0 when the model sows none); train steps
    add it to the objective so e.g. MoE routers actually feel their
    balance loss.  ``model_stats`` is what the model's layers sowed into
    the ``counters`` and ``gauges`` collections under telemetry names,
    reduced over the layers (counters summed, gauges by their worst
    layer); empty for a model that sows none."""
    variables = {"params": policy.cast_params_for_compute(params)}
    has_stats = bool(jax.tree.leaves(state.batch_stats))
    if has_stats:
        variables["batch_stats"] = state.batch_stats
    kwargs: dict[str, Any] = {"train": train}
    if train and rng is not None:
        kwargs["rngs"] = {"dropout": rng}
    # "input" is the generic key (token ids, features); "image" the vision
    # alias the reference examples use.  Int inputs pass cast_batch untouched.
    x = batch["input"] if "input" in batch else batch["image"]
    x = policy.cast_batch(x)
    aux = jnp.zeros((), jnp.float32)
    if train:
        if _supports_mutable(state.apply_fn):
            mutable = ["aux_loss", "counters", "gauges"] + (
                ["batch_stats"] if has_stats else [])
            logits, updates = state.apply_fn(variables, x, mutable=mutable, **kwargs)
        else:
            # non-flax apply_fn (e.g. PipelinedTransformerLM's duck-typed
            # adapter) takes no `mutable` kwarg
            logits = state.apply_fn(variables, x, **kwargs)
            updates = {}
        new_stats = updates.get("batch_stats", state.batch_stats)
        aux_leaves = jax.tree.leaves(updates.get("aux_loss", {}))
        if aux_leaves:
            aux = sum(jnp.sum(a) for a in aux_leaves)
        model_stats = _model_stats(updates)
    else:
        logits = state.apply_fn(variables, x, **kwargs)
        new_stats = state.batch_stats
        model_stats = {}
    logits = policy.cast_outputs(logits)
    if isinstance(loss_fn, ModelObjective):
        # under "input" what the model was given, whichever key held it
        losses = loss_fn(logits, {**batch, "input": x})
        return losses, None, new_stats, aux, model_stats
    losses = loss_fn(logits, batch["label"])
    return losses, logits, new_stats, aux, model_stats


def _model_stats(updates: Mapping[str, Any]) -> dict:
    """{"counters": {name: sum over layers}, "gauges": {name: max over
    layers}} of what the layers sowed; a collection nothing was sown
    into is left out, so a model without them adds no leaf to the
    step's metrics."""
    out: dict[str, dict] = {}
    for collection, reduce in (("counters", jnp.sum), ("gauges", jnp.max)):
        by_name: dict[str, list] = {}
        flat = jax.tree_util.tree_flatten_with_path(updates.get(collection, {}))[0]
        for path, leaf in flat:
            name = [k.key for k in path if hasattr(k, "key")][-1]
            by_name.setdefault(name, []).append(jnp.asarray(leaf, jnp.float32))
        if by_name:
            out[collection] = {name: reduce(jnp.stack(leaves))
                               for name, leaves in by_name.items()}
    return out


def _train_metrics(loss, logits, labels) -> dict:
    """The summed train-metrics triple every train-step flavor reports
    (mean is taken by whoever logs).  One definition — grad-accum adds
    across microbatches, the compressed step psums across shards."""
    if logits is None:  # a model's own objective: rows, nothing to score
        n = jnp.asarray(labels.shape[0], jnp.float32)
        return {"loss_sum": loss * n, "correct": jnp.zeros(()), "count": n}
    hard = jnp.argmax(labels, -1) if labels.ndim == logits.ndim else labels
    n = jnp.asarray(hard.size, jnp.float32)  # tokens for LM, images for vision
    return {
        "loss_sum": loss * n,
        "correct": jnp.sum(jnp.argmax(logits, -1) == hard).astype(jnp.float32),
        "count": n,
    }


def _apply_with_health(state: TrainState, grads: Any, new_stats: Any,
                       loss, metrics: dict, health, *,
                       apply_fn: Callable | None = None, grad_sq=None,
                       extra_state: dict | None = None):
    """The sentinel tail every train-step flavor shares
    (``tpuframe.fault.health``): one fused grad-norm/finiteness
    reduction + the EWMA spike test produce a scalar ``bad`` verdict,
    and a bad step applies NO update — ``jnp.where`` selects the old
    params/opt_state/batch_stats leaf-by-leaf, so the compiled program
    is branch-free and the batch/AOT signature is untouched.  A bad
    step's metrics contributions are zeroed (a NaN loss_sum would
    poison the whole window sum); the health flags ride the metrics
    pytree to the host, which reads them at its window cadence.

    ``apply_fn`` overrides the plain ``state.apply_gradients`` (the
    compressed ZeRO step applies a sharded update + all-gather);
    ``grad_sq`` supplies a pre-reduced global gradient square when the
    gradient tree is sharded across the mesh (the verdict must be
    identical on every shard); ``extra_state`` = ``{field: (old, new)}``
    adds more state fields to the bad-step rollback (the EF residual —
    a poisoned step's quantization error must not be committed).
    """
    from tpuframe.fault.health import health_verdict

    hstate = getattr(state, "health", None)
    if not hstate:
        raise ValueError(
            "health-checked step needs a TrainState with a health slot; "
            "create_train_state initializes one (or pass "
            "health=tpuframe.fault.health.init_health_state() to replace)"
        )
    bad, new_hstate, hmetrics = health_verdict(
        loss, grads, hstate, state.step, health, grad_sq=grad_sq
    )
    if apply_fn is None:
        applied = _apply_gradients(state, grads, batch_stats=new_stats)
    else:
        applied = apply_fn(grads)

    def keep_old(old, new):
        return jax.tree.map(lambda o, n: jnp.where(bad, o, n), old, new)

    changes = {
        "params": keep_old(state.params, applied.params),
        "opt_state": keep_old(state.opt_state, applied.opt_state),
        "batch_stats": keep_old(state.batch_stats, applied.batch_stats),
        "health": new_hstate,
    }
    for field, (old, new) in (extra_state or {}).items():
        changes[field] = keep_old(old, new)
    new_state = applied.replace(**changes)
    metrics = {
        k: jnp.where(bad, jnp.zeros_like(v), v) for k, v in metrics.items()
    }
    metrics.update(hmetrics)
    return new_state, metrics


def _bind_loss(loss_fn: LossFn, plan: ParallelPlan | None) -> LossFn:
    """Give the default loss its mesh so the fused CE kernel can run
    per-shard on multi-chip meshes; custom losses pass through untouched."""
    if plan is not None and loss_fn is cross_entropy:
        return functools.partial(
            cross_entropy, mesh=plan.mesh, batch_axes=tuple(plan.data_axes)
        )
    return loss_fn


def _wrap_offload(jstep, plan: ParallelPlan | None):
    """Return the new opt state to pinned host after each step when the
    plan offloads it (jit outputs land on device; the put-back keeps the
    steady-state HBM footprint at params+grads, not params+grads+moments)."""
    if plan is None or not plan._offload_active():
        return jstep
    cache: dict[str, Any] = {}

    def step(state, batch):
        # Restore the *input* placement (pinned_host for offloaded leaves,
        # device for scalars like the adamw count): step N+1 then has the
        # exact sharding signature step N traced with — no recompile, and
        # the step counter stays deviceside where it gates control flow.
        if "sh" not in cache:
            cache["sh"] = jax.tree.map(lambda x: x.sharding, state.opt_state)
        new_state, metrics = jstep(state, batch)
        return (
            new_state.replace(
                opt_state=jax.device_put(new_state.opt_state, cache["sh"])
            ),
            metrics,
        )

    # the compile spine (tpuframe.compile) AOT-lowers through the inner
    # jitted program; the wrapper itself stays the call path (its
    # per-call put-back is host work an executable can't carry)
    step._inner_jit = jstep
    return step


def make_train_step(
    policy: Policy | None = None,
    loss_fn: LossFn = cross_entropy,
    donate: bool = True,
    plan: ParallelPlan | None = None,
    batch_transform: Callable[[dict], dict] | None = None,
    grad_compression: str | None = None,
    health=None,
    grad_clip: float | None = None,
) -> Callable[[TrainState, Mapping[str, jax.Array]], tuple[TrainState, dict]]:
    """Build the jitted train step: (state, batch) -> (state, metrics).

    Metrics are summed (loss_sum, correct, count) so they aggregate exactly
    across microbatches and hosts — the mean is taken by whoever logs.
    ``plan`` (optional) lets the default cross-entropy run its Pallas
    kernel per batch shard over the plan's mesh.  ``batch_transform``
    runs *inside* the jitted program (e.g. fused uint8 normalization:
    ship raw bytes over PCIe, normalize on-chip).

    ``grad_compression="int8"``/``"fp8"`` (or a
    :class:`~tpuframe.parallel.comms_env.CommsConfig`) swaps the
    implicit GSPMD gradient all-reduce for an explicit bucketed,
    error-feedback quantized mean (EQuARX-style, see
    :mod:`tpuframe.parallel.compression`) — ~4x fewer sync bytes where
    DCN bandwidth bounds DP scaling.  Composes with DP and ZeRO-1/2/3
    plans (plan-derived compressed reduce-scatter -> sharded update ->
    all-gather; stage 3 adds gather-on-use over the fsdp-resident
    params); TP/pipeline rules re-shard params inside the model and
    refuse — their shard_map cannot nest inside the compressed one.
    ``grad_clip`` applies a plan-global-norm clip inside the compressed
    step (the uncompressed path chains ``optax.clip_by_global_norm``
    into ``tx`` instead and refuses the kwarg).
    BatchNorm: use the models' PLAIN/sync BN — inside ``shard_map`` it
    sees only its shard, i.e. shard-local statistics (torch-DDP
    semantics) fall out for free; ``bn_stats="local"``/``bn_groups`` is
    the GSPMD-path emulation of the same thing and would degenerate to
    per-sample groups here.

    ``health`` (a :class:`tpuframe.fault.health.HealthPolicy`) arms the
    training-health sentinel: grad-norm/finiteness + EWMA loss-spike
    detection fused into the step, with bad steps applying no update
    (branch-free skip) — see :func:`_apply_with_health`.
    """
    policy = policy or full_precision()
    batch_transform = _named_transform(batch_transform)
    if grad_compression is not None:
        # the step body runs INSIDE shard_map there: the loss must stay
        # unbound (mesh=None) or the fused-CE kernel would open a second,
        # mismatched shard_map and crash
        return _make_compressed_train_step(
            policy, loss_fn, donate, plan, batch_transform, grad_compression,
            health, grad_clip=grad_clip,
        )
    if grad_clip is not None:
        raise ValueError(
            "grad_clip is a compressed-step parameter (the clip needs the "
            "plan-global synced norm); for the uncompressed step chain "
            "optax.clip_by_global_norm into tx instead"
        )
    loss_fn = _bind_loss(loss_fn, plan)

    def step(state: TrainState, batch: Mapping[str, jax.Array]):
        if batch_transform is not None:
            batch = batch_transform(batch)
        rng = state.step_rng("dropout")

        def compute_loss(params):
            losses, logits, new_stats, aux, model_stats = _forward(
                state, params, batch, policy, True, rng, loss_fn
            )
            data_loss = jnp.mean(losses)
            # aux (MoE load balance etc.) joins the objective; metrics
            # report the data loss so learning curves stay comparable
            return data_loss + aux, (data_loss, logits, new_stats, model_stats)

        (_, (loss, logits, new_stats, model_stats)), grads = jax.value_and_grad(
            compute_loss, has_aux=True
        )(state.params)
        metrics = _train_metrics(loss, logits, batch["label"])
        if health is None:
            new_state = _apply_gradients(state, grads, batch_stats=new_stats)
        else:
            new_state, metrics = _apply_with_health(
                state, grads, new_stats, loss, metrics, health)
        if model_stats:
            # the layers' own counters ride the metrics window to its
            # drain, as health_stats does: no sync of their own
            metrics["model_stats"] = model_stats
        return new_state, metrics

    return _wrap_offload(jax.jit(step, donate_argnums=(0,) if donate else ()), plan)


class _CompressedStep:
    """Deferred-built compressed train step.

    The shard_map in/out specs depend on the *state's* tree structure
    (per-leaf update sharding, the EF residual layout), which a factory
    can't know — so the program is built on the first call (or AOT
    lower) from the state's shapes, then cached.  ``lower`` makes the
    object a first-class citizen of the compile spine
    (``precompile_call`` AOT-compiles it and dispatches straight to the
    executable — zero recompiles with compression on)."""

    def __init__(self, builder: Callable):
        self._builder = builder
        self._fn = None
        #: static per-step wire accounting (``comms/wire_plan``), set at
        #: build; the Trainer meters ``comms/bytes_on_wire`` from it
        self.wire = None

    def _ensure(self, state):
        if self._fn is None:
            self._fn, self.wire = self._builder(state)

    def __call__(self, state, batch):
        self._ensure(state)
        return self._fn(state, batch)

    def lower(self, state, batch):
        self._ensure(state)
        return self._fn.lower(state, batch)


def _make_compressed_train_step(
    policy: Policy,
    loss_fn: LossFn,
    donate: bool,
    plan: ParallelPlan | None,
    batch_transform: Callable[[dict], dict] | None,
    grad_compression,
    health=None,
    n_microbatches: int = 1,
    grad_clip: float | None = None,
):
    """shard_map train step with explicit bucketed, error-feedback
    compressed gradient sync (:mod:`tpuframe.parallel.compression`).

    Each data shard computes grads on its slice of the batch (grad-accum
    scans microbatches first and compresses ONCE per super-batch), the
    mean crosses the wire as int8/fp8 buckets with per-bucket scales,
    and:

    - stage 0: every shard applies the identical update to its
      replicated params;
    - stage 1/2: plan-sharded leaves take a compressed reduce-scatter,
      the optimizer updates only the owned slice against the plan's
      sharded state, and the f32 update is all-gathered back (the
      arXiv:2004.13336 pipeline, derived from
      ``ParallelPlan.update_shard_specs``);
    - stage 3: params additionally live fsdp-sharded BETWEEN steps
      (``plan.param_spec``): the step all-gathers them on entry
      (gather-on-use), runs the stage-1/2 sliced update against the
      full view, and re-slices the new params back to their storage
      shard on exit — the compressed wire is untouched, only the
      params' resting layout changes.

    ``grad_clip`` (a float) applies torch-style global-norm clipping to
    the *synced* gradient before the update, using the plan-global norm
    (sliced leaves psum across shards), so the scale is identical
    everywhere; the health sentinel still judges the RAW norm — a
    clipped-away spike is exactly what it must see.

    Metrics psum exactly (they're tiny).  Error feedback needs the
    ``TrainState.comms`` residual (``init_comms_state``); a state
    without one runs compressed-without-EF, loudly
    (``comms/ef_inactive``).

    **Overlapped flavor** (``plan.comms_groups`` > 1 or
    ``TPUFRAME_COMMS_GROUPS``): the sync fires as the layout's
    bucket-group schedule (reverse-backward order, one collective per
    group — see :func:`~tpuframe.parallel.compression.sync_gradients`),
    and the grad-accum path peels the last microbatch out of the scan
    so the groups overlap its open backward graph.  Pair with
    ``TPUFRAME_COMMS_ASYNC=1`` so XLA's latency-hiding scheduler
    actually moves the independent collectives into the compute gaps.
    Bit-exact against the single-shot step; the schedule rides
    ``comms/wire_plan`` as the ``overlap_groups``/``groups`` block.
    """
    from jax.sharding import PartitionSpec as P

    from tpuframe.parallel.compression import (
        CommsConfig,
        comms_template,
        grad_layout,
        resolve_fused,
        sync_gradients,
        wire_plan,
    )
    from tpuframe.parallel.sharding import path_str

    config = CommsConfig.from_env(grad_compression)
    assert config is not None  # caller checked grad_compression truthy
    if plan is None:
        raise ValueError("grad_compression needs a plan (its mesh and data axes)")
    # a pinned plan.comms_fused wins over the TPUFRAME_COMMS_FUSED env
    # (plan-first, like comms_groups); the resolved flag rides the plan
    # signature, so fused and staged programs get distinct AOT keys
    config = resolve_fused(plan, config)
    if plan.rules:
        raise ValueError(
            "grad_compression composes with DP and ZeRO-1/2/3 (the "
            "compressed step owns the whole gradient wire); TP/pipeline "
            "rules re-shard params inside the model and own their "
            "collectives — a second shard_map cannot nest inside the "
            f"compressed one (got rules={len(plan.rules)} on this plan)"
        )
    if plan.offload_optimizer:
        raise ValueError(
            "grad_compression does not compose with offload_optimizer: the "
            "compressed step's explicit collectives pin the optimizer "
            "state layout on device"
        )
    mesh = plan.mesh
    data_axes = tuple(a for a in plan.data_axes if mesh.shape[a] > 1) or tuple(
        plan.data_axes[:1]
    )

    def build(state: TrainState):
        from tpuframe.track.telemetry import get_telemetry

        layout = grad_layout(state.params, config, plan)
        expected = comms_template(state.params, config, plan)
        have = {
            path_str(p): tuple(leaf.shape)
            for p, leaf in jax.tree_util.tree_flatten_with_path(state.comms)[0]
        }
        ef = bool(expected) and bool(have)
        if ef and have != {k: tuple(v) for k, v in expected.items()}:
            raise ValueError(
                "TrainState.comms does not match this plan/config's EF "
                f"residual layout (have {have}, expected {expected}); "
                "re-initialize it with parallel.compression."
                "init_comms_state(params, plan, config)"
            )
        run_config = (
            config if ef or not config.error_feedback
            else dataclasses.replace(config, error_feedback=False)
        )
        wire = wire_plan(layout, run_config)
        tele = get_telemetry()
        if config.error_feedback and not ef:
            tele.event(
                "comms/ef_inactive",
                reason="TrainState.comms is empty — init_comms_state() "
                       "was never applied; running compressed without "
                       "error feedback",
            )
        tele.event(
            "comms/wire_plan",
            zero_stage=plan.zero_stage,
            error_feedback=ef,
            n_microbatches=n_microbatches,
            stochastic=run_config.stochastic_rounding,
            **wire,
        )
        sliced_dims = {path: dim for path, _, _, dim in layout.sliced}
        world = layout.world
        # ZeRO-3 gather-on-use: params REST fsdp-sharded (plan.param_spec)
        # and the step materializes the full view on entry / re-slices on
        # exit.  fsdp_dims maps each sharded leaf to its storage dim.
        fsdp_world = plan.axis_size(plan.fsdp_axis)
        fsdp_dims: dict[str, int] = {}
        if plan.zero_stage == 3 and fsdp_world > 1:
            for p, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
                spec = plan.param_spec(path_str(p), tuple(leaf.shape))
                for d, entry in enumerate(spec):
                    names = entry if isinstance(entry, tuple) else (entry,)
                    if plan.fsdp_axis in names:
                        fsdp_dims[path_str(p)] = d

        def gather_param(path, leaf):
            dim = fsdp_dims.get(path_str(path))
            if dim is None:
                return leaf
            return jax.lax.all_gather(leaf, plan.fsdp_axis, axis=dim, tiled=True)

        def scatter_param(path, leaf):
            dim = fsdp_dims.get(path_str(path))
            if dim is None:
                return leaf
            chunk = leaf.shape[dim] // fsdp_world
            i = jax.lax.axis_index(plan.fsdp_axis)
            return jax.lax.dynamic_slice_in_dim(leaf, i * chunk, chunk, axis=dim)

        def shard_step(state: TrainState, batch: Mapping[str, jax.Array]):
            if fsdp_dims:
                # gather-on-use: full params for forward/backward/update;
                # the steady-state HBM between steps holds only the shard
                state = state.replace(
                    params=jax.tree_util.tree_map_with_path(
                        gather_param, state.params
                    )
                )

            def _reslice(out):
                new_state, out_metrics = out
                if fsdp_dims:
                    new_state = new_state.replace(
                        params=jax.tree_util.tree_map_with_path(
                            scatter_param, new_state.params
                        )
                    )
                return new_state, out_metrics

            rng = state.step_rng("dropout")
            # decorrelate dropout across shards (params stay identical:
            # the synced gradient is what updates them)
            for ax in data_axes:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))

            if n_microbatches == 1:
                b = batch_transform(batch) if batch_transform else batch

                def compute_loss(params):
                    losses, logits, new_stats, aux, _ = _forward(
                        state, params, b, policy, True, rng, loss_fn
                    )
                    return (
                        jnp.mean(losses) + aux,
                        (jnp.mean(losses), logits, new_stats),
                    )

                (_, (loss, logits, new_stats)), grads = jax.value_and_grad(
                    compute_loss, has_aux=True
                )(state.params)
                metrics = _train_metrics(loss, logits, b["label"])
            else:
                # grad-accum composition: scan the microbatches, average
                # the accumulated gradient, compress ONCE per super-batch
                zero_grads = jax.tree.map(jnp.zeros_like, state.params)

                def micro(carry, scanned):
                    mb, micro_idx = scanned
                    if batch_transform is not None:
                        mb = batch_transform(mb)
                    grads_acc, stats, acc_metrics = carry
                    mb_rng = jax.random.fold_in(rng, micro_idx)

                    def compute_loss(params):
                        losses, logits, new_stats, aux, _ = _forward(
                            state.replace(batch_stats=stats),
                            params, mb, policy, True, mb_rng, loss_fn,
                        )
                        data_loss = jnp.mean(losses)
                        return data_loss + aux, (data_loss, logits, new_stats)

                    (_, (mloss, logits, new_stats)), g = jax.value_and_grad(
                        compute_loss, has_aux=True
                    )(state.params)
                    acc_metrics = jax.tree.map(
                        jnp.add, acc_metrics,
                        _train_metrics(mloss, logits, mb["label"]),
                    )
                    return (
                        jax.tree.map(jnp.add, grads_acc, g),
                        new_stats,
                        acc_metrics,
                    ), None

                init_metrics = {
                    "loss_sum": jnp.zeros(()),
                    "correct": jnp.zeros(()),
                    "count": jnp.zeros(()),
                }
                carry0 = (zero_grads, state.batch_stats, init_metrics)
                if layout.n_groups > 1:
                    # microbatch interleave: peel the LAST microbatch out
                    # of the scan and inline its VJP, so the grouped sync
                    # below depends on the scan result plus an OPEN
                    # backward graph — group i's collective needs only
                    # its own leaves' final grads and can go on the wire
                    # while the peeled VJP is still producing the rest.
                    # Addition order is the scan's exactly
                    # (((g0+g1)+...)+g_{n-1}), so grads are bit-identical
                    # to the unpeeled scan.
                    head = jax.tree.map(lambda x: x[:-1], batch)
                    carry, _ = jax.lax.scan(
                        micro, carry0, (head, jnp.arange(n_microbatches - 1))
                    )
                    tail = jax.tree.map(lambda x: x[-1], batch)
                    (grads, new_stats, metrics), _ = micro(
                        carry, (tail, jnp.int32(n_microbatches - 1))
                    )
                else:
                    (grads, new_stats, metrics), _ = jax.lax.scan(
                        micro, carry0, (batch, jnp.arange(n_microbatches))
                    )
                grads = jax.tree.map(lambda g: g / n_microbatches, grads)
                loss = metrics["loss_sum"] / jnp.maximum(metrics["count"], 1.0)

            # -- the wire: bucketed compressed sync (+EF residual) --
            srng = None
            if run_config.stochastic_rounding:
                srng = state.step_rng("comms")
                for ax in data_axes:
                    srng = jax.random.fold_in(srng, jax.lax.axis_index(ax))
            synced, new_comms = sync_gradients(
                grads, state.comms, layout, run_config, srng
            )
            # BN moments were computed shard-locally (torch-DDP
            # semantics); average the *updated running stats* so the
            # replicated state is deterministic rather than whichever
            # shard's copy wins assembly
            new_stats = jax.tree.map(
                lambda s: jax.lax.pmean(s, data_axes)
                if jnp.issubdtype(s.dtype, jnp.floating)
                else s,
                new_stats,
            )
            metrics = jax.tree.map(
                lambda m: jax.lax.psum(m, data_axes), metrics
            )
            gloss = jax.lax.pmean(loss, data_axes)

            if not sliced_dims:
                # stage 0 (or a plan too small to slice): identical full
                # mean grads on every shard
                raw_sq = None
                if grad_clip is not None:
                    raw_sq = sum(
                        jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                        for leaf in jax.tree.leaves(synced)
                    )
                    scale = jnp.minimum(
                        1.0, grad_clip / jnp.maximum(jnp.sqrt(raw_sq), 1e-12)
                    )
                    synced = jax.tree.map(lambda g: g * scale, synced)
                if health is None:
                    new_state = _apply_gradients(
                        state, synced, batch_stats=new_stats
                    ).replace(comms=new_comms)
                    return _reslice((new_state, metrics))
                # the verdict must be identical on every shard (params
                # are replicated and updated in lockstep): judge the
                # GLOBAL mean loss — the grads are already synced
                return _reslice(_apply_with_health(
                    state, synced, new_stats, gloss, metrics, health,
                    grad_sq=raw_sq,
                    extra_state={"comms": (state.comms, new_comms)},
                ))

            # -- stage 1/2: sharded optimizer update over owned slices --
            idx = jnp.int32(0)
            for ax in layout.axes:
                idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)

            def slice_leaf(path, leaf):
                dim = sliced_dims.get(path_str(path))
                if dim is None:
                    return leaf
                chunk = leaf.shape[dim] // world
                return jax.lax.dynamic_slice_in_dim(
                    leaf, idx * chunk, chunk, axis=dim
                )

            def gather_leaf(path, leaf):
                dim = sliced_dims.get(path_str(path))
                if dim is None:
                    return leaf
                return jax.lax.all_gather(
                    leaf, layout.axes, axis=dim, tiled=True
                )

            @jax.named_scope("tpuframe/optimizer")
            def zero_apply(grads_mixed):
                # opt_state arrived SLICED (the step's in_specs shard it
                # per update_shard_specs); update the owned slices, then
                # all-gather the f32 *update* onto the replicated params
                params_view = jax.tree_util.tree_map_with_path(
                    slice_leaf, state.params
                )
                updates, new_opt = state.tx.update(
                    grads_mixed, state.opt_state, params_view
                )
                full_updates = jax.tree_util.tree_map_with_path(
                    gather_leaf, updates
                )
                new_params = optax.apply_updates(state.params, full_updates)
                return state.replace(
                    step=state.step + 1,
                    params=new_params,
                    opt_state=new_opt,
                    batch_stats=new_stats,
                )

            # global grad norm: slices psum across shards, full leaves
            # (identical everywhere) added once — same scalar on every
            # shard, so the health verdict can't split the fleet
            sliced_sq = sum(
                jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                for p, leaf in jax.tree_util.tree_flatten_with_path(synced)[0]
                if path_str(p) in sliced_dims
            )
            full_sq = sum(
                jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                for p, leaf in jax.tree_util.tree_flatten_with_path(synced)[0]
                if path_str(p) not in sliced_dims
            )
            grad_sq = jax.lax.psum(sliced_sq, layout.axes) + full_sq
            if grad_clip is not None:
                # plan-global norm → identical scale on every shard
                # (torch clip_grad_norm_ semantics, never shard-local);
                # grad_sq stays RAW for the health verdict below
                scale = jnp.minimum(
                    1.0, grad_clip / jnp.maximum(jnp.sqrt(grad_sq), 1e-12)
                )
                synced = jax.tree.map(lambda g: g * scale, synced)
            if health is None:
                return _reslice(
                    (zero_apply(synced).replace(comms=new_comms), metrics)
                )
            return _reslice(_apply_with_health(
                state, synced, new_stats, gloss, metrics, health,
                apply_fn=zero_apply, grad_sq=grad_sq,
                extra_state={"comms": (state.comms, new_comms)},
            ))

        # -- specs: state fields replicated except the plan-sharded
        # optimizer slices and the per-shard EF residuals --
        param_shapes = {
            path_str(p): tuple(leaf.shape)
            for p, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
        }

        def opt_spec(path: str, shape: tuple):
            # longest param-path suffix match (mu/nu/EMA mirror params)
            parts = path.split("/")
            for start in range(len(parts)):
                suffix = "/".join(parts[start:])
                if suffix in param_shapes:
                    dim = sliced_dims.get(suffix)
                    if dim is not None and param_shapes[suffix] == tuple(shape):
                        entries = [None] * len(shape)
                        entries[dim] = layout.axes
                        return P(*entries)
                    return P()
            return P()

        def spec_assign(path, leaf):
            field = path_str(path[:1])
            rest = path_str(path[1:])
            if field == "comms":
                return P(layout.axes)
            if field == "params":
                dim = fsdp_dims.get(rest)
                if dim is not None:  # ZeRO-3 storage shard
                    entries = [None] * len(leaf.shape)
                    entries[dim] = plan.fsdp_axis
                    return P(*entries)
                return P()
            if field == "opt_state" and hasattr(leaf, "shape") and leaf.shape:
                return opt_spec(rest, tuple(leaf.shape))
            return P()

        state_specs = jax.tree_util.tree_map_with_path(spec_assign, state)
        batch_spec = P(data_axes)
        if n_microbatches > 1:
            batch_spec = P(None, *batch_spec)
        mapped = shard_map(
            shard_step,
            mesh=mesh,
            in_specs=(state_specs, batch_spec),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        return (
            jax.jit(mapped, donate_argnums=(0,) if donate else ()),
            wire,
        )

    return _wrap_offload(_CompressedStep(build), plan)


def make_eval_step(
    policy: Policy | None = None,
    loss_fn: LossFn = cross_entropy,
    plan: ParallelPlan | None = None,
    batch_transform: Callable[[dict], dict] | None = None,
) -> Callable[[TrainState, Mapping[str, jax.Array]], dict]:
    """Jitted eval step: (state, batch) -> summed metrics.

    ``batch["weight"]`` (0/1 per example) masks wrap-around-padded duplicates
    the DataLoader adds to equalize per-host counts — eval never double-counts
    (the reference's rank-0-only eval sidesteps this by not distributing eval
    at all, `01_basic_torch_distributor.py:302-323`)."""
    policy = policy or full_precision()
    batch_transform = _named_transform(batch_transform)
    loss_fn = _bind_loss(loss_fn, plan)

    def step(state: TrainState, batch: Mapping[str, jax.Array]):
        if batch_transform is not None:
            batch = batch_transform(batch)
        losses, logits, _, _, _ = _forward(
            state, state.params, batch, policy, False, None, loss_fn
        )
        labels = batch["label"]
        weight = batch.get("weight")
        if weight is None:
            weight = jnp.ones_like(losses)
        weight = weight.astype(jnp.float32)
        if weight.ndim < losses.ndim:  # per-example mask over per-token losses
            weight = weight.reshape(weight.shape + (1,) * (losses.ndim - weight.ndim))
        if logits is None:  # a model's own objective scores nothing else
            correct = jnp.zeros(())
        else:
            hard = jnp.argmax(labels, -1) if labels.ndim == logits.ndim else labels
            correct = jnp.sum(
                (jnp.argmax(logits, -1) == hard).astype(jnp.float32) * weight)
        return {
            "loss_sum": jnp.sum(losses * weight),
            "correct": correct,
            "count": jnp.sum(weight),
        }

    return jax.jit(step)


def make_predict_fn(
    policy: Policy | None = None,
    input_transform: Callable[[jax.Array], jax.Array] | None = None,
) -> Callable[[TrainState, jax.Array], jax.Array]:
    """Jitted logits fn for inference (the reference's ``predict_image``
    path, `02_cifar_torch_distributor_resnet.py:370-387`).

    ``input_transform`` runs inside the jitted program — the Trainer wires
    its ``normalize=`` transform here so inference sees the same
    preprocessing as training."""
    policy = policy or full_precision()

    def predict(state: TrainState, x: jax.Array) -> jax.Array:
        if input_transform is not None:
            x = input_transform(x)
        variables = {"params": policy.cast_params_for_compute(state.params)}
        if jax.tree.leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        logits = state.apply_fn(variables, policy.cast_batch(x), train=False)
        return policy.cast_outputs(logits)

    return jax.jit(predict)


def make_grad_accum_step(
    n_microbatches: int,
    policy: Policy | None = None,
    loss_fn: LossFn = cross_entropy,
    donate: bool = True,
    plan: ParallelPlan | None = None,
    batch_transform: Callable[[dict], dict] | None = None,
    health=None,
    grad_compression=None,
    grad_clip: float | None = None,
):
    """Gradient accumulation over leading-dim microbatches via ``lax.scan``.

    Batch arrays must be shaped (n_microbatches, micro_size, ...).  Grads are
    averaged across microbatches; BN stats roll forward through the scan.
    Replaces DeepSpeed's ``gradient_accumulation_steps: auto``
    (`/root/reference/02_deepspeed/deepspeed_config.py:17`).

    ``grad_compression`` composes: the scan accumulates the super-batch
    gradient first and the compressed sync runs ONCE per optimizer step
    (not per micro-step) — see :func:`_make_compressed_train_step`.
    """
    policy = policy or full_precision()
    batch_transform = _named_transform(batch_transform)
    if grad_compression is not None:
        # the step body runs inside shard_map there: the loss must stay
        # unbound (mesh=None), same as make_train_step's compressed path
        return _make_compressed_train_step(
            policy, loss_fn, donate, plan, batch_transform,
            grad_compression, health, n_microbatches, grad_clip=grad_clip,
        )
    if grad_clip is not None:
        raise ValueError(
            "grad_clip is a compressed-step parameter (the clip needs the "
            "plan-global synced norm); for the uncompressed step chain "
            "optax.clip_by_global_norm into tx instead"
        )
    loss_fn = _bind_loss(loss_fn, plan)

    def step(state: TrainState, batch: Mapping[str, jax.Array]):
        rng = state.step_rng("dropout")
        zero_grads = jax.tree.map(jnp.zeros_like, state.params)

        def micro(carry, scanned):
            mb, micro_idx = scanned
            # transform per microbatch: a whole-super-batch transform
            # before the scan would materialize the full float copy and
            # defeat grad-accum's memory purpose
            if batch_transform is not None:
                mb = batch_transform(mb)
            grads_acc, stats, metrics = carry
            # distinct dropout mask per microbatch — matching what the same
            # samples would draw as separate steps
            mb_rng = jax.random.fold_in(rng, micro_idx)

            def compute_loss(params):
                losses, logits, new_stats, aux, _ = _forward(
                    state.replace(batch_stats=stats),
                    params, mb, policy, True, mb_rng, loss_fn,
                )
                data_loss = jnp.mean(losses)
                return data_loss + aux, (data_loss, logits, new_stats)

            (_, (loss, logits, new_stats)), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params)
            metrics = jax.tree.map(
                jnp.add, metrics, _train_metrics(loss, logits, mb["label"])
            )
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            return (grads_acc, new_stats, metrics), None

        init_metrics = {
            "loss_sum": jnp.zeros(()),
            "correct": jnp.zeros(()),
            "count": jnp.zeros(()),
        }
        (grads, new_stats, metrics), _ = jax.lax.scan(
            micro,
            (zero_grads, state.batch_stats, init_metrics),
            (batch, jnp.arange(n_microbatches)),
        )
        grads = jax.tree.map(lambda g: g / n_microbatches, grads)
        if health is None:
            new_state = _apply_gradients(state, grads, batch_stats=new_stats)
            return new_state, metrics
        # the super-batch is the unit of update, so it is the unit of
        # health too: one NaN microbatch poisons the accumulated grads
        # (sum propagates it) and the whole step skips
        mean_loss = metrics["loss_sum"] / jnp.maximum(metrics["count"], 1.0)
        return _apply_with_health(
            state, grads, new_stats, mean_loss, metrics, health
        )

    return _wrap_offload(jax.jit(step, donate_argnums=(0,) if donate else ()), plan)


def merge_metrics(acc: dict | None, new: Mapping[str, jax.Array]) -> dict:
    """Host-side accumulation of summed metrics across steps."""
    new = {k: float(v) for k, v in new.items()}
    if acc is None:
        return new
    return {k: acc.get(k, 0.0) + v for k, v in new.items()}


def summarize_metrics(acc: Mapping[str, float], prefix: str = "") -> dict:
    """Summed metrics -> {loss, accuracy} means."""
    count = max(acc.get("count", 0.0), 1.0)
    out = {
        f"{prefix}loss": acc.get("loss_sum", 0.0) / count,
        f"{prefix}accuracy": acc.get("correct", 0.0) / count,
    }
    return out
