"""Pipeline parallelism: SPMD GPipe over the ``pipe`` mesh axis.

Absent from the reference (SURVEY.md §2.2 marks PP "No"), but a
first-class tpuframe axis.  TPU-native design — no per-stage processes,
no send/recv graphs: every device runs the SAME program under
``shard_map``; stage identity is ``lax.axis_index('pipe')``, stage
weights are the slice of a layer-stacked parameter pytree sharded over
``pipe``, and activations hop stage->stage with ``lax.ppermute``
(nearest-neighbour ICI transfers).  The schedule is GPipe: M microbatches
fill the S-deep pipeline over M+S-1 ticks; reverse-mode AD through the
``lax.scan`` of ticks gives the backward pipeline automatically.

Bubble fraction is (S-1)/(M+S-1) — choose ``n_microbatches >> stages``.

Schedule choice (why GPipe + ``remat_stages`` rather than 1F1B): in this
SPMD formulation the backward pipeline comes from reverse-mode through
the tick scan, whose per-tick residuals with ``remat_stages=True`` are
just each tick's stage *input* — activation memory O(M · micro · L · D)
per device, the same order as non-pipelined rematerialized training.
1F1B's win over that is only the M/S factor on the stash; buying it
requires hand-scheduling interleaved forward/backward ticks under a
custom VJP (manual pipeline backprop with an O(S) recompute buffer),
whose complexity is not justified until profiling shows the stash —
not the bubble — is the binding constraint on real configs.

Two layers of API:

- :func:`gpipe_spmd` — the schedule primitive: (stage_fn, stacked params,
  (M, micro, ...) batch) -> (M, micro, ...) outputs.
- :class:`PipelinedTransformerLM` — a drop-in LM whose blocks run under
  the schedule (same math as ``TransformerLM`` with equal weights).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS, PIPELINE_AXIS
from tpuframe.parallel.comms_env import PP_SCHEDULE_CHOICES


@jax.custom_vjp
def _tick_barrier(xs):
    """``optimization_barrier`` with a gradient: identity math, but XLA
    may not move work across it.  ``lax.optimization_barrier`` has no
    autodiff rule, and the barriered schedule must be trainable (it is
    the serialized baseline arm of the schedule A/B) — the cotangents
    get the same barrier, pinning the backward hops to their tick
    boundaries too."""
    return lax.optimization_barrier(xs)


def _tick_barrier_fwd(xs):
    return lax.optimization_barrier(xs), None


def _tick_barrier_bwd(_, cts):
    return (lax.optimization_barrier(cts),)


_tick_barrier.defvjp(_tick_barrier_fwd, _tick_barrier_bwd)

#: The pipeline hop/compute interleave policies :func:`gpipe_spmd`
#: understands (resolved from ``ParallelPlan.pp_schedule`` /
#: ``TPUFRAME_PP_SCHEDULE``):
#:
#: - ``interleaved`` (default) — each tick's ``ppermute`` hop is
#:   dataflow-independent of the next tick's stage compute for every
#:   stage but the hop's consumer, so the latency-hiding scheduler slots
#:   the nearest-neighbour transfer behind compute (the PR-15 group-
#:   scheduler discipline applied to the pipeline wire).
#: - ``barriered`` — an ``optimization_barrier`` ties each hop to the
#:   tick boundary: hop-then-compute, strictly serialized.  Exists as
#:   the baseline arm of an A/B against ``interleaved``,
#:   not a production schedule.
#: - ``1f1b`` — interleaved hops plus per-tick stage rematerialization
#:   forced ON: the backward stash is bounded to each tick's stage
#:   *input* (the 1F1B-style O(S) stash bound this SPMD formulation can
#:   honestly buy — see the schedule-choice note above) regardless of
#:   the ``remat_stages`` flag.
#:
#: The tuple itself lives in the stdlib-only knob registry
#: (``comms_env.PP_SCHEDULE_CHOICES``) so doctor/aggregator can read it
#: from a jax-less process; this is the same object.
PP_SCHEDULES = PP_SCHEDULE_CHOICES


def gpipe_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh,
    axis: str = PIPELINE_AXIS,
    batch_axes: tuple = (DATA_AXIS, FSDP_AXIS),
    remat_stages: bool = False,
    schedule: str = "interleaved",
) -> jax.Array:
    """Run ``stage_fn`` as an S-stage GPipe pipeline over ``mesh[axis]``.

    Args:
      stage_fn: ``(params_s, y) -> y`` — one stage's computation; every
        stage must preserve the activation shape (transformer blocks do).
      stage_params: pytree whose leaves are stacked on a leading stage dim
        of size S = ``mesh.shape[axis]`` (sharded or shardable over it).
      x: microbatched input ``(M, micro, ...)``; ``M >= S`` required.
      batch_axes: mesh axes sharding the micro dim (dim 1).
      remat_stages: ``jax.checkpoint`` each stage call — the tick scan
        then saves only each tick's stage *input* instead of every
        intermediate inside the stage, cutting pipeline activation
        memory by roughly the stage depth at ~1/3 extra FLOPs (the
        standard trade for deep stages / long sequences).
      schedule: hop/compute interleave policy — one of
        :data:`PP_SCHEDULES`.  Every schedule computes the identical
        values (``barriered`` only constrains ordering; ``1f1b`` only
        changes what the backward stashes), so the A/B across schedules
        is bit-exact on outputs.

    Returns ``(M, micro, ...)`` outputs, numerically identical to applying
    stages 0..S-1 sequentially to each microbatch.
    """
    if schedule not in PP_SCHEDULES:
        raise ValueError(
            f"schedule must be one of {PP_SCHEDULES}, got {schedule!r}"
        )
    if remat_stages or schedule == "1f1b":
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = mesh.shape[axis] if axis in mesh.shape else 1
    if n_stages == 1:
        def seq(params, y):
            for s in range(jax.tree.leaves(stage_params)[0].shape[0]):
                y = stage_fn(jax.tree.map(lambda a: a[s], params), y)
            return y

        return jax.vmap(lambda mb: seq(stage_params, mb))(x)

    n_micro = x.shape[0]
    if n_micro < n_stages:
        raise ValueError(
            f"n_microbatches ({n_micro}) must be >= pipeline stages "
            f"({n_stages}); the pipeline can't even fill"
        )

    data_axes = tuple(a for a in batch_axes if a in mesh.shape and mesh.shape[a] > 1)
    x_spec = P(None, data_axes if data_axes else None, *([None] * (x.ndim - 2)))
    param_spec = jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params
    )

    def local(params_local, x_local):
        # params_local: this stage's slice, leading dim 1
        p = jax.tree.map(lambda a: a[0], params_local)
        s = lax.axis_index(axis)
        last = n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        state = jnp.zeros_like(x_local[0])  # activation entering this stage
        outputs = jnp.zeros_like(x_local)   # filled on the last stage

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t while t < M; later ticks drain
            feed = x_local[jnp.clip(t, 0, n_micro - 1)]
            y_in = jnp.where(s == 0, feed, state)
            y_out = stage_fn(p, y_in)
            # the last stage completes microbatch t-(S-1) at tick t
            done = t - last
            updated = lax.dynamic_update_index_in_dim(
                outputs, y_out, jnp.clip(done, 0, n_micro - 1), 0
            )
            outputs = jnp.where((s == last) & (done >= 0), updated, outputs)
            # hop: stage i's output becomes stage i+1's next input
            state = lax.ppermute(y_out, axis, perm)
            if schedule == "barriered":
                # pin the hop to the tick boundary: nothing in the next
                # tick may start until the transfer lands (the serialized
                # baseline the interleaved schedule is measured against)
                state, outputs = _tick_barrier((state, outputs))
            return (state, outputs), None

        (state, outputs), _ = lax.scan(
            tick, (state, outputs), jnp.arange(n_micro + n_stages - 1)
        )
        # outputs are only genuine on the last stage; psum replicates them
        # (every other stage contributes zeros)
        return lax.psum(jnp.where(s == last, outputs, 0.0), axis)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(param_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def stack_stage_params(per_stage: list) -> Any:
    """[stage0_params, stage1_params, ...] -> one pytree with a leading
    stage dim (what :func:`gpipe_spmd` consumes)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage)


def pipeline_param_spec(stage_params: Any, axis: str = PIPELINE_AXIS) -> Any:
    """PartitionSpec pytree placing the stage dim on the pipe axis."""
    return jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params
    )


@dataclasses.dataclass
class PipelinedTransformerLM:
    """Decoder LM with its blocks executed as a GPipe pipeline.

    Same math as :class:`tpuframe.models.TransformerLM` (pre-norm blocks,
    learned positions, weight-untied head) with layers grouped into
    ``mesh.shape['pipe']`` stages.  Duck-types the flax ``init``/``apply``
    contract so ``create_train_state``/``make_train_step`` work unchanged;
    the batch enters as ``(B, L)`` and is internally split into
    ``n_microbatches`` along B.

    num_layers must be divisible by the stage count; B by n_microbatches.
    """

    vocab_size: int
    num_layers: int = 4
    num_heads: int = 8
    head_dim: int = 32
    max_len: int = 2048
    mlp_ratio: int = 4
    #: microbatches per step; None resolves ``TPUFRAME_PP_MICROBATCHES``
    #: (falling back to 4) — an explicit value (or a composed plan's
    #: ``pp_microbatches`` pin threaded here) wins over the env
    n_microbatches: int | None = None
    dtype: Any = jnp.float32
    #: rematerialize each stage in the backward (see gpipe_spmd)
    remat: bool = False
    #: hop/compute interleave policy (one of ``PP_SCHEDULES``); None
    #: resolves ``TPUFRAME_PP_SCHEDULE`` (default ``interleaved``) — an
    #: explicit value (or a plan pin threaded here) wins over the env
    schedule: str | None = None

    def __post_init__(self):
        import flax.linen as nn

        d_model = self.num_heads * self.head_dim

        class EmbedHead(nn.Module):
            vocab: int
            max_len: int
            d: int
            dtype: Any

            def setup(self):
                self.embed = nn.Embed(self.vocab, self.d, dtype=self.dtype)
                self.pos_embed = nn.Embed(self.max_len, self.d, dtype=self.dtype)
                self.ln_f = nn.LayerNorm(dtype=self.dtype)
                self.lm_head = nn.Dense(
                    self.vocab, use_bias=False, dtype=self.dtype
                )

            def __call__(self, tokens):
                x = self.embed(tokens)
                return x + self.pos_embed(jnp.arange(tokens.shape[1])[None, :])

            def head(self, x):
                return self.lm_head(self.ln_f(x)).astype(jnp.float32)

        from tpuframe.models.transformer import Block

        self._embed_head = EmbedHead(
            vocab=self.vocab_size, max_len=self.max_len, d=d_model, dtype=self.dtype
        )
        # one Block module reused for every layer; per-layer weights come
        # from the stacked params (attention stays the XLA full path —
        # ring attention composes with PP via the seq axis inside blocks)
        self._block = Block(
            self.num_heads, self.head_dim, mlp_ratio=self.mlp_ratio,
            causal=True, attn_impl="full", dtype=self.dtype,
            ln_use_mesh=False,  # runs inside gpipe's shard_map already
        )

    # -- flax-like contract -------------------------------------------------
    def init(self, rngs, tokens, train: bool = False):
        params_rng = rngs["params"] if isinstance(rngs, dict) else rngs
        eh = self._embed_head.init(params_rng, tokens)["params"]
        # head params initialize lazily via init-with-method
        head_vars = self._embed_head.init(
            params_rng, jnp.zeros(
                (1, tokens.shape[1], self.num_heads * self.head_dim), self.dtype
            ),
            method=self._embed_head.head,
        )["params"]
        eh = {**eh, **head_vars}
        d_model = self.num_heads * self.head_dim
        sample = jnp.zeros((1, tokens.shape[1], d_model), self.dtype)
        keys = jax.random.split(params_rng, self.num_layers)
        per_layer = [
            self._block.init(keys[i], sample)["params"]
            for i in range(self.num_layers)
        ]
        blocks = stack_stage_params(per_layer)  # leading dim = num_layers
        return {"params": {"embed_head": eh, "blocks": blocks}}

    def apply(self, variables, tokens, train: bool = False, rngs=None):
        params = variables["params"]
        x = self._embed_head.apply({"params": params["embed_head"]}, tokens)

        from tpuframe.core.runtime import current_runtime

        mesh = current_runtime().mesh
        n_stages = mesh.shape.get(PIPELINE_AXIS, 1)
        if self.num_layers % max(n_stages, 1):
            raise ValueError(
                f"num_layers={self.num_layers} must divide into "
                f"{n_stages} pipeline stages"
            )
        layers_per_stage = self.num_layers // max(n_stages, 1)

        # regroup the layer-stacked params as (S, layers_per_stage, ...)
        blocks = jax.tree.map(
            lambda a: a.reshape((n_stages, layers_per_stage) + a.shape[1:]),
            params["blocks"],
        )

        def stage_fn(stage_p, y):
            for i in range(layers_per_stage):
                layer_p = jax.tree.map(lambda a: a[i], stage_p)
                y = self._block.apply({"params": layer_p}, y, train=train)
            return y

        from tpuframe.parallel.comms_env import pp_microbatches, pp_schedule

        b = x.shape[0]
        n_micro = (
            self.n_microbatches if self.n_microbatches is not None
            else (pp_microbatches() or 4)
        )
        m = min(n_micro, b)
        if b % m:
            raise ValueError(
                f"batch size {b} must be divisible by n_microbatches={m}"
            )
        micro = x.reshape((m, b // m) + x.shape[1:])
        out = gpipe_spmd(
            stage_fn, blocks, micro, mesh=mesh, remat_stages=self.remat,
            schedule=self.schedule or pp_schedule(),
        )
        x = out.reshape((b,) + out.shape[2:])
        return self._embed_head.apply(
            {"params": params["embed_head"]}, x, method=self._embed_head.head
        )
