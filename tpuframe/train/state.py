"""Train state: one pytree holding everything a step updates.

Replaces the reference's scattered mutable objects — ``model`` +
``optimizer`` + implicit BN buffers inside torch modules
(`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:282-291`)
— with a single immutable :class:`TrainState` that jit can donate and a
ParallelPlan can shard leaf-by-leaf.  Checkpoints serialize exactly this
object (plus step metadata), which is what makes resume trivial.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import optax

from tpuframe.parallel.sharding import ParallelPlan
from tpuframe.track.telemetry import get_telemetry


def _any_host_resident(tree: Any) -> bool:
    """True if any leaf's (traced or concrete) aval sits in host memory."""
    host_space = jax.memory.Space.Host
    return any(
        getattr(getattr(leaf, "aval", None), "memory_space", None) == host_space
        for leaf in jax.tree.leaves(tree)
    )


class TrainState(flax.struct.PyTreeNode):
    """Params + optimizer state + mutable model collections + step counter.

    ``apply_fn``/``tx`` are static (not traced); everything else is data.
    ``batch_stats`` carries BatchNorm running statistics (flax's ``mutable``
    collection) — empty dict for stat-free models.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any
    rng: jax.Array
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    #: training-health sentinel state (``tpuframe.fault.health``): loss
    #: EWMA + bad-step bookkeeping, a plain-dict pytree of f32 scalars
    #: carried through the jitted step so spike detection is branch-free
    #: on device.  Deliberately NOT serialized into checkpoints
    #: (``ckpt._DATA_FIELDS``): a restore restarts the EWMA warmup on
    #: fresh ground, and pre-sentinel checkpoints stay restorable.
    health: Any = flax.struct.field(default_factory=dict)
    #: wire-compression error-feedback residuals
    #: (``tpuframe.parallel.compression.init_comms_state``): one
    #: full-size quantization residual per data-parallel shard, carried
    #: through the compressed train step (EF-SGD).  Empty dict when
    #: gradient compression (or error feedback) is off.  Unlike
    #: ``health``, this IS checkpointed when present — the residual is
    #: accumulated gradient mass, and dropping it on resume would lose
    #: exactly the updates EF was deferring; reshard-on-restore folds
    #: it onto a different world size (``ckpt.checkpoint``).
    comms: Any = flax.struct.field(default_factory=dict)

    def apply_gradients(self, grads: Any, **changes: Any) -> "TrainState":
        opt_state = self.opt_state
        if _any_host_resident(opt_state):
            # ZeRO-3 CPU offload (`deepspeed_config.py:87-105`): the state
            # lives in pinned host memory; stream it to HBM for the update.
            # The step wrapper (make_train_step) moves the new state back.
            opt_state = jax.tree.map(
                lambda x: jax.device_put(x, jax.memory.Space.Device), opt_state
            )
        updates, new_opt_state = self.tx.update(grads, opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            **changes,
        )

    def step_rng(self, name: str = "dropout") -> jax.Array:
        """Per-step, per-collection RNG derived from the state's base key.

        crc32, not ``hash()``: PYTHONHASHSEED randomizes ``hash`` per process,
        which would bake different fold-in constants into each host's compiled
        step and desynchronize nominally-replicated computation."""
        import zlib

        key = jax.random.fold_in(self.rng, self.step)
        return jax.random.fold_in(key, zlib.crc32(name.encode()) % (2**31))


def create_train_state(
    model: Any,
    rng: jax.Array,
    sample_input: jax.Array,
    tx: optax.GradientTransformation,
    plan: ParallelPlan | None = None,
    init_kwargs: dict | None = None,
) -> TrainState:
    """Initialize a TrainState, sharded per ``plan`` from the very first byte.

    With a plan, initialization runs under jit with ``out_shardings`` so
    ZeRO-3 params materialize *already sharded* — no single-device spike,
    the property DeepSpeed stage-3 buys with ``zero.Init()``.
    """
    init_kwargs = dict(init_kwargs or {})
    params_rng, dropout_rng, state_rng = jax.random.split(rng, 3)

    def init_fn():
        variables = model.init(
            {"params": params_rng, "dropout": dropout_rng},
            sample_input,
            **init_kwargs,
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        return params, batch_stats, tx.init(params)

    from tpuframe.fault.health import init_health_state

    step = jnp.zeros((), jnp.int32)
    health = init_health_state()
    # the jitted initialiser's trace, lowering, cache load or compile and
    # its dispatch (its compile records land under this span)
    with get_telemetry().span("setup/state_init"):
        if plan is None:
            params, batch_stats, opt_state = init_fn()
        else:
            a_params, a_stats, a_opt = jax.eval_shape(init_fn)
            shardings = (
                plan.param_shardings(a_params),
                plan.param_shardings(a_stats),
                # memory kinds are illegal in jit out_shardings; offload moves
                # the state to pinned host right after init
                plan.state_shardings(a_opt, a_params, with_offload=False),
            )
            params, batch_stats, opt_state = jax.jit(
                init_fn, out_shardings=shardings)()
            offloaded = plan.state_shardings(a_opt, a_params)
            if offloaded != shardings[2]:
                opt_state = jax.device_put(opt_state, offloaded)
            # Scalars must be *committed replicated* on the same mesh as the
            # params: a checkpoint restore reproduces the template's placement,
            # and a single-device committed step next to mesh-wide params is a
            # jit device mismatch.
            step = jax.device_put(step, plan.replicated())
            state_rng = jax.device_put(state_rng, plan.replicated())
            health = jax.device_put(health, plan.replicated())

    return TrainState(
        step=step,
        params=params,
        opt_state=opt_state,
        batch_stats=batch_stats,
        rng=state_rng,
        apply_fn=model.apply,
        tx=tx,
        health=health,
    )


def param_count(state_or_params: Any) -> int:
    params = getattr(state_or_params, "params", state_or_params)
    return sum(int(x.size) for x in jax.tree.leaves(params))
