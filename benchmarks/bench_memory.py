#!/usr/bin/env python
"""Memory-plane benchmark: estimator vs compiled truth vs live HBM.

Prices the same donated-state train step under three composed plans
(plain DP, ZeRO-1, ZeRO-3) three ways:

- **estimate** — ``parallel.plan_memory`` (stdlib math off the plan, no
  compile);
- **compiled** — the AOT executable's ``memory_analysis()`` peak
  (arguments + temps + outputs - aliased), recorded through
  ``track.memory.record_executable_memory`` so the run exercises the
  same ``memory/executable`` event + persisted record the trainer
  ships;
- **live** — the post-step device watermark (``memory_stats()``; absent
  on CPU, real on TPU — the committed CPU record carries null).

The record's ``memory`` block carries ``peak_executable_mb`` (and
``hbm_peak_mb`` when the backend reports device stats), so
``python -m tpuframe.track analyze --baseline benchmarks/results/``
regression-gates the footprint as ``ratio_peak_hbm`` exactly like step
time (exit 3): a plan whose peak ballooned fails CI even at flat speed.

CPU-friendly by design (``memory_analysis`` works on the CPU backend;
``memory_stats`` doesn't); on chip: not measured.

Usage: python benchmarks/bench_memory.py [--dim N] [--hidden N]
           [--batch N] [--steps N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

_MB = 1024 * 1024


def make_step(jnp, jax):
    def step(params, opt, batch):
        def loss_fn(p):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, opt["mu"], grads)
        nu = jax.tree.map(
            lambda v, g: 0.99 * v + 0.01 * g * g, opt["nu"], grads
        )
        new_p = jax.tree.map(
            lambda p, m, v: p - 1e-3 * m / (jnp.sqrt(v) + 1e-8),
            params, mu, nu,
        )
        return new_p, {"mu": mu, "nu": nu}, loss

    return step


def price_plan(name, plan, args, jax, jnp):
    """One plan, three sources of truth."""
    from tpuframe.parallel import plan_memory
    from tpuframe.track.memory import record_executable_memory

    d, h, b = args.dim, args.hidden, args.batch
    params = {
        "w1": jax.ShapeDtypeStruct((d, h), jnp.float32),
        "b1": jax.ShapeDtypeStruct((h,), jnp.float32),
        "w2": jax.ShapeDtypeStruct((h, d), jnp.float32),
    }
    opt = {"mu": dict(params), "nu": dict(params)}
    batch = {
        "x": jax.ShapeDtypeStruct((b, d), jnp.float32),
        "y": jax.ShapeDtypeStruct((b, d), jnp.float32),
    }

    est = plan_memory(plan, params, batch, opt_template=opt)

    p_sh = plan.param_shardings(params)
    o_sh = plan.state_shardings(opt, params, with_offload=False)
    b_sh = jax.tree.map(lambda _: plan.batch_sharding(), batch)
    sds = lambda t, sh: jax.tree.map(  # noqa: E731
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), t, sh
    )
    # out_shardings pinned to the plan: otherwise XLA picks its own
    # output layout and step N+1 can't feed step N's state back in
    scalar = jax.sharding.NamedSharding(plan.mesh, jax.sharding.PartitionSpec())
    compiled = jax.jit(
        make_step(jnp, jax), donate_argnums=(0, 1),
        out_shardings=(p_sh, o_sh, scalar),
    ).lower(
        sds(params, p_sh), sds(opt, o_sh), sds(batch, b_sh)
    ).compile()
    rec = record_executable_memory(compiled, f"bench_memory/{name}",
                                   persist=False)
    compiled_peak = rec["peak_mb"] if rec else None

    # live: run real steps through the executable and read the device
    # watermark (present on TPU/GPU; None on CPU)
    live_peak = None
    if args.steps > 0:
        import numpy as np

        from tpuframe.track.memory import peaks, reset_peaks, update_watermarks
        from tpuframe.track.system_metrics import _rss_mb, device_memory_stats

        rng = np.random.default_rng(0)
        mk = lambda l, s: jax.device_put(  # noqa: E731
            rng.standard_normal(l.shape, dtype=np.float32), s
        )
        p = jax.tree.map(mk, params, p_sh)
        o = jax.tree.map(mk, opt, o_sh)
        bt = jax.tree.map(mk, batch, b_sh)
        reset_peaks()
        for _ in range(args.steps):
            p, o, loss = compiled(p, o, bt)
            jax.block_until_ready(loss)
            update_watermarks(device_memory_stats(), _rss_mb())
        live_peak = peaks()["hbm_peak_mb"] or None

    out = {
        "signature": plan.signature(),
        "zero_stage": plan.zero_stage,
        "estimate_total_mb": est["per_device_mb"]["total"],
        "estimate": est["per_device_mb"],
        "compiled_peak_mb": compiled_peak,
        "live_peak_mb": live_peak,
    }
    if compiled_peak:
        out["est_over_compiled"] = round(
            est["per_device_mb"]["total"] / compiled_peak, 4
        )
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=3,
                    help="real steps per plan for the live watermark "
                         "(0 = static pricing only)")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or (
        "JAX_PLATFORMS" not in os.environ
        and not os.environ.get("TPU_NAME")
    ):
        from tpuframe.core.runtime import simulate_cpu_devices

        simulate_cpu_devices(8)

    import jax
    import jax.numpy as jnp

    # price REAL compiles: a persistent-cache hit deserializes the
    # executable without aliasing info, inflating peak by the donated
    # bytes (and the host-shared scratch cache outlives bench runs).
    # jax memoizes its is-the-cache-used verdict at first compile, so
    # reset it too in case the runtime hook already enabled the cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()

    from tpuframe.parallel import compose

    world = len(jax.devices())
    plans = {
        "dp": compose(),
        "zero1": compose(fsdp=world, dp=1, zero_stage=1),
        "zero3": compose(fsdp=world, dp=1, zero_stage=3),
    }
    per_plan = {
        name: price_plan(name, plan, args, jax, jnp)
        for name, plan in plans.items()
    }

    peak_exec = max(
        (p["compiled_peak_mb"] or 0.0 for p in per_plan.values()), default=0.0
    )
    live = max((p["live_peak_mb"] or 0.0 for p in per_plan.values()),
               default=0.0) or None
    ratios = [p["est_over_compiled"] for p in per_plan.values()
              if p.get("est_over_compiled")]
    rec = {
        "metric": "peak_executable_mb",
        "value": round(peak_exec, 3),
        "unit": (
            f"per-device compiled peak MB (MLP {args.dim}x{args.hidden}, "
            f"batch {args.batch}, adam, worst plan of "
            f"{'/'.join(per_plan)}, {jax.default_backend()})"
        ),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "world": world,
        "plans": per_plan,
        "worst_est_over_compiled": (
            round(max(ratios, key=lambda r: abs(r - 1.0)), 4)
            if ratios else None
        ),
        # the block baseline_diff gates on: ratio_peak_hbm regresses
        # (exit 3) when the footprint grows past threshold
        "memory": {
            "peak_executable_mb": round(peak_exec, 3),
            "hbm_peak_mb": round(live, 3) if live else None,
            "executables": {
                f"bench_memory/{name}": p["compiled_peak_mb"]
                for name, p in per_plan.items()
            },
            "ooms": 0,
        },
    }
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
