#!/usr/bin/env python
"""Wire-level collectives benchmark: bytes-on-wire + collective wall.

Prices the compressed gradient allreduce
(``tpuframe.parallel.compression``) against the exact f32 one at matched
step semantics:

- **bytes-on-wire** — the static per-step wire plan (ring model) for
  f32 vs int8/int8-EF/fp8 over the same gradient tree; the committed
  ``reduction_x`` is the headline EQuARX-style saving (int8 payloads ~4x
  under f32, minus bucket padding + scale traffic).
- **allreduce wall** — the standalone measured collective
  (``make_compressed_pmean``: ``comms/allreduce`` spans,
  ``comms/allreduce_s`` histogram) per mode, p50 over ``--iters`` calls.
  On CPU the quantize/dequantize arithmetic *costs* wall (no DCN to
  win back) — the honest number is the TPU one (on chip: not measured).
- **step time** — a short matched A/B fit of the SAME model/batches
  through ``make_train_step`` exact vs compressed (EF on), committed as
  ``step_time_compressed`` (deliberately NOT a top-level ``step_time``
  block: this record gates wire regressions via its ``comms`` block,
  not the fleet step-time baseline).

The committed record's ``comms`` block is what ``python -m
tpuframe.track analyze --baseline benchmarks/results/`` ratios future
runs against (``ratio_bytes_on_wire`` / ``ratio_allreduce_p50``,
exit 3 on regression).

``--overlap`` runs the other A/B this file owns: the SAME compressed
fit single-shot (one sync after backward) vs bucket-group scheduled
(``plan.comms_groups`` — the sync fires as N collectives in
reverse-backward order so group i's wire rides while group i+1's math
is still executing).  Both arms are AOT-compiled through the compile
spine (``precompile_call`` + ``ShapeGuard`` — the committed record
proves zero ``compile/recompile`` / ``compile/aot_fallback`` during the
fit), profiled with ``jax.profiler`` and parsed by
``device_time_report``; the headline is **exposed comms** (collective
wall NOT hidden behind compute) per step and ``overlap_efficiency``,
plus a bit-exact check of the synced gradients and EF residual across
arms (grouping must not change a single bit of the wire math; final
params drift only at the ulp level from XLA refusing the *optimizer*
arithmetic differently across the two programs).  The grouped
arm's parsed capture is committed as the record's top-level
``device_time`` block — the ``ratio_exposed_comms`` baseline the
analyzer gates future runs against.

``--fused`` runs the in-collective A/B: the SAME compressed fit (int8,
EF on) staged (quantize -> one psum -> dequantize) vs fused (the
payloads ride the backend-dispatched in-collective transport — the
ring reduce-scatter/all-gather hops on TPU, the single fused
all-reduce thunk on this CPU host; ``plan.comms_fused`` pins each arm,
so the env can't leak in).  Matched
payloads by construction: bytes-on-wire is INVARIANT under fusion (the
same quantized buckets cross the wire either way — the fused win is hop
granularity and the encode/decode staging, never wire bytes), and the
record says so.  Both arms AOT-compiled (zero
``compile/recompile``/``aot_fallback`` committed), synced grads + EF
residual compared bit-for-bit across arms, exposed comms measured per
arm off a parsed capture.  The committed record carries analyzer-
gateable ``step_time`` + ``comms`` + ``device_time`` blocks
(``ratio_p50`` / ``ratio_bytes_on_wire`` / ``ratio_exposed_comms``).

``--pipeline`` runs the schedule A/B the composed-parallelism plan pins
(``plan.pp_schedule``): the SAME pipelined-LM fit on a pipe x data mesh
with the ``interleaved`` schedule (``ppermute`` hops free to slot
between stage compute) vs ``barriered`` (an ``optimization_barrier``
pins every hop to its tick boundary — the serialized baseline).  Every
schedule computes identical values, so the single-apply logits are
compared bit-for-bit across arms; both arms AOT-compiled (zero
``compile/recompile``/``aot_fallback`` committed), exposed comms
measured per arm off a parsed capture.  The committed record carries
analyzer-gateable ``step_time`` + ``device_time`` blocks
(``ratio_p50`` / ``ratio_exposed_comms``), with the interleaved arm's
capture as the top-level ``device_time`` baseline anchor.

Usage: python benchmarks/bench_collectives.py [--payload-mb 8]
           [--iters 30] [--steps 30] [--json-only]
       python benchmarks/bench_collectives.py --overlap
           [--overlap-groups 4] [--overlap-steps 12] [--overlap-width 768]
       python benchmarks/bench_collectives.py --fused
           [--overlap-steps 12] [--overlap-width 768] [--bucket-mb 4]
       python benchmarks/bench_collectives.py --pipeline
           [--pipeline-steps 12] [--pipeline-microbatches 8]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def make_grad_tree(payload_mb: float, jnp, rng):
    """A transformer-ish gradient pytree totaling ~payload_mb MiB of f32:
    a few big matrices, several small vectors (the shape mix per-bucket
    scales exist for)."""
    total = int(payload_mb * (1 << 20) / 4)
    big = max(256, int((total * 0.96) ** 0.5))
    tree = {
        "layer0/kernel": rng.standard_normal((big, big)) * 0.05,
        "layer0/bias": rng.standard_normal((big,)) * 1e-3,
        "layer1/kernel": rng.standard_normal((big, max(8, total // big - big))) * 2.0,
        "layer1/bias": rng.standard_normal((max(8, total // big - big),)) * 1e-4,
        "norm/scale": rng.standard_normal((big,)),
    }
    return {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}


def time_collective(fn, tree, residual, iters: int) -> dict:
    walls = []
    out = None
    for _ in range(max(3, iters)):
        t0 = time.perf_counter()
        out, residual = fn(tree, residual)
        walls.append(time.perf_counter() - t0)
    walls = sorted(walls[2:])  # drop compile + warmup
    return {
        "p50_s": round(statistics.median(walls), 6),
        "min_s": round(walls[0], 6),
        "iters": len(walls),
    }, out


def time_steps(step, state, batches) -> list[float]:
    import jax

    walls = []
    for batch in batches:
        t0 = time.perf_counter()
        state, metrics = step(state, dict(batch))
        jax.block_until_ready(metrics)
        walls.append(time.perf_counter() - t0)
    return walls


def run_overlap(args) -> int:
    """The grouped-schedule A/B: single-shot sync vs bucket-group
    scheduled sync, same model, same batches, same seeds — exposed
    comms measured off a parsed profiler capture per arm, final params
    compared bit-for-bit."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn

    from tpuframe.compile.precompile import (
        ShapeGuard,
        abstract_state,
        batch_signature,
        precompile_call,
    )
    from tpuframe.core.runtime import MeshSpec
    from tpuframe.parallel import ParallelPlan
    from tpuframe.parallel.compression import (
        CommsConfig,
        comms_template,
        grad_layout,
        init_comms_state,
        make_compressed_pmean,
        wire_plan,
    )
    from tpuframe.track.device_time import device_time_report
    from tpuframe.track.profiler import trace
    from tpuframe.track.telemetry import get_telemetry
    from tpuframe.train import (
        create_train_state,
        make_grad_accum_step,
        make_train_step,
    )

    world = len(jax.devices())
    mesh = MeshSpec(data=world).build()
    width = int(args.overlap_width)
    n_steps = int(args.overlap_steps)
    accum = max(1, int(args.overlap_accum))
    warmup = 3

    class Net(nn.Module):
        """Deep enough that backward has real math for the wire to hide
        behind; wide enough that the gradient tree spans many buckets."""

        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            for _ in range(4):
                x = nn.relu(nn.Dense(width)(x))
            return nn.Dense(16)(x)

    config = CommsConfig(
        mode="int8", bucket_mb=args.bucket_mb, error_feedback=True
    )

    per_dev = int(args.overlap_batch)

    def mk_batches(plan, n):
        # grad-accum batches lead with the microbatch dim: the overlap
        # story IS the accum path (the peeled last microbatch's backward
        # is the compute the per-group collectives spread into)
        r = np.random.default_rng(7)
        out = []
        for _ in range(n):
            shape = (accum, per_dev * world) if accum > 1 else (per_dev * world,)
            img = r.standard_normal(shape + (16, 16, 1)).astype(np.float32)
            lab = r.integers(0, 16, shape).astype(np.int32)
            out.append(plan.shard_batch(
                {"image": img, "label": lab}, leading_microbatch=accum > 1,
            ))
        return out

    tele = get_telemetry()
    plan_single = ParallelPlan(mesh=mesh)
    plan_grouped = ParallelPlan(
        mesh=mesh, comms_groups=max(2, int(args.overlap_groups))
    )

    def mk_state(plan):
        s = create_train_state(
            Net(), jax.random.PRNGKey(0),
            jnp.ones((1, 16, 16, 1), jnp.float32), optax.adamw(1e-3),
            plan=plan,
        )
        return s.replace(comms=init_comms_state(s.params, plan, config))

    def bits_equal(a, b) -> bool:
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.asarray(x).tobytes() == np.asarray(y).tobytes()
            for x, y in zip(la, lb)
        )

    # the bit-exactness contract is on the SYNC: same params, same
    # grads, same residual -> the grouped schedule must produce the
    # identical mean gradient and EF residual, bit for bit.  (Full-fit
    # params drift at the ulp level because XLA fuses the *optimizer*
    # math differently across the two programs — FMA reassociation, not
    # schedule semantics; reported as a max-abs diff for honesty.)
    # Runs BEFORE the fits: the train step donates its state, so the
    # init params wouldn't survive an arm.
    s0 = mk_state(plan_single)

    def loss(params, img, lab):
        logits = s0.apply_fn({"params": params}, img)
        oh = jax.nn.one_hot(lab, 16)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    rr = np.random.default_rng(7)
    img = jnp.asarray(rr.standard_normal((16, 16, 16, 1)), jnp.float32)
    lab = jnp.asarray(rr.integers(0, 16, 16), jnp.int32)
    grads = jax.grad(loss)(s0.params, img, lab)
    resid = {
        k: jnp.zeros(v, jnp.float32)
        for k, v in comms_template(s0.params, config, plan_single).items()
    }
    o1, r1 = make_compressed_pmean(plan_single, config)(grads, resid)
    og, rg = make_compressed_pmean(plan_grouped, config)(grads, resid)
    bit_exact = bits_equal(o1, og)
    bit_exact_resid = bits_equal(r1, rg)
    del s0, grads, resid, o1, r1, og, rg

    def run_arm(plan) -> dict:
        groups = plan.comms_groups or 1
        if accum > 1:
            step = make_grad_accum_step(
                accum, plan=plan, grad_compression=config
            )
        else:
            step = make_train_step(plan=plan, grad_compression=config)
        state = mk_state(plan)
        batches = mk_batches(plan, warmup + n_steps)
        recompiles0 = tele.registry.counter("compile/recompiles").value
        compiled = precompile_call(
            step, (abstract_state(state), batches[0]),
            label=f"bench/overlap@groups{groups}",
        )
        # the Trainer's dispatch contract in miniature: armed guard +
        # AOT executable, jit fallback only on a loud event — the
        # committed zero counts are the no-recompile proof
        guard = ShapeGuard(tele)
        guard.expect("train", batch_signature(batches[0]))
        fallbacks = 0

        def dispatch(state, batch):
            nonlocal fallbacks
            guard.check("train", batch_signature(batch))
            if compiled is not None:
                try:
                    return compiled(state, batch)
                except Exception as e:
                    fallbacks += 1
                    tele.event(
                        "compile/aot_fallback", step_kind="train",
                        error=f"{type(e).__name__}: {e}"[:200],
                    )
            return step(state, batch)

        for b in batches[:warmup]:
            state, metrics = dispatch(state, b)
            jax.block_until_ready(metrics)
        walls = []
        logdir = tempfile.mkdtemp(prefix=f"tpuframe_overlap_g{groups}_")
        with trace(logdir):
            for b in batches[warmup:]:
                t0 = time.perf_counter()
                state, metrics = dispatch(state, b)
                jax.block_until_ready(metrics)
                walls.append(time.perf_counter() - t0)
            jax.block_until_ready(state)
        dt = device_time_report(logdir, steps=n_steps) or {}
        dt["trace_dir"] = None  # temp dir: gone by the time anyone reads this
        shutil_rmtree(logdir)
        wire = getattr(step, "wire", None) or wire_plan(
            grad_layout(state.params, config, plan), config
        )
        return {
            "groups": groups,
            "state": state,
            "wire": wire,
            "device_time": dt,
            "step_p50_s": round(statistics.median(sorted(walls)), 6),
            "recompile_events": int(
                tele.registry.counter("compile/recompiles").value
                - recompiles0
            ),
            "aot_fallback_events": fallbacks,
            "aot_dispatch": compiled is not None,
        }

    single = run_arm(plan_single)
    grouped = run_arm(plan_grouped)
    params_drift = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(single["state"].params),
            jax.tree.leaves(grouped["state"].params),
        )
    )

    def arm_rec(arm: dict) -> dict:
        dt = arm["device_time"]
        return {
            "groups": arm["groups"],
            "step_p50_s": arm["step_p50_s"],
            "exposed_comms_per_step_s": dt.get("exposed_comms_per_step_s"),
            "overlap_efficiency": dt.get("overlap_efficiency"),
            "collective_wall_s": (
                (dt.get("classes") or {}).get("collective") or {}
            ).get("wall_s"),
            "recompile_events": arm["recompile_events"],
            "aot_fallback_events": arm["aot_fallback_events"],
            "aot_dispatch": arm["aot_dispatch"],
        }

    se = single["device_time"].get("exposed_comms_per_step_s") or 0.0
    ge = grouped["device_time"].get("exposed_comms_per_step_s") or 0.0
    rec = {
        "benchmark": "collectives_overlap",
        "backend": jax.default_backend(),
        "world": world,
        "mode": "int8_ef",
        "model_params_mb": round(
            sum(int(x.size) for x in jax.tree.leaves(single["state"].params))
            * 4 / (1 << 20), 3,
        ),
        "steps_per_arm": n_steps,
        "overlap": {
            "single": arm_rec(single),
            "grouped": arm_rec(grouped),
            "bit_exact_synced_grads": bit_exact,
            "bit_exact_ef_residual": bit_exact_resid,
            "final_params_max_abs_diff": params_drift,
            "exposed_reduction_x": (
                round(se / ge, 3) if se and ge else None
            ),
        },
        "wire": {
            k: grouped["wire"].get(k)
            for k in ("mode", "world", "n_buckets", "bucket_elems",
                      "bytes_per_step", "overlap_groups", "groups")
        },
        # the analyzer's ratio_exposed_comms baseline anchor — the
        # grouped arm IS the configuration this record recommends
        "device_time": grouped["device_time"],
    }
    print(json.dumps(rec, indent=1))
    ok = (
        bit_exact
        and bit_exact_resid
        and grouped["recompile_events"] == 0
        and grouped["aot_fallback_events"] == 0
    )
    return 0 if ok else 4


def run_fused(args) -> int:
    """The in-collective A/B: staged wire vs the fused transport (form
    backend-dispatched — ring on TPU, single thunk on CPU), same
    model, same batches, same seeds — each arm pinned by
    ``plan.comms_fused`` so the comparison can't be skewed by env.  The
    contract under test is the tentpole's: fusing the transport changes
    WHERE the payloads cross the wire, never a bit of what arrives."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn

    from tpuframe.compile.precompile import (
        ShapeGuard,
        abstract_state,
        batch_signature,
        precompile_call,
    )
    from tpuframe.core.runtime import MeshSpec
    from tpuframe.parallel import ParallelPlan
    from tpuframe.parallel.compression import (
        CommsConfig,
        comms_template,
        grad_layout,
        init_comms_state,
        make_compressed_pmean,
        wire_plan,
    )
    from tpuframe.track.device_time import device_time_report
    from tpuframe.track.profiler import trace
    from tpuframe.track.telemetry import get_telemetry
    from tpuframe.train import (
        create_train_state,
        make_grad_accum_step,
        make_train_step,
    )

    world = len(jax.devices())
    mesh = MeshSpec(data=world).build()
    width = int(args.overlap_width)
    n_steps = int(args.overlap_steps)
    per_dev = int(args.overlap_batch)
    accum = max(1, int(args.overlap_accum))
    warmup = 3

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            for _ in range(4):
                x = nn.relu(nn.Dense(width)(x))
            return nn.Dense(16)(x)

    config = CommsConfig(
        mode="int8", bucket_mb=args.bucket_mb, error_feedback=True
    )
    tele = get_telemetry()
    plan_staged = ParallelPlan(mesh=mesh, comms_fused=False)
    plan_fused = ParallelPlan(mesh=mesh, comms_fused=True)

    def mk_state(plan):
        s = create_train_state(
            Net(), jax.random.PRNGKey(0),
            jnp.ones((1, 16, 16, 1), jnp.float32), optax.adamw(1e-3),
            plan=plan,
        )
        return s.replace(comms=init_comms_state(s.params, plan, config))

    def mk_batches(plan, n):
        # grad-accum batches: the hop-granularity story needs backward
        # compute for the per-hop sends to hide behind — same shape as
        # the overlap A/B
        r = np.random.default_rng(7)
        out = []
        for _ in range(n):
            shape = (accum, per_dev * world) if accum > 1 else (per_dev * world,)
            img = r.standard_normal(shape + (16, 16, 1)).astype(np.float32)
            lab = r.integers(0, 16, shape).astype(np.int32)
            out.append(plan.shard_batch(
                {"image": img, "label": lab}, leading_microbatch=accum > 1,
            ))
        return out

    def bits_equal(a, b) -> bool:
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.asarray(x).tobytes() == np.asarray(y).tobytes()
            for x, y in zip(la, lb)
        )

    # the bit-exactness contract is on the SYNC: same params, same
    # grads, same residual -> the fused transport must hand back the
    # identical mean gradient and EF residual, bit for bit.  Runs
    # BEFORE the fits (the train step donates its state).
    s0 = mk_state(plan_staged)

    def loss(params, img, lab):
        logits = s0.apply_fn({"params": params}, img)
        oh = jax.nn.one_hot(lab, 16)
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), -1))

    rr = np.random.default_rng(7)
    img = jnp.asarray(rr.standard_normal((16, 16, 16, 1)), jnp.float32)
    lab = jnp.asarray(rr.integers(0, 16, 16), jnp.int32)
    grads = jax.grad(loss)(s0.params, img, lab)
    resid = {
        k: jnp.zeros(v, jnp.float32)
        for k, v in comms_template(s0.params, config, plan_staged).items()
    }
    os_, rs_ = make_compressed_pmean(plan_staged, config)(grads, resid)
    of_, rf_ = make_compressed_pmean(plan_fused, config)(grads, resid)
    bit_exact = bits_equal(os_, of_)
    bit_exact_resid = bits_equal(rs_, rf_)
    del os_, rs_, of_, rf_

    # standalone collective wall per arm on the model's own gradients —
    # the comms.allreduce_s the analyzer ratios
    ar_staged, _ = time_collective(
        make_compressed_pmean(plan_staged, config), grads, resid, 10)
    ar_fused, _ = time_collective(
        make_compressed_pmean(plan_fused, config), grads, resid, 10)
    del s0, grads, resid

    def run_arm(plan, tag: str) -> dict:
        if accum > 1:
            step = make_grad_accum_step(
                accum, plan=plan, grad_compression=config
            )
        else:
            step = make_train_step(plan=plan, grad_compression=config)
        state = mk_state(plan)
        batches = mk_batches(plan, warmup + n_steps)
        recompiles0 = tele.registry.counter("compile/recompiles").value
        compiled = precompile_call(
            step, (abstract_state(state), batches[0]),
            label=f"bench/fused@{tag}",
        )
        guard = ShapeGuard(tele)
        guard.expect("train", batch_signature(batches[0]))
        fallbacks = 0

        def dispatch(state, batch):
            nonlocal fallbacks
            guard.check("train", batch_signature(batch))
            if compiled is not None:
                try:
                    return compiled(state, batch)
                except Exception as e:
                    fallbacks += 1
                    tele.event(
                        "compile/aot_fallback", step_kind="train",
                        error=f"{type(e).__name__}: {e}"[:200],
                    )
            return step(state, batch)

        for b in batches[:warmup]:
            state, metrics = dispatch(state, b)
            jax.block_until_ready(metrics)
        walls = []
        logdir = tempfile.mkdtemp(prefix=f"tpuframe_fused_{tag}_")
        with trace(logdir):
            for b in batches[warmup:]:
                t0 = time.perf_counter()
                state, metrics = dispatch(state, b)
                jax.block_until_ready(metrics)
                walls.append(time.perf_counter() - t0)
            jax.block_until_ready(state)
        dt = device_time_report(logdir, steps=n_steps) or {}
        dt["trace_dir"] = None
        shutil_rmtree(logdir)
        walls = sorted(walls)
        wire = getattr(step, "wire", None) or wire_plan(
            grad_layout(state.params, config, plan), config
        )
        return {
            "tag": tag,
            "state": state,
            "wire": wire,
            "walls": walls,
            "device_time": dt,
            "step_p50_s": round(statistics.median(walls), 6),
            "recompile_events": int(
                tele.registry.counter("compile/recompiles").value
                - recompiles0
            ),
            "aot_fallback_events": fallbacks,
            "aot_dispatch": compiled is not None,
        }

    staged = run_arm(plan_staged, "staged")
    fused = run_arm(plan_fused, "fused")
    params_drift = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(staged["state"].params),
            jax.tree.leaves(fused["state"].params),
        )
    )

    def arm_rec(arm: dict) -> dict:
        dt = arm["device_time"]
        return {
            "fused": arm["tag"] == "fused",
            "step_p50_s": arm["step_p50_s"],
            "exposed_comms_per_step_s": dt.get("exposed_comms_per_step_s"),
            "overlap_efficiency": dt.get("overlap_efficiency"),
            "collective_wall_s": (
                (dt.get("classes") or {}).get("collective") or {}
            ).get("wall_s"),
            "recompile_events": arm["recompile_events"],
            "aot_fallback_events": arm["aot_fallback_events"],
            "aot_dispatch": arm["aot_dispatch"],
        }

    se = staged["device_time"].get("exposed_comms_per_step_s") or 0.0
    fe = fused["device_time"].get("exposed_comms_per_step_s") or 0.0
    fw = fused["wire"]
    walls = fused["walls"]
    rec = {
        "benchmark": "collectives_fused",
        "backend": jax.default_backend(),
        "world": world,
        "mode": "int8_ef",
        "model_params_mb": round(
            sum(int(x.size) for x in jax.tree.leaves(fused["state"].params))
            * 4 / (1 << 20), 3,
        ),
        "steps_per_arm": n_steps,
        "fused_ab": {
            "staged": arm_rec(staged),
            "fused": arm_rec(fused),
            "bit_exact_synced_grads": bit_exact,
            "bit_exact_ef_residual": bit_exact_resid,
            "final_params_max_abs_diff": params_drift,
            "allreduce_p50_staged_s": ar_staged["p50_s"],
            "allreduce_p50_fused_s": ar_fused["p50_s"],
            # <= 1.0 means fused exposed no more collective wall than
            # staged — the number the acceptance bar reads
            "exposed_ratio_fused_vs_staged": (
                round(fe / se, 3) if se and fe else None
            ),
        },
        # bytes are INVARIANT under fusion — committed so a future run
        # that breaks the invariant (fused padding leaking onto the
        # wire) diffs loudly instead of silently
        "bytes_on_wire": {
            "f32_bytes_per_step": fw.get("f32_bytes_per_step"),
            "bytes_per_step": fw.get("bytes_per_step"),
            "reduction_x": fw.get("reduction_x"),
            "invariant_under_fusion": (
                staged["wire"].get("bytes_per_step")
                == fw.get("bytes_per_step")
            ),
            "fused_hops": fw.get("fused_hops"),
        },
        # the fused arm IS the configuration this record recommends:
        # its step distribution + capture are the baselines the
        # analyzer gates against (ratio_p50 / ratio_exposed_comms)
        "step_time": {
            "p50": round(statistics.median(walls), 6),
            "p95": round(walls[max(0, int(len(walls) * 0.95) - 1)], 6),
            "count": len(walls),
        },
        "comms": {
            "mode": "int8",
            "error_feedback": True,
            "fused": True,
            "bytes_per_step": fw.get("bytes_per_step"),
            "f32_bytes_per_step": fw.get("f32_bytes_per_step"),
            "reduction_x": fw.get("reduction_x"),
            "allreduce_s": {"p50": ar_fused["p50_s"]},
        },
        "wire": {
            k: fw.get(k)
            for k in ("mode", "world", "n_buckets", "bucket_elems",
                      "bytes_per_step", "fused", "fused_hops")
        },
        "device_time": fused["device_time"],
    }
    print(json.dumps(rec, indent=1))
    ok = (
        bit_exact
        and bit_exact_resid
        and staged["recompile_events"] == 0
        and fused["recompile_events"] == 0
        and staged["aot_fallback_events"] == 0
        and fused["aot_fallback_events"] == 0
    )
    return 0 if ok else 4


def run_pipeline(args) -> int:
    """The pipeline-schedule A/B: interleaved hop/compute vs barriered
    hop-then-compute on a pipe x data mesh, same composed plan shape,
    same model, same batches, same seeds — exposed comms measured off a
    parsed profiler capture per arm, single-apply logits compared
    bit-for-bit across schedules."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe.compile.precompile import (
        ShapeGuard,
        abstract_state,
        batch_signature,
        precompile_call,
    )
    from tpuframe.core import runtime as rt
    from tpuframe.core.runtime import MeshSpec
    from tpuframe.parallel import PipelinedTransformerLM
    from tpuframe.parallel.compose import compose
    from tpuframe.track.device_time import device_time_report
    from tpuframe.track.profiler import trace
    from tpuframe.track.telemetry import get_telemetry
    from tpuframe.train import create_train_state, make_train_step

    n_steps = int(args.pipeline_steps)
    n_micro = int(args.pipeline_microbatches)
    warmup = 3
    vocab, layers, heads, head_dim, seq = 256, 4, 4, 32, 128
    batch = 16

    # the pipelined LM reads its stage count from the process runtime
    rt.reset_runtime()
    runtime = rt.initialize(MeshSpec(pipe=4, data=-1))
    world = runtime.device_count
    tele = get_telemetry()

    def mk_plan(schedule):
        return compose(
            mesh=runtime.mesh, pp=4, microbatches=n_micro,
            schedule=schedule, min_shard_elems=1024,
        )

    def mk_model(plan):
        return PipelinedTransformerLM(
            vocab_size=vocab, num_layers=layers, num_heads=heads,
            head_dim=head_dim, max_len=seq,
            n_microbatches=plan.pp_microbatches, schedule=plan.pp_schedule,
        )

    def mk_state(plan):
        return create_train_state(
            mk_model(plan), jax.random.PRNGKey(0),
            jnp.zeros((1, seq), jnp.int32), optax.adamw(1e-3), plan=plan,
        )

    def mk_batches(plan, n):
        r = np.random.default_rng(7)
        out = []
        for _ in range(n):
            toks = r.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
            out.append(plan.shard_batch(
                {"input": toks[:, :-1], "label": toks[:, 1:]}
            ))
        return out

    def bits_equal(a, b) -> bool:
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.asarray(x).tobytes() == np.asarray(y).tobytes()
            for x, y in zip(la, lb)
        )

    # the bit-exactness contract is on the SCHEDULE: every schedule
    # computes the identical values (barriered only constrains ordering),
    # so one forward apply must agree bit-for-bit across arms.  Runs
    # BEFORE the fits: the train step donates its state.
    plan_i, plan_b = mk_plan("interleaved"), mk_plan("barriered")
    probe = mk_state(plan_i)
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, vocab, (batch, seq)), jnp.int32
    )
    logits_i = mk_model(plan_i).apply({"params": probe.params}, toks)
    logits_b = mk_model(plan_b).apply({"params": probe.params}, toks)
    bit_exact = bits_equal(logits_i, logits_b)
    n_params = sum(int(x.size) for x in jax.tree.leaves(probe.params))
    del probe, logits_i, logits_b

    def run_arm(plan) -> dict:
        schedule = plan.pp_schedule
        step = make_train_step(plan=plan)
        state = mk_state(plan)
        batches = mk_batches(plan, warmup + n_steps)
        recompiles0 = tele.registry.counter("compile/recompiles").value
        compiled = precompile_call(
            step, (abstract_state(state), batches[0]),
            label=f"bench/pipeline@{schedule}",
        )
        guard = ShapeGuard(tele)
        guard.expect("train", batch_signature(batches[0]))
        fallbacks = 0

        def dispatch(state, batch):
            nonlocal fallbacks
            guard.check("train", batch_signature(batch))
            if compiled is not None:
                try:
                    return compiled(state, batch)
                except Exception as e:
                    fallbacks += 1
                    tele.event(
                        "compile/aot_fallback", step_kind="train",
                        error=f"{type(e).__name__}: {e}"[:200],
                    )
            return step(state, batch)

        for b in batches[:warmup]:
            state, metrics = dispatch(state, b)
            jax.block_until_ready(metrics)
        walls = []
        logdir = tempfile.mkdtemp(prefix=f"tpuframe_pipeline_{schedule}_")
        with trace(logdir):
            for b in batches[warmup:]:
                t0 = time.perf_counter()
                state, metrics = dispatch(state, b)
                jax.block_until_ready(metrics)
                walls.append(time.perf_counter() - t0)
            jax.block_until_ready(state)
        dt = device_time_report(logdir, steps=n_steps) or {}
        dt["trace_dir"] = None  # temp dir: gone by the time anyone reads this
        shutil_rmtree(logdir)
        return {
            "schedule": schedule,
            "state": state,
            "device_time": dt,
            "step_p50_s": round(statistics.median(sorted(walls)), 6),
            "recompile_events": int(
                tele.registry.counter("compile/recompiles").value
                - recompiles0
            ),
            "aot_fallback_events": fallbacks,
            "aot_dispatch": compiled is not None,
        }

    inter = run_arm(plan_i)
    barr = run_arm(plan_b)
    params_drift = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(inter["state"].params),
            jax.tree.leaves(barr["state"].params),
        )
    )

    def arm_rec(arm: dict) -> dict:
        dt = arm["device_time"]
        return {
            "schedule": arm["schedule"],
            "step_p50_s": arm["step_p50_s"],
            "exposed_comms_per_step_s": dt.get("exposed_comms_per_step_s"),
            "overlap_efficiency": dt.get("overlap_efficiency"),
            "collective_wall_s": (
                (dt.get("classes") or {}).get("collective") or {}
            ).get("wall_s"),
            "recompile_events": arm["recompile_events"],
            "aot_fallback_events": arm["aot_fallback_events"],
            "aot_dispatch": arm["aot_dispatch"],
        }

    ie = inter["device_time"].get("exposed_comms_per_step_s") or 0.0
    be = barr["device_time"].get("exposed_comms_per_step_s") or 0.0
    rec = {
        "benchmark": "pipeline_schedule",
        "backend": jax.default_backend(),
        "world": world,
        "topology": {"pipe": 4, "data": world // 4},
        "model": {
            "vocab": vocab, "layers": layers, "d_model": heads * head_dim,
            "seq_len": seq, "microbatches": n_micro,
            "params_mb": round(n_params * 4 / (1 << 20), 3),
        },
        "steps_per_arm": n_steps,
        "pipeline": {
            "interleaved": arm_rec(inter),
            "barriered": arm_rec(barr),
            "bit_exact_logits": bit_exact,
            "final_params_max_abs_diff": params_drift,
            "exposed_reduction_x": (
                round(be / ie, 3) if be and ie else None
            ),
        },
        # the fleet step-time baseline block (ratio_p50): the
        # interleaved arm IS the configuration this record recommends
        "step_time": {
            "p50_s": inter["step_p50_s"],
            "barriered_p50_s": barr["step_p50_s"],
            "steps": n_steps,
        },
        # the analyzer's ratio_exposed_comms baseline anchor
        "device_time": inter["device_time"],
    }
    print(json.dumps(rec, indent=1))
    ok = (
        bit_exact
        and inter["recompile_events"] == 0
        and inter["aot_fallback_events"] == 0
        and barr["recompile_events"] == 0
        and barr["aot_fallback_events"] == 0
    )
    return 0 if ok else 4


def shutil_rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--payload-mb", type=float, default=8.0)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--steps", type=int, default=30,
                    help="matched A/B train steps per arm")
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--overlap", action="store_true",
                    help="run the bucket-group overlap A/B instead")
    ap.add_argument("--fused", action="store_true",
                    help="run the staged-vs-in-collective wire A/B instead")
    ap.add_argument("--overlap-groups", type=int, default=4)
    ap.add_argument("--overlap-steps", type=int, default=12)
    ap.add_argument("--overlap-width", type=int, default=768)
    ap.add_argument("--overlap-batch", type=int, default=8,
                    help="per-device samples per microbatch per overlap step")
    ap.add_argument("--overlap-accum", type=int, default=4,
                    help="microbatches per overlap step (1 = plain step)")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the pipeline-schedule A/B instead")
    ap.add_argument("--pipeline-steps", type=int, default=12)
    ap.add_argument("--pipeline-microbatches", type=int, default=8)
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or (
        "JAX_PLATFORMS" not in os.environ
        and not os.environ.get("TPU_NAME")
    ):
        from tpuframe.core.runtime import simulate_cpu_devices

        simulate_cpu_devices(8)

    if args.overlap:
        return run_overlap(args)
    if args.fused:
        return run_fused(args)
    if args.pipeline:
        return run_pipeline(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tpuframe.core.runtime import MeshSpec
    from tpuframe.parallel import ParallelPlan
    from tpuframe.parallel.compression import (
        CommsConfig,
        comms_template,
        grad_layout,
        init_comms_state,
        make_compressed_pmean,
        wire_plan,
    )

    world = len(jax.devices())
    mesh = MeshSpec(data=world).build()
    plan = ParallelPlan(mesh=mesh)
    rng = np.random.default_rng(0)
    tree = make_grad_tree(args.payload_mb, jnp, rng)
    n_elems = sum(int(x.size) for x in jax.tree.leaves(tree))

    rec: dict = {
        "backend": jax.default_backend(),
        "world": world,
        "payload_mb": round(n_elems * 4 / (1 << 20), 3),
        "modes": {},
    }

    # exact f32 pmean — the uncompressed control, same call shape
    exact = jax.jit(shard_map(
        lambda t: jax.tree.map(lambda g: jax.lax.pmean(g, ("data",)), t),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))
    f32_wall, exact_out = time_collective(
        lambda t, r: (exact(t), r), tree, {}, args.iters
    )
    base_layout = grad_layout(tree, CommsConfig(bucket_mb=args.bucket_mb), plan)
    f32_bytes = wire_plan(
        base_layout, CommsConfig(bucket_mb=args.bucket_mb)
    )["f32_bytes_per_step"]
    rec["modes"]["f32"] = {"bytes_per_step": f32_bytes, **f32_wall}

    for mode, ef in (("int8", False), ("int8", True), ("fp8", True)):
        name = f"{mode}_ef" if ef else mode
        config = CommsConfig(
            mode=mode, bucket_mb=args.bucket_mb, error_feedback=ef
        )
        residual = (
            {
                k: jnp.zeros(s, jnp.float32)
                for k, s in comms_template(tree, config, plan).items()
            }
            if ef else {}
        )
        fn = make_compressed_pmean(plan, config)
        wall, out = time_collective(fn, tree, residual, args.iters)
        wp = wire_plan(grad_layout(tree, config, plan), config)
        err = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(exact_out))
        )
        rec["modes"][name] = {
            "bytes_per_step": wp["bytes_per_step"],
            "reduction_x": wp["reduction_x"],
            "n_buckets": wp["n_buckets"],
            "max_abs_err_vs_f32": round(err, 8),
            **wall,
        }

    int8_ef = rec["modes"]["int8_ef"]
    rec["bytes_on_wire"] = {
        "f32_bytes_per_step": f32_bytes,
        "int8_ef_bytes_per_step": int8_ef["bytes_per_step"],
        "reduction_x": round(f32_bytes / int8_ef["bytes_per_step"], 3),
    }

    # matched A/B step semantics: same model, same batches, exact vs
    # compressed train step (EF on)
    from flax import linen as nn

    from tpuframe.train import create_train_state, make_train_step

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Dense(256)(x.reshape((x.shape[0], -1)))
            x = nn.relu(x)
            return nn.Dense(16)(x)

    def mk_state(config=None):
        s = create_train_state(
            Net(), jax.random.PRNGKey(0),
            jnp.ones((1, 16, 16, 1), jnp.float32), optax.adamw(1e-3),
            plan=plan,
        )
        if config is not None:
            s = s.replace(comms=init_comms_state(s.params, plan, config))
        return s

    def mk_batches(n):
        r = np.random.default_rng(5)
        out = []
        for _ in range(n):
            img = r.standard_normal((8 * world, 16, 16, 1)).astype(np.float32)
            lab = r.integers(0, 16, 8 * world).astype(np.int32)
            out.append(plan.shard_batch({"image": img, "label": lab}))
        return out

    batches = mk_batches(args.steps)
    config = CommsConfig(mode="int8", bucket_mb=args.bucket_mb)
    exact_walls = time_steps(make_train_step(plan=plan), mk_state(), batches)
    comp_step = make_train_step(plan=plan, grad_compression=config)
    comp_walls = time_steps(comp_step, mk_state(config), batches)
    drop = 3  # compile + warmup
    rec["step_time_compressed"] = {
        "f32_p50_s": round(statistics.median(sorted(exact_walls[drop:])), 6),
        "int8_ef_p50_s": round(statistics.median(sorted(comp_walls[drop:])), 6),
        "steps": len(comp_walls) - drop,
        "note": (
            "CPU pays the quantize arithmetic with no DCN to win back; "
            "the wire saving is the bytes_on_wire block, the wall story "
            "is the TPU rung"
        ),
    }

    # the analyzer-gateable block (ratio_bytes_on_wire / ratio_allreduce_p50)
    rec["comms"] = {
        "mode": "int8",
        "error_feedback": True,
        "bytes_per_step": int8_ef["bytes_per_step"],
        "f32_bytes_per_step": f32_bytes,
        "reduction_x": rec["bytes_on_wire"]["reduction_x"],
        "allreduce_s": {"p50": int8_ef["p50_s"]},
    }
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
