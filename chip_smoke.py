#!/usr/bin/env python3
"""Does the system still start on the chip?  One ResNet50/ImageNet-width
training run through the normal entry points, in ONE process that drives
every local device:

    core.initialize() -> ParallelPlan -> DataLoader(uint8 transfer)
    -> Trainer(ResNet50, bf16, normalize=, sgd) -> fit()

at 224 px, 1000 classes, per-chip batch 128 — no width cut, only the step
count is small; weights and data are random, made from a seed.  It checks
what came out by the repo's own means (kernel oracles, a reference
first-step loss, the precompile report, telemetry events, the compiled
step's HLO, the shardings) and exits non-zero if any check failed or a
phase degraded: a lazy-jit fallback, an interpreted kernel or a jnp
reference standing in for a kernel is a failed smoke, not an exit 0.
(The image normalize is plain ops, not a stand-in: it has no kernel.)

With no TPU it exits non-zero and prints no result — there is no CPU arm
and no ``JAX_PLATFORMS`` override.  ``--rehearsal`` is the one explicit
exception, for debugging the script itself on a CPU before spending chip
time: tiny sizes (ResNet18, 32 px, batch 8, 3 steps), interpret-mode
kernels, every line says "rehearsal", and its last line is NOT the
result object.

On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Timings printed on the way are one run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

_T0 = time.perf_counter()
_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT_DIR = os.path.join(_ROOT, "chiprun_out", "chip_smoke")

#: the Pallas kernels the train step must carry as Mosaic custom calls
_STEP_KERNELS = ("tpuframe_ce_fwd", "tpuframe_ce_bwd")


@dataclasses.dataclass(frozen=True)
class Size:
    model: str
    image: int
    per_chip_batch: int
    steps: int
    num_classes: int = 1000


CHIP = Size("ResNet50", 224, 128, 12)
REHEARSAL = Size("ResNet18", 32, 8, 3)


class Checks:
    """Collects pass/fail lines; the run fails if any check failed."""

    def __init__(self, tag: str):
        self.tag = tag
        self.failed: list[str] = []

    def say(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", flush=True)

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.say(f"{'PASS' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}")
        if not ok:
            self.failed.append(name)
        return bool(ok)


def _refuse(reason: str) -> int:
    print(f"chip_smoke: {reason}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="CPU rehearsal of this script at tiny size with interpret-mode "
             "kernels; never prints the result object",
    )
    args = ap.parse_args(argv)

    # the repo first: in a directory that holds nothing but this file the
    # run ends here, before anything touches the chip
    sys.path.insert(0, _ROOT)
    try:
        import tpuframe  # noqa: F401
    except ImportError as e:
        return _refuse(
            f"tpuframe is not importable from {_ROOT} ({e}); run from the "
            "root of a checkout"
        )

    import jax

    if args.rehearsal:
        os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"
        jax.config.update("jax_platforms", "cpu")
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        return _refuse(f"no accelerator: jax could not start a backend: {e}")
    if not args.rehearsal and backend != "tpu":
        return _refuse(
            f"no accelerator: jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this smoke "
            "has no CPU arm — use --rehearsal to debug the script itself"
        )
    checks = Checks("rehearsal" if args.rehearsal else "chip_smoke")
    try:
        device = run(REHEARSAL if args.rehearsal else CHIP, checks,
                     rehearsal=args.rehearsal)
    except Exception:
        traceback.print_exc()
        return _refuse("a phase raised (traceback above)")
    if checks.failed:
        return _refuse(f"FAILED checks: {', '.join(checks.failed)}")
    if args.rehearsal:
        print(json.dumps({"rehearsal": True, "passed": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


def run(size: Size, checks: Checks, *, rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuframe import core, models
    from tpuframe.compile import cache as compile_cache
    from tpuframe.data import DataLoader, SyntheticImageDataset
    from tpuframe.data.transforms import IMAGENET_MEAN, IMAGENET_STD
    from tpuframe.ops import dispatch
    from tpuframe.ops.cross_entropy import (
        cross_entropy_reference,
        fused_cross_entropy,
    )
    from tpuframe.ops.normalize import normalize_images, normalize_images_reference
    from tpuframe.parallel import ParallelPlan
    from tpuframe.track import telemetry
    from tpuframe.train import Trainer

    say, check = checks.say, checks.check
    tele = telemetry.configure(jsonl_dir=_OUT_DIR, max_events=16384)

    # -- device ------------------------------------------------------------
    devices = jax.local_devices()
    n_dev = len(devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices())}
    say(f"device platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__}")
    check("backend", rehearsal or jax.default_backend() == "tpu",
          f"jax.default_backend()={jax.default_backend()!r}")
    want_mode = "interpret" if rehearsal else "compiled"
    check("pallas_mode", dispatch.pallas_mode() == want_mode,
          f"{dispatch.pallas_mode()!r}, want {want_mode!r}")

    # -- runtime + compile cache placement ---------------------------------
    rt = core.initialize()
    want_cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
                  or compile_cache.DEFAULT_CACHE_DIR)
    check(
        "compile_cache_dir",
        compile_cache.enabled_dir() == want_cache
        and jax.config.jax_compilation_cache_dir == want_cache,
        f"enabled_dir={compile_cache.enabled_dir()!r} "
        f"jax.config={jax.config.jax_compilation_cache_dir!r} want={want_cache!r}",
    )
    plan = ParallelPlan(mesh=rt.mesh)

    # -- what the step runs against the repo's own oracles, on a small input
    rng = np.random.default_rng(0)
    raw = jnp.asarray(rng.integers(0, 256, (8 * n_dev, 32, 32, 3)), jnp.uint8)
    got = jax.jit(lambda r: normalize_images(
        r, IMAGENET_MEAN, IMAGENET_STD, out_dtype=jnp.bfloat16))(raw)
    want = normalize_images_reference(
        raw, IMAGENET_MEAN, IMAGENET_STD, out_dtype=jnp.bfloat16)
    diff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    check("normalize_vs_oracle", got.shape == raw.shape and diff <= 2e-2,
          f"max_abs_diff={diff:.3g} (bf16 out)")
    logits = jnp.asarray(rng.standard_normal((16 * n_dev, size.num_classes)) * 3,
                         jnp.float32)
    labels = jnp.asarray(rng.integers(0, size.num_classes, (16 * n_dev,)), jnp.int32)
    ce = lambda fn: jax.jit(jax.value_and_grad(lambda lg: jnp.mean(fn(lg))))(logits)
    vk, gk = ce(lambda lg: fused_cross_entropy(
        lg, labels, mesh=plan.mesh, batch_axes=tuple(plan.data_axes)))
    vr, gr = ce(lambda lg: cross_entropy_reference(lg, labels))
    dv, dg = abs(float(vk - vr)), float(jnp.max(jnp.abs(gk - gr)))
    check("cross_entropy_vs_oracle", dv <= 1e-4 and dg <= 1e-5,
          f"value diff={dv:.3g} grad max_abs_diff={dg:.3g}")

    # -- the training run --------------------------------------------------
    global_batch = size.per_chip_batch * n_dev
    dataset = SyntheticImageDataset(
        n=global_batch * size.steps, image_size=size.image,
        num_classes=size.num_classes, seed=0,
    )
    loader = DataLoader(dataset, batch_size=global_batch, transfer_dtype="uint8")
    probe = _step_probe()
    trainer = Trainer(
        getattr(models, size.model)(num_classes=size.num_classes),
        train_dataloader=loader,
        optimizer="sgd", lr=0.01,
        max_duration=f"{size.steps}ba",
        precision="bf16",
        normalize=(IMAGENET_MEAN, IMAGENET_STD),
        plan=plan,
        callbacks=[probe],
        log_interval=1,
        seed=0,
    )
    say(f"run model={size.model} image={size.image}px classes={size.num_classes} "
        f"precision=bf16 per_chip_batch={size.per_chip_batch} "
        f"global_batch={global_batch} steps={size.steps}")

    # reference loss of the first step: same weights, same first batch, but
    # jnp normalize + jnp cross entropy — what the kernels must agree with
    state = trainer.init_state()
    images0, labels0 = (np.array(x) for x in next(iter(loader))[:2])
    policy, model = trainer.policy, trainer.model

    def reference_loss(params, batch_stats, batch):
        x = normalize_images_reference(
            batch["image"], IMAGENET_MEAN, IMAGENET_STD,
            out_dtype=policy.compute_dtype)
        variables = {"params": policy.cast_params_for_compute(params),
                     "batch_stats": batch_stats}
        out, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
        return jnp.mean(cross_entropy_reference(
            policy.cast_outputs(out), batch["label"]))

    ref_loss = float(jax.jit(reference_loss)(
        state.params, state.batch_stats,
        plan.shard_batch({"image": images0, "label": labels0.astype(np.int32)})))
    del state

    setup_s = time.perf_counter() - _T0
    probe.start()
    result = trainer.fit()
    fit_s = time.perf_counter() - probe.t_fit

    # -- what came out -----------------------------------------------------
    step_now = int(jax.device_get(trainer.state.step))
    check("steps", step_now == size.steps and len(probe.losses) == size.steps,
          f"device step counter={step_now}, losses seen={len(probe.losses)}, "
          f"asked={size.steps}")
    check("loss_finite",
          bool(probe.losses) and all(math.isfinite(v) for v in probe.losses)
          and all(c == global_batch for c in probe.counts),
          "losses=" + " ".join(f"{v:.4f}" for v in probe.losses))
    if probe.losses:
        check("first_loss_vs_reference", abs(probe.losses[0] - ref_loss) <= 5e-2,
              f"kernel path {probe.losses[0]:.4f} vs jnp reference {ref_loss:.4f}")
    check("fit_result", result.error is None and "train_loss" in result.metrics,
          f"metrics keys={sorted(result.metrics)[:4]}...")

    report = trainer.precompile(wait=True) or {}
    train_entry = next(
        (s for s in report.get("steps", []) if s.get("kind") == "train"), {})
    check("precompile",
          bool(train_entry) and "error" not in train_entry
          and train_entry.get("dispatchable") is True,
          json.dumps(train_entry))
    events = tele.recent_events(10**6)
    degraded = {name: sum(1 for e in events if e.get("name") == name)
                for name in ("compile/precompile_error", "compile/aot_fallback",
                             "compile/recompile")}
    check("no_degraded_phase", not any(degraded.values()), json.dumps(degraded))

    verdicts = [e for e in events if e.get("name") == "ops/kernel_verdict"]
    mine = [e for e in verdicts if e.get("op") == "cross_entropy"]
    check("kernel_verdict_cross_entropy",
          bool(mine) and all(e.get("enable") is True for e in mine),
          json.dumps([{k: e.get(k) for k in ("shape_class", "enable", "source")}
                      for e in mine]))

    compiled = next((c for (kind, _), c in trainer._compiled.items()
                     if kind == "train"), None)
    if rehearsal:
        say("rehearsal: interpret-mode kernels leave no Mosaic custom call — "
            "HLO check skipped")
    elif check("train_executable", compiled is not None,
               "the AOT executable the steps dispatched to"):
        hlo = compiled.as_text()
        calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
        missing = [k for k in _STEP_KERNELS if not any(k in ln for ln in calls)]
        if missing:
            os.makedirs(_OUT_DIR, exist_ok=True)
            with open(os.path.join(_OUT_DIR, "train_step.hlo.txt"), "w") as f:
                f.write(hlo)
        check("mosaic_custom_calls", not missing,
              f"{len(calls)} tpu_custom_call(s) in the compiled train step; "
              f"missing kernels: {missing or 'none'}")

    # every local device holds a replica of the parameters and a shard of
    # the batch
    local = set(devices)
    params_ok = all(
        {s.device for s in leaf.addressable_shards} == local
        and leaf.sharding.is_fully_replicated
        for leaf in jax.tree.leaves(trainer.state.params)
    )
    check("params_replicated", params_ok,
          f"{len(jax.tree.leaves(trainer.state.params))} leaves on {n_dev} device(s)")
    if compiled is not None:
        image_sh = compiled.input_shardings[0][1]["image"]
        shard = image_sh.shard_shape((global_batch, size.image, size.image, 3))
        check("batch_sharded",
              set(image_sh.device_set) == local and shard[0] == size.per_chip_batch,
              f"image shard shape {shard} on {len(image_sh.device_set)} device(s)")

    # -- one run, not a benchmark ------------------------------------------
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    say("one run, not a benchmark:")
    say(f"  set-up before fit() {setup_s:.1f}s (imports, backend, kernel oracles, "
        f"reference loss)")
    say(f"  train-step precompile {train_entry.get('wall_s', float('nan')):.1f}s "
        f"persistent_cache={train_entry.get('persistent_cache')} "
        f"(hit = no backend compile of the train step)")
    say(f"  fit() entry to first step done {probe.first_step_s:.1f}s; "
        f"fit() total {fit_s:.1f}s")
    say("  fit() host-side split (Trainer's own spans, whole run): " + " ".join(
        f"{k}={result.metrics.get(k, float('nan')):.2f}s"
        for k in ("data_wait_s", "dispatch_s", "host_block_s")))
    say(f"  last step, closed by the step-counter readback: "
        f"{probe.step_times[-1] * 1e3:.1f} ms "
        f"(global batch {global_batch}; post-warm-up steps ms: "
        + " ".join(f"{t * 1e3:.0f}" for t in probe.step_times[2:]) + ")")
    say(f"  memory_stats peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB, max "
        f"over {n_dev} device(s))" if peak else "  memory_stats "
        "peak_bytes_in_use: not reported by this backend")
    if compiled is not None:
        ma = compiled.memory_analysis()
        say("  compiled train step memory_analysis (per device, GiB): " + " ".join(
            f"{k}={getattr(ma, k + '_size_in_bytes') / 2**30:.2f}"
            for k in ("argument", "output", "alias", "temp")))
    counters = tele.registry.snapshot()
    say("  whole-process compile counters " + json.dumps(
        {k: int(counters.get(k, 0))
         for k in ("compile/cache_hits", "compile/cache_misses",
                   "compile/backend_compiles", "compile/recompiles")}))
    return device


def _step_probe():
    """Trainer callback that closes every step with a readback of the
    device step counter and keeps the per-step loss the Trainer logs."""
    from tpuframe.train import Callback

    class StepProbe(Callback):
        def __init__(self):
            self.losses: list[float] = []
            self.counts: list[float] = []
            self.step_times: list[float] = []
            self.first_step_s = float("nan")
            self.t_fit = self._t_prev = 0.0

        def start(self) -> None:
            self.t_fit = self._t_prev = time.perf_counter()

        def on_step_end(self, trainer) -> None:
            int(trainer.state.step)  # the readback that closes the step
            now = time.perf_counter()
            if not self.step_times:
                self.first_step_s = now - self.t_fit
            self.step_times.append(now - self._t_prev)
            self._t_prev = now

        def on_batch_end(self, trainer, metrics) -> None:
            count = float(metrics.get("count", 0.0))
            self.counts.append(count)
            self.losses.append(
                float(metrics.get("loss_sum", float("nan"))) / count if count
                else float("nan"))

    return StepProbe()


if __name__ == "__main__":
    raise SystemExit(main())
