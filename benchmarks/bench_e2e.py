#!/usr/bin/env python
"""End-to-end data-fed train benchmark: stream -> decode -> augment ->
prefetch -> train, vs the synthetic-tensor rate.

``bench.py`` times the train step on tensors already in memory; this
script closes the gap VERDICT r04 named (missing #3): it generates a
synthetic JPEG shard volume in-sandbox (PIL encodes; no egress needed),
then drives the REAL input pipeline —
:class:`tpuframe.data.StreamingDataset` / :class:`MDSDataset` (zstd
shards, remote->local-cache contract) -> host decode+augment in
:class:`DataLoader` workers -> :class:`DevicePrefetcher` double-buffered
H2D -> the same jitted train step ``bench.py`` measures — and reports
both rates plus the input-stall fraction.  This is the measured version
of SURVEY §7's "input pipeline feeding HBM at ImageNet rate" hard part
and the capability half of the reference's MDS recipe
(`/root/reference/01_torch_distributor/03a_tiny_imagenet_torch_distributor_resnet_mds.py:346-515`),
which streams MDS shards into a ResNet train loop but never measures
whether the input side keeps the accelerator busy.

Prints ONE JSON line:
  {"metric": "resnet50_e2e_data_fed_images_per_sec_per_chip",
   "value": ..., "synthetic_images_per_sec_per_chip": ...,
   "input_stall_pct": ..., "host_input_wait_frac": ..., ...}

``input_stall_pct``  = 1 - fed/synthetic (what the input pipeline costs).
``host_input_wait_frac`` = fraction of the fed window the host spent
blocked on ``next(batch)`` — attribution: ~0 with a nonzero stall means
H2D/layout, not production rate, is the limiter.

``--consumer null`` swaps the train step for an *instant* consumer and
never imports jax: it measures the loader's **producer ceiling** — the
max sustained img/s the shards->decode->augment->ring-assembly path can
produce on THIS host, per worker count (``--workers`` takes a comma
list).  That makes ``input_stall_pct`` computable on chip-less hosts:
with the ceiling below the chip's ingest rate, the stall on a chip is
arithmetic, not speculation (the "~7 cores feed one chip" projection,
PERF.md).  The instant consumer releases each ring lease immediately, so
the mode also exercises steady-state zero-allocation recycling.

Usage:
  python benchmarks/bench_e2e.py [--format tfs|mds] [--workers N[,N...]]
      [--worker-mode thread|process] [--steps N] [--images N]
      [--consumer train|null] [--uint8-input]
Defaults size themselves by backend (224px/batch-128 on an accelerator,
tiny on CPU so the script runs anywhere, same convention as bench.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def synth_image(rng, size: int) -> "np.ndarray":
    """Low-frequency synthetic image: upsampled 8x8 noise + a gradient.

    Compresses like a photograph (~10:1 JPEG) instead of like noise
    (~1:1), so decode cost and volume size stay ImageNet-realistic.
    """
    import numpy as np

    base = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
    tile = -(-size // 8)  # round up, then crop: any size works
    img = np.kron(base, np.ones((tile, tile, 1), np.uint8))[:size, :size]
    ramp = np.linspace(0, 64, size, dtype=np.uint8)[:, None, None]
    return np.clip(img.astype(np.int16) + ramp, 0, 255).astype(np.uint8)


def _zstd_available() -> bool:
    """Native C++ codec or the python module — either can serve shards."""
    from tpuframe.data import streaming

    if streaming._native_codec() is not None:
        return True
    try:
        import zstandard  # noqa: F401

        return True
    except ImportError:
        return False


def build_volume(path: str, fmt: str, n: int, size: int) -> None:
    """Write (or reuse) a JPEG shard volume of ``n`` ``size``px images.

    Shard compression follows what the host can decode: zstd when a
    codec exists, raw otherwise (JPEG columns are already compressed, so
    the measured decode path barely changes) — the producer ceiling must
    be measurable on any host, including codec-less sandboxes.
    """
    meta_path = os.path.join(path, "bench_e2e_meta.json")
    zstd = _zstd_available()
    want = {"fmt": fmt, "n": n, "size": size, "zstd": zstd}
    if os.path.exists(meta_path) and json.load(open(meta_path)) == want:
        return
    import numpy as np

    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    if fmt == "mds":
        from tpuframe.data.mds import MDSWriter

        with MDSWriter(path, {"image": "jpeg", "label": "int"},
                       compression="zstd" if zstd else None) as w:
            for i in range(n):
                w.write({"image": synth_image(rng, size), "label": i % 1000})
    else:
        from tpuframe.data.streaming import ShardWriter

        with ShardWriter(path, columns={"image": "jpg", "label": "int"},
                         compression="zstd" if zstd else "none") as w:
            for i in range(n):
                w.write({"image": synth_image(rng, size), "label": i % 1000})
    with open(meta_path, "w") as f:
        json.dump(want, f)
    print(f"# built {fmt} volume: {n} x {size}px JPEG in "
          f"{time.perf_counter() - t0:.1f}s at {path}", file=sys.stderr)


def build_dataset(args, vol: str, size: int):
    """The measured dataset: real transform + fused decode-at-scale."""
    if args.uint8_input:
        # host side does decode + geometric augmentation ONLY; dtype stays
        # uint8 (normalize happens fused on device)
        from tpuframe.data.transforms import uint8_image_transforms

        transform = uint8_image_transforms(size)
    else:
        from tpuframe.data.transforms import default_image_transforms

        transform = default_image_transforms(size)
    # fused decode-at-scale: decode covers (size, size) straight out of
    # the IDCT; the transform's Resize is the exact-size finisher
    if args.format == "mds":
        from tpuframe.data.mds import MDSDataset

        return MDSDataset(vol, transform=transform, decode_min_hw=(size, size))
    from tpuframe.data.streaming import StreamingDataset

    return StreamingDataset(vol, transform=transform, decode_min_hw=(size, size))


def run_null_consumer(args) -> None:
    """Producer-ceiling mode: loader vs an instant consumer, no jax.

    Sweeps the ``--workers`` list and prints ONE JSON record with
    img/s per worker count — the committed answer to "can this host
    feed a chip", measurable anywhere (VERDICT r05 weak #1/#2).
    """
    from tpuframe.data import DataLoader
    from tpuframe.track.telemetry import get_telemetry

    size = args.size or 224
    batch = args.batch or 64
    seconds = args.seconds
    n_images = args.images or 512
    src_size = args.source_size or -(-size * 8 // 7)
    worker_counts = [int(w) for w in str(args.workers or "1").split(",")]
    vol = args.volume_dir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"),
        f"tpuframe_e2e_{args.format}_{src_size}to{size}px_{n_images}",
    )
    build_volume(vol, args.format, n_images, src_size)
    reg = get_telemetry().registry
    per_workers: dict[str, float] = {}
    steady_allocs: dict[str, float] = {}
    for workers in worker_counts:
        ds = build_dataset(args, vol, size)
        loader = DataLoader(
            ds, batch_size=batch, shuffle=True, seed=0,
            num_workers=workers, worker_mode=args.worker_mode,
            process_index=0, process_count=1,
            transfer_dtype="uint8" if args.uint8_input else None,
        )
        try:
            # warmup epoch fraction: decode caches, worker spinup, ring fill
            it = iter(loader)
            for _ in range(2):
                next(it)
                loader.release_oldest()
            allocs0 = reg.counter("data/ring_allocs").value
            n = 0
            t0 = time.perf_counter()
            epoch = 0
            while time.perf_counter() - t0 < seconds:
                for images, labels in loader:
                    n += labels.shape[0]
                    # the instant consumer: done with the batch the moment
                    # it lands — recycle its ring lease immediately
                    loader.release_oldest()
                    if time.perf_counter() - t0 >= seconds:
                        break
                epoch += 1
                loader.set_epoch(epoch)
            elapsed = time.perf_counter() - t0
            per_workers[str(workers)] = round(n / elapsed, 1)
            steady_allocs[str(workers)] = (
                reg.counter("data/ring_allocs").value - allocs0
            )
        finally:
            loader.close()
    best_workers, best = max(per_workers.items(), key=lambda kv: kv[1])
    # per-core producer rate: the 1-worker rung when swept, else best/N
    per_core = per_workers.get("1") or best / max(int(best_workers), 1)
    from bench_decode import CHIP_INGEST_IMG_S  # measured chip train rate

    print(json.dumps({
        "metric": "input_producer_ceiling_images_per_sec",
        "value": best,
        "unit": f"images/sec ({args.format} shards -> decode+augment -> "
        f"ring assembly, {size}px, batch={batch}, "
        f"{'uint8' if args.uint8_input else 'f32'} transfer, "
        f"{args.worker_mode} workers, null consumer)",
        "per_workers": per_workers,
        "best_workers": int(best_workers),
        "steady_state_ring_allocs": steady_allocs,
        "format": args.format,
        "worker_mode": args.worker_mode,
        "uint8_input": args.uint8_input,
        "images_in_volume": n_images,
        "source_size": src_size,
        "size": size,
        "host_cores": os.cpu_count(),
        "chip_ingest_img_s": CHIP_INGEST_IMG_S,
        # cores one host needs to feed ONE chip at the measured train
        # rate, from THIS host's per-core producer ceiling
        "cores_to_feed_chip": round(CHIP_INGEST_IMG_S / max(per_core, 1e-9), 1),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--format", choices=("tfs", "mds"), default="tfs")
    ap.add_argument("--workers", default=None,
                    help="DataLoader workers (default: os.cpu_count, cap "
                    "16); --consumer null accepts a comma list to sweep")
    ap.add_argument("--worker-mode", choices=("thread", "process"),
                    default="thread")
    ap.add_argument("--consumer", choices=("train", "null"), default="train",
                    help="null = instant consumer, no jax: measures the "
                    "producer ceiling (max loader img/s) on any host")
    ap.add_argument("--seconds", type=float, default=6.0,
                    help="timed window per worker count (null consumer)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--images", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--size", type=int, default=None)
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument("--volume-dir", default=None)
    ap.add_argument("--uint8-input", action="store_true",
                    help="assemble raw uint8 ring buffers "
                    "(DataLoader(transfer_dtype='uint8')), ship them "
                    "host->HBM and normalize on-device (fused kernel) — "
                    "4x less PCIe traffic and no host normalize cost")
    ap.add_argument("--source-size", type=int, default=None,
                    help="stored JPEG size (default ~8/7 of --size: "
                    "sources larger than the train size, the ImageNet "
                    "reality, exercising the fused decode-at-scale path)")
    args = ap.parse_args()

    if args.consumer == "null":
        # the whole point: measurable without a chip — and without jax
        run_null_consumer(args)
        return

    from bench import (
        BASELINE_IMG_PER_SEC,
        enable_compile_cache,
        time_train_step,
    )

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpuframe.core.runtime import MeshSpec
    from tpuframe.data import DataLoader, DevicePrefetcher
    from tpuframe.models import ResNet50
    from tpuframe.parallel import (
        ParallelPlan,
        align_model_dtype,
        bf16_compute,
        full_precision,
    )
    from tpuframe.train import create_train_state, make_train_step

    on_accel = jax.default_backend() != "cpu"
    chips = max(jax.local_device_count(), 1)
    size = args.size or (224 if on_accel else 32)
    batch = args.batch or (128 * chips if on_accel else 8)
    steps = args.steps or (40 if on_accel else 6)
    workers = (
        int(str(args.workers).split(",")[0])
        if args.workers is not None
        else min(os.cpu_count() or 1, 16)
    )
    # enough images that the timed window spans >=2 epochs at most (decode
    # cache effects show up, volume build stays bounded)
    n_images = args.images or max(batch * 4, min(batch * (steps + 4), 4096))
    src_size = args.source_size or -(-size * 8 // 7)
    vol = args.volume_dir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"),
        f"tpuframe_e2e_{args.format}_{src_size}to{size}px_{n_images}",
    )
    build_volume(vol, args.format, n_images, src_size)

    # --- model + step: identical shape to bench.py's headline ----------
    plan = ParallelPlan(mesh=MeshSpec(data=-1).build())
    policy = bf16_compute() if on_accel else full_precision()
    model = align_model_dtype(
        ResNet50(num_classes=1000,
                 norm_dtype=jnp.bfloat16 if on_accel else None),
        policy,
    )
    state = create_train_state(
        model,
        jax.random.PRNGKey(0),
        jnp.ones((1, size, size, 3), jnp.float32),
        optax.sgd(0.1, momentum=0.9),
        plan=plan,
        init_kwargs={"train": False},
    )
    from bench import make_uint8_normalize_transform

    # raw bytes ride host->HBM; the fused normalize emits the compute
    # dtype directly (no f32 image tensor on chip)
    batch_transform = (
        make_uint8_normalize_transform(on_accel)
        if args.uint8_input else None
    )
    step_fn = make_train_step(policy, batch_transform=batch_transform)
    rng = np.random.default_rng(0)
    if args.uint8_input:
        synth_images = rng.integers(0, 256, (batch, size, size, 3),
                                    dtype=np.uint8)
    else:
        synth_images = rng.standard_normal(
            (batch, size, size, 3)).astype(np.float32)
    synth = plan.shard_batch({
        "image": synth_images,
        "label": rng.integers(0, 1000, (batch,)).astype(np.int32),
    })
    compiled = step_fn.lower(state, synth).compile()

    # --- window 1: synthetic tensors (bench.py's number) ----------------
    synth_img_s, state, _ = time_train_step(
        compiled, state, synth, batch=batch, steps=steps
    )

    # --- window 2: the real pipeline ------------------------------------
    ds = build_dataset(args, vol, size)
    loader = DataLoader(
        ds, batch_size=batch, shuffle=True, seed=0,
        num_workers=workers, worker_mode=args.worker_mode,
        process_index=0, process_count=1,
        # uint8 ring buffers: raw bytes cross host->HBM, normalize is
        # fused on-device (batch_transform above)
        transfer_dtype="uint8" if args.uint8_input else None,
    )

    host_dtype = np.uint8 if args.uint8_input else np.float32

    def epochs():
        e = 0
        while True:
            loader.set_epoch(e)
            for images, labels in loader:
                # asarray: no-op when the transform already produced the
                # right dtype — an unconditional astype would add a fat
                # per-step host copy to the very pipeline being measured
                yield {"image": np.asarray(images, dtype=host_dtype),
                       "label": labels}
            e += 1

    pf = iter(DevicePrefetcher(
        epochs(), depth=args.prefetch_depth,
        sharding=plan.batch_sharding(),
        # epochs() yields one dict per loader batch: FIFO lease release
        # after each H2D recycles the ring (steady-state zero allocs)
        recycler=loader,
    ))
    # warmup: fills the prefetch queue, pays any worker-pool spinup
    for _ in range(2):
        state, metrics = compiled(state, next(pf))
    jax.block_until_ready((state, metrics))
    _ = int(state.step)

    wait_s = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        tw = time.perf_counter()
        data = next(pf)
        wait_s += time.perf_counter() - tw
        state, metrics = compiled(state, data)
    _ = int(state.step)  # value readback = execution barrier (see bench.py)
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    fed_img_s = batch * steps / elapsed
    loader.close()

    value = fed_img_s / chips
    stall = max(0.0, 1.0 - fed_img_s / synth_img_s)
    print(json.dumps({
        "metric": "resnet50_e2e_data_fed_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": f"images/sec/chip ({args.format} zstd JPEG shards -> "
        f"decode+augment x{workers} {args.worker_mode} -> prefetch -> "
        f"train step; batch={batch}, {size}px, "
        f"{'bf16' if on_accel else 'fp32'}, {jax.default_backend()})",
        "vs_baseline": round(value / BASELINE_IMG_PER_SEC, 3),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "chips": chips,
        "synthetic_images_per_sec_per_chip": round(synth_img_s / chips, 2),
        "input_stall_pct": round(100 * stall, 1),
        "host_input_wait_frac": round(wait_s / elapsed, 3),
        "format": args.format,
        "workers": workers,
        "worker_mode": args.worker_mode,
        "uint8_input": args.uint8_input,
        "images_in_volume": n_images,
        "source_size": src_size,
    }))


if __name__ == "__main__":
    main()
