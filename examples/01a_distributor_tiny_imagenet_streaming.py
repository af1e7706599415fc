"""TinyImageNet-scale training from streamed TFS shards, end to end.

Mirrors `/root/reference/01_torch_distributor/
03a_tiny_imagenet_torch_distributor_resnet_mds.py` — the reference's only
streaming recipe: HF dataset -> MDS shards in a UC volume (`:180-224`),
workers streaming shards remote->local cache (`:240-255,382-390`) with
stale-cache cleanup (`:282`), transforms applied in ``__getitem__``
(`:240-255`), ResNet50 at 64px/200 classes (`:125-143` wrapper,
dataset scale at `03_tiny_imagenet_torch_distributor_resnet.py:63-66`),
per-epoch validation + early-stopping scaffold (`:501-509`), and the
five-image inference spot check (`:688-707`).

The tpuframe shape of it:

- driver writes TFS shards once (synthetic TinyImageNet-shaped data by
  default; ``--hf-dataset zh-plus/tiny-imagenet`` on a connected machine),
- only the *shard directory path* crosses the process boundary ("dataset
  handles, not dataset bytes" — fixing the reference's pickled-dataset
  anti-pattern, SURVEY.md §7),
- each worker streams its shard subset into a local cache and feeds a
  jitted bf16 train step over the mesh.

Run:  python 01a_distributor_tiny_imagenet_streaming.py \
          --num-processes 2 --simulate-devices 2 --train-samples 512
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from _common import base_parser
from tpuframe import core
from tpuframe.data import (
    Compose,
    DataLoader,
    Normalize,
    RandomHorizontalFlip,
    ShardWriter,
    StreamingDataset,
    SyntheticImageDataset,
    Timer,
    ToFloat,
    clean_stale_cache,
)
from tpuframe.launch import Distributor
from tpuframe.models import ResNet50
from tpuframe.parallel import ParallelPlan, align_model_dtype, bf16_compute, full_precision
from tpuframe.track import MLflowLogger
from tpuframe.train import (
    create_train_state,
    make_eval_step,
    make_predict_fn,
    make_train_step,
    merge_metrics,
    summarize_metrics,
)

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def write_shards(args) -> tuple[str, str, int]:
    """Driver-side conversion (≈ the MDSWriter loop, `03a_…:180-224`).

    Returns (train_remote, val_remote, num_classes).  Small shard limit so
    even the smoke-scale run exercises multi-shard streaming.
    """
    root = os.path.join(args.workdir, "tiny_imagenet_tfs")
    columns = {"image": "ndarray", "label": "int"}
    splits = {}
    for split, n, seed in (
        ("train", args.train_samples, args.seed),
        ("val", args.eval_samples, args.seed + 1),
    ):
        out = os.path.join(root, split)
        if os.path.exists(os.path.join(out, "index.json")):
            splits[split] = out
            continue  # idempotent, like the reference's cached volume
        ds = _source_dataset(args, n, seed)
        with ShardWriter(out, columns, shard_size_limit=1 << 20) as w:
            for i in range(len(ds)):
                img, label = ds[i]
                w.write({"image": np.asarray(img, np.uint8), "label": int(label)})
        splits[split] = out
    return splits["train"], splits["val"], args.num_classes


def _source_dataset(args, n: int, seed: int):
    if args.hf_dataset:
        from tpuframe.data import hfds_download, make_image_dataset

        raw = hfds_download(args.hf_dataset, cache_dir=f"{args.workdir}/hf_cache")
        split = "train" if seed == args.seed else (
            "valid" if "valid" in raw else "test"
        )
        return make_image_dataset(raw[split])
    # synthetic uint8 images in TinyImageNet shape: 64px, 200 classes
    base = SyntheticImageDataset(
        n=n, image_size=args.image_size, num_classes=args.num_classes, seed=seed
    )

    class AsUint8:
        def __len__(self):
            return len(base)

        def __getitem__(self, i):
            img, label = base[i]
            return (np.clip(np.asarray(img), 0, 1) * 255).astype(np.uint8), label

    return AsUint8()


def train_tiny_imagenet(cfg: dict):
    """Worker fn (≈ ``train_func`` building datasets *inside* the worker,
    `03a_…:346-515`)."""
    rt = core.initialize()
    plan = ParallelPlan(mesh=rt.mesh)

    # stale partial downloads from a killed run must not poison the cache
    # (≈ clean_stale_shared_memory, `03a_…:282`)
    local_cache = os.path.join(cfg["workdir"], "stream_cache", f"host{rt.process_index}")
    clean_stale_cache(local_cache)

    train_tf = Compose([
        RandomHorizontalFlip(0.5),
        ToFloat(),
        Normalize(IMAGENET_MEAN, IMAGENET_STD),
    ])
    eval_tf = Compose([ToFloat(), Normalize(IMAGENET_MEAN, IMAGENET_STD)])
    # NOTE for jpg-column volumes (this example's shards store ndarray
    # columns): pass decode_min_hw=(px, px) AND lead the transform with
    # Resize(px) — jpeg then decodes at the covering M/8 DCT scale
    # (fused decode+resize, GIL-free) and Resize finishes the exact size.
    train_ds = StreamingDataset(
        cfg["train_remote"],
        local_cache=os.path.join(local_cache, "train"),
        transform=train_tf,
        rng_seed=cfg["seed"],
    )
    val_ds = StreamingDataset(
        cfg["val_remote"],
        local_cache=os.path.join(local_cache, "val"),
        transform=eval_tf,
    )
    train_loader = DataLoader(
        train_ds, cfg["batch_size"], shuffle=True, seed=cfg["seed"], drop_last=True
    )
    val_loader = DataLoader(val_ds, cfg["batch_size"], drop_last=False)

    policy = bf16_compute() if rt.platform == "tpu" else full_precision()
    model = align_model_dtype(ResNet50(num_classes=cfg["num_classes"]), policy)
    state = create_train_state(
        model, jax.random.PRNGKey(cfg["seed"]),
        jnp.ones((1, cfg["image_size"], cfg["image_size"], 3)),
        optax.adamw(cfg["lr"]), plan=plan, init_kwargs={"train": False},
    )
    train_step = make_train_step(policy, plan=plan)
    eval_step = make_eval_step(policy, plan=plan)

    logger = MLflowLogger("tiny_imagenet_streaming", tracking_uri=cfg["tracking_uri"])
    if rt.is_main:
        logger.log_params({
            "epochs": cfg["epochs"], "lr": cfg["lr"],
            "image_size": cfg["image_size"], "classes": cfg["num_classes"],
            "train_shards": "streamed",
        })

    timer = Timer()
    best_val, patience_left = float("inf"), cfg["patience"]
    summary = {}
    for epoch in range(cfg["epochs"]):
        train_loader.set_epoch(epoch)
        train_ds.set_epoch(epoch)
        acc = None
        for images, labels in train_loader:
            batch = plan.shard_batch({"image": images, "label": labels})
            state, metrics = train_step(state, batch)
            acc = merge_metrics(acc, metrics)
        summary = summarize_metrics(acc or {}, "train_")

        vacc = None
        for images, labels, mask in val_loader:
            batch = plan.shard_batch({"image": images, "label": labels, "weight": mask})
            vacc = merge_metrics(vacc, eval_step(state, batch))
        summary.update(summarize_metrics(vacc or {}, "val_"))
        if rt.is_main:
            logger.log_metrics(summary, step=epoch)

        # early stopping (patience), `03a_…:501-509` made real
        if summary["val_loss"] < best_val - 1e-4:
            best_val, patience_left = summary["val_loss"], cfg["patience"]
        else:
            patience_left -= 1
            if patience_left <= 0:
                break
    elapsed = timer.stop()
    if rt.is_main:
        logger.flush()

    # five-image inference spot check (`03a_…:688-707`)
    predict = make_predict_fn(policy)
    images = np.stack([val_ds[i][0] for i in range(5)])
    preds = np.argmax(np.asarray(predict(state, images)), axis=-1).tolist()
    labels = [val_ds[i][1] for i in range(5)]
    return {**summary, "spot_preds": preds, "spot_labels": labels}, elapsed


def main(argv=None):
    p = base_parser(__doc__)
    p.set_defaults(image_size=64, num_classes=200, train_samples=256, eval_samples=64)
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--patience", type=int, default=3)
    args = p.parse_args(argv)

    train_remote, val_remote, num_classes = write_shards(args)
    cfg = {
        "epochs": args.epochs,
        "batch_size": args.batch_size,
        "image_size": args.image_size,
        "num_classes": num_classes,
        "lr": args.lr,
        "seed": args.seed,
        "patience": args.patience,
        "workdir": args.workdir,
        "train_remote": train_remote,
        "val_remote": val_remote,
        "tracking_uri": os.path.join(args.workdir, "tiny_imagenet", "mlruns"),
    }
    dist = Distributor(
        num_processes=args.num_processes, simulate_devices=args.simulate_devices
    )
    summary, elapsed = dist.run(train_tiny_imagenet, cfg)
    print(f"{cfg['epochs']} epochs in {elapsed:.1f}s: {summary}")


if __name__ == "__main__":
    main()
