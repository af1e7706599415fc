"""CIFAR ResNet18 through the Distributor + single-image inference demo.

Mirrors `/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py`:
the launcher recipe (`:340-353`), rank-0 metrics (`:254-301`), the 1-epoch
vs N-epoch timing comparison (`:337,408-421`), and the post-hoc
``predict_image`` demo (`:370-387`).

Deliberately fixed anti-patterns (SURVEY.md §7): the reference's worker
never init'd a process group (N independent replicas) and pickled whole
datasets through ``.run`` kwargs — here the mesh makes training truly
data-parallel and only the *config* crosses the process boundary; the
dataset is constructed inside the worker.

Run:  python 01_distributor_cifar_resnet.py --num-processes 2 --simulate-devices 2
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
from tpuframe.data import DataLoader, SyntheticImageDataset, Timer
from tpuframe.launch import Distributor
from tpuframe.models import ResNet18
from tpuframe.parallel import ParallelPlan, align_model_dtype, bf16_compute, full_precision
from tpuframe.track import MLflowLogger
from tpuframe.train import (
    create_train_state,
    make_predict_fn,
    make_train_step,
    merge_metrics,
    summarize_metrics,
)


def train_cifar(cfg: dict):
    """Worker fn (≈ ``train_func``, `02_cifar_torch_distributor_resnet.py:165`).
    Returns (final metrics, elapsed seconds) — picklable, rank 0's copy wins."""
    rt = core.initialize()
    plan = ParallelPlan(mesh=rt.mesh)

    # dataset handles, not dataset bytes, cross the boundary
    train_ds = SyntheticImageDataset(
        n=cfg["train_samples"], image_size=cfg["image_size"],
        num_classes=cfg["num_classes"], seed=cfg["seed"],
    )
    loader = DataLoader(train_ds, cfg["batch_size"], shuffle=True, seed=cfg["seed"])

    policy = bf16_compute() if rt.platform == "tpu" else full_precision()
    # align the model's compute dtype with the policy (f32 model under a
    # bf16 policy would silently up-cast inside every layer)
    model = align_model_dtype(
        ResNet18(num_classes=cfg["num_classes"], stem="cifar"), policy
    )
    state = create_train_state(
        model, jax.random.PRNGKey(cfg["seed"]),
        jnp.ones((1, cfg["image_size"], cfg["image_size"], 3)),
        optax.adam(cfg["lr"]), plan=plan, init_kwargs={"train": False},
    )
    train_step = make_train_step(policy)

    logger = MLflowLogger("cifar_distributor", tracking_uri=cfg["tracking_uri"])
    if rt.is_main:
        logger.log_params({"epochs": cfg["epochs"], "lr": cfg["lr"]})

    timer = Timer()
    summary = {}
    for epoch in range(cfg["epochs"]):
        loader.set_epoch(epoch)
        acc = None
        for images, labels in loader:
            batch = plan.shard_batch({"image": images, "label": labels})
            state, metrics = train_step(state, batch)
            acc = merge_metrics(acc, metrics)
        summary = summarize_metrics(acc or {}, "train_")
        if rt.is_main:
            logger.log_metrics(summary, step=epoch)  # `:258-260`
    elapsed = timer.stop()
    if rt.is_main:
        logger.flush()

    # single-image inference demo (`:370-387`): logits -> argmax class
    predict = make_predict_fn(policy)
    img, label = train_ds[0]
    pred = int(np.argmax(np.asarray(predict(state, np.asarray(img)[None]))))
    # name where it ran: a worker that fell back to another backend must
    # not read as a run on the chip
    where = {"platform": rt.platform, "devices": rt.device_count}
    return {**summary, "demo_label": label, "demo_pred": pred, **where}, elapsed


def main(argv=None):
    p = base_parser(__doc__)
    p.add_argument("--num-processes", type=int, default=1)
    args = p.parse_args(argv)
    cfg = {
        "epochs": 1,
        "batch_size": args.batch_size,
        "train_samples": args.train_samples,
        "image_size": args.image_size,
        "num_classes": args.num_classes,
        "lr": args.lr,
        "seed": args.seed,
        "tracking_uri": os.path.join(args.workdir, "cifar", "mlruns"),
    }
    dist = Distributor(
        num_processes=args.num_processes, simulate_devices=args.simulate_devices
    )

    # 1-epoch cheap run before the full run (`:337` "Single epoch for testing")
    _, one_epoch_s = dist.run(train_cifar, cfg)
    print(f"1 epoch: {one_epoch_s:.1f}s")

    cfg["epochs"] = args.epochs
    summary, full_s = dist.run(train_cifar, cfg)
    print(f"{args.epochs} epochs: {full_s:.1f}s  metrics: {summary}")


if __name__ == "__main__":
    main()
