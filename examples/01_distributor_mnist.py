"""MNIST CNN through the Distributor — the reference's basic DDP recipe.

Mirrors `/root/reference/01_torch_distributor/01_basic_torch_distributor.py`:
local-first smoke run (`:185-201`), then the same train fn under the
launcher (`:360-367`) with the full rank-0 discipline — checkpoint per
epoch, eval, experiment tracking, picklable "finished" return (`:248-328`).

TPU-idiom differences: no process group or DDP wrap — the worker builds a
device mesh and the jitted step's gradient all-reduce is compiled in; the
checkpoint is a sharded orbax save instead of ``torch.save``.

Run:  python 01_distributor_mnist.py --num-processes 2 --simulate-devices 2
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import optax

from _common import base_parser
from tpuframe import core
from tpuframe.ckpt import Checkpointer
from tpuframe.data import DataLoader, SyntheticImageDataset
from tpuframe.launch import Distributor, run_with_restarts
from tpuframe.models import MnistNet
from tpuframe.parallel import ParallelPlan
from tpuframe.track import MLflowLogger
from tpuframe.train import (
    create_train_state,
    make_eval_step,
    make_train_step,
    merge_metrics,
    summarize_metrics,
)


def train_mnist(cfg: dict) -> str:
    """The worker fn (≈ ``main_fn``, `01_basic_torch_distributor.py:248`)."""
    rt = core.initialize()  # picks up the injected rank/world env
    plan = ParallelPlan(mesh=rt.mesh)

    train_ds = SyntheticImageDataset(
        n=cfg["train_samples"], image_size=28, channels=1,
        num_classes=10, seed=cfg["seed"],
    )
    eval_ds = SyntheticImageDataset(
        n=cfg["eval_samples"], image_size=28, channels=1,
        num_classes=10, seed=cfg["seed"] + 1,
    )
    train_loader = DataLoader(train_ds, cfg["batch_size"], shuffle=True, seed=cfg["seed"])
    eval_loader = DataLoader(eval_ds, cfg["batch_size"], drop_last=False)

    model = MnistNet(num_classes=10)
    # momentum SGD like the reference (`01_basic_torch_distributor.py:283`)
    state = create_train_state(
        model, jax.random.PRNGKey(cfg["seed"]), jnp.ones((1, 28, 28, 1)),
        optax.sgd(cfg["lr"], momentum=0.9), plan=plan,
    )
    train_step = make_train_step()
    eval_step = make_eval_step()

    logger = MLflowLogger("mnist_distributor", tracking_uri=cfg["tracking_uri"])
    ckpt = Checkpointer(cfg["ckpt_dir"], max_to_keep=3)
    if rt.is_main:
        logger.log_params({"epochs": cfg["epochs"], "lr": cfg["lr"]})

    for epoch in range(cfg["epochs"]):
        train_loader.set_epoch(epoch)
        acc = None
        for images, labels in train_loader:
            batch = plan.shard_batch({"image": images, "label": labels})
            state, metrics = train_step(state, batch)
            acc = merge_metrics(acc, metrics)
        summary = summarize_metrics(acc or {}, "train_")
        if rt.is_main:
            logger.log_metrics(summary, step=epoch)
        # every process participates in a sharded save (vs. the reference's
        # rank-0 torch.save, `:298-299`)
        ckpt.save(state, metrics=summary, meta={"epoch": epoch + 1})

    # rank-0 eval, like `:302-323`
    eacc = None
    for batch_parts in eval_loader:
        images, labels, mask = batch_parts
        batch = plan.shard_batch({"image": images, "label": labels, "weight": mask})
        eacc = merge_metrics(eacc, eval_step(state, batch))
    esum = summarize_metrics(eacc or {}, "test_")
    if rt.is_main:
        logger.log_metrics(esum, step=cfg["epochs"])
        logger.flush()
        print(f"rank0 eval: {esum}")

    # checkpoint round trip (`:155-181`)
    restored, meta = ckpt.restore(state)
    assert int(jax.device_get(restored.step)) == int(jax.device_get(state.step))
    ckpt.close()
    return "finished"  # picklable result, `:328`


def main(argv=None):
    p = base_parser(__doc__)
    p.add_argument("--num-processes", type=int, default=1)
    args = p.parse_args(argv)
    cfg = {
        "epochs": args.epochs,
        "batch_size": args.batch_size,
        "train_samples": args.train_samples,
        "eval_samples": args.eval_samples,
        "lr": args.lr,
        "seed": args.seed,
        "tracking_uri": os.path.join(args.workdir, "mnist", "mlruns"),
        "ckpt_dir": os.path.join(args.workdir, "mnist", "ckpt"),
    }

    # Local-first smoke: the reference trains 1 epoch in-process before
    # distributing (`01_basic_torch_distributor.py:185-201`).
    smoke = dict(cfg, epochs=1, ckpt_dir=cfg["ckpt_dir"] + "_local")
    print("local smoke:", train_mnist(smoke))

    dist = Distributor(
        num_processes=args.num_processes, simulate_devices=args.simulate_devices
    )
    # Elastic wrapper: a killed/lost rank surfaces within seconds (poll
    # loop + heartbeat), the run relaunches, and train_mnist resumes from
    # its Checkpointer instead of recomputing — SURVEY §5 failure
    # recovery, absent in the reference.
    result = run_with_restarts(
        lambda: dist.run(train_mnist, cfg), max_restarts=2
    )
    print("distributed:", result)
    assert result == "finished"


if __name__ == "__main__":
    main()
