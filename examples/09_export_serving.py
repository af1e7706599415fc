"""Train -> export -> serve: the deployment path end to end.

Extends the reference's in-notebook inference demo
(`/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py:370-387`)
to a deployable artifact: fit a model (optionally with parameter EMA),
freeze it WITH its preprocessing into one StableHLO blob
(``tpuframe.serve``), then reload it the way a serving box would — no
trainer, no flax module, no checkpoint — and time batched inference.

Then stands up the real serving spine over the artifact: a
:class:`~tpuframe.serve.ServeEngine` (deadline-aware dynamic batching
into AOT-precompiled bucket shapes, bounded-queue admission control,
graceful drain — SERVE.md) and fires a small closed-loop load generator
at it, printing its throughput and latency distribution.

Also demonstrates the migration entry: ``--from-torch <state_dict.pt>``
skips training and exports a torchvision-format checkpoint directly
(uses the committed width-4 ResNet18 test fixture by default shape).

Run:  python 09_export_serving.py --epochs 2
      python 09_export_serving.py --from-torch ../tests/fixtures/resnet18_tv_w4.pt
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from _common import base_parser
from tpuframe import core


def main() -> None:
    ap = base_parser(__doc__)
    ap.add_argument("--ema", type=float, default=0.99,
                    help="parameter EMA decay (0 disables)")
    ap.add_argument("--from-torch", default=None,
                    help="torchvision-format ResNet18 state_dict .pt; "
                         "skips training and exports it directly")
    ap.add_argument("--serve-batch", type=int, default=64)
    ap.add_argument("--clients", type=int, default=4,
                    help="closed-loop load-generator clients against the "
                         "ServeEngine (0 skips the engine demo)")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per client")
    args = ap.parse_args()
    rt = core.initialize()
    os.makedirs(args.workdir, exist_ok=True)
    artifact = os.path.join(args.workdir, "model.shlo")

    from tpuframe.serve import load_model

    if args.from_torch:
        import torch

        from tpuframe.models import ResNet18
        from tpuframe.models.interop import import_torch_resnet
        from tpuframe.serve import export_model

        sd = torch.load(args.from_torch, map_location="cpu", weights_only=True)
        width = sd["conv1.weight"].shape[0]
        num_classes = sd["fc.weight"].shape[0]
        model = ResNet18(num_filters=width, num_classes=num_classes)
        export_model(
            model,
            import_torch_resnet(sd),
            np.zeros((1, 32, 32, 3), np.float32),
            artifact,
        )
        sample_dtype = np.float32
        shape = (32, 32, 3)
        print(f"exported torch checkpoint (width={width}) -> {artifact}")
    else:
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import MnistNet
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(
            n=args.train_samples, image_size=args.image_size, channels=1,
            num_classes=args.num_classes, seed=args.seed,
        )
        trainer = Trainer(
            MnistNet(num_classes=args.num_classes),
            train_dataloader=DataLoader(ds, args.batch_size, shuffle=True,
                                        seed=args.seed),
            max_duration=f"{args.epochs}ep",
            num_classes=args.num_classes,
            log_interval=0,
            normalize=((0.5,), (0.25,)),
            ema_decay=args.ema or None,
        )
        result = trainer.fit()
        trainer.export(artifact)
        sample_dtype = trainer.sample_input.dtype
        shape = trainer.sample_input.shape[1:]
        print(f"trained (loss {result.metrics['train_loss']:.3f}, "
              f"ema={'on' if args.ema else 'off'}) -> {artifact}")

    # ---- the serving side: nothing but the artifact ----------------------
    served = load_model(artifact)
    print(f"loaded {os.path.getsize(artifact)/1024:.0f} KiB artifact; "
          f"meta: model={served.meta['model']} "
          f"platforms={served.meta['platforms']}")
    rng = np.random.default_rng(0)
    batch = (rng.integers(0, 255, (args.serve_batch, *shape))
             .astype(sample_dtype))
    logits = np.asarray(served(batch))  # warmup/compile
    t0 = time.perf_counter()
    n = 20
    for _ in range(n):
        logits = np.asarray(served(batch))
    dt = (time.perf_counter() - t0) / n
    print(f"serving batch={args.serve_batch}: {dt*1000:.2f} ms/batch "
          f"({args.serve_batch/dt:.0f} img/s) on {rt.platform}; "
          f"logits {logits.shape}")

    # ---- the serving spine: engine + closed-loop load --------------------
    if args.clients:
        import threading

        from tpuframe.serve import ServeEngine, ServeKnobs

        knobs = ServeKnobs(buckets=(1, 4, 8), slo_ms=5000.0,
                           batch_wait_ms=1.0)
        engine = ServeEngine(served, knobs=knobs).start()
        rng = np.random.default_rng(1)
        lats: list[float] = []
        lock = threading.Lock()

        def client(k: int) -> None:
            for _ in range(args.requests):
                x = (rng.integers(0, 255, shape)
                     .astype(sample_dtype))
                res = engine.submit(x)
                res.result(timeout=30)
                with lock:
                    lats.append(res.latency_s)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(args.clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        engine.drain(timeout=30)
        lats.sort()
        p = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))]  # noqa: E731
        print(f"engine: {len(lats)} requests from {args.clients} "
              f"closed-loop clients in {wall:.2f}s "
              f"({len(lats)/wall:.0f} req/s); latency p50="
              f"{p(.5)*1e3:.1f}ms p95={p(.95)*1e3:.1f}ms; drained cleanly")
    print("finished")


if __name__ == "__main__":
    main()
