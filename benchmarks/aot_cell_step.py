#!/usr/bin/env python
"""A benchmark cell's own train step, compiled for a described v5e: no chip.

``chipbench/run.py`` builds a ``Trainer`` for a cell and reads
``memory_analysis()`` of its compiled step on the chip; ``peak_hbm_gib`` is
that program's arguments + outputs - aliased + temporaries wherever it
passes the runtime's own peak.  This script builds the same ``Trainer`` on a
described chip (``jax.experimental.topologies``: the TPU's compiler is
installed here, the chip is not), lowers ``Trainer._train_step`` at the
cell's batch with an abstract state, and prints the same numbers: for
``qwen3next_seq8192`` they equal the chip's to the last digit (PR 45).  A
``value_and_grad`` over the model with a hand-written update is NOT that
program: its heap packs differently (+0.16 GiB where the real step read
-0.13, PR 45).

Usage: JAX_PLATFORMS=cpu python benchmarks/aot_cell_step.py --workload
qwen3next_seq8192 [--hlo out.txt]   (one chip cells only; ~1 minute).
Nothing runs, so it says nothing of times; a compile that passes is not a
chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hlo", default=None, help="write the compiled module's text here")
    cli = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from chipbench import run
    from chipbench.traffic import generator
    from tpuframe import models
    from tpuframe.core import MeshSpec
    from tpuframe.core import runtime as rt
    from tpuframe.data import DataLoader
    from tpuframe.ops import dispatch
    from tpuframe.parallel import ParallelPlan
    from tpuframe.train import Trainer

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cell = run.load_cell(cli.workload, rehearsal=False)
    cfg, mix = cell["cfg"], cell["mix"]
    if int(cell["cell"]["chips"]) != 1:
        raise SystemExit("one-chip cells only")

    # the described chip as the process's runtime, and the dispatch plane's
    # view of the backend as a one-device TPU process sees it
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = MeshSpec(data=-1)
    mesh = spec.build(list(topo.devices)[:1])
    rt._CURRENT = rt.Runtime(mesh=mesh, spec=spec, process_index=0, process_count=1,
                             platform="tpu")
    dispatch.pallas_mode = lambda: "compiled"
    jax.device_count = lambda *a: 1

    batch = int(cfg["per_chip_batch"])
    loader = DataLoader(generator.make_dataset(mix, cfg, 1, batch), batch_size=batch,
                        shuffle=False, **mix["loader"])
    kwargs = {k: jnp.dtype(v) if k.endswith("dtype") and isinstance(v, str) else v
              for k, v in cfg["model"]["kwargs"].items()}
    tr = cfg["trainer"]
    norm = tr.get("normalize")
    trainer = Trainer(
        getattr(models, cfg["model"]["class"])(**kwargs), train_dataloader=loader,
        optimizer=tr["optimizer"], lr=tr["lr"], max_duration="8ba", precision=tr["precision"],
        normalize=(tuple(norm["mean"]), tuple(norm["std"])) if norm else None,
        plan=ParallelPlan(mesh=mesh), callbacks=[], log_interval=1, eval_interval=0, seed=0)
    # the state as shapes on the described chip: nothing is placed, nothing runs
    state = jax.eval_shape(trainer.init_state)
    replicated = NamedSharding(mesh, P())
    trainer.state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
        if hasattr(a, "shape") else a, state)
    compiled = run._train_step_executable(trainer, loader)
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    program = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
               + ma.temp_size_in_bytes)
    print(f"{cli.workload}: temporaries {ma.temp_size_in_bytes / gib!r} GiB "
          f"(memory.temp_gib), arguments {ma.argument_size_in_bytes / gib:.4f}, outputs "
          f"{ma.output_size_in_bytes / gib:.4f}, aliased {ma.alias_size_in_bytes / gib:.4f}, "
          f"program {program / gib!r} GiB (peak_hbm_gib where the runtime's peak is under it)")
    if cli.hlo:
        with open(cli.hlo, "w") as f:
            f.write(compiled.as_text())


if __name__ == "__main__":
    main()
