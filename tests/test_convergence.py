"""Convergence acceptance tests: models must actually LEARN, not just
produce falling losses.

The reference's de-facto validation ladder is local-smoke -> 1-epoch
cheap run -> full run with accuracy watched by hand (SURVEY.md §4);
these tests automate the "does it learn" rung with accuracy thresholds
on deterministic synthetic tasks, so a silent optimizer/sharding/
precision regression that merely slows divergence cannot pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow  # acceptance tier: replays/convergence, minutes not seconds

from tpuframe.core import MeshSpec
from tpuframe.core import runtime as rt
from tpuframe.data import DataLoader, SyntheticImageDataset
from tpuframe.models import ResNet18, TransformerLM
from tpuframe.parallel import ParallelPlan
from tpuframe.train import (
    Trainer,
    create_train_state,
    make_train_step,
    merge_metrics,
    summarize_metrics,
)


@pytest.mark.slow  # ~90 s; deselect with -m "not slow"
def test_resnet_converges_on_learnable_vision_task():
    """ResNet18 on the class-conditional synthetic images: >90% train
    accuracy and clearly-above-chance eval in 6 epochs (chance = 25%)."""
    ds = SyntheticImageDataset(n=256, image_size=16, num_classes=4, seed=0)
    ev = SyntheticImageDataset(n=64, image_size=16, num_classes=4, seed=1)
    trainer = Trainer(
        ResNet18(num_classes=4, stem="cifar"),
        train_dataloader=DataLoader(ds, batch_size=32, shuffle=True, seed=0),
        eval_dataloader=DataLoader(ev, batch_size=32, drop_last=False),
        max_duration="6ep",
        lr=3e-3,
        optimizer="adamw",
        eval_interval=6,
        log_interval=0,
    )
    result = trainer.fit()  # raises on failure; no error to inspect
    assert result.metrics["train_accuracy"] > 0.9, result.metrics
    assert result.metrics["eval_accuracy"] > 0.45, result.metrics  # 1.8x chance


def test_real_data_digits_full_trainer_accuracy(tmp_path):
    """The accuracy half of the north star, at sandbox scale: REAL data
    (sklearn's bundled 1,797 scanned handwritten digits — the largest
    real dataset available in this zero-egress image; CIFAR-10 itself
    cannot be fetched here), full Trainer recipe (augmentation, warmup+
    cosine schedule, checkpointing, held-out eval), accuracy threshold at
    the published ballpark for small CNNs on this dataset (~98-99%).

    Mirrors the reference's per-epoch-accuracy validation loop
    (`/root/reference/02_deepspeed/02_tiny_imagenet_deepspeed_resnet.py:219-297`).
    The same recipe at CIFAR scale is examples/08_real_data_convergence.py.
    """
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    script = os.path.join(
        os.path.dirname(__file__), os.pardir, "examples",
        "08_real_data_convergence.py",
    )
    proc = subprocess.run(
        [sys.executable, script, "--dataset", "digits", "--epochs", "25",
         "--min-accuracy", "0.97", "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, (
        f"--- stdout ---\n{proc.stdout[-2000:]}\n--- stderr ---\n"
        f"{proc.stderr[-3000:]}"
    )
    assert "ACCEPTED" in proc.stdout


def test_real_data_digits_compressed_wire_same_gate(tmp_path):
    """Convergence parity for the wire-compression spine at FULL recipe
    scale: the digits run over the int8-EF compressed gradient wire must
    clear the exact --min-accuracy threshold the committed f32 recipe
    uses (the fast 6-epoch both-arms variant runs in tier-1:
    tests/test_comms.py::test_digits_convergence_gate_compressed_matches_f32)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    script = os.path.join(
        os.path.dirname(__file__), os.pardir, "examples",
        "08_real_data_convergence.py",
    )
    proc = subprocess.run(
        [sys.executable, script, "--dataset", "digits", "--epochs", "25",
         "--min-accuracy", "0.97", "--grad-compression", "int8",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, (
        f"--- stdout ---\n{proc.stdout[-2000:]}\n--- stderr ---\n"
        f"{proc.stderr[-3000:]}"
    )
    assert "ACCEPTED" in proc.stdout


def test_transformer_lm_learns_deterministic_sequences():
    """Next-token accuracy >80% on affine token streams in 60 steps —
    the LM/attention/CE stack end to end, sharded over the mesh."""
    rt.reset_runtime()
    try:
        rt.initialize(MeshSpec(data=-1))
        plan = ParallelPlan(mesh=rt.current_runtime().mesh)
        model = TransformerLM(
            vocab_size=32, num_layers=2, num_heads=4, head_dim=8,
            max_len=32, attn_impl="full",
        )
        rng = np.random.default_rng(0)

        def make_batch(b=32):
            start = rng.integers(0, 32, b)
            stride = rng.integers(1, 4, b)
            toks = (start[:, None] + stride[:, None] * np.arange(33)) % 32
            return toks.astype(np.int32)

        state = create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32),
            optax.adamw(3e-3), plan=plan,
        )
        step = make_train_step()
        acc = None
        for i in range(60):
            t = make_batch()
            batch = plan.shard_batch({"input": t[:, :-1], "label": t[:, 1:]})
            state, metrics = step(state, batch)
            if i >= 50:  # steady-state window
                acc = merge_metrics(acc, metrics)
        summary = summarize_metrics(acc, prefix="")
        assert summary["accuracy"] > 0.8, summary
        assert summary["loss"] < 0.8, summary
    finally:
        rt.reset_runtime()


def test_digits_elastic_crash_resume_reaches_gate(tmp_path):
    """Elastic + accuracy in ONE run (VERDICT r04 #5): the recipe's first
    attempt is hard-killed (os._exit, no cleanup) MID-epoch, the
    supervisor restarts it, auto-resume picks up from the mid-epoch
    snapshot, and the finished run still clears the accuracy gate.
    Previously elasticity (tests/test_launch.py kill cases) and accuracy
    (the digits gate above) were proven separately."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "08_real_data_convergence.py"),
         "--dataset", "digits", "--epochs", "8", "--min-accuracy", "0.90",
         "--eval-interval", "4", "--elastic",
         "--simulate-crash-at-batch", "25",
         "--checkpoint-interval-batches", "4",
         "--workdir", str(tmp_path / "w")],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    out = proc.stdout
    assert proc.returncode == 0, out[-2000:] + proc.stderr[-2000:]
    # the crash really happened, mid-epoch (25 % 15-batch epochs != 0)...
    assert "[crash-sim] hard exit at global batch 25" in out, out[-2000:]
    # ...and the gate was cleared by the RESUMED attempt
    assert "recovered and finished after 1 restart(s)" in out, out[-2000:]
    assert "ACCEPTED" in out, out[-2000:]
