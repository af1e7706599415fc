"""Transformer LM + sequence-parallel training on the simulated mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe.core import MeshSpec
from tpuframe.core import runtime as rt
from tpuframe.models.transformer import TransformerLM, transformer_tp_rules
from tpuframe.parallel import ParallelPlan
from tpuframe.train import create_train_state, make_train_step


@pytest.fixture()
def seq_runtime():
    """Runtime with a dp x sp x tp mesh; restored after the test."""
    rt.reset_runtime()
    runtime = rt.initialize(MeshSpec(data=2, seq=2, model=2))
    yield runtime
    rt.reset_runtime()


def _tokens(b=4, l=32, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, (b, l)).astype(np.int32))


@pytest.mark.slow
def test_full_vs_ring_forward_match(seq_runtime):
    tokens = _tokens()
    model_kw = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8, max_len=64)
    full = TransformerLM(attn_impl="full", **model_kw)
    ring = TransformerLM(attn_impl="ring", **model_kw)
    variables = full.init({"params": jax.random.PRNGKey(0)}, tokens, train=False)
    out_full = full.apply(variables, tokens, train=False)
    out_ring = ring.apply(variables, tokens, train=False)
    assert out_full.shape == (4, 32, 64)
    np.testing.assert_allclose(
        np.asarray(out_full), np.asarray(out_ring), atol=2e-4
    )


@pytest.mark.slow
def test_auto_dispatch_uses_ring_when_seq_sharded(seq_runtime):
    # auto == ring on this mesh (seq axis size 2): outputs must match full
    tokens = _tokens(b=2, l=16)
    kw = dict(vocab_size=64, num_layers=1, num_heads=4, head_dim=8, max_len=32)
    auto = TransformerLM(attn_impl="auto", **kw)
    full = TransformerLM(attn_impl="full", **kw)
    variables = auto.init({"params": jax.random.PRNGKey(1)}, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(auto.apply(variables, tokens, train=False)),
        np.asarray(full.apply(variables, tokens, train=False)),
        atol=2e-4,
    )


@pytest.mark.slow
def test_lm_train_step_dp_sp_tp(seq_runtime):
    """Full training step: ZeRO-3 + TP rules + sequence-parallel ring
    attention, one jitted step on the dp x sp x tp mesh."""
    plan = ParallelPlan(
        mesh=seq_runtime.mesh,
        zero_stage=3,
        rules=transformer_tp_rules(),
        min_shard_elems=1,
    )
    model = TransformerLM(
        vocab_size=64, num_layers=2, num_heads=4, head_dim=8, max_len=64,
        attn_impl="auto",
    )
    tokens = _tokens(b=4, l=32)
    state = create_train_state(
        model, jax.random.PRNGKey(0), tokens[:1], optax.adamw(1e-3), plan=plan,
        init_kwargs={"train": False},
    )
    # TP rules must actually shard a projection over 'model'
    specs = jax.tree.map(lambda a: a.sharding.spec, state.params)
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(specs)[0]
    }
    assert any("model" in str(s) for s in flat.values()), flat

    step_fn = make_train_step()
    labels = jnp.roll(tokens, -1, axis=1)
    batch = plan.shard_batch({"input": tokens, "label": labels})
    losses = []
    for _ in range(3):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss_sum"]) / float(metrics["count"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # tiny batch memorizes fast


def test_lm_without_runtime_defaults_to_full():
    rt.reset_runtime()
    try:
        tokens = _tokens(b=2, l=8)
        model = TransformerLM(
            vocab_size=32, num_layers=1, num_heads=2, head_dim=4, max_len=16
        )
        variables = model.init({"params": jax.random.PRNGKey(0)}, tokens, train=False)
        out = model.apply(variables, tokens, train=False)
        assert out.shape == (2, 8, 32)
    finally:
        rt.reset_runtime()


# (positions, runtime mesh, initializing, the dispatch plane's view of the
# backend, batch) -> the form attn_impl="auto" takes, and whether it runs per
# shard under shard_map.  The first three are the benchmark's cells by name on
# THIS backend (no kernel compiles on a CPU): they pin that tier-1's programs
# are the ones they were before the flash kernels entered the rule.  The
# ``_tpu`` cases pin the backend's answer to "compiled": the programs the
# numbers in PERF_LEDGER.jsonl are for since PR 30.
_AUTO_RULE = {
    "gpt2m_seq1024": (1024, None, False, None, 4, "full", False),
    "dsv2lite_seq4096": (4096, None, False, None, 2, "blockwise", False),
    "gpt2m_dp4_data_mesh": (1024, MeshSpec(data=-1), False, None, 16, "full", False),
    "one_below_the_threshold": (None, None, False, None, 2, "full", False),
    "at_the_threshold": (None, None, False, None, 2, "blockwise", False),
    "long_on_a_data_mesh": (8192, MeshSpec(data=-1), False, None, 8, "blockwise", False),
    "sequence_axis_sharded": (
        1024, MeshSpec(data=2, seq=2, model=2), False, None, 2, "ring", True),
    "sequence_axis_sharded_long": (
        4096, MeshSpec(data=2, seq=2, model=2), False, None, 2, "ring", True),
    "initializing": (4096, None, True, None, 2, "full", False),
    "initializing_sequence_axis_sharded": (
        1024, MeshSpec(data=2, seq=2, model=2), True, None, 2, "full", False),
    "gpt2m_seq1024_tpu": (1024, None, False, "one_chip", 4, "blockwise", False),
    "dsv2lite_seq4096_tpu": (4096, None, False, "one_chip", 2, "blockwise", False),
    "gpt2m_dp4_data_mesh_tpu": (
        1024, MeshSpec(data=-1), False, "compiled", 16, "blockwise", True),
    "heads_over_the_model_axis_tpu": (
        1024, MeshSpec(data=4, model=2), False, "compiled", 4, "blockwise", True),
    "batch_does_not_divide_tpu": (
        1024, MeshSpec(data=-1), False, "compiled", 12, "full", False),
    "heads_do_not_divide_tpu": (
        1024, MeshSpec(data=1, model=8), False, "compiled", 4, "full", False),
    "long_batch_does_not_divide_tpu": (
        4096, MeshSpec(data=-1), False, "compiled", 4, "blockwise", False),
    "below_the_floor_tpu": ("floor-1", None, False, "one_chip", 4, "full", False),
    "at_the_floor_tpu": ("floor", None, False, "one_chip", 4, "blockwise", False),
    "below_the_floor_on_a_mesh_tpu": (
        "floor-1", MeshSpec(data=-1), False, "compiled", 8, "full", False),
    "many_devices_no_mesh_tpu": (1024, None, False, "compiled", 8, "full", False),
    "disable_pallas_tpu": (1024, None, False, "disabled", 4, "full", False),
    "disable_pallas_on_a_mesh_tpu": (
        1024, MeshSpec(data=-1), False, "disabled", 16, "full", False),
    "sequence_axis_sharded_tpu": (
        1024, MeshSpec(data=2, seq=2, model=2), False, "compiled", 2, "ring", True),
    "initializing_tpu": (1024, None, True, "one_chip", 4, "full", False),
    "interpret_mode_on_a_mesh": (
        1024, MeshSpec(data=-1), False, "interpret", 8, "blockwise", True),
}


@pytest.mark.parametrize("case", sorted(_AUTO_RULE))
def test_attend_auto_rule(case, monkeypatch):
    """One rule behind ``attn_impl="auto"``: the sequence axis sharded ->
    ring; else from ``_FLASH_AUTO_LEN`` positions on, where the flash
    kernels would run for the call -> blockwise (per shard on a mesh);
    else ``_BLOCKWISE_AUTO_LEN`` positions or more -> blockwise (the scan
    schedule, never under shard_map); else full.  Read off which
    attention core ``_attend`` calls, and whether inside a manual region."""
    import importlib

    from tpuframe.models import transformer
    from tpuframe.ops import dispatch

    length, spec, initializing, backend, batch, want, per_shard = _AUTO_RULE[case]
    if length is None:
        length = transformer._BLOCKWISE_AUTO_LEN - (case == "one_below_the_threshold")
    elif isinstance(length, str):
        length = transformer._FLASH_AUTO_LEN - length.endswith("-1")
    if backend == "disabled":  # a TPU with the off switch thrown
        monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    elif backend == "interpret":
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    elif backend is not None:
        monkeypatch.setattr(dispatch, "pallas_mode", lambda: "compiled")
        if backend == "one_chip":
            monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    took = []

    def core(name):
        return lambda q, k, v, **kw: took.append(
            (name, dispatch.inside_shard_map())) or v

    monkeypatch.setattr(transformer, "attention_reference", core("full"))
    monkeypatch.setattr(transformer, "ring_attention_local", core("ring"))
    monkeypatch.setattr(
        # by module path: tpuframe.ops re-exports the function under this name
        importlib.import_module("tpuframe.ops.blockwise_attention"),
        "blockwise_attention", core("blockwise"))
    transformer._blockwise_per_shard.clear_cache()  # traced with the real op?
    rt.reset_runtime()
    try:
        if spec is not None:
            rt.initialize(spec)
        qkv = jnp.zeros((batch, length, 4, 8), jnp.float32)
        out = transformer._attend(qkv, qkv, qkv, impl="auto", causal=True,
                                  num_heads=4, initializing=initializing)
        assert out.shape == qkv.shape
        assert took == [(want, per_shard)]
    finally:
        rt.reset_runtime()


class TestRemat:
    def test_remat_lm_identical_outputs_and_grads(self):
        """remat=True changes memory/compute scheduling, never numerics."""
        kw = dict(vocab_size=32, num_layers=2, num_heads=2, head_dim=8,
                  max_len=16, attn_impl="full")
        tokens = _tokens(b=2, l=16, vocab=32)
        base = TransformerLM(**kw)
        variables = base.init({"params": jax.random.PRNGKey(0)}, tokens)
        rematted = TransformerLM(remat=True, **kw)
        # identical param structure: remat wraps apply, not parameters
        v2 = rematted.init({"params": jax.random.PRNGKey(0)}, tokens)
        assert jax.tree_util.tree_structure(variables) == jax.tree_util.tree_structure(v2)

        out_a = base.apply(variables, tokens)
        out_b = rematted.apply(variables, tokens)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), atol=1e-6)

        def loss(m, p):
            logits = m.apply({"params": p}, tokens, train=True)
            return jnp.mean(logits ** 2)

        g_a = jax.grad(lambda p: loss(base, p))(variables["params"])
        g_b = jax.grad(lambda p: loss(rematted, p))(variables["params"])
        for a, b in zip(jax.tree.leaves(g_a), jax.tree.leaves(g_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_remat_vit_trains(self):
        from tpuframe.data import DataLoader, SyntheticImageDataset
        from tpuframe.models import ViT
        from tpuframe.train import Trainer

        ds = SyntheticImageDataset(n=32, image_size=16, num_classes=4, seed=0)
        tr = Trainer(
            ViT(num_classes=4, patch_size=4, hidden_dim=32, num_layers=2,
                num_heads=4, remat=True, attn_impl="full"),
            train_dataloader=DataLoader(ds, batch_size=16),
            max_duration="1ep", eval_interval=0, log_interval=0,
        )
        result = tr.fit()
        assert result.error is None
        assert np.isfinite(result.metrics["train_loss"])
