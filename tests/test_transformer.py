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


# (positions, runtime mesh, initializing) -> the form attn_impl="auto" takes.
# The first two are the benchmark's cells by name: they pin the programs the
# numbers in PERF_LEDGER.jsonl are for.
_AUTO_RULE = {
    "gpt2m_seq1024": (1024, None, False, "full"),
    "dsv2lite_seq4096": (4096, None, False, "blockwise"),
    "gpt2m_dp4_data_mesh": (1024, MeshSpec(data=-1), False, "full"),
    "one_below_the_threshold": (None, None, False, "full"),
    "at_the_threshold": (None, None, False, "blockwise"),
    "long_on_a_data_mesh": (8192, MeshSpec(data=-1), False, "blockwise"),
    "sequence_axis_sharded": (1024, MeshSpec(data=2, seq=2, model=2), False, "ring"),
    "sequence_axis_sharded_long": (
        4096, MeshSpec(data=2, seq=2, model=2), False, "ring"),
    "initializing": (4096, None, True, "full"),
    "initializing_sequence_axis_sharded": (
        1024, MeshSpec(data=2, seq=2, model=2), True, "full"),
}


@pytest.mark.parametrize("case", sorted(_AUTO_RULE))
def test_attend_auto_rule(case, monkeypatch):
    """One rule behind ``attn_impl="auto"``: the sequence axis sharded ->
    ring; else ``_BLOCKWISE_AUTO_LEN`` positions or more -> blockwise; else
    full.  Read off which attention core ``_attend`` calls."""
    import importlib

    from tpuframe.models import transformer

    length, spec, initializing, want = _AUTO_RULE[case]
    if length is None:
        length = transformer._BLOCKWISE_AUTO_LEN - (case == "one_below_the_threshold")
    took = []
    monkeypatch.setattr(transformer, "attention_reference",
                        lambda q, k, v, **kw: took.append("full") or v)
    monkeypatch.setattr(transformer, "ring_attention_local",
                        lambda q, k, v, **kw: took.append("ring") or v)
    monkeypatch.setattr(
        # by module path: tpuframe.ops re-exports the function under this name
        importlib.import_module("tpuframe.ops.blockwise_attention"),
        "blockwise_attention",
        lambda q, k, v, **kw: took.append("blockwise") or v)
    rt.reset_runtime()
    try:
        if spec is not None:
            rt.initialize(spec)
        qkv = jnp.zeros((2, length, 2, 8), jnp.float32)
        out = transformer._attend(qkv, qkv, qkv, impl="auto", causal=True,
                                  num_heads=2, initializing=initializing)
        assert out.shape == qkv.shape
        assert took == [want]
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
