"""MoE tests: dense-dispatch correctness, capacity behavior, expert
parallelism over the ``expert`` mesh axis."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuframe.core import MeshSpec
from tpuframe.models import MoEMLP, moe_rules
from tpuframe.parallel import ParallelPlan


def _tokens(n=16, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))


class TestMoEMLP:
    def test_single_expert_equals_plain_mlp(self):
        # E=1, k=1, generous capacity: routing is the identity, so the MoE
        # must equal the plain gelu MLP with that expert's weights.
        x = _tokens()
        moe = MoEMLP(num_experts=1, top_k=1, capacity_factor=2.0, mlp_ratio=2)
        variables = moe.init(jax.random.PRNGKey(0), x)
        out = moe.apply(variables, x)
        w_in = variables["params"]["w_in"][0]
        w_out = variables["params"]["w_out"][0]
        want = jax.nn.gelu(x @ w_in) @ w_out
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)

    def test_topk_routing_mixes_and_is_finite(self):
        x = _tokens(n=32, d=8, seed=1)
        moe = MoEMLP(num_experts=4, top_k=2, mlp_ratio=2)
        variables = moe.init(jax.random.PRNGKey(1), x)
        out, aux = moe.apply(variables, x, mutable=["aux_loss"])
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()
        # balanced-ish init: aux loss near its weight (sum p*f * E ~ 1)
        aux_val = float(jax.tree.leaves(aux)[0])
        assert 0 < aux_val < 10 * 1e-2

    def test_capacity_truncation_drops_tokens(self):
        # capacity ~0: every token overflows, so the output must be zero
        x = _tokens(n=16, d=4, seed=2)
        moe = MoEMLP(num_experts=2, top_k=1, capacity_factor=1e-9, mlp_ratio=1)
        variables = moe.init(jax.random.PRNGKey(2), x)
        out = moe.apply(variables, x)
        # capacity clamps to 1 slot/expert: at most 2 tokens survive
        nonzero_rows = int(np.sum(np.any(np.asarray(out) != 0, axis=-1)))
        assert nonzero_rows <= 2

    def test_3d_input_and_grads_flow(self):
        x = _tokens(n=24, d=8, seed=3).reshape(2, 12, 8)
        moe = MoEMLP(num_experts=4, top_k=2, mlp_ratio=2)
        variables = moe.init(jax.random.PRNGKey(3), x)

        def loss(p):
            return jnp.mean(moe.apply({"params": p}, x) ** 2)

        grads = jax.grad(loss)(variables["params"])
        for leaf in jax.tree.leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()
        # expert weights receive gradient (routing reaches them)
        assert float(jnp.sum(jnp.abs(grads["w_in"]))) > 0

    def test_expert_sharded_matches_unsharded(self):
        # the same forward with w_in/w_out sharded over a 4-way expert axis
        mesh = MeshSpec(expert=4, data=2).build()
        plan = ParallelPlan(mesh=mesh, rules=moe_rules(), min_shard_elems=1)
        x = _tokens(n=32, d=8, seed=4)
        moe = MoEMLP(num_experts=4, top_k=2, mlp_ratio=2)
        variables = moe.init(jax.random.PRNGKey(4), x)
        want = moe.apply(variables, x)

        sharded = plan.shard_params(variables["params"])
        spec = sharded["w_in"].sharding.spec
        assert spec[0] == "expert", spec  # rules actually engaged
        got = jax.jit(lambda p, x: moe.apply({"params": p}, x))(sharded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_trains_inside_transformer_style_step(self):
        # MoE as the MLP of a tiny classifier: loss falls under adam
        from flax import linen as nn

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                x = x.reshape((x.shape[0], -1))
                x = nn.Dense(16)(x)
                x = MoEMLP(num_experts=4, top_k=2, mlp_ratio=2, name="moe")(
                    x, train=train
                )
                return nn.Dense(4)(x)

        from tpuframe.train import create_train_state, make_train_step

        rng = np.random.default_rng(5)
        batch = {
            "image": jnp.asarray(rng.standard_normal((16, 4, 4, 1)).astype(np.float32)),
            "label": jnp.asarray(rng.integers(0, 4, (16,)).astype(np.int32)),
        }
        state = create_train_state(
            Tiny(), jax.random.PRNGKey(0), batch["image"][:1], optax.adam(3e-3)
        )
        step = make_train_step(donate=False)
        losses = []
        for _ in range(5):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss_sum"]))
        assert losses[-1] < losses[0]


class TestMoEGatingKernel:
    """The fused scatter/gather dispatch vs the dense-einsum oracle
    (``tpuframe.ops.moe_gating`` — the OPS_REGISTRY parity pin)."""

    def _case(self, n=64, d=8, e=4, k=2, h=16, seed=0, capacity=None):
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        logits = jnp.asarray(rng.standard_normal((n, e)).astype(np.float32))
        gate_vals, gate_idx = jax.lax.top_k(jax.nn.softmax(logits), k)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
        w_in = jnp.asarray(rng.standard_normal((e, d, h)).astype(np.float32) * 0.1)
        w_out = jnp.asarray(rng.standard_normal((e, h, d)).astype(np.float32) * 0.1)
        if capacity is None:
            capacity = max(1, (k * n) // e)
        return tokens, gate_vals, gate_idx, w_in, w_out, capacity

    def test_fused_matches_reference(self):
        from tpuframe.ops.moe_gating import (
            moe_dispatch_combine, moe_dispatch_combine_reference,
        )

        for seed in range(3):
            args = self._case(seed=seed)
            *inputs, capacity = args
            want = moe_dispatch_combine_reference(*inputs, capacity=capacity)
            got = moe_dispatch_combine(*inputs, capacity=capacity, fused=True)
            # bit-close, not bit-identical: the scatter accumulates in a
            # different order than the einsum reduction (atol pinned by
            # the module docstring)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )

    def test_fused_matches_reference_tight_capacity(self):
        from tpuframe.ops.moe_gating import (
            moe_dispatch_combine, moe_dispatch_combine_reference,
        )

        # capacity 1: most slots overflow — drop semantics must agree
        *inputs, _ = self._case(n=32, e=2, k=2, seed=7)
        want = moe_dispatch_combine_reference(*inputs, capacity=1)
        got = moe_dispatch_combine(*inputs, capacity=1, fused=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_fused_grads_match_reference(self):
        from tpuframe.ops.moe_gating import (
            moe_dispatch_combine, moe_dispatch_combine_reference,
        )

        tokens, gate_vals, gate_idx, w_in, w_out, capacity = self._case(n=32)

        def loss(fn, t, wi, wo):
            return jnp.sum(fn(t, gate_vals, gate_idx, wi, wo,
                              capacity=capacity) ** 2)

        g_ref = jax.grad(lambda *a: loss(moe_dispatch_combine_reference, *a),
                         argnums=(0, 1, 2))(tokens, w_in, w_out)
        fused = lambda *a, **kw: moe_dispatch_combine(*a, fused=True, **kw)  # noqa: E731
        g_fus = jax.grad(lambda *a: loss(fused, *a),
                         argnums=(0, 1, 2))(tokens, w_in, w_out)
        for a, b in zip(g_ref, g_fus):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_kernels_off_forces_reference_path(self, monkeypatch):
        from tpuframe.ops import moe_gating
        from tpuframe.ops.moe_gating import moe_dispatch_combine

        *inputs, capacity = self._case(n=16)
        calls = []
        real = moe_gating.moe_dispatch_combine_reference
        monkeypatch.setattr(
            moe_gating, "moe_dispatch_combine_reference",
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        off = moe_dispatch_combine(*inputs, capacity=capacity, fused=False)
        assert calls == [1]
        on = moe_dispatch_combine(*inputs, capacity=capacity)
        assert calls == [1]  # the default is the fused form
        np.testing.assert_allclose(np.asarray(on), np.asarray(off), atol=1e-5)


def test_aux_loss_reaches_training_objective():
    # the framework train step must fold the sown balance loss into the
    # gradient: router grads differ between aux weight 0 and a large one
    from flax import linen as nn

    from tpuframe.train import create_train_state, make_train_step

    def build(aux_w):
        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                x = x.reshape((x.shape[0], -1))
                x = nn.Dense(8, name="proj")(x)
                x = MoEMLP(
                    num_experts=4, top_k=1, mlp_ratio=1,
                    aux_loss_weight=aux_w, name="moe",
                )(x, train=train)
                return nn.Dense(4, name="out")(x)

        return Tiny()

    rng = np.random.default_rng(7)
    batch = {
        "image": jnp.asarray(rng.standard_normal((16, 2, 2, 1)).astype(np.float32)),
        "label": jnp.asarray(rng.integers(0, 4, (16,)).astype(np.int32)),
    }
    step = make_train_step(donate=False)
    routers = []
    for aux_w in (0.0, 10.0):
        state = create_train_state(
            build(aux_w), jax.random.PRNGKey(0), batch["image"][:1],
            optax.sgd(1e-1),
        )
        state, _ = step(state, batch)
        routers.append(np.asarray(state.params["moe"]["router"]["kernel"]))
    assert not np.allclose(routers[0], routers[1]), (
        "aux loss weight had no effect on the router update"
    )


class TestMoETransformer:
    @pytest.mark.slow
    def test_moe_lm_trains_with_expert_parallelism(self):
        """GShard-style MoE transformer: MoE MLP in every block, expert
        weights sharded over the expert axis, router aux loss folded into
        the objective by the train step."""
        import optax

        from tpuframe.core import runtime as rt
        from tpuframe.models import TransformerLM
        from tpuframe.train import create_train_state, make_train_step

        rt.reset_runtime()
        try:
            runtime = rt.initialize(MeshSpec(data=2, expert=4))
            plan = ParallelPlan(mesh=runtime.mesh, rules=moe_rules(),
                                min_shard_elems=1)
            model = TransformerLM(vocab_size=32, num_layers=2, num_heads=2,
                                  head_dim=8, max_len=16, attn_impl="full",
                                  moe_experts=4)
            toks = np.random.default_rng(0).integers(0, 32, (8, 16)).astype(np.int32)
            state = create_train_state(model, jax.random.PRNGKey(0),
                                       jnp.asarray(toks[:1]), optax.adamw(1e-2),
                                       plan=plan)
            # expert weights actually sharded over the expert axis
            specs = jax.tree.leaves(
                jax.tree.map(lambda a: str(a.sharding.spec), state.params)
            )
            assert any("expert" in sp for sp in specs), specs
            step = make_train_step()
            batch = plan.shard_batch({"input": toks, "label": toks})
            losses = []
            for _ in range(8):
                state, m = step(state, batch)
                losses.append(float(m["loss_sum"]))
            assert np.isfinite(losses).all()
            assert losses[-1] < losses[0]
            # router aux loss is live: every MoE block sows a nonzero
            # balance term (the step folds these into the objective)
            _, collected = model.apply(
                {"params": jax.device_get(state.params)},
                jnp.asarray(toks), train=True, mutable=["aux_loss"],
            )
            sown = jax.tree.leaves(collected["aux_loss"])
            assert sown and all(float(v) != 0.0 for v in sown)
        finally:
            rt.reset_runtime()

    def test_moe_lm_param_tree_has_moe_blocks(self):
        from tpuframe.models import TransformerLM

        m = TransformerLM(vocab_size=32, num_layers=1, num_heads=2, head_dim=8,
                          max_len=16, attn_impl="full", moe_experts=2)
        v = m.init({"params": jax.random.PRNGKey(0)},
                   jnp.zeros((1, 16), jnp.int32))
        blk = v["params"]["block0"]
        assert "moe" in blk and "mlp_in" not in blk
        assert blk["moe"]["w_in"].shape[0] == 2  # expert-major weights
