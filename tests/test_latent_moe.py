"""Latent attention, YaRN rotary, the no-drop expert layer with held and
shared experts, and ``ops.grouped_matmul``: the program against the plain
reference of ``chipbench/reference/deepseek-v2-lite.py`` and against
hand-computed values, at small sizes on the CPU."""

import functools
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe.models import TransformerLM
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP, slot_bound
from tpuframe.ops.blockwise_attention import blockwise_attention
from tpuframe.ops import dispatch
from tpuframe.ops.grouped_matmul import (
    RAGGED_TILE_ROWS,
    grouped_matmul,
    grouped_matmul_grads,
    grouped_matmul_reference,
    row_tile,
    tiles_visited,
)
from tpuframe.ops.ring_attention import attention_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes (1 dense + 2 sparse layers, 8
    experts of which 4 held), its reference, seeded weights and a batch."""
    with open(os.path.join(ROOT, "chipbench", "configs", "deepseek-v2-lite.json")) as f:
        full = json.load(f)
    cfg = _merge(full, full["rehearsal"])
    ref = correct.load_by_name("reference", cfg["name"])
    params = correct.init_params(ref.param_shapes(cfg), 2147483999)
    rng = np.random.default_rng(5)
    rows = rng.integers(0, cfg["vocab_size"], (2, cfg["seq_len"] + 1))
    return {"full": full, "cfg": cfg, "ref": ref, "params": params,
            "x": jnp.asarray(rows[:, :-1], jnp.int32), "y": jnp.asarray(rows[:, 1:], jnp.int32),
            "model": TransformerLM(**cfg["model"]["kwargs"])}


def _program_losses(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
    aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
    return ce, aux, upd


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])

    def test_loss_and_every_gradient_leaf(self, small):
        m, p, x, y = small["model"], small["params"], small["x"], small["y"]

        def objective(params):
            ce, aux, _ = _program_losses(m, params, x, y)
            return ce + aux, ce

        (_, ce), grads = jax.value_and_grad(objective, has_aux=True)(p)
        want_loss, want = jax.value_and_grad(small["ref"].loss)(p, x, y, small["cfg"])
        assert abs(float(ce) - float(want_loss)) < 1e-5
        assert float(want_loss) == pytest.approx(math.log(small["cfg"]["vocab_size"]), abs=0.2)
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                jax.tree.leaves(want)):
            err = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12))
            assert err < 2e-4, (jax.tree_util.keystr(path), err)

    def test_balance_loss_and_its_gradient_on_the_router(self, small):
        m, p, x, y = small["model"], small["params"], small["x"], small["y"]
        aux, g = jax.value_and_grad(lambda q: _program_losses(m, q, x, y)[1])(p)
        want, gw = jax.value_and_grad(
            lambda q: small["ref"].logits(q, x, small["cfg"])[1])(p)
        assert float(aux) == pytest.approx(float(want), rel=1e-5) and float(aux) > 0
        for i in (1, 2):
            a = g[f"block{i}"]["moe"]["router"]["kernel"]
            b = gw[f"block{i}"]["moe"]["router"]["kernel"]
            assert float(jnp.linalg.norm(b)) > 0
            assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-4

    def test_counters_say_how_many_assignments_were_here(self, small):
        m, p, x, y = small["model"], small["params"], small["x"], small["y"]
        cfg = small["cfg"]
        _, _, upd = _program_losses(m, p, x, y)
        pairs = x.size * cfg["num_experts_per_tok"]
        for i in (1, 2):
            c = upd["counters"][f"block{i}"]["moe"]
            here = float(c["moe/assignments_here"])
            assert 0 < here < pairs
            assert float(c["moe/rows_computed"]) >= here
            # 4 of 8 experts held: twice the balanced share is every slot
            assert float(c["moe/slot_rows"]) == pairs and float(c["moe/overflow_calls"]) == 0.0
            assert float(upd["gauges"][f"block{i}"]["moe"]["moe/expert_load_max_over_mean"]) >= 1.0
        assert "block0" not in upd["counters"]  # the leading dense layer

    def test_an_expert_left_out_is_seen(self, small):
        p = jax.tree.map(lambda a: a, small["params"])
        moe = dict(p["block1"]["moe"])
        moe["w_out"] = moe["w_out"].at[1].set(0.0)
        p = {**p, "block1": {**p["block1"], "moe": moe}}
        ce, _, _ = _program_losses(small["model"], p, small["x"], small["y"])
        want = small["ref"].loss(small["params"], small["x"], small["y"], small["cfg"])
        assert abs(float(ce) - float(want)) > 1e-6


class TestSharesAddUpToTheUncutLayer:
    def test_routed_parts_of_all_shares_plus_shared_once(self, small):
        cfg, ref = small["cfg"], small["ref"]
        uncut = {**cfg, "n_routed_experts": 8, "held_first": 0}
        d, e, h = cfg["hidden_size"], 8, cfg["moe_intermediate_size"]
        k = jax.random.split(jax.random.PRNGKey(3), 8)
        n = lambda key, *s: 0.2 * jax.random.normal(key, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(k[0], d, e)},
             "w_gate": n(k[1], e, d, h), "w_in": n(k[2], e, d, h), "w_out": n(k[3], e, h, d),
             "shared_gate": {"kernel": n(k[4], d, 2 * h)}, "shared_in": {"kernel": n(k[5], d, 2 * h)},
             "shared_out": {"kernel": n(k[6], 2 * h, d)}}
        x = jax.random.normal(k[7], (2, 16, d), jnp.float32)
        want, _ = ref._moe(p, x, uncut, lambda f: f, False)
        shared = ref._gated(x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                            p["shared_out"]["kernel"], lambda f: f)
        total = shared
        for first in range(0, e, 2):   # four chips, two experts each
            layer = MoEMLP(num_experts=e, top_k=cfg["num_experts_per_tok"], expert_dim=h,
                           held=(first, 2), gated=True, shared_dim=2 * h, renormalize=False,
                           seq_aux=True, capacity_factor=None)
            share = {**p, **{w: p[w][first:first + 2] for w in ("w_gate", "w_in", "w_out")}}
            out = layer.apply({"params": share}, x)
            total = total + (out - shared)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-5, atol=2e-6)


class TestNoTokenDropped:
    def _layer(self, held):
        return MoEMLP(num_experts=8, top_k=1, expert_dim=8, held=held, gated=True,
                      renormalize=False, capacity_factor=None)

    def _params(self, d=4):
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        router = jnp.zeros((d, 8)).at[:, 0].set(5.0)   # positive inputs all choose expert 0
        return {"router": {"kernel": router},
                "w_gate": jax.random.normal(k[0], (2, d, 8)), "w_in": jax.random.normal(k[1], (2, d, 8)),
                "w_out": jax.random.normal(k[2], (2, 8, d))}

    def test_every_token_chooses_one_held_expert(self):
        p = self._params()
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (3, 10, 4))) + 0.1
        out, upd = self._layer((0, 2)).apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
        probs = jax.nn.softmax(x @ p["router"]["kernel"], -1)[..., 0:1]
        dense = (jax.nn.silu(x @ p["w_gate"][0]) * (x @ p["w_in"][0])) @ p["w_out"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(probs * dense), rtol=1e-5, atol=1e-6)
        assert float(upd["counters"]["moe/assignments_here"]) == 30.0   # 30 of 30: far past capacity 1.25
        assert float(upd["gauges"]["moe/expert_load_max_over_mean"]) == 2.0

    def test_no_token_chooses_a_held_expert(self):
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (3, 10, 4))) + 0.1
        out, upd = self._layer((4, 2)).apply({"params": self._params()}, x,
                                             mutable=["counters", "gauges", "aux_loss"])
        assert float(jnp.max(jnp.abs(out))) == 0.0
        assert float(upd["counters"]["moe/assignments_here"]) == 0.0
        assert float(upd["counters"]["moe/rows_computed"]) == 0.0

    def test_held_experts_need_the_no_drop_layer(self):
        with pytest.raises(ValueError, match="no-drop"):
            MoEMLP(num_experts=8, held=(0, 2)).init(jax.random.PRNGKey(0), jnp.ones((1, 4, 4)))


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its inner jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestSlotBuffersBoundedToTheRowsRoutedHere:
    """2,048 tokens x 2 choices over 16 experts of which 2 are held: 4,096
    (token, choice) pairs, buffers of 1,024 slots, against a dense oracle."""

    E, K, D, H, N, HELD, PAIRS, CAP = 16, 2, 8, 16, 2048, 2, 4096, 1024

    def _layer(self, held=(0, 2), **kw):
        return MoEMLP(num_experts=self.E, top_k=self.K, expert_dim=self.H, held=held,
                      gated=True, capacity_factor=None, **kw)

    def _params(self, router=None):
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        n = lambda key, *s: 0.3 * jax.random.normal(key, s, jnp.float32)  # noqa: E731
        return {"router": {"kernel": n(k[0], self.D, self.E) if router is None else router},
                "w_gate": n(k[1], self.HELD, self.D, self.H), "w_in": n(k[2], self.HELD, self.D, self.H),
                "w_out": n(k[3], self.HELD, self.H, self.D)}

    def _tokens(self):
        return jax.random.normal(jax.random.PRNGKey(4), (self.N, self.D), jnp.float32)

    def _router_sending(self, here):
        """A router under which exactly ``here`` of the 4,096 pairs choose a held
        expert: the first ``here`` tokens (their first feature set to +1) put
        expert 0 first and expert 2 second, the others experts 2 and 3."""
        x = self._tokens().at[:, 0].set(-1.0).at[:here, 0].set(1.0)
        router = jnp.zeros((self.D, self.E)).at[0, 0].set(8.0).at[0, 3].set(-4.0)
        return x.at[:, 1].set(1.0), router.at[1, 2].set(2.0).at[1, 3].set(1.0)

    def _oracle(self, p, x):
        """Every token through every held expert, weighed by its renormalised gate."""
        gates, chosen = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["kernel"], -1), self.K)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        out = 0.0
        for e in range(self.HELD):
            weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1, keepdims=True)
            out = out + weight * ((jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_in"][e])) @ p["w_out"][e])
        return out

    def _agrees_with_the_oracle(self, p, x):
        layer = self._layer()
        out, upd = layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(self._oracle(p, x)), rtol=2e-5, atol=2e-6)
        co = jax.random.normal(jax.random.PRNGKey(5), out.shape)
        got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x) * co), argnums=(0, 1))(p, x)
        want = jax.grad(lambda p, x: jnp.sum(self._oracle(p, x) * co), argnums=(0, 1))(p, x)
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
            assert float(jnp.linalg.norm(w)) > 0, jax.tree_util.keystr(path)
            err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            assert err < 2e-5, (jax.tree_util.keystr(path), err)
        return {k: float(v) for k, v in upd["counters"].items()}

    @pytest.mark.parametrize("pairs, count, experts, want", [
        (8192 * 6, 8, 64, 12288),     # dsv2lite_seq4096: a quarter of 49,152
        (8192 * 8, 16, 128, 16384),   # sdar_blockdiff_seq4096: a quarter of 65,536
        (4096, 2, 16, 1024),          # this class
        (4096, 16, 16, 4096),         # all experts held: a slot a pair
        (4096, 9, 16, 4096),          # more than half of them: the same
        (30, 2, 8, 30),               # small shapes: a tile is more than the pairs
        (5000, 1, 16, 1024),          # 2 x 313 = 626 rows, in whole tiles of 512
        (4096, 1, 16, 512),
    ])
    def test_the_bound_is_twice_the_balanced_share_in_whole_tiles(self, pairs, count, experts, want):
        assert slot_bound(pairs, count, experts) == want

    def test_bounded_path_output_and_every_gradient_leaf(self):
        counters = self._agrees_with_the_oracle(self._params(), self._tokens())
        assert 0 < counters["moe/assignments_here"] < self.CAP
        assert counters["moe/slot_rows"] == self.CAP
        assert counters["moe/overflow_calls"] == 0.0

    def test_a_router_that_overflows_the_bound_runs_a_second_window(self):
        x, router = self._router_sending(1500)
        counters = self._agrees_with_the_oracle(self._params(router), x)
        assert counters["moe/assignments_here"] == 1500.0
        assert counters["moe/slot_rows"] == 2 * self.CAP
        assert counters["moe/overflow_calls"] == 1.0

    def test_every_pair_routed_here(self):
        router = jnp.zeros((self.D, self.E)).at[:, 0].set(3.0).at[:, 1].set(2.7)
        counters = self._agrees_with_the_oracle(self._params(router), jnp.abs(self._tokens()) + 0.1)
        assert counters["moe/assignments_here"] == self.PAIRS
        assert counters["moe/slot_rows"] == self.PAIRS and counters["moe/overflow_calls"] == 1.0

    @pytest.mark.parametrize("here, rows, overflow", [
        (1024, 1024, 0.0), (1025, 2048, 1.0), (2048, 2048, 1.0)])
    def test_exactly_the_bound_fits_and_one_more_does_not(self, here, rows, overflow):
        x, router = self._router_sending(here)
        counters = self._agrees_with_the_oracle(self._params(router), x)
        assert counters["moe/assignments_here"] == here
        assert counters["moe/slot_rows"] == rows and counters["moe/overflow_calls"] == overflow

    def _grad_jaxpr(self, layer, p, x):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda p, x: jnp.sum(layer.apply({"params": p}, x) ** 2), argnums=(0, 1)))(p, x).jaxpr

    @pytest.mark.parametrize("held, tokens", [(None, 2048), ((0, 9), 2048), ((0, 2), 15)])
    def test_where_no_bound_applies_the_program_has_no_branch_or_loop(self, held, tokens):
        count = self.E if held is None else held[1]
        p = {**self._params(), **{w: jnp.ones((count,) + s) for w, s in (
            ("w_gate", (self.D, self.H)), ("w_in", (self.D, self.H)), ("w_out", (self.H, self.D)))}}
        x = self._tokens()[:tokens]
        assert slot_bound(tokens * self.K, count, self.E) == tokens * self.K
        names = {e.primitive.name for e in _eqns(self._grad_jaxpr(self._layer(held), p, x))}
        assert not names & {"cond", "while"} and "gather" in names
        _, upd = self._layer(held).apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
        assert float(upd["counters"]["moe/slot_rows"]) == tokens * self.K
        assert float(upd["counters"]["moe/overflow_calls"]) == 0.0

    def test_no_array_but_the_unsort_has_a_row_for_every_slot(self):
        """What crosses from the forward to the backward pass is 1,024 rows long
        (differentiating a plain ``cond`` between buffers of 1,024 and of 4,096
        slots would save both branches' arrays).  On the way the un-sort alone
        reads a row a pair out of those buffers, the choices in front, and sums
        them at once: a gather and its mask, nothing an expert computed."""
        layer, p, x = self._layer(), self._params(), self._tokens()
        _, pullback = jax.vjp(lambda p, x: layer.apply({"params": p}, x), p, x)
        saved = [tuple(a.shape) for a in jax.tree.leaves(pullback) if hasattr(a, "shape")]
        assert saved.count((self.CAP, self.D)) == 2 and saved.count((self.CAP, self.H)) == 3
        # rows of tokens or of an expert's width; the counting route's own tables
        # (a block of 256 pairs' keys and gate values a slot, PR 49) are none
        wide = lambda shape: (len(shape) >= 2 and shape[-1] in (self.D, self.H)  # noqa: E731
                              and int(np.prod(shape[:-1])) >= self.PAIRS)
        assert not [s for s in saved if wide(s)]
        made = {(e.primitive.name, tuple(v.aval.shape)) for e in _eqns(self._grad_jaxpr(layer, p, x))
                for v in e.outvars if wide(v.aval.shape)}
        assert {shape for _, shape in made} == {(self.K, self.N, self.D)}, made
        assert {name for name, _ in made} <= {  # "jit" is jnp.where's own
            "gather", "select_n", "broadcast_in_dim", "convert_element_type", "jit"}, made
        # the layer that holds every expert does gather a row a slot, as it always has
        everywhere = self._grad_jaxpr(self._layer(None), {**p, **{
            w: jnp.ones((self.E,) + p[w].shape[1:]) for w in ("w_gate", "w_in", "w_out")}}, x)
        assert any(wide(v.aval.shape) for e in _eqns(everywhere) for v in e.outvars)


class TestGroupedMatmul:
    @pytest.mark.parametrize("sizes", [(3, 0, 5, 0), (0, 0, 0, 0), (16, 0, 0, 0), (1, 2, 3, 4)])
    def test_forward_and_both_gradients_with_empty_groups(self, sizes):
        k = jax.random.split(jax.random.PRNGKey(7), 2)
        rows = jax.random.normal(k[0], (16, 6), jnp.float32)
        w = jax.random.normal(k[1], (4, 6, 5), jnp.float32)
        g = jnp.asarray(sizes, jnp.int32)
        np.testing.assert_allclose(np.asarray(grouped_matmul(rows, w, g)),
                                   np.asarray(grouped_matmul_reference(rows, w, g)),
                                   rtol=1e-5, atol=1e-6)
        loss = lambda f: lambda r, m: jnp.sum(jnp.sin(f(r, m, g)))  # noqa: E731
        got = jax.grad(loss(grouped_matmul), argnums=(0, 1))(rows, w)
        want = jax.grad(loss(grouped_matmul_reference), argnums=(0, 1))(rows, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        # rows past the groups come out zero, an empty group's weight gets no gradient
        assert float(jnp.max(jnp.abs(grouped_matmul(rows, w, g)[sum(sizes):]), initial=0.0)) == 0.0
        for i, s in enumerate(sizes):
            if s == 0:
                assert float(jnp.max(jnp.abs(got[1][i]))) == 0.0

    def test_tiles_visited_counts_a_shared_tile_for_each_group(self):
        sizes = jnp.asarray([768, 0, 300, 1], jnp.int32)
        # [0,768): tiles 0,1; [768,1068): tiles 1,2; [1068,1069): tile 2
        assert int(tiles_visited(sizes, 512)) == 2 + 0 + 2 + 1

    def test_shapes_are_checked(self):
        with pytest.raises(ValueError):
            grouped_matmul(jnp.ones((4, 3)), jnp.ones((2, 4, 5)), jnp.ones((2,), jnp.int32))


#: the kernels under interpret at cut shapes: 320 rows in tiles of 64, five groups
_M, _TILE = 320, 64
_GROUPS = {
    "boundary_inside_a_tile": (100, 60, 30, 40, 20),
    "a_group_of_several_tiles": (200, 10, 10, 10, 10),
    "empty_first": (0, 100, 50, 20, 30),
    "empty_middle": (64, 0, 0, 128, 5),
    "empty_last": (70, 80, 90, 10, 0),
    "sum_equal_to_m_on_tile_edges": (64, 64, 64, 64, 64),
    "sum_equal_to_m_off_tile_edges": (100, 100, 60, 30, 30),
    "sum_well_under_m": (10, 0, 7, 0, 3),
    "every_group_empty": (0, 0, 0, 0, 0),
}


@functools.lru_cache(maxsize=None)
def _kernel_products(k, n):
    """(operands, kernels, oracle) at (320, k) x (5, k, n) in float32: each
    a jitted ``(rows, weights, sizes, cotangent) -> (result, d rows, d
    weights)``, the group sizes an argument so one trace serves every case."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    operands = (jax.random.normal(keys[0], (_M, k), jnp.float32),
                jax.random.normal(keys[1], (5, k, n), jnp.float32) / 8,
                jax.random.normal(keys[2], (_M, n), jnp.float32))

    def both(op):
        def run(rows, w, sizes, g):
            y, vjp = jax.vjp(lambda r, m: op(r, m, sizes), rows, w)
            return (y,) + vjp(g)
        return jax.jit(run)

    kernels = both(functools.partial(grouped_matmul, interpret=True, tile_rows=_TILE))
    return operands, kernels, both(grouped_matmul_reference)


class TestGroupedKernels:
    """``ops.grouped_matmul``'s three Pallas kernels in interpret mode."""

    @pytest.mark.parametrize("widths", [(896, 1408), (1408, 896)], ids=["7x128_by_11x128", "11x128_by_7x128"])
    @pytest.mark.parametrize("case", list(_GROUPS))
    def test_value_and_both_gradients_against_the_oracle(self, case, widths):
        (rows, w, g), kernels, oracle = _kernel_products(*widths)
        sizes = jnp.asarray(_GROUPS[case], jnp.int32)
        for name, got, want in zip(("result", "d_rows", "d_weights"),
                                   kernels(rows, w, sizes, g), oracle(rows, w, sizes, g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("case", ["boundary_inside_a_tile", "empty_first", "empty_middle",
                                      "empty_last", "sum_well_under_m", "every_group_empty"])
    def test_exact_zeros_past_the_groups_whatever_the_buffers_held(self, case):
        """The buffers' tails (rows and cotangent past the groups) and an
        empty group's weights hold NaN: the result and the row gradient are
        exact zeros past the groups, an empty group's weight gradient is an
        exact zero block, and inside the groups nothing is touched by it."""
        (rows, w, g), kernels, oracle = _kernel_products(896, 1408)
        sizes = _GROUPS[case]
        total = sum(sizes)
        empty = jnp.asarray([s == 0 for s in sizes])
        dirty = (rows.at[total:].set(jnp.nan), jnp.where(empty[:, None, None], jnp.nan, w),
                 g.at[total:].set(jnp.nan))
        sizes = jnp.asarray(sizes, jnp.int32)
        got = kernels(dirty[0], dirty[1], sizes, dirty[2])
        want = oracle(rows, jnp.where(empty[:, None, None], 0.0, w), sizes, g.at[total:].set(0.0))
        for a, b in zip(got, want):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
        assert float(jnp.max(jnp.abs(got[0][total:]), initial=0.0)) == 0.0
        assert float(jnp.max(jnp.abs(got[1][total:]), initial=0.0)) == 0.0
        assert float(jnp.max(jnp.abs(jnp.where(empty[:, None, None], got[2], 0.0)))) == 0.0

    @pytest.mark.parametrize("tile", [16, 64, 128, 320])
    @pytest.mark.parametrize("case", list(_GROUPS))
    def test_tiles_visited_counts_the_plans_own_visits(self, case, tile):
        """``tiles_visited`` (the ``moe/rows_computed`` counter) against the
        steps of the kernels' plan that multiply; every row tile is visited
        at least once (the tiles past the groups to be zeroed), every group
        at least once by the weight gradient's walk, and the steps fit the grid."""
        gm = importlib.import_module("tpuframe.ops.grouped_matmul")
        sizes = jnp.asarray(_GROUPS[case], jnp.int32)
        tiles = -(-_M // tile)
        for every_group in (False, True):
            n, group, tile_id, lo, hi = (np.asarray(a) for a in gm._plan(
                sizes, _M, tile, every_group=every_group))
            n = int(n[0])
            assert 1 <= n <= tiles + len(_GROUPS[case]) - 1 == len(group)
            assert int(np.sum((hi > lo)[:n])) == int(tiles_visited(sizes, tile))
            if tile % 32 == 0:
                # a step that touches one half of its tile multiplies that half alone
                live, middle = (hi > lo)[:n], (tile_id * tile + tile // 2)[:n]
                halves = (lo[:n] < middle).astype(int) + (hi[:n] > middle).astype(int)
                assert int(np.sum(halves[live])) == int(tiles_visited(sizes, tile // 2))
            assert (tile_id >= 0).all() and (tile_id < tiles).all()
            if every_group:
                assert set(group[:n]) == set(range(len(_GROUPS[case])))
                assert (np.diff(group[:n]) >= 0).all()
            else:
                assert set(tile_id[:n]) == set(range(tiles))
            # a step's rows lie in its tile, and the steps behind the plan repeat the last
            live = hi[:n] > lo[:n]
            assert (lo[:n][live] < (tile_id[:n][live] + 1) * tile).all()
            assert (hi[:n][live] > tile_id[:n][live] * tile).all()
            assert (group[n:] == group[n - 1]).all() and (tile_id[n:] == tile_id[n - 1]).all()

    def test_bfloat16_operands_accumulate_in_float32(self):
        """The configurations' precision: bfloat16 operands, float32 sums over
        K and over a group's rows, one rounding at the end."""
        (rows, w, g), _, oracle = _kernel_products(896, 1408)
        sizes = jnp.asarray(_GROUPS["boundary_inside_a_tile"], jnp.int32)
        narrow = [a.astype(jnp.bfloat16) for a in (rows, w, g)]
        y, vjp = jax.vjp(lambda r, m: grouped_matmul(r, m, sizes, interpret=True, tile_rows=_TILE),
                         narrow[0], narrow[1])
        got = (y,) + vjp(narrow[2])
        want = oracle(*(a.astype(jnp.float32) for a in narrow[:2]), sizes, narrow[2].astype(jnp.float32))
        for a, b in zip(got, want):
            assert a.dtype == jnp.bfloat16
            scale = float(jnp.max(jnp.abs(b)))
            assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) < 2 ** -7 * scale

    @pytest.mark.parametrize("env, kernels", [({}, False), ({"TPUFRAME_PALLAS_INTERPRET": "1"}, True)])
    def test_dispatch_and_the_verdict_event(self, env, kernels, monkeypatch, tmp_path):
        """Through ``ops/dispatch.py`` like every kernel: ``jax.lax.ragged_dot``
        where Pallas is not compiled, the kernels where it runs; one
        ``ops/kernel_verdict`` event a distinct decision, with the tile; and
        ``row_tile`` names the tile of the product that runs."""
        from tpuframe.track import telemetry as T

        for knob in ("TPUFRAME_PALLAS_INTERPRET", "TPUFRAME_DISABLE_PALLAS"):
            monkeypatch.delenv(knob, raising=False)
        for knob, value in env.items():
            monkeypatch.setenv(knob, value)
        rows, w = jnp.ones((48, 8)), jnp.ones((3, 8, 4))
        sizes = jnp.asarray([5, 0, 20], jnp.int32)
        dispatch._VERDICT_EMITTED.clear()
        tele = T.configure(str(tmp_path / "events.jsonl"))
        try:
            for _ in range(3):
                text = str(jax.make_jaxpr(jax.grad(
                    lambda r, m: jnp.sum(grouped_matmul(r, m, sizes)), (0, 1)))(rows, w))
                assert ("ragged_dot" in text) is not kernels
                assert [name in text for name in (
                    "tpuframe_grouped_fwd", "tpuframe_grouped_drows",
                    "tpuframe_grouped_dweights")] == [kernels] * 3
            (event,) = [e for e in tele.recent_events(50) if e["name"] == "ops/kernel_verdict"]
            assert (event["op"], event["shape_class"]) == ("grouped_matmul", "g4_k8_m64_n4")
            assert event["enable"] is kernels and event["source"] == "default"
            assert event.get("tile_rows") == event.get("edge_rows") == (48 if kernels else None)
            assert row_tile(48, 8, 4, jnp.float32) == (48 if kernels else RAGGED_TILE_ROWS)
            assert row_tile(4096, 8, 4, jnp.float32) == (128 if kernels else RAGGED_TILE_ROWS)
            np.testing.assert_allclose(np.asarray(grouped_matmul(rows, w, sizes)),
                                       np.asarray(grouped_matmul_reference(rows, w, sizes)))
        finally:
            T.reset()
            dispatch._VERDICT_EMITTED.clear()

    @pytest.mark.parametrize("how", [{"interpret": True, "tile_rows": _TILE}, {"kernels": False}, {}],
                             ids=["kernels", "ragged_dot_kept", "auto_on_a_cpu"])
    def test_the_gradients_alone_are_what_the_vjp_computes(self, how):
        """``grouped_matmul_grads``: both gradients from the forward pass's
        arrays, by the same dispatch, with no forward product traced."""
        (rows, w, g), _, oracle = _kernel_products(896, 1408)
        sizes = jnp.asarray(_GROUPS["empty_middle"], jnp.int32)
        text = str(jax.make_jaxpr(lambda *a: grouped_matmul_grads(*a, **how))(rows, w, sizes, g))
        assert "tpuframe_grouped_fwd" not in text
        assert ("tpuframe_grouped_drows" in text) is ("interpret" in how)
        for got, want in zip(grouped_matmul_grads(rows, w, sizes, g, **how),
                             oracle(rows, w, sizes, g)[1:]):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_the_further_windows_keep_ragged_dot(self, monkeypatch):
        """A layer whose router overflows its buffers: the first window's
        products are the kernels, the loops' bodies hold ``ragged_dot``
        alone, and the layer matches itself on ``ragged_dot`` throughout."""
        layer = MoEMLP(num_experts=8, top_k=2, expert_dim=16, held=(0, 2), gated=True,
                       capacity_factor=None)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 2048, 8)).at[..., 0].set(1.0)
        p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
        p = {**p, "router": {"kernel": p["router"]["kernel"].at[0, :2].add(8.0)}}  # most pairs here

        def loss(p, x):
            out, upd = layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
            return jnp.sum(out ** 2), upd

        (want, _), want_grads = jax.value_and_grad(loss, has_aux=True)(p, x)
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        jax.clear_caches()  # the layer's jitted bodies were traced on `ragged_dot` just now
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x)[0]))(p, x)
        (value, upd), grads = jax.value_and_grad(loss, has_aux=True)(p, x)
        assert float(upd["counters"]["moe/overflow_calls"]) == 1.0

        def names(jaxpr, inside_a_loop=False):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    yield e.params["name"], inside_a_loop
                if e.primitive.name == "ragged_dot_general":
                    yield "ragged_dot", inside_a_loop
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from names(sub, inside_a_loop or e.primitive.name == "while")

        found = set(names(jaxpr.jaxpr))
        assert {n for n, looped in found if not looped} == {
            "tpuframe_grouped_fwd", "tpuframe_grouped_drows", "tpuframe_grouped_dweights"}
        assert {n for n, looped in found if looped} == {"ragged_dot"}
        np.testing.assert_allclose(float(value), float(want), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_a_manual_region_keeps_the_reference_lowering(self, monkeypatch):
        """The kernels were written for one device's whole operands: under a
        ``shard_map`` the auto dispatch takes ``ragged_dot``."""
        from jax.sharding import PartitionSpec as P

        from tpuframe.core import MeshSpec

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        mesh = MeshSpec(data=2).build(jax.devices()[:2])
        rows, w = jnp.ones((2, 32, 8)), jnp.ones((3, 8, 4))
        sizes = jnp.asarray([5, 0, 20], jnp.int32)
        per_shard = jax.shard_map(lambda r: grouped_matmul(r[0], w, sizes)[None], mesh=mesh,
                                  in_specs=P("data"), out_specs=P("data"), check_vma=False)
        text = str(jax.make_jaxpr(per_shard)(rows))
        assert "ragged_dot" in text and "tpuframe_grouped" not in text

    def test_the_layers_counter_counts_the_tile_that_ran(self, monkeypatch):
        """``moe/rows_computed`` in row tiles of the product that ran: XLA's
        512 where ``ragged_dot`` runs, the kernels' own where they do."""
        layer = MoEMLP(num_experts=4, top_k=2, expert_dim=16, capacity_factor=None)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 384, 8))
        p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
        read = {}
        for mode, env in (("ragged", "0"), ("kernels", "1")):
            monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", env)
            _, upd = layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
            read[mode] = (float(upd["counters"]["moe/rows_computed"]),
                          float(upd["counters"]["moe/assignments_here"]))
        assert read["ragged"][1] == read["kernels"][1] == 768
        assert read["ragged"][0] % 512 == 0 and read["kernels"][0] % 128 == 0
        assert 768 <= read["kernels"][0] <= read["ragged"][0]


class TestYarn:
    """Against values computed by hand from the source's rope_scaling:
    width 64, theta 10000, factor 40 over 4096, beta 32 / 1, mscale 0.707."""

    SCALING = {"factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32,
               "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}

    def test_correction_range(self):
        # 64 ln(4096 / (2 pi 32)) / (2 ln 1e4) = 10.47 -> 10; with 1 turn: 22.51 -> 23
        assert tr.yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)

    def test_inverse_frequencies(self):
        f = tr.rope_inv_freq(64, 10000.0, self.SCALING)
        assert f.shape == (32,)
        assert f[0] == pytest.approx(1.0)                       # below the range: as published
        assert f[10] == pytest.approx(10000.0 ** (-20 / 64))
        assert f[16] == pytest.approx(0.0055, rel=1e-6)         # ramp 6/13 between f/40 and f
        assert f[31] == pytest.approx(10000.0 ** (-62 / 64) / 40)
        assert np.allclose(tr.rope_inv_freq(64, 10000.0), 10000.0 ** (-np.arange(32) / 32))

    def test_temperature_and_softmax_scale(self):
        assert tr.yarn_mscale(40, 0.707) == pytest.approx(1.260804, rel=1e-6)
        assert tr.yarn_mscale(1, 0.707) == 1.0
        lm = TransformerLM(vocab_size=8, head_dim=128, rope_dim=64, kv_lora_rank=512,
                           rope_scaling=dict(self.SCALING))
        assert lm.attn_scale() == pytest.approx(192 ** -0.5 * 1.260804 ** 2, rel=1e-6)
        assert TransformerLM(vocab_size=8).attn_scale() is None
        cos, sin = tr.rope_tables(8, 64, 10000.0, self.SCALING)
        assert cos.shape == (8, 64) and float(cos[0, 0]) == 1.0 and float(sin[0, 5]) == 0.0

    def test_the_reference_computes_the_same(self, small):
        ref, full = small["ref"], small["full"]
        np.testing.assert_allclose(ref.yarn_inv_freq(full), tr.rope_inv_freq(64, 10000.0, self.SCALING))
        assert ref.softmax_scale(full) == pytest.approx(192 ** -0.5 * 1.260804 ** 2, rel=1e-6)

    def test_rotation_keeps_norms_and_depends_on_distance_only(self):
        cos, sin = tr.rope_tables(16, 8, 10000.0)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 8))
        q = jnp.broadcast_to(q, (1, 16, 1, 8))
        r = tr.apply_rope(q, cos, sin)
        np.testing.assert_allclose(np.linalg.norm(r, axis=-1), np.linalg.norm(q, axis=-1), rtol=1e-5)
        dots = jnp.einsum("ld,md->lm", r[0, :, 0], r[0, :, 0])
        np.testing.assert_allclose(np.asarray(dots[2, 5]), np.asarray(dots[7, 10]), rtol=1e-4)


class TestWidenedAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_full_against_blockwise_with_scale_and_a_value_width(self, causal):
        k = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(k[0], (2, 40, 3, 12))
        kk = jax.random.normal(k[1], (2, 40, 3, 12))
        v = jax.random.normal(k[2], (2, 40, 3, 8))
        full = lambda q, k, v: attention_reference(q, k, v, causal=causal, scale=0.31)  # noqa: E731
        blk = lambda q, k, v: blockwise_attention(  # noqa: E731
            q, k, v, causal=causal, block_size=16, scale=0.31)
        assert blk(q, kk, v).shape == (2, 40, 3, 8)
        np.testing.assert_allclose(np.asarray(blk(q, kk, v)), np.asarray(full(q, kk, v)),
                                   rtol=2e-5, atol=2e-6)
        loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)  # noqa: E731
        for a, b in zip(jax.grad(loss(blk), argnums=(0, 1, 2))(q, kk, v),
                        jax.grad(loss(full), argnums=(0, 1, 2))(q, kk, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)

    def test_the_default_scale_is_unchanged(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 4))
        a = attention_reference(q, q, q, causal=True)
        b = attention_reference(q, q, q, causal=True, scale=0.5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


class TestModelStatsRideTheMetricsWindow:
    def test_the_trainer_publishes_them_at_the_drain(self, small):
        from tpuframe.data import DataLoader
        from tpuframe.track.telemetry import get_telemetry
        from tpuframe.train import Trainer

        cfg = small["cfg"]
        rng = np.random.default_rng(0)
        rows = rng.integers(0, cfg["vocab_size"], (32, cfg["seq_len"] + 1)).astype(np.int32)

        class Rows:
            def __len__(self):
                return len(rows)

            def __getitem__(self, i):
                return rows[i, :-1], rows[i, 1:]

        reg = get_telemetry().registry
        before = reg.counter("moe/assignments_here").value
        slots_before = reg.counter("moe/slot_rows").value
        trainer = Trainer(TransformerLM(**cfg["model"]["kwargs"]),
                          train_dataloader=DataLoader(Rows(), batch_size=8, shuffle=False),
                          optimizer="sgd", lr=1e-3, max_duration="4ba", log_interval=2,
                          eval_interval=0, seed=0)
        result = trainer.fit()
        assert result.error is None
        here = reg.counter("moe/assignments_here").value - before
        pairs = 4 * 8 * cfg["seq_len"] * cfg["num_experts_per_tok"] * 2   # steps x rows x tokens x k x layers
        assert 0 < here < pairs
        assert reg.counter("moe/slot_rows").value - slots_before == pairs
        assert reg.counter("moe/rows_computed").value >= here
        assert reg.gauge("moe/expert_load_max_over_mean").value >= 1.0

    def test_a_model_without_them_adds_no_leaf(self):
        from tpuframe.train.step import _model_stats

        assert _model_stats({"aux_loss": {"moe": jnp.ones(())}}) == {}
