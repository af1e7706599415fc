"""Block-diffusion training rows: the mask rule in the oracle, the scan
schedule and both flash kernels (interpret mode), grouped heads, head norms
with rotary at repeated position ids, the forward process, the model against
the plain reference of ``chipbench/reference/sdar-30b-a3b-chat.py``, the
share test for its expert layer, and a model that brings its own objective
to the Trainer.  Small sizes, on the CPU."""

import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe.models import BlockDiffusionLM, TransformerLM
from tpuframe.models import block_diffusion as bd
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP
from tpuframe.ops.blockwise_attention import (
    blockwise_attention,
    blockwise_attention_reference,
    tile_counts,
)
from tpuframe.ops.ring_attention import BlockDiffusionMask, attention_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "sdar-30b-a3b-chat"


def by_hand(half, block, n=None):
    """The dense mask written out case by case, [query, key]."""
    n = 2 * half if n is None else n
    m = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            i_noised, j_noised = i < half, j < half
            bi = (i if i_noised else i - half) // block
            bj = (j if j_noised else j - half) // block
            if i_noised and j_noised:
                m[i, j] = bi == bj
            elif i_noised:
                m[i, j] = bj < bi
            elif not j_noised:
                m[i, j] = bj <= bi
    return m


class TestTheRule:
    @pytest.mark.parametrize("half, block", [(8, 2), (12, 4), (6, 3), (16, 1), (16, 16)])
    def test_allowed_is_the_mask_by_hand(self, half, block):
        pos = np.arange(2 * half)
        rule = BlockDiffusionMask(half, block)
        got = np.asarray(rule.allowed(pos[:, None], pos[None, :]))
        assert (got == by_hand(half, block)).all()
        assert got.sum() == rule.area() == half * half + half * block
        assert got.any(axis=1).all()          # every query has a key
        assert not got[half:, :half].any()    # a clean query sees no noised key

    def test_padded_positions_and_padded_keys(self):
        rule = BlockDiffusionMask(6, 3)
        pos = np.arange(16)
        got = np.asarray(rule.allowed(pos[:, None], pos[None, :], kv_len=12))
        want = by_hand(6, 3, 16)
        want[:, 12:] = False
        assert (got == want).all()
        assert got[12:, 6:12].all()           # rows past the row see every clean key

    def test_a_block_that_does_not_divide_is_refused(self):
        with pytest.raises(ValueError, match="do not divide"):
            BlockDiffusionMask(10, 4).allowed(np.arange(20)[:, None], np.arange(20)[None, :])

    @pytest.mark.parametrize("half, block, side", [
        (8, 2, 4), (12, 4, 8), (12, 4, 5), (6, 3, 4), (16, 4, 32), (16, 1, 3), (64, 4, 16)])
    def test_tiles_are_judged_as_their_scores_are(self, half, block, side):
        rule = BlockDiffusionMask(half, block)
        n = -(-2 * half // side)
        lo = np.arange(n) * side
        live, whole = rule.tiles(lo[:, None], lo[:, None] + side - 1,
                                 lo[None, :], lo[None, :] + side - 1)
        dense = by_hand(half, block, n * side).reshape(n, side, n, side).transpose(0, 2, 1, 3)
        assert (live == dense.any(axis=(2, 3))).all()
        assert (whole == dense.all(axis=(2, 3))).all()

    def test_the_oracle_applies_it(self):
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        q, kk, v = (jax.random.normal(x, (1, 16, 2, 8)) for x in k)
        got = attention_reference(q, kk, v, mask=BlockDiffusionMask(8, 4))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(8)
        p = jax.nn.softmax(jnp.where(by_hand(8, 4), s, -jnp.inf), -1)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, v)), atol=1e-6)

    def test_tile_counts_of_the_cell(self):
        # 24 forward tiles of 1024 and 80 backward tiles of 512, for 128.1 tiles' worth
        visited, needed = tile_counts(BlockDiffusionMask(4096, 4), 8192)
        assert visited == 24 * 4 + 80
        assert needed == pytest.approx(2 * (4096 * 4100) / 512 ** 2)
        scan_visited, scan_needed = tile_counts(BlockDiffusionMask(4096, 4), 8192, kernels=False)
        assert scan_visited == 3 * 80 and scan_needed == pytest.approx(1.5 * needed)


def _qkv(half, heads=2, kv_heads=2, d=16, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + half), 4)
    n = 2 * half
    return (jax.random.normal(k[0], (b, n, heads, d)), jax.random.normal(k[1], (b, n, kv_heads, d)),
            jax.random.normal(k[2], (b, n, kv_heads, d)), jax.random.normal(k[3], (b, n, heads, d)))


def _value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w), (0, 1, 2))(q, k, v)


#: (half, block, tile side): tiles far larger than a block; a half that is no
#: multiple of the tile (one tile straddles the two copies and masks
#: element-wise); rows that pad up to a tile (padded keys); a block of one
GRID = [(64, 4, 128), (128, 4, 128), (96, 4, 128), (160, 8, 128), (200, 4, 128),
        (72, 1, 128), (192, 64, 128), (320, 4, 256)]


class TestSchedulesAgainstTheOracle:
    @pytest.mark.parametrize("half, block, side", GRID)
    @pytest.mark.parametrize("form", ["scan", "kernels"])
    def test_forward_and_all_three_gradients(self, half, block, side, form):
        rule = BlockDiffusionMask(half, block)
        q, k, v, w = _qkv(half)
        if form == "scan":
            fn = lambda q, k, v: blockwise_attention_reference(  # noqa: E731
                q, k, v, mask=rule, block_size=side)
        else:
            fn = lambda q, k, v: blockwise_attention(  # noqa: E731
                q, k, v, mask=rule, block_size=side, interpret=True)
        got = _value_and_grads(fn, q, k, v, w)
        want = _value_and_grads(lambda q, k, v: attention_reference(q, k, v, mask=rule), q, k, v, w)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    def test_the_kernels_visit_the_live_tiles_alone(self):
        rule = BlockDiffusionMask(512, 4)
        # the module, by path: ``tpuframe.ops`` rebinds the name to the function
        module = importlib.import_module("tpuframe.ops.blockwise_attention")
        order, kinds, width = module._tile_plan(rule, 1024, 128, 1024, "k")
        kinds = kinds.reshape(8, width)
        assert width == 5                       # the last noised tile: itself and 4 clean ones
        assert (kinds > 0).sum() == 4 + 10 + 10  # the diagonal, two triangles with theirs
        assert (kinds[:4, 0] == 2).all()        # a noised tile's own blocks mask element-wise
        # a held block with fewer live tiles names its last one again, to run nothing
        assert (order.reshape(8, width)[0] == [0, 4, 4, 4, 4]).all() and kinds[0, 2:].sum() == 0

    def test_a_rule_for_another_row_is_refused(self):
        q, k, v, _ = _qkv(64)
        with pytest.raises(ValueError, match="no rule for a row"):
            blockwise_attention(q, k, v, mask=BlockDiffusionMask(32, 4), interpret=True)


class TestGroupedHeads:
    @pytest.mark.parametrize("form", ["full", "scan", "kernels"])
    @pytest.mark.parametrize("mask", ["causal", "rule"])
    def test_against_multi_head_attention_on_repeated_keys_and_values(self, form, mask):
        half, group = 64, 8
        q, k, v, w = _qkv(half, heads=8, kv_heads=1, seed=3)
        kw = {"causal": True} if mask == "causal" else {"mask": BlockDiffusionMask(half, 4)}
        fn = {"full": lambda q, k, v: attention_reference(q, k, v, **kw),
              "scan": lambda q, k, v: blockwise_attention_reference(q, k, v, block_size=128, **kw),
              "kernels": lambda q, k, v: blockwise_attention(
                  q, k, v, block_size=128, interpret=True, **kw)}[form]
        got = _value_and_grads(fn, q, k, v, w)
        rep = lambda a: jnp.repeat(a, group, axis=2)  # noqa: E731
        loss, (dq, dk, dv) = _value_and_grads(
            lambda q, k, v: attention_reference(q, k, v, **kw), q, rep(k), rep(v), w)
        # the copies' gradients, summed over the group
        want = (loss, (dq, dk.sum(2, keepdims=True), dv.sum(2, keepdims=True)))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    def test_heads_that_do_not_group_are_refused(self):
        q, k, v, _ = _qkv(64, heads=4, kv_heads=3)
        for fn in (attention_reference, blockwise_attention):
            with pytest.raises(ValueError):
                fn(q, k, v)

    def test_the_sequence_sharded_forms_refuse_them(self, monkeypatch):
        q, k, v, _ = _qkv(64, heads=4, kv_heads=2)
        monkeypatch.setattr(tr, "_mesh_or_none", object)
        monkeypatch.setattr(tr, "_per_shard_spec", lambda mesh, batch, heads: None)
        monkeypatch.setattr(tr, "_resolve_impl", lambda impl, *rest: impl)
        with pytest.raises(ValueError, match="ungrouped heads"):
            tr._attend(q, k, v, impl="ulysses", causal=True, num_heads=4, initializing=False)


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes, its reference, seeded weights
    and a batch of samples (token, mask draw, level draw)."""
    with open(os.path.join(ROOT, "chipbench", "configs", f"{NAME}.json")) as f:
        full = json.load(f)
    cfg = _merge(full, full["rehearsal"])
    ref = correct.load_by_name("reference", NAME)
    params = correct.init_params(ref.param_shapes(cfg), 2147483999)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, cfg["mask_token_id"], (2, cfg["seq_len"], 3)), jnp.int32)
    return {"full": full, "cfg": cfg, "ref": ref, "params": params, "x": x,
            "model": BlockDiffusionLM(**cfg["model"]["kwargs"])}


def _program_objective(model, params, x):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    data = jnp.mean(model.objective(logits, {"input": x}))
    aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
    return data, aux, upd


class TestSelfAttentionAgainstTheReference:
    @pytest.mark.parametrize("impl", ["full", "blockwise"])
    def test_head_norms_and_rotary_at_repeated_position_ids(self, small, impl, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        cfg, ref = small["cfg"], small["ref"]
        half = cfg["seq_len"]
        p = jax.tree.map(lambda a: a, small["params"]["block1"]["attn"])
        # scales that are not one, so that the norms' scales are seen
        p["q_norm"] = {"scale": 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(1), (cfg["head_dim"],))}
        p["k_norm"] = {"scale": 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (cfg["head_dim"],))}
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 2 * half, cfg["hidden_size"]))
        layer = tr.SelfAttention(
            cfg["num_attention_heads"], cfg["head_dim"], num_kv_heads=cfg["num_key_value_heads"],
            qk_norm=True, attn_impl=impl, mask=BlockDiffusionMask(half, cfg["block_length"]))
        rope = tr.rope_tables(2 * half, cfg["head_dim"], cfg["rope_theta"],
                              positions=np.tile(np.arange(half), 2))
        got = layer.apply({"params": p}, x, rope=rope, mutable=["counters"])[0]
        want = ref._attn(p, x, cfg, lambda f: f, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_rope_tables_at_position_ids(self):
        cos, sin = tr.rope_tables(8, 16, 1e6, positions=np.tile(np.arange(4), 2))
        base_cos, base_sin = tr.rope_tables(4, 16, 1e6)
        np.testing.assert_array_equal(np.asarray(cos), np.tile(np.asarray(base_cos), (2, 1)))
        np.testing.assert_array_equal(np.asarray(sin), np.tile(np.asarray(base_sin), (2, 1)))

    def test_a_rotary_width_over_the_head_in_multi_head_attention_is_refused(self):
        """Since PR 43 a width under the head's turns its first dimensions
        (`tests/test_qwen3_next.py`); one over it, or an odd one, has no pairs."""
        m = TransformerLM(vocab_size=16, num_layers=1, num_heads=2, head_dim=8, rope_dim=10)
        with pytest.raises(ValueError, match="odd or over"):
            m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
        TransformerLM(vocab_size=16, num_layers=1, num_heads=2, head_dim=8, rope_dim=4).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


class TestForwardProcess:
    def test_draws_levels_and_weights_bit_for_bit(self, small):
        cfg, x = small["cfg"], small["x"]
        x0, masked, t = bd.forward_process(
            x, block=cfg["block_length"], draw_range=cfg["mask_token_id"], eps=cfg["noise_eps"])
        r0, rmasked, rt = small["ref"].forward_process(x, cfg)
        np.testing.assert_array_equal(np.asarray(x0), np.asarray(r0))
        np.testing.assert_array_equal(np.asarray(masked), np.asarray(rmasked))
        assert np.asarray(1.0 / t).tobytes() == np.asarray(1.0 / rt).tobytes()
        # by hand: one level a block, from the draw at its first position
        d = np.asarray(x, np.float64)
        level = cfg["noise_eps"] + (1 - cfg["noise_eps"]) * (d[:, ::cfg["block_length"], 2] + 0.5) / cfg["mask_token_id"]
        np.testing.assert_allclose(np.asarray(t)[:, ::cfg["block_length"]], level, rtol=1e-6)
        assert (np.asarray(t)[:, 1] == np.asarray(t)[:, 0]).all()
        assert 0.2 < float(jnp.mean(masked)) < 0.8 and float(jnp.min(t)) >= cfg["noise_eps"]

    def test_the_objective_weighs_masked_positions_by_one_over_the_level(self, small):
        cfg, x = small["cfg"], small["x"]
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, cfg["seq_len"], cfg["vocab_size"]))
        got = small["model"].objective(logits, {"input": x})
        x0, masked, t = small["ref"].forward_process(x, cfg)
        ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), x0[..., None], -1)[..., 0]
        want = jnp.sum(jnp.where(masked, ce / t, 0.0), axis=1) / cfg["seq_len"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
        assert got.shape == (2,)


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])

    def test_loss_and_every_gradient_leaf(self, small, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        m, p, x = small["model"], small["params"], small["x"]

        def objective(params):
            data, aux, _ = _program_objective(m, params, x)
            return data + aux, data

        (_, data), grads = jax.value_and_grad(objective, has_aux=True)(p)
        want_loss, want = jax.value_and_grad(small["ref"].loss)(p, x, None, small["cfg"])
        assert abs(float(data) - float(want_loss)) < 1e-5
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                jax.tree.leaves(want)):
            err = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12))
            assert err < 2e-4, (jax.tree_util.keystr(path), err)

    def test_three_sgd_steps(self, small, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        m, cfg, ref = small["model"], small["cfg"], small["ref"]
        p = r = small["params"]
        rng = np.random.default_rng(9)
        for _ in range(3):
            x = jnp.asarray(rng.integers(0, cfg["mask_token_id"], (2, cfg["seq_len"], 3)), jnp.int32)
            g = jax.grad(lambda q: sum(_program_objective(m, q, x)[:2]))(p)
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            gr = jax.grad(ref.loss)(r, x, None, cfg)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, gr)
        moved = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)), r, small["params"])
        for (path, a), b, d in zip(jax.tree_util.tree_flatten_with_path(p)[0],
                                   jax.tree.leaves(r), jax.tree.leaves(moved)):
            assert d > 0, jax.tree_util.keystr(path)
            assert float(jnp.linalg.norm(a - b)) < 3e-4 * d, jax.tree_util.keystr(path)

    def test_counters(self, small, monkeypatch):
        from tpuframe.models.block_diffusion import forward_process
        from tpuframe.track import telemetry

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        cfg, m = small["cfg"], small["model"]
        telemetry.reset()
        registry = telemetry.get_telemetry().registry
        _, _, upd = _program_objective(m, small["params"], small["x"])
        c = upd["counters"]
        # the positions that show the mask token: the model's own forward
        # process, held to the reference's (nothing of it rides the step)
        _, masked, _ = small["ref"].forward_process(small["x"], cfg)
        _, got, _ = forward_process(small["x"], **m._process())
        assert float(jnp.sum(got)) == float(jnp.sum(masked))
        assert got.size == 2 * cfg["seq_len"]
        assert not [k for k in c if k.startswith("blockdiff/")]
        # the tiles: static numbers, counted on the host where a call is traced
        assert "attn" not in c["block0"]
        visited = registry.counter("attention/tiles_visited").value
        needed = registry.counter("attention/tiles_needed").value
        assert visited >= needed > 0
        # a second trace counts the same again: the ratio holds
        _program_objective(m, small["params"], small["x"])
        assert registry.counter("attention/tiles_visited").value == 2 * visited
        assert registry.counter("attention/tiles_needed").value == 2 * needed
        telemetry.reset()
        assert float(c["block1"]["moe"]["moe/assignments_here"]) > 0

    @pytest.mark.parametrize("fault", ["causal_over_the_row", "weights_left_out", "a_shift"])
    def test_a_fault_in_the_row_or_the_objective_is_seen(self, small, monkeypatch, fault):
        m, p, x, cfg = small["model"], small["params"], small["x"], small["cfg"]
        want = float(small["ref"].loss(p, x, None, cfg))
        if fault == "causal_over_the_row":
            real = tr._attend
            monkeypatch.setattr(tr, "_attend", lambda *a, mask=None, **kw: real(
                *a, **{**kw, "causal": True}))
            got = float(_program_objective(m, p, x)[0])
        else:
            logits, _ = m.apply({"params": p}, x, train=True, mutable=["aux_loss", "counters", "gauges"])
            x0, masked, t = small["ref"].forward_process(x, cfg)
            logp = jax.nn.log_softmax(logits, -1)
            if fault == "a_shift":
                ce = -jnp.take_along_axis(logp[:, :-1], x0[:, 1:, None], -1)[..., 0]
                got = float(jnp.sum(jnp.where(masked[:, 1:], ce / t[:, 1:], 0.0)) / x0.size)
            else:
                ce = -jnp.take_along_axis(logp, x0[..., None], -1)[..., 0]
                got = float(jnp.mean(jnp.where(masked, ce, 0.0)))
        assert abs(got - want) > 1e-3


class TestSharesAddUpToTheUncutLayer:
    def test_the_eight_shares_of_sixteen_experts(self, small):
        """128 experts, 8 a token, gates renormalised, no shared MLP: the
        parts the eight chips' 16 experts give add up to the uncut layer."""
        cfg, ref = small["cfg"], small["ref"]
        d, e, h, k = 32, 128, 16, 8
        uncut = {**cfg, "hidden_size": d, "moe_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_tok": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(3), 5)
        n = lambda kk, *s: 0.3 * jax.random.normal(kk, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(key[0], d, e)}, "w_gate": n(key[1], e, d, h),
             "w_in": n(key[2], e, d, h), "w_out": n(key[3], e, h, d)}
        x = jax.random.normal(key[4], (2, 24, d), jnp.float32)
        want, _ = ref._moe(p, x, uncut, lambda f: f, False)
        total = jnp.zeros_like(x)
        for first in range(0, e, 16):
            layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(first, 16), gated=True,
                           shared_dim=0, renormalize=True, capacity_factor=None)
            share = {**p, **{w: p[w][first:first + 16] for w in ("w_gate", "w_in", "w_out")}}
            total = total + layer.apply({"params": share}, x)
            # and the reference's own share is the program's
            held = {**uncut, "num_experts": 16, "held_first": first}
            np.testing.assert_allclose(
                np.asarray(layer.apply({"params": share}, x)),
                np.asarray(ref._moe(share, x, held, lambda f: f, False)[0]), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-5, atol=2e-6)


class TestAModelBringsItsObjective:
    def _loader(self, cfg, n=32):
        from tpuframe.data import DataLoader

        rng = np.random.default_rng(0)
        inputs = rng.integers(0, cfg["mask_token_id"], (n, cfg["seq_len"], 3)).astype(np.int32)
        labels = rng.integers(0, 2, (n,)).astype(np.int32)

        class Samples:
            def __len__(self):
                return n

            def __getitem__(self, i):
                return inputs[i], labels[i]

        return DataLoader(Samples(), batch_size=8, shuffle=False, num_workers=0), inputs

    def test_the_trainer_trains_it_with_no_loss_fn(self, small):
        from tpuframe.train import ModelObjective, Trainer

        cfg = small["cfg"]
        loader, inputs = self._loader(cfg)
        model = BlockDiffusionLM(**{**cfg["model"]["kwargs"], "attn_impl": "full"})
        from tpuframe.train.callbacks import Callback

        class Losses(Callback):
            seen = []

            def on_batch_end(self, trainer, metrics):
                self.seen.append(float(metrics["loss_sum"]) / float(metrics["count"]))

        trainer = Trainer(model, train_dataloader=loader, optimizer="sgd", lr=0.05,
                          max_duration="4ba", log_interval=1, eval_interval=0, seed=0,
                          callbacks=[Losses()])
        assert isinstance(trainer.loss_fn, ModelObjective)
        state = trainer.init_state()
        first = float(jnp.mean(model.objective(
            model.apply({"params": state.params}, jnp.asarray(inputs[:8])),
            {"input": jnp.asarray(inputs[:8])})))
        result = trainer.fit()
        assert result.error is None and trainer.batches_seen == 4
        assert len(Losses.seen) == 4 and all(math.isfinite(v) for v in Losses.seen)
        assert Losses.seen[0] == pytest.approx(first, rel=1e-4)

    def test_the_step_reports_the_mean_objective_a_row(self, small):
        from tpuframe.train.state import create_train_state
        from tpuframe.train.step import make_eval_step, make_train_step, model_objective
        import optax

        cfg = small["cfg"]
        model = BlockDiffusionLM(**{**cfg["model"]["kwargs"], "attn_impl": "full"})
        x = small["x"]
        state = create_train_state(model, jax.random.PRNGKey(0), x[:1], optax.sgd(0.1))
        batch = {"input": x, "label": jnp.zeros((2,), jnp.int32)}
        want = float(jnp.mean(model.objective(model.apply({"params": state.params}, x), batch)))
        evaluated = make_eval_step(loss_fn=model_objective(model))(state, batch)
        assert float(evaluated["loss_sum"] / evaluated["count"]) == pytest.approx(want, rel=1e-5)
        assert float(evaluated["count"]) == 2 and float(evaluated["correct"]) == 0
        _, metrics = make_train_step(loss_fn=model_objective(model), donate=False)(state, batch)
        assert float(metrics["loss_sum"] / metrics["count"]) == pytest.approx(want, rel=1e-5)
        assert float(metrics["count"]) == 2 and float(metrics["correct"]) == 0
        assert set(metrics["model_stats"]["counters"]) == {
            "moe/assignments_here", "moe/rows_computed", "moe/slot_rows", "moe/overflow_calls"}

    def test_cross_entropy_stays_the_default(self):
        from tpuframe.train import Trainer, cross_entropy
        from tpuframe.train.step import model_objective

        model = TransformerLM(vocab_size=32, num_layers=1, num_heads=2, head_dim=8, max_len=16)
        assert model_objective(model) is None
        assert Trainer(model, optimizer="sgd").loss_fn is cross_entropy
        mine = lambda logits, labels: jnp.zeros(labels.shape)  # noqa: E731
        assert Trainer(model, optimizer="sgd", loss_fn=mine).loss_fn is mine
        # a caller's loss_fn outranks the model's own
        bdm = BlockDiffusionLM(vocab_size=32, num_layers=1, num_heads=2, head_dim=8, rope_dim=8)
        assert Trainer(bdm, optimizer="sgd", loss_fn=mine).loss_fn is mine
