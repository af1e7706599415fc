"""Sliding-window layers beside full-attention layers in one model: the band
rule in the oracle, the scan schedule and both flash kernels (interpret
mode), the protocol the two rules share, rotary tables by kind of layer, the
model against the plain reference of
``chipbench/reference/mellum2-12b-a2.5b-instruct.py``, and the share test for
its expert layer.  Small sizes, on the CPU."""

import functools
import importlib
import json
import os
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe.models import BlockDiffusionLM, TransformerLM
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP
from tpuframe.ops.blockwise_attention import (
    blockwise_attention,
    blockwise_attention_reference,
    tile_counts,
)
from tpuframe.ops.ring_attention import (
    BlockDiffusionMask,
    SlidingWindowMask,
    attention_reference,
    mask_or_causal,
)

# the module, by path: ``tpuframe.ops`` rebinds the name to the function
bw = importlib.import_module("tpuframe.ops.blockwise_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-instruct"
REF = correct.load_by_name("reference", NAME)
with open(os.path.join(ROOT, "chipbench", "configs", f"{NAME}.json")) as _f:
    FULL = json.load(_f)
#: the configuration at its rehearsal sizes (one window layer of 8 keys, one
#: full layer, 8 experts of which 4 held, rows of 32)
CFG = {**FULL, **{k: v for k, v in FULL["rehearsal"].items() if not isinstance(v, dict)},
       "model": {"class": "TransformerLM",
                 "kwargs": {**FULL["model"]["kwargs"], **FULL["rehearsal"]["model"]["kwargs"],
                            "moe_kwargs": {**FULL["model"]["kwargs"]["moe_kwargs"],
                                           **FULL["rehearsal"]["model"]["kwargs"]["moe_kwargs"]}}}}
YARN = FULL["rope_parameters"]["full_attention"]


def band(length, window):
    q, k = np.arange(length)[:, None], np.arange(length)[None, :]
    return (k <= q) & (k > q - window)


class TestTheBand:
    @pytest.mark.parametrize("length, window", [(16, 4), (16, 1), (24, 24), (24, 40), (33, 7)])
    def test_allowed_is_the_dense_band_and_the_area_its_count(self, length, window):
        pos = np.arange(length)
        rule = SlidingWindowMask(window)
        got = np.asarray(rule.allowed(pos[:, None], pos[None, :]))
        assert (got == band(length, window)).all()
        assert got.sum() == rule.area(length)
        assert got.sum(axis=1).max() == min(window, length)   # its own key among them
        assert got.diagonal().all()

    def test_the_area_at_the_cells_shape(self):
        assert SlidingWindowMask(1024).area(8192) == 8192 * 1024 - 1024 * 1023 // 2 == 7_864_832
        assert SlidingWindowMask(1024).area(512) == 512 * 513 // 2

    def test_padded_rows_see_what_the_last_row_sees(self):
        rule = SlidingWindowMask(4)
        pos = np.arange(16)
        got = np.asarray(rule.allowed(pos[:, None], pos[None, :], kv_len=10))
        want = band(16, 4)
        want[10:] = want[9]
        assert (got == want).all() and not got[:, 10:].any() and got.any(axis=1).all()

    @pytest.mark.parametrize("side", [128, 256, 512, 1024])
    @pytest.mark.parametrize("window", ["smaller", "equal", "larger"])
    def test_tiles_are_judged_as_their_scores_are(self, side, window):
        window = {"smaller": side // 2 + 3, "equal": side, "larger": 2 * side + side // 4}[window]
        n = 5
        lo = np.arange(n) * side
        live, whole = SlidingWindowMask(window).tiles(
            lo[:, None], lo[:, None] + side - 1, lo[None, :], lo[None, :] + side - 1)
        dense = band(n * side, window).reshape(n, side, n, side).transpose(0, 2, 1, 3)
        assert (live == dense.any(axis=(2, 3))).all()
        assert (whole == dense.all(axis=(2, 3))).all()
        if window <= side:
            assert not whole.any()        # a window as wide as a tile leaves no whole tile

    def test_tile_counts_of_the_cell(self):
        # 15 forward tiles of 1024 (8 on the diagonal, 7 below it) and 45
        # backward tiles of 512 (three a K/V block, the last two blocks fewer)
        rule = SlidingWindowMask(1024)
        visited, needed = tile_counts(rule, 8192)
        assert visited == 15 * 4 + 45
        assert needed == pytest.approx(2 * 7_864_832 / 512 ** 2)
        assert round(visited / needed, 4) == 1.7499
        kinds = bw._tile_classes(rule, 8192, 1024, 8192)
        assert (kinds > 0).sum() == 15 and (kinds == 1).sum() == 0
        order, kinds, width = bw._tile_plan(rule, 8192, 512, 8192, "q")
        assert width == 3 and (kinds.reshape(16, 3)[:14] == [2, 1, 2]).all()

    def test_the_oracle_applies_it(self):
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        q, kk, v = (jax.random.normal(x, (1, 16, 2, 8)) for x in k)
        got = attention_reference(q, kk, v, mask=SlidingWindowMask(5))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(8)
        p = jax.nn.softmax(jnp.where(band(16, 5), s, -jnp.inf), -1)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, v)), atol=1e-6)


def _qkv(length, heads=2, kv_heads=2, d=16, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + length), 4)
    return (jax.random.normal(k[0], (b, length, heads, d)),
            jax.random.normal(k[1], (b, length, kv_heads, d)),
            jax.random.normal(k[2], (b, length, kv_heads, d)),
            jax.random.normal(k[3], (b, length, heads, d)))


def _value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w), (0, 1, 2))(q, k, v)


def _form(form, rule, side):
    if form == "scan":
        return lambda q, k, v: blockwise_attention_reference(
            q, k, v, mask=rule, block_size=side)
    return lambda q, k, v: blockwise_attention(
        q, k, v, mask=rule, block_size=side, interpret=True)


#: (row, window, tile side, query heads, key/value heads): a row that is no
#: tile multiple (padded rows and keys) under a window narrower than a tile;
#: a window as wide as a tile (no whole tile); one of two and a half tiles
#: (dead, whole and masked tiles); grouped heads; a row of one tile
GRID = [(300, 64, 128, 2, 2), (384, 128, 128, 2, 2), (640, 320, 128, 2, 2),
        (520, 100, 256, 4, 2), (200, 72, 128, 4, 1), (100, 30, 128, 2, 2)]


class TestSchedulesAgainstTheOracle:
    @pytest.mark.parametrize("length, window, side, heads, kv_heads", GRID)
    @pytest.mark.parametrize("form", ["scan", "kernels"])
    def test_forward_and_all_three_gradients(self, length, window, side, heads, kv_heads, form):
        rule = SlidingWindowMask(window)
        q, k, v, w = _qkv(length, heads, kv_heads)
        got = _value_and_grads(_form(form, rule, side), q, k, v, w)
        want = _value_and_grads(lambda q, k, v: attention_reference(q, k, v, mask=rule), q, k, v, w)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("form", ["full", "scan", "kernels"])
    def test_a_window_as_long_as_the_row_is_causal_and_runs_as_causal(self, form, monkeypatch):
        q, k, v, w = _qkv(200)
        assert mask_or_causal(False, SlidingWindowMask(200), 200) is True
        assert mask_or_causal(False, SlidingWindowMask(199), 200) == SlidingWindowMask(199)
        seen = []
        real = bw._padded_call
        monkeypatch.setattr(bw, "_padded_call", lambda q, k, v, causal, *a: seen.append(causal)
                            or real(q, k, v, causal, *a))
        op = {"full": attention_reference,
              "scan": functools.partial(blockwise_attention_reference, block_size=128),
              "kernels": functools.partial(blockwise_attention, block_size=128,
                                           interpret=True)}[form]
        fn = lambda mask, causal: functools.partial(op, causal=causal, mask=mask)  # noqa: E731
        got = _value_and_grads(fn(SlidingWindowMask(4096), False), q, k, v, w)
        want = _value_and_grads(fn(None, True), q, k, v, w)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert all(c is True for c in seen)    # no plan, no rule

    def test_a_window_of_nothing_is_refused(self):
        q, k, v, _ = _qkv(64)
        with pytest.raises(ValueError, match="no rule for a row"):
            blockwise_attention(q, k, v, mask=SlidingWindowMask(0), interpret=True)

    @pytest.mark.parametrize("rule, names", [
        (SlidingWindowMask(256), ("tpuframe_flash_fwd_window", "tpuframe_flash_bwd_window")),
        (BlockDiffusionMask(512, 4), ("tpuframe_flash_fwd", "tpuframe_flash_bwd")),
        (SlidingWindowMask(1024), ("tpuframe_flash_fwd", "tpuframe_flash_bwd")),   # causal
    ], ids=["window", "block", "window_as_long_as_the_row"])
    def test_the_kernels_names_carry_the_rules_suffix(self, rule, names):
        q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v, mask=rule, interpret=False)
                           .astype(jnp.float32))

        text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, k, k).lower(
            lowering_platforms=("tpu",)).as_text()
        found = {line.split('kernel_name = "')[1].split('"')[0]
                 for line in text.splitlines() if 'kernel_name = "tpuframe_flash' in line}
        assert found == set(names)


class TestTheProtocol:
    """What the schedules ask of a rule, and that the block rule through it
    is the parent's (PR 40's tree: plan checksums taken there)."""

    @pytest.mark.parametrize("rule, length, fits", [
        (BlockDiffusionMask(32, 4), 64, True), (BlockDiffusionMask(32, 4), 48, False),
        (SlidingWindowMask(8), 64, True), (SlidingWindowMask(8), 5, True)])
    def test_a_rule_says_which_rows_it_fits(self, rule, length, fits):
        assert rule.fits(length) is fits
        if not fits:
            with pytest.raises(ValueError, match="no rule for a row"):
                mask_or_causal(False, rule, length)
        else:
            assert mask_or_causal(False, rule, length) in (rule, True)

    @pytest.mark.parametrize("rule", [BlockDiffusionMask(8, 2), SlidingWindowMask(8)])
    def test_every_rule_answers_the_whole_protocol(self, rule):
        pos = np.arange(16)
        dense = np.asarray(rule.allowed(pos[:, None], pos[None, :]))
        live, whole = rule.tiles(np.array([0]), np.array([15]), np.array([0]), np.array([15]))
        assert live.all() and not whole.any()
        assert rule.area(16) == dense.sum() and rule.fits(16) and not rule.plain(16)
        assert isinstance(rule.suffix, str) and hash(rule) == hash(type(rule)(*rule))

    def test_without_a_rule_causal_stands(self):
        assert mask_or_causal(True, None, 7) is True and mask_or_causal(0, None, 7) is False

    @pytest.mark.parametrize("side, streams, width, order_crc, kinds_crc, live", [
        (1024, "k", 5, 0x183CD2D2, 0x139F78E0, 24), (512, "q", 16, 0xA0CF4D2D, 0x95169AF1, 80)])
    def test_the_block_rule_gives_the_parents_plan(self, side, streams, width, order_crc,
                                                   kinds_crc, live):
        order, kinds, got = bw._tile_plan(BlockDiffusionMask(4096, 4), 8192, side, 8192, streams)
        assert got == width and int((kinds > 0).sum()) == live
        assert (zlib.crc32(order.tobytes()), zlib.crc32(kinds.tobytes())) == (order_crc, kinds_crc)

    def test_the_block_rule_counts_what_it_counted(self):
        visited, needed = tile_counts(BlockDiffusionMask(4096, 4), 8192)
        assert visited == 176 and round(visited / needed, 4) == 1.3737


class TestRotaryTablesByKind:
    def test_yarn_at_the_sources_parameters_gives_its_attention_factor(self):
        scaling = {k: YARN[k] for k in ("factor", "original_max_position_embeddings",
                                        "beta_fast", "beta_slow")}
        assert tr.yarn_mscale(16, 1.0) == pytest.approx(YARN["attention_factor"], abs=1e-15)
        assert tr.yarn_correction_range(128, 500000, 8192, 32, 1) == (18, 35)
        cos, sin = tr.rope_tables(64, 128, YARN["rope_theta"], scaling)
        stated, _ = tr.rope_tables(64, 128, YARN["rope_theta"], YARN)
        np.testing.assert_array_equal(np.asarray(cos), np.asarray(stated))
        assert float(cos[0, 0]) == pytest.approx(1.2772588722239782, rel=1e-7)
        # and they are the reference's own, which takes nothing of the program
        want = REF.tables(64, 128, YARN)
        np.testing.assert_array_equal(np.asarray(cos), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(sin), np.asarray(want[1]))

    def test_the_window_layers_tables_are_others(self):
        model = TransformerLM(**CFG["model"]["kwargs"])
        assert model._rope_of("sliding_attention") == (500000, None)
        theta, scaling = model._rope_of("full_attention")
        assert theta == 500000 and scaling["factor"] == 16
        plain = tr.rope_tables(64, 128, theta, None)
        yarn = tr.rope_tables(64, 128, theta, scaling)
        assert float(jnp.max(jnp.abs(plain[0] - yarn[0]))) > 0.2
        want = REF.tables(64, 128, FULL["rope_parameters"]["sliding_attention"])
        np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(want[1]))

    def test_a_kind_without_an_entry_takes_the_models_own(self):
        model = TransformerLM(vocab_size=8, rope_dim=32, rope_theta=123.0,
                              rope_parameters={"sliding_attention": {"rope_type": "default",
                                                                     "rope_theta": 7.0}})
        assert model._rope_of("full_attention") == (123.0, None)
        assert model._rope_of("sliding_attention") == (7.0, None)

    def test_a_mask_for_every_block_and_window_layers_do_not_go_together(self):
        kw = {**CFG["model"]["kwargs"], "block_length": 4}
        model = BlockDiffusionLM(**kw)
        with pytest.raises(ValueError, match="do not go together"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 3), jnp.int32))


# -- the model against the plain reference ---------------------------------------
def _leaf_names(tree):
    return sorted(correct.leaf_paths(tree))


LEAVES = _leaf_names(jax.tree.map(lambda s: 0, REF.param_shapes(CFG), is_leaf=correct._is_spec))


def _program_loss(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    data = -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
    aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
    return data + (aux - jax.lax.stop_gradient(aux)), upd


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes, seeded weights, a batch, and loss
    and gradients both ways, the kernels in interpret mode."""
    params = correct.init_params(REF.param_shapes(CFG), 2147484001)
    rng = np.random.default_rng(41)
    rows = rng.integers(0, CFG["vocab_size"], (2, CFG["seq_len"] + 1))
    x, y = jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)
    model = TransformerLM(**CFG["model"]["kwargs"])
    os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"
    try:
        got = jax.value_and_grad(lambda p: _program_loss(model, p, x, y)[0])(params)
    finally:
        del os.environ["TPUFRAME_PALLAS_INTERPRET"]
    want = jax.value_and_grad(REF.loss)(params, x, y, CFG)
    return {"params": params, "x": x, "y": y, "model": model, "got": got, "want": want}


class TestProgramAgainstReference:
    def test_parameter_tree_is_the_references(self, small):
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), small["x"]))
        got = jax.tree.map(lambda a: tuple(a.shape), got["params"])
        assert got == jax.tree.map(lambda a: tuple(a.shape), small["params"])
        # a window layer's leaves are a full layer's
        assert (jax.tree.map(lambda a: a.shape, small["params"]["block0"])
                == jax.tree.map(lambda a: a.shape, small["params"]["block1"]))

    def test_loss(self, small):
        assert abs(float(small["got"][0]) - float(small["want"][0])) < 1e-5

    @pytest.mark.parametrize("leaf", LEAVES)
    def test_gradient_leaf(self, small, leaf):
        g, w = (correct.leaf_paths(small[side][1])[leaf] for side in ("got", "want"))
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * float(jnp.linalg.norm(w)), leaf

    def test_counters_and_scope(self, small):
        from tpuframe.track.telemetry import get_telemetry

        registry = get_telemetry().registry
        before = [registry.counter(f"attention/tiles_{n}").value for n in ("visited", "needed")]
        model = TransformerLM(**{**CFG["model"]["kwargs"], "attn_impl": "blockwise"})
        text = jax.jit(lambda p: model.apply({"params": p}, small["x"])).lower(
            small["params"]).as_text(debug_info=True)
        # the window layer's core under a scope of its own, the full layer's where it was
        assert "tpuframe/attn/window/" in text and re.search(r"tpuframe/attn/(?!window)", text)
        # the window layer adds its static counts once a trace; the full layer nothing
        visited, needed = tile_counts(SlidingWindowMask(CFG["sliding_window"]), CFG["seq_len"],
                                      kernels=False)
        heads = 2 * CFG["num_attention_heads"]
        after = [registry.counter(f"attention/tiles_{n}").value for n in ("visited", "needed")]
        assert after[0] - before[0] == pytest.approx(visited * heads)
        assert after[1] - before[1] == pytest.approx(needed * heads)

    @pytest.mark.parametrize("fault", ["window_is_causal", "full_layer_with_the_windows_tables",
                                       "window_one_key_short"])
    def test_a_fault_in_the_new_layers_is_seen(self, small, monkeypatch, fault):
        if fault == "window_is_causal":
            real = tr._attend
            monkeypatch.setattr(tr, "_attend", lambda *a, mask=None, **kw: real(*a, **kw))
        elif fault == "full_layer_with_the_windows_tables":
            monkeypatch.setattr(TransformerLM, "_rope_of", lambda self, kind: (500000, None))
        else:
            monkeypatch.setattr(tr, "SlidingWindowMask",
                                lambda window: SlidingWindowMask(window - 1))
        got = float(_program_loss(small["model"], small["params"], small["x"], small["y"])[0])
        assert abs(got - float(small["want"][0])) > 1e-4

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_the_sequence_sharded_forms_refuse_a_window(self, small, impl):
        from tpuframe.core import MeshSpec, initialize

        initialize(MeshSpec(data=-1))
        model = TransformerLM(**{**CFG["model"]["kwargs"], "attn_impl": impl})
        with pytest.raises(ValueError, match="mask rules run full or blockwise"):
            model.apply({"params": small["params"]}, small["x"])

    def test_unknown_kinds_are_named_from_one_tuple(self, small):
        model = TransformerLM(**{**CFG["model"]["kwargs"],
                                 "layer_types": ["sliding_attention", "mamba"]})
        with pytest.raises(ValueError, match="known: " + ", ".join(tr.Block.MIXERS)):
            model.init(jax.random.PRNGKey(0), small["x"])


class TestSharesAddUpToTheUncutLayer:
    def test_the_eight_shares_of_sixty_four_experts(self):
        """Mellum2's expert layer at a small width: 64 experts, 8 a token,
        softmax gates renormalised, no shared expert (nothing is counted
        once): the parts the eight chips' 8 experts give add up to the uncut
        layer, and each share is the reference's own."""
        d, e, h, k = 32, 64, 16, 8
        uncut = {**FULL, "hidden_size": d, "moe_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_tok": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(41), 5)
        n = lambda kk, *s: 0.3 * jax.random.normal(kk, s, jnp.float32)  # noqa: E731
        p = {"router": {"kernel": n(key[0], d, e)}, "w_gate": n(key[1], e, d, h),
             "w_in": n(key[2], e, d, h), "w_out": n(key[3], e, h, d)}
        x = jax.random.normal(key[4], (2, 24, d), jnp.float32)
        want, _ = REF._moe(p, x, uncut, lambda f: f, False)
        total = jnp.zeros_like(x)
        for first in range(0, e, 8):
            layer = MoEMLP(num_experts=e, top_k=k, expert_dim=h, held=(first, 8), gated=True,
                           renormalize=True, aux_loss_weight=0.001, capacity_factor=None)
            share = {**p, **{w: p[w][first:first + 8] for w in ("w_gate", "w_in", "w_out")}}
            part, _ = layer.apply({"params": share}, x, mutable=["aux_loss", "counters", "gauges"])
            total = total + part
            held = {**uncut, "num_experts": 8, "held_first": first}
            np.testing.assert_allclose(np.asarray(part), np.asarray(
                REF._moe(share, x, held, lambda f: f, False)[0]), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-5, atol=2e-6)
