"""Attention over the keys a learned index chooses: the index op against its
oracle (scores, exactly ``topk`` chosen, ties to the earlier key), the rule that
reads its choice in the oracle, the scan schedule and both flash kernels
(interpret mode), the model against the plain reference of
``chipbench/reference/keye-vl-2.0-30b-a3b.py`` (loss, gradients, three optimizer
steps, a frozen index), the share test for its expert layer, the sharding
rules for the new leaves, and the older configurations' StableHLO digests.
Small sizes, on the CPU."""

import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct
from tpuframe import models
from tpuframe.models import TransformerLM, moe_rules, transformer_tp_rules
from tpuframe.models import transformer as tr
from tpuframe.models.moe import MoEMLP
from tpuframe.ops import sparse_index
from tpuframe.ops.blockwise_attention import (
    blockwise_attention,
    blockwise_attention_reference,
    tile_counts,
)
from tpuframe.ops.ring_attention import (
    BlockDiffusionMask,
    SelectedKeysMask,
    SlidingWindowMask,
    attention_reference,
    mask_or_causal,
    pad_operands,
)
from tpuframe.ops.sparse_index import (
    index_scores_reference,
    select_keys,
    select_keys_reference,
)

bw = importlib.import_module("tpuframe.ops.blockwise_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "keye-vl-2.0-30b-a3b"
REF = correct.load_by_name("reference", NAME)
FLOPS = correct.load_by_name("flops", NAME)


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def _config(name, rehearsal=True):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        full = json.load(f)
    return _merge(full, full["rehearsal"]) if rehearsal else full


FULL = _config(NAME, rehearsal=False)
#: the configuration at its rehearsal sizes: rows of 64, 16 keys a query (so the
#: choice is real), 2 index heads of 64 (whole lanes: the kernel runs in
#: interpret mode), 8 experts of which 4 held
CFG = _config(NAME)
TOPK = CFG["sa_config"]["topk"]


def _leaf_names(tree):
    return sorted(correct.leaf_paths(tree))


LEAVES = _leaf_names(jax.tree.map(lambda s: 0, REF.param_shapes(CFG), is_leaf=correct._is_spec))
INDEX_LEAVES = [n for n in LEAVES if "/index_" in n]


# -- the index op -----------------------------------------------------------------
def _index_inputs(b, l, h, d, seed=0, grid=True):
    """Values on a coarse grid: every product and sum is exact in float32, so
    two orders of summation give one score, and ties abound."""
    rng = np.random.default_rng(seed)
    if grid:
        draw = lambda *shape: rng.integers(-2, 3, shape) / 4  # noqa: E731
    else:
        draw = lambda *shape: rng.standard_normal(shape)  # noqa: E731
    return (jnp.asarray(draw(b, l, h, d), jnp.float32), jnp.asarray(draw(b, l, d), jnp.float32),
            jnp.asarray(draw(b, l, h) * 2, jnp.float32))


def _chosen_by_hand(scores, topk):
    """The ``topk`` largest of each row's keys not after the query, ties to the
    earlier key, one row at a time in numpy."""
    b, l, _ = scores.shape
    out = np.zeros((b, l, l), np.int8)
    for r in range(b):
        for t in range(l):
            order = np.lexsort((np.arange(t + 1), -scores[r, t, :t + 1]))[:topk]
            out[r, t, order] = 1
    return out


INDEX_SHAPES = [(2, 64, 2, 64, 16), (1, 200, 4, 64, 48), (2, 130, 1, 128, 7),
                (1, 96, 8, 32, 33), (1, 640, 2, 64, 100)]


class TestIndexOp:
    @pytest.mark.parametrize("shape", INDEX_SHAPES, ids=lambda s: "x".join(map(str, s)))
    def test_the_oracle_chooses_as_the_formula_says(self, shape):
        b, l, h, d, topk = shape
        qi, ki, w = _index_inputs(b, l, h, d)
        scores = np.asarray(index_scores_reference(qi, ki, w))
        by_hand = np.einsum("bqh,bhqk->bqk", np.asarray(w), np.maximum(
            np.einsum("bqhd,bkd->bhqk", np.asarray(qi), np.asarray(ki)), 0.0))
        np.testing.assert_array_equal(scores, by_hand)       # exact arithmetic on the grid
        chosen, counts = select_keys_reference(qi, ki, w, topk)
        np.testing.assert_array_equal(np.asarray(chosen), _chosen_by_hand(scores, topk))
        np.testing.assert_array_equal(np.asarray(counts[0]), np.minimum(np.arange(l) + 1, topk))

    @pytest.mark.parametrize("shape", INDEX_SHAPES, ids=lambda s: "x".join(map(str, s)))
    def test_the_kernel_matches_the_oracle(self, shape):
        """Bit for bit, ties and all: exactly ``topk`` chosen, the earlier key
        of equals, every key of a row under ``topk``."""
        b, l, h, d, topk = shape
        args = _index_inputs(b, l, h, d, seed=1)
        want, want_n = select_keys_reference(*args, topk)
        got, got_n = select_keys(*args, topk, interpret=True)
        assert got.dtype == jnp.int8 and got.shape == (b, l, l)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
        np.testing.assert_array_equal(np.asarray(got).sum(-1)[0], np.minimum(np.arange(l) + 1, topk))

    def test_the_kernel_on_scores_without_ties(self):
        qi, ki, w = _index_inputs(1, 300, 2, 64, seed=2, grid=False)
        got, _ = select_keys(qi, ki, w, 50, interpret=True)
        scores = np.asarray(index_scores_reference(qi, ki, w))
        by_hand = _chosen_by_hand(scores, 50)
        # the two float32 sums may differ in their order: a flip only at the threshold
        differ = np.asarray(got) != by_hand
        assert differ.sum() <= 4
        edge = np.sort(np.where(np.tril(np.ones((300, 300), bool)), scores[0], -np.inf), -1)[:, -50]
        assert np.all(np.abs(scores[0] - edge[:, None])[differ[0]] < 1e-5)

    def test_all_equal_scores_choose_the_earliest_keys(self):
        qi, ki, w = (jnp.zeros(s, jnp.float32) for s in ((1, 128, 2, 64), (1, 128, 64), (1, 128, 2)))
        for fn in (select_keys_reference, lambda *a: select_keys(*a, interpret=True)):
            chosen = np.asarray(fn(qi, ki, w, 10)[0])[0]
            assert np.array_equal(chosen, np.tril(np.ones((128, 128), np.int8)) * (np.arange(128) < 10))

    def test_no_gradient_and_what_the_shape_rule_turns_away(self):
        qi, ki, w = _index_inputs(1, 64, 3, 48)             # no whole lanes: the oracle runs
        np.testing.assert_array_equal(
            np.asarray(select_keys(qi, ki, w, 5)[0]), np.asarray(select_keys_reference(qi, ki, w, 5)[0]))
        with pytest.raises(ValueError, match="at least one key"):
            select_keys(qi, ki, w, 0)
        with pytest.raises(ValueError, match="not \\(B, L, Hi, Di\\)"):
            select_keys(qi, ki[:, :, :8], w, 4)

    def test_registered(self):
        from tpuframe.ops.registry import OPS_REGISTRY, map_op_name

        assert OPS_REGISTRY["sparse_index"]["module"] == sparse_index.__name__
        assert map_op_name("tpuframe_index_topk") == "sparse_index"


# -- the rule ---------------------------------------------------------------------
class TestTheRule:
    @pytest.mark.parametrize("length, topk", [(64, 16), (64, 64), (64, 100), (8192, 2048), (33, 1)])
    def test_area_plain_and_fits(self, length, topk):
        rule = SelectedKeysMask(topk)
        assert rule.area(length) == sum(min(t + 1, topk) for t in range(length))
        assert rule.plain(length) == (length <= topk)
        assert rule.fits(length) and not SelectedKeysMask(0).fits(length)
        assert rule.reads == 1 and rule.suffix == "_select"

    def test_the_area_at_the_cells_shape(self):
        rule = SelectedKeysMask(2048)
        assert rule.area(8192) == 14_681_088 and 8192 * 8193 // 2 == 33_558_528
        assert rule.area(8192) / 33_558_528 == pytest.approx(0.4375, abs=2e-4)

    @pytest.mark.parametrize("side", [128, 256, 512])
    def test_tiles_under_topk_are_judged_as_causal_judges_them(self, side):
        rule, n = SelectedKeysMask(512), 1024 // side
        lo = np.arange(n) * side
        live, whole = rule.tiles(lo[:, None], lo[:, None] + side - 1, lo[None, :], lo[None, :] + side - 1)
        assert np.array_equal(live, np.tril(np.ones((n, n), bool)))
        under = (lo + side <= 512)[:, None]
        assert np.array_equal(whole, np.tril(np.ones((n, n), bool), -1) & under)

    def test_tile_counts_of_the_cell(self):
        visited, needed = tile_counts(SelectedKeysMask(2048), 8192)
        # the causal tiles of both sweeps in tiles of 512: 36 x 4 + 136; the chosen pairs twice
        assert visited == 36 * 4 + 136 and needed == pytest.approx(2 * 14_681_088 / 512 ** 2)

    def test_a_rule_that_reads_operands_wants_them(self):
        q = jnp.zeros((1, 32, 1, 16))
        with pytest.raises(ValueError, match="reads 1 operand"):
            attention_reference(q, q, q, mask=SelectedKeysMask(4))
        with pytest.raises(ValueError, match="reads 0 operand"):
            blockwise_attention_reference(q, q, q, mask=SlidingWindowMask(4),
                                          mask_operands=(jnp.zeros((1, 32, 32), jnp.int8),))
        assert mask_or_causal(True, SelectedKeysMask(32), 32) is True

    def test_padding_keeps_every_row_a_key(self):
        chosen = jnp.ones((1, 5, 5), jnp.int8)
        (padded,) = pad_operands((chosen,), 5, 8)
        assert padded.shape == (1, 8, 8) and int(padded[0, :5, 5:].sum()) == 0
        np.testing.assert_array_equal(np.asarray(padded[0, 5:]), np.eye(8, dtype=np.int8)[0][None].repeat(3, 0))

    @pytest.mark.parametrize("rule", [BlockDiffusionMask(8, 2), SlidingWindowMask(8)])
    def test_the_rules_on_positions_read_none(self, rule):
        assert getattr(rule, "reads", 0) == 0


def _qkv(length, heads, kv_heads, d, b=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, length, h, d)), jnp.float32)
                 for h in (heads, kv_heads, kv_heads))


def _choice(b, length, topk, seed=0):
    return select_keys_reference(*_index_inputs(b, length, 2, 64, seed=seed, grid=False), topk)[0]


def _dense(q, k, v, chosen):
    """A softmax over the chosen keys alone, by hand."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(chosen[:, None] != 0, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _form(form, rule, chosen, side):
    how = {"mask": rule, "mask_operands": (chosen,)}
    if form == "oracle":
        return lambda q, k, v: attention_reference(q, k, v, **how)
    if form == "scan":
        return lambda q, k, v: blockwise_attention_reference(q, k, v, block_size=side, **how)
    return lambda q, k, v: blockwise_attention(q, k, v, block_size=side, interpret=True, **how)


#: (length, topk, tile side, heads, key/value heads, head width)
GRID = [(64, 16, 32, 4, 2, 16), (200, 48, 128, 4, 2, 128), (256, 100, 128, 2, 2, 64),
        (300, 33, 64, 8, 2, 128)]


class TestSchedulesAgainstADenseSoftmaxOverTheChosenKeys:
    @pytest.mark.parametrize("length, topk, side, heads, kv_heads, d", GRID)
    @pytest.mark.parametrize("form", ["oracle", "scan", "kernels"])
    def test_forward_and_all_three_gradients(self, length, topk, side, heads, kv_heads, d, form):
        q, k, v = _qkv(length, heads, kv_heads, d)
        chosen = _choice(2, length, topk)
        w = jnp.asarray(np.random.default_rng(3).standard_normal(q.shape), jnp.float32)
        want, vjp = jax.vjp(lambda *a: _dense(*a, chosen), q, k, v)
        got, got_vjp = jax.vjp(_form(form, SelectedKeysMask(topk), chosen, side), q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        for a, b in zip(got_vjp(w), vjp(w)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    @pytest.mark.parametrize("form", ["oracle", "scan", "kernels"])
    def test_a_row_no_longer_than_topk_lowers_to_the_causal_program(self, form, monkeypatch):
        """``plain(length)``: no plan, no operand, the causal op's own StableHLO."""
        q, k, v = _qkv(64, 2, 2, 64)
        chosen = jnp.ones((2, 64, 64), jnp.int8)            # never read
        under = _form(form, SelectedKeysMask(64), chosen, 32)
        causal = {"oracle": lambda *a: attention_reference(*a, causal=True),
                  "scan": lambda *a: blockwise_attention_reference(*a, causal=True, block_size=32),
                  "kernels": lambda *a: blockwise_attention(*a, causal=True, block_size=32,
                                                            interpret=True)}[form]
        lower = lambda f: jax.jit(f).lower(q, k, v).as_text()  # noqa: E731
        assert lower(lambda q, k, v: under(q, k, v)) == lower(lambda q, k, v: causal(q, k, v))

    def test_the_kernels_names_carry_the_rules_suffix(self):
        q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)
        chosen = jax.ShapeDtypeStruct((1, 1024, 1024), jnp.int8)

        def loss(q, k, v, chosen):
            return jnp.sum(blockwise_attention(
                q, k, v, mask=SelectedKeysMask(256), mask_operands=(chosen,), interpret=False)
                .astype(jnp.float32))

        text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, k, k, chosen).lower(
            lowering_platforms=("tpu",)).as_text()
        found = {line.split('kernel_name = "')[1].split('"')[0]
                 for line in text.splitlines() if 'kernel_name = "tpuframe_flash' in line}
        assert found == {"tpuframe_flash_fwd_select", "tpuframe_flash_bwd_select"}

    def test_a_rule_is_no_other_rules_static_argument(self):
        """NamedTuples compare as tuples: a band of 64 keys and a choice of 64
        must not share a trace in the caches that key on the rule."""
        assert SelectedKeysMask(64) != SlidingWindowMask(64)
        assert hash(SelectedKeysMask(64)) != hash(SlidingWindowMask(64))
        assert SelectedKeysMask(8, "selected") != BlockDiffusionMask(8, 2)


# -- the model against the plain reference ----------------------------------------
def _program_loss(model, params, x, y):
    logits, upd = model.apply({"params": params}, x, train=True,
                              mutable=["aux_loss", "counters", "gauges"])
    logp = jax.nn.log_softmax(logits, -1)
    data = -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
    aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"]))
    return data + (aux - jax.lax.stop_gradient(aux)), upd


#: rows a batch: with these widths no other configuration's rehearsal has as many
#: tokens, so no jitted wrapper of an op keeps a trace here that another file's
#: digest test would meet at its own shapes
ROWS = 3


def _rows(rng, length=None):
    rows = rng.integers(0, CFG["vocab_size"], (ROWS, (length or CFG["seq_len"]) + 1))
    return jnp.asarray(rows[:, :-1], jnp.int32), jnp.asarray(rows[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def small():
    """The configuration's rehearsal sizes, seeded weights, a batch, and loss
    and gradients both ways, the kernels in interpret mode."""
    params = correct.init_params(REF.param_shapes(CFG), 2147484047)
    x, y = _rows(np.random.default_rng(47))
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
        assert LEAVES == _leaf_names(small["params"]) and len(INDEX_LEAVES) == 5 * 2

    def test_the_parameter_tree_is_the_same_where_the_index_does_not_run(self, small):
        x = small["x"][:, :TOPK]
        got = jax.eval_shape(lambda: small["model"].init(jax.random.PRNGKey(0), x))
        assert _leaf_names(got["params"]) == LEAVES

    def test_loss(self, small):
        assert abs(float(small["got"][0]) - float(small["want"][0])) < 3e-6

    @pytest.mark.parametrize("leaf", [n for n in LEAVES if n not in INDEX_LEAVES])
    def test_gradient_leaf(self, small, leaf):
        g, w = (correct.leaf_paths(small[side][1])[leaf] for side in ("got", "want"))
        assert float(jnp.linalg.norm(w)) > 0, leaf
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * float(jnp.linalg.norm(w)), leaf

    @pytest.mark.parametrize("leaf", INDEX_LEAVES)
    def test_the_index_is_frozen(self, small, leaf):
        for side in ("got", "want"):
            assert float(jnp.max(jnp.abs(correct.leaf_paths(small[side][1])[leaf]))) == 0.0, side

    def test_the_choice_is_the_references(self, small, monkeypatch):
        """The program's choice in block 0, captured where the rule gets it,
        is the reference's (L, L) mask."""
        seen = []
        real = tr._attend
        monkeypatch.setattr(tr, "_attend", lambda *a, **kw: (
            seen.append(kw.get("mask_operands")), real(*a, **kw))[1])
        small["model"].apply({"params": small["params"]}, small["x"],
                             mutable=["aux_loss", "counters", "gauges"])
        p = small["params"]["block0"]
        x = REF._rms(small["params"]["embed"]["embedding"][small["x"]], p["ln1"], 1e-6)
        want = REF.chosen_keys(p["attn"], x, CFG)
        assert len(seen) == CFG["num_hidden_layers"]
        np.testing.assert_array_equal(np.asarray(seen[0][0]) != 0, np.asarray(want))
        assert int(want.sum()) == ROWS * SelectedKeysMask(TOPK).area(CFG["seq_len"])

    def test_three_optimizer_steps(self, small, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        m, p = small["model"], small["params"]
        r = p
        rng = np.random.default_rng(9)
        for _ in range(3):
            x, y = _rows(rng)
            g = jax.grad(lambda q: _program_loss(m, q, x, y)[0])(p)
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            gr = jax.grad(REF.loss)(r, x, y, CFG)
            r = jax.tree.map(lambda a, b: a - 0.1 * b, r, gr)
        start = correct.leaf_paths(small["params"])
        for (name, a), b in zip(correct.leaf_paths(p).items(), jax.tree.leaves(r)):
            moved = float(jnp.linalg.norm(b - start[name]))
            assert (moved == 0) == (name in INDEX_LEAVES), name
            assert float(jnp.linalg.norm(a - b)) <= 5e-4 * moved, name

    def test_counters_and_scopes(self, small, monkeypatch):
        from tpuframe.track.telemetry import get_telemetry

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        registry = get_telemetry().registry
        before = [registry.counter(f"attention/tiles_{n}").value for n in ("visited", "needed")]
        text = jax.jit(lambda p: small["model"].apply(
            {"params": p}, small["x"], mutable=["aux_loss", "counters", "gauges"])).lower(
            small["params"]).as_text(debug_info=True)
        for scope in ("tpuframe/attn/index", "tpuframe/attn/select"):
            assert scope in text, scope
        assert "tpuframe_index_topk" in text and "tpuframe_flash_fwd_select" in text
        # static counts a trace, both layers: rows x heads x (visited, needed)
        visited, needed = tile_counts(SelectedKeysMask(TOPK), CFG["seq_len"], kernels=True)
        heads = ROWS * CFG["num_attention_heads"] * CFG["num_hidden_layers"]
        after = [registry.counter(f"attention/tiles_{n}").value for n in ("visited", "needed")]
        assert after[0] - before[0] == pytest.approx(visited * heads)
        assert after[1] - before[1] == pytest.approx(needed * heads)
        # what rides the step: the pairs the index chose, counted from what it wrote
        _, upd = _program_loss(small["model"], small["params"], small["x"], small["y"])
        for block in ("block0", "block1"):
            got = upd["counters"][block]["attn"]
            assert float(got["attention/pairs_selected"]) == ROWS * SelectedKeysMask(TOPK).area(64)
            assert float(got["attention/pairs_causal"]) == ROWS * 64 * 65 // 2

    def test_a_plain_row_runs_no_index_and_counts_every_causal_pair(self, small):
        x, _ = _rows(np.random.default_rng(1), TOPK)
        fn = lambda p: small["model"].apply(  # noqa: E731
            {"params": p}, x, mutable=["aux_loss", "counters", "gauges"])
        text = jax.jit(fn).lower(small["params"]).as_text(debug_info=True)
        assert "tpuframe/attn/index" not in text and "tpuframe/attn/select" not in text
        got = fn(small["params"])[1]["counters"]["block1"]["attn"]
        assert (float(got["attention/pairs_selected"]) == float(got["attention/pairs_causal"])
                == ROWS * TOPK * (TOPK + 1) // 2)
        # and is the model without an index, leaf for leaf of what it uses
        kw = {k: v for k, v in CFG["model"]["kwargs"].items() if k != "sparse_index"}
        bare = jax.tree_util.tree_map_with_path(lambda path, a: a, {
            k: ({**v, "attn": {n: a for n, a in v["attn"].items() if not n.startswith("index_")}}
                if "attn" in v else v) for k, v in small["params"].items()})
        np.testing.assert_array_equal(
            np.asarray(fn(small["params"])[0]),
            np.asarray(TransformerLM(**kw).apply({"params": bare}, x,
                                                 mutable=["aux_loss", "counters", "gauges"])[0]))

    @pytest.mark.parametrize("fault", ["one_key_too_few", "a_choice_that_ignores_w",
                                       "attention_over_all_causal_keys"])
    def test_a_fault_in_the_new_layers_is_seen(self, small, monkeypatch, fault):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        if fault == "one_key_too_few":
            real = tr.select_keys
            monkeypatch.setattr(tr, "select_keys", lambda qi, ki, w, topk, **kw: real(
                qi, ki, w, topk - 1, **kw))
        elif fault == "a_choice_that_ignores_w":
            real = tr.select_keys
            monkeypatch.setattr(tr, "select_keys", lambda qi, ki, w, topk, **kw: real(
                qi, ki, jnp.ones_like(w), topk, **kw))
        else:
            real = tr._attend
            monkeypatch.setattr(tr, "_attend", lambda *a, mask=None, mask_operands=(), **kw: real(
                *a, **kw))
        got = float(_program_loss(small["model"], small["params"], small["x"], small["y"])[0])
        # a sound run reads 5e-7; one key of sixteen left out moves the loss least
        assert abs(got - float(small["want"][0])) > 1e-5

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_the_sequence_sharded_forms_refuse_the_rule(self, small, impl):
        from tpuframe.core import MeshSpec, initialize

        initialize(MeshSpec(data=-1))
        model = TransformerLM(**{**CFG["model"]["kwargs"], "attn_impl": impl})
        with pytest.raises(ValueError, match="mask rules run full or blockwise"):
            model.apply({"params": small["params"]}, small["x"])

    @pytest.mark.parametrize("kw, match", [
        ({"layer_types": ["sliding_attention", "full_attention"], "sliding_window": 8}, None),
        ({"attn_impl": "full"}, None),
    ])
    def test_other_layers_and_forms_beside_it(self, small, kw, match):
        """A window layer brings its own rule and takes no index (its leaves
        are a plain layer's); the full form reads the choice too."""
        model = TransformerLM(**{**CFG["model"]["kwargs"], **kw})
        tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), small["x"]))["params"]
        has = ["index_q" in tree[f"block{i}"]["attn"] for i in range(2)]
        assert has == ([False, True] if "layer_types" in kw else [True, True])
        if "attn_impl" in kw:
            got = float(_program_loss(model, small["params"], small["x"], small["y"])[0])
            assert abs(got - float(small["want"][0])) < 1e-5

    def test_an_index_beside_another_rule_is_refused(self, small):
        attn = tr.SelfAttention(4, 16, mask=SlidingWindowMask(8),
                                sparse_index=(("head_dim", 64), ("num_heads", 2), ("topk", 4)))
        with pytest.raises(ValueError, match="no other mask rule"):
            attn.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 64)))


class TestSharesAddUpToTheUncutLayer:
    def test_the_sixteen_shares_of_one_hundred_and_twenty_eight_experts(self):
        """Keye-VL-2.0's expert layer at a small width: 128 experts, 8 a token,
        softmax gates renormalised, no shared expert (nothing is counted
        once): the parts the sixteen chips' 8 experts give add up to the uncut
        layer, and each share is the reference's own."""
        d, e, h, k = 32, 128, 16, 8
        uncut = {**FULL, "hidden_size": d, "moe_intermediate_size": h, "num_experts": e,
                 "num_experts_published": e, "num_experts_per_tok": k, "held_first": 0}
        key = jax.random.split(jax.random.PRNGKey(47), 5)
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


# -- the configuration's file and its counts ---------------------------------------
class TestTheConfiguration:
    def test_parameters_and_required_work(self):
        assert FLOPS.total_params(FULL) == FULL["parameters"] == 314_396_160
        assert FLOPS.total_params(FULL, published=True) == FULL["parameters_published"]
        assert FLOPS.layer_params(FULL, 8) == 59_150_720
        assert FLOPS.chosen_pairs(FULL) == 14_681_088 and FLOPS.causal_pairs(FULL) == 33_558_528
        shapes = REF.param_shapes(FULL)
        assert sum(int(np.prod(s[0])) for s in jax.tree.leaves(
            shapes, is_leaf=correct._is_spec)) == FULL["parameters"]

    def test_the_index_is_counted_once_and_attention_at_the_chosen_pairs(self):
        once = 2 * 4 * FLOPS.index_macs_per_sample(FULL)
        assert FLOPS.train_flops_per_sample(FULL) == 6 * FLOPS.forward_macs_per_sample(FULL) + once
        dense = {**FULL, "sa_config": {**FULL["sa_config"], "topk": 8192}}
        gap = FLOPS.forward_macs_per_sample(dense) - FLOPS.forward_macs_per_sample(FULL)
        assert gap == 4 * 2 * 32 * 128 * (33_558_528 - 14_681_088)
        assert FLOPS.index_macs_per_sample(dense) == 0      # a plain row runs no index

    def test_no_width_differs_from_the_catalogs(self):
        catalog = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
                   "moe_intermediate_size": 768, "num_attention_heads": 32,
                   "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_local_experts": 128,
                   "rope_theta": 10000000, "max_position_embeddings": 262144,
                   "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                 "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                 "q_chunk_size": 512, "topk": 2048}}
        assert {k: FULL[k] for k in catalog} == catalog
        assert FULL["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
        assert {k: FULL[k] for k in FULL["reduced"]} == {
            "num_hidden_layers": 4, "num_experts": 8, "vocab_size": 18992}
        index = FULL["model"]["kwargs"]["sparse_index"]
        assert index == {"num_heads": 16, "head_dim": 64, "topk": 2048}


# -- sharding rules ----------------------------------------------------------------
@pytest.fixture(scope="module")
def placed():
    from tpuframe.parallel.compose import compose

    plan = compose(dp=2, fsdp=2, tp=2, zero_stage=3, min_shard_elems=1,
                   rules=transformer_tp_rules() + moe_rules())
    params = correct.init_params(REF.param_shapes(CFG), 7)
    shardings = plan.param_shardings(params)
    return {"plan": plan, "params": params,
            "specs": {k: s.spec for k, s in correct.leaf_paths(shardings).items()}}


class TestShardingRulesPlaceTheNewLeaves:
    @pytest.mark.parametrize("leaf", LEAVES)
    def test_every_leaf_has_a_spec_that_divides_it(self, placed, leaf):
        spec, shape = placed["specs"][leaf], correct.leaf_paths(placed["params"])[leaf].shape
        assert len(spec) <= len(shape)
        for size, entry in zip(shape, spec):
            names = () if entry is None else entry if isinstance(entry, tuple) else (entry,)
            assert size % int(np.prod([placed["plan"].axis_size(a) for a in names] or [1])) == 0

    @pytest.mark.parametrize("leaf", [n for n in INDEX_LEAVES if n.startswith("block0/")])
    def test_the_index_stays_off_the_model_axis(self, placed, leaf):
        assert "model" not in jax.tree.leaves(tuple(placed["specs"][leaf]))

    @pytest.mark.parametrize("leaf, axis, dim", [
        ("block0/attn/query/kernel", "model", 1), ("block1/attn/key/kernel", "model", 1),
        ("block0/attn/attn_out/kernel", "model", 0), ("block0/moe/w_gate", "expert", 0)])
    def test_a_rule_still_names_the_leaf(self, placed, leaf, axis, dim):
        assert placed["specs"][leaf][dim] == axis


# -- the older configurations ------------------------------------------------------
@pytest.mark.parametrize("name, digest", [
    ("deepseek-v2-lite", "9ef30ed59827517aa2315c8e75be0c17f7f6f5cd4032f09b37db003e27ff2962"),
    ("sdar-30b-a3b-chat", "b2e5bddd8e0a687f4322a5b8a1cc3fdb827518dec58ab48555100aab35b03ad0"),
    ("lfm2-8b-a1b", "4f0e9ae9ad79049bcbb547f82be78402e9955df5ce96601a2462ee8c7f98024b"),
    ("mellum2-12b-a2.5b-instruct", "7269ae3fcccacf7193dab9f0f055033f0afc7bfbe5883d042d047c55e89778b5"),
    ("gpt2-medium", "5b88065afea35c114fa6b9f4aff4c17c9297d6b6aa62d2ec8f607b9cb1fad14c"),
    ("qwen3-next-80b-a3b-instruct", "b5fdfee528749a0322ca91351f91ae67e9826c20ca4c196091ddc9e8bcaac6cb"),
])
@pytest.mark.parametrize("kernels", [False, True], ids=["scan", "interpret_kernels"])
def test_the_older_configurations_lower_to_the_parents_program(name, digest, kernels, monkeypatch):
    """Every transformer configuration the benchmark had before this one, at its
    rehearsal sizes, loss and every gradient, lowers to the StableHLO the parent
    commit (PR 46) lowers it to, byte for byte: a rule that reads operands adds
    no operand, argument or mask to a schedule that runs under a rule on
    positions, under causal or under no mask.  The first five digests are
    `tests/test_qwen3_next.py`'s, unchanged; the sixth was taken with this very
    function under jax 0.9.0 on PR 46's commit, as were the six with the
    kernels in interpret mode (the form the chip runs).  PR 48 (the un-sort as
    a kernel, `ops/unsort.py`) left all twelve as they stand: at these sizes a
    layer's buffers hold a slot a pair (`slot_bound`), so the un-sort is the
    gather by ``inv`` in both modes, and the expert layer carries the groups'
    sizes to the kernel's path only where the buffers are bounded
    (`tests/test_unsort.py` holds the layer with the kernel in)."""
    interpret = {
        "deepseek-v2-lite": "38cb80fea5179eb002f883e4a617b816ab37f92bba5c502a5573f7cc8148bfd0",
        "sdar-30b-a3b-chat": "07ec3cd55dd5ea804aa0e0051242348238a588a129d82648c18a20eec2ab0527",
        "lfm2-8b-a1b": "54539be9ae7b2dec60e6dbd44c87ba553be43c11c47b9a8502cd9dff9b2f1f8a",
        "mellum2-12b-a2.5b-instruct": "81566b4e61d1740d1720309390fb51e0547f1f013b5e455ee32cdadd6f525a81",
        "gpt2-medium": "8fc326c54a9170733dcde7211eb6d86b669df4fcc6a54ea78ef9d0f8c85e8f6e",
        "qwen3-next-80b-a3b-instruct": "eed5000f1a3b5f3353d2bf7c2d04fc2e2f244b17241a6691a2d2eaa4eea4662b",
    }
    # a jitted wrapper inside an op keeps the trace of the mode it first ran in:
    # start from none, whatever this process lowered before at these shapes
    jax.clear_caches()
    if kernels:
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        digest = interpret[name]
    cfg = _config(name)
    model = getattr(models, cfg["model"]["class"])(**cfg["model"]["kwargs"])
    shape = (2, cfg["seq_len"], 3) if cfg["sample"] == "blockdiff" else (2, cfg["seq_len"])
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(shape, jnp.int32)))["params"]

    def objective(p, x):
        out, upd = model.apply({"params": p}, x, train=True,
                               mutable=["aux_loss", "counters", "gauges"])
        aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd.get("aux_loss", {})))
        return jnp.sum(out.astype(jnp.float32)) + aux, upd

    try:
        text = jax.jit(jax.value_and_grad(objective, has_aux=True)).lower(
            params, jax.ShapeDtypeStruct(shape, jnp.int32)).as_text()
    finally:
        jax.clear_caches()      # and leave none for the files that follow in this process
    assert hashlib.sha256(text.encode()).hexdigest() == digest
