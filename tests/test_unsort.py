"""``ops.unsort``: the expert layer's un-sort as a kernel, in interpret mode
on the CPU, against XLA's form (``models.moe._sum_choices_impl``, a gather a
(token, choice) pair) and against the scatter-add oracle; the plan's runs;
the dispatch; and the layer's value and gradients with the kernel in."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models import moe
from tpuframe.models.moe import MoEMLP, slot_bound
from tpuframe.ops import dispatch

us = importlib.import_module("tpuframe.ops.unsort")

#: (choices a token, held experts, experts) of the six expert cells' layers
CELLS = {
    "dsv2lite_seq4096": (6, 8, 64),
    "sdar_blockdiff_seq4096": (8, 16, 128),
    "lfm2moe_seq4096": (4, 8, 32),
    "mellum2_seq8192": (8, 8, 64),
    "qwen3next_seq8192": (10, 16, 512),
    "keyevl2_seq8192": (8, 8, 128),
}


def _route(choices, held, cap, lo=0):
    """What the layer makes of ``choices`` (n, k), the experts each token
    chose, for the window ``[lo, lo + cap)`` of its sorted slots:
    ``(tok, inv, sizes, routed)`` as ``_expert_parts`` hands them on."""
    n, k = choices.shape
    key = np.where(choices.reshape(-1) < held, choices.reshape(-1), held)
    order = np.argsort(key, kind="stable")
    sizes = np.bincount(key, minlength=held + 1)[:held]
    ends = np.cumsum(sizes)
    sizes = np.clip(ends, lo, lo + cap) - np.clip(ends - sizes, lo, lo + cap)
    tok = np.pad(order // k, (0, -(n * k) % cap + cap))[lo:lo + cap]
    return (jnp.asarray(tok, jnp.int32), jnp.asarray(np.argsort(order) - lo, jnp.int32),
            jnp.asarray(sizes, jnp.int32), int(sizes.sum()))


def _choices(rng, n, k, experts, lift=0.0, held=0):
    scores = rng.standard_normal((n, experts))
    scores[:, :held] += lift
    return np.argsort(-scores, axis=1)[:, :k]


def _rows(rng, cap, d, routed, dtype, dead=0.0):
    rows = jnp.asarray(rng.standard_normal((cap, d)), dtype)
    return jnp.where((jnp.arange(cap) < routed)[:, None], rows, dead)


def _holds(got, want, here, dtype=jnp.bfloat16):
    """Equal where a token has at most two live rows (a float32 sum of two
    is one rounding whatever the order), within one rounding of ``dtype``
    elsewhere (float32 rows: within the roundings of a float32 sum of
    ``k`` taken in another order)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    few = np.asarray(here) <= 2
    np.testing.assert_array_equal(got[few], want[few])
    eps = float(jnp.finfo(dtype).eps)
    floor, slack = (1e-3, 1) if dtype == jnp.bfloat16 else (1.0, 8)
    assert np.all(np.abs(got - want) <= slack * eps * np.maximum(np.abs(want), floor))


def _against_xlas_form(rng, choices, held, cap, d, dtype=jnp.bfloat16, lo=0):
    n = choices.shape[0]
    tok, inv, sizes, routed = _route(choices, held, cap, lo)
    rows = _rows(rng, cap, d, routed, dtype)
    got = us.unsort(rows, tok, sizes, n, interpret=True)
    assert got.shape == (n, d) and got.dtype == rows.dtype
    here = np.bincount(np.asarray(tok[:routed]), minlength=n)
    _holds(got, moe._sum_choices_impl(rows, inv, n), here, dtype)
    return routed, here


class TestKernel:
    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_the_cells_shapes_against_xlas_form(self, cell):
        """Each expert cell's (k, held / experts) at 512 tokens and 256
        columns, the buffers ``slot_bound`` gives, a seeded router."""
        k, held, experts = CELLS[cell]
        rng = np.random.default_rng(sum(cell.encode()))
        cap = slot_bound(512 * k, held, experts)
        assert cap < 512 * k
        routed, here = _against_xlas_form(rng, _choices(rng, 512, k, experts), held, cap, 256)
        assert 0 < routed < cap and here.max() >= 2

    def test_a_column_count_that_is_no_power_of_two(self):
        rng = np.random.default_rng(1)
        _against_xlas_form(rng, _choices(rng, 256, 8, 64), 8, 512, 18 * 128)

    @pytest.mark.parametrize("lo", [0, 512, 1024])
    def test_a_window_with_lo_above_zero(self, lo):
        """A router that sends 1,100-1,300 of 2,048 pairs here: the windows
        of 512 slots from 0, 512 and 1,024, the last partly past the pairs."""
        rng = np.random.default_rng(2)
        choices = _choices(rng, 512, 4, 16, lift=1.0, held=4)
        routed, _ = _against_xlas_form(rng, choices, 4, 512, 128, lo=lo)
        assert routed == 512 if lo < 1024 else 0 < routed < 512

    def test_an_empty_held_expert(self):
        rng = np.random.default_rng(3)
        choices = _choices(rng, 512, 4, 32)
        choices = np.where(choices == 2, 31, choices)      # nobody chooses held expert 2
        tok, _, sizes, _ = _route(choices, 8, 512)
        assert int(sizes[2]) == 0 and int(sizes[1]) > 0 and int(sizes[3]) > 0
        _against_xlas_form(rng, choices, 8, 512, 128)

    def test_a_token_with_every_choice_here_and_one_with_none(self):
        rng = np.random.default_rng(4)
        choices = _choices(rng, 256, 4, 32)
        choices[7] = [3, 0, 5, 1]
        choices[8] = [30, 31, 29, 28]
        _, here = _against_xlas_form(rng, choices, 8, 512, 128)
        assert here[7] == 4 and here[8] == 0
        tok, _, sizes, routed = _route(choices, 8, 512)
        out = us.unsort(_rows(rng, 512, 128, routed, jnp.bfloat16), tok, sizes, 256, interpret=True)
        assert not np.any(np.asarray(out[8], np.float32))

    def test_every_slot_of_the_window_routed(self):
        rng = np.random.default_rng(5)
        choices = rng.permuted(np.tile(np.arange(4), (512, 1)), axis=1)[:, :2]  # all four held
        routed, here = _against_xlas_form(rng, choices, 4, 512, 128)
        assert routed == 512 and here.max() == 2

    def test_nan_past_the_groups_never_reaches_a_sum(self):
        rng = np.random.default_rng(6)
        tok, _, sizes, routed = _route(_choices(rng, 512, 8, 128), 8, 512)
        rows = _rows(rng, 512, 128, routed, jnp.bfloat16, dead=jnp.nan)
        got = us.unsort(rows, tok, sizes, 512, interpret=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(us.unsort_reference(rows, tok, sizes, 512), np.float32))

    def test_float32_rows_add_in_float32(self):
        rng = np.random.default_rng(7)
        _against_xlas_form(rng, _choices(rng, 256, 6, 64), 8, 512, 128, dtype=jnp.float32)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
    def test_a_run_longer_than_its_window_takes_further_rounds(self, dtype):
        """Every token chooses held expert 1: runs of 256 slots a tile of
        tokens through the shape rule's window of 80, four rounds of them."""
        rng = np.random.default_rng(8)
        choices = _choices(rng, 512, 4, 32)
        choices[:, 0] = 1
        choices[:, 1:] = np.where(choices[:, 1:] == 1, 31, choices[:, 1:])
        tok, _, sizes, _ = _route(choices, 8, 1024)
        assert int(sizes[1]) == 512 and us.unsort_window(1024, 128, 8, 512, dtype) == 80
        _against_xlas_form(rng, choices, 8, 1024, 128, dtype=dtype)

    def test_fewer_tokens_than_a_tile(self):
        rng = np.random.default_rng(9)
        _against_xlas_form(rng, _choices(rng, 64, 8, 64), 8, 128, 128)


class TestPlan:
    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_the_runs_reassemble_the_routed_slots(self, cell):
        """``[lo, hi)`` over (tile, expert), tile-major: within an expert the
        runs follow one another and together they are ``arange(routed)``;
        every slot of a run is the run's expert's and its tile's."""
        k, held, experts = CELLS[cell]
        rng = np.random.default_rng(len(cell))
        n, tokens = 1024, 256
        tok, _, sizes, routed = _route(_choices(rng, n, k, experts), held, slot_bound(n * k, held, experts))
        lo, hi = (np.asarray(a).reshape(n // tokens, held) for a in us._runs(tok, sizes, n, tokens))
        ends = np.cumsum(np.asarray(sizes))
        slots = []
        for e in range(held):
            assert lo[0, e] == ends[e] - sizes[e] and hi[-1, e] == ends[e]
            np.testing.assert_array_equal(hi[:-1, e], lo[1:, e])
            for i in range(n // tokens):
                run = np.arange(lo[i, e], hi[i, e])
                assert np.all(np.asarray(tok)[run] // tokens == i)
                slots.append(run)
        np.testing.assert_array_equal(np.concatenate(slots), np.arange(routed))

    def test_a_window_that_starts_inside_a_group(self):
        rng = np.random.default_rng(10)
        choices = _choices(rng, 512, 4, 16, lift=1.0, held=4)
        tok, _, sizes, routed = _route(choices, 4, 512, lo=512)
        lo, hi = (np.asarray(a) for a in us._runs(tok, sizes, 512, 256))
        assert routed == 512 and lo.min() == 0 and hi.max() == 512
        assert int(np.sum(hi - lo)) == 512 and np.all(hi >= lo)


class TestDispatch:
    @pytest.mark.parametrize("env, kernel", [({}, False), ({"TPUFRAME_PALLAS_INTERPRET": "1"}, True)])
    def test_dispatch_and_the_verdict_event(self, env, kernel, monkeypatch, tmp_path):
        """Through ``ops/dispatch.py`` like the grouped products: the caller's
        ``otherwise`` where Pallas is not compiled, the kernel where it
        runs, one ``ops/kernel_verdict`` event a distinct decision."""
        from tpuframe.track import telemetry as T

        for knob in ("TPUFRAME_PALLAS_INTERPRET", "TPUFRAME_DISABLE_PALLAS"):
            monkeypatch.delenv(knob, raising=False)
        for knob, value in env.items():
            monkeypatch.setenv(knob, value)
        rng = np.random.default_rng(11)
        tok, inv, sizes, routed = _route(_choices(rng, 256, 4, 32), 8, 512)
        rows = _rows(rng, 512, 128, routed, jnp.float32)
        dispatch._VERDICT_EMITTED.clear()
        tele = T.configure(str(tmp_path / "events.jsonl"))
        try:
            for _ in range(3):
                text = str(jax.make_jaxpr(lambda r: us.unsort(
                    r, tok, sizes, 256, otherwise=lambda: moe._sum_choices_impl(r, inv, 256)))(rows))
                assert ("tpuframe_unsort" in text) is kernel
                assert ("gather" in text) is not kernel
            (event,) = [e for e in tele.recent_events(50) if e["name"] == "ops/kernel_verdict"]
            assert (event["op"], event["shape_class"]) == ("unsort", "cap512_d128_g8_n256")
            assert event["enable"] is kernel and event["source"] == "default"
            assert event.get("window") == (us.unsort_window(512, 128, 8, 256, jnp.float32)
                                           if kernel else None)
        finally:
            T.reset()
            dispatch._VERDICT_EMITTED.clear()

    @pytest.mark.parametrize("cap, d, groups, n, why", [
        (512, 100, 8, 256, "columns that are no whole lanes"),
        (512, 128, 8, 300, "tokens that are no whole tiles"),
        (500, 128, 8, 256, "slots that are no whole sublane tiles"),
        (16384, 8192, 64, 8192, "windows that do not fit the buffers"),
    ])
    def test_a_shape_the_kernel_does_not_take(self, cap, d, groups, n, why):
        assert us.unsort_window(cap, d, groups, n, jnp.bfloat16) is None, why
        if cap * d > 1 << 20:
            return
        rows, tok = jnp.ones((cap, d), jnp.bfloat16), jnp.zeros((cap,), jnp.int32)
        sizes = jnp.zeros((groups,), jnp.int32).at[0].set(3)
        with pytest.raises(ValueError, match="takes no"):
            us.unsort(rows, tok, sizes, n, interpret=True)
        with pytest.raises(ValueError, match="gave no ``otherwise``"):
            us.unsort(rows, tok, sizes, n)
        oracle = functools.partial(us.unsort_reference, rows, tok, sizes, n)
        assert float(us.unsort(rows, tok, sizes, n, otherwise=oracle)[0, 0]) == 3.0

    @pytest.mark.parametrize("cap, d, groups, n, window", [
        (12288, 2048, 8, 8192, 64), (16384, 2048, 16, 8192, 48), (16384, 2048, 8, 8192, 80),
        (16384, 2304, 8, 8192, 80), (5120, 2048, 16, 8192, 32), (8192, 2048, 8, 8192, 48)],
        ids=sorted(CELLS, key=list(CELLS).index))
    def test_the_window_the_cells_shapes_take(self, cap, d, groups, n, window):
        assert us.unsort_window(cap, d, groups, n, jnp.bfloat16) == window

    def test_shapes_are_checked(self):
        with pytest.raises(ValueError, match=r"\(cap,\) tokens"):
            us.unsort(jnp.ones((64, 128)), jnp.zeros((32,), jnp.int32), jnp.ones((2,), jnp.int32), 16)


class TestTheLayerWithTheKernelIn:
    """512 tokens x 2 choices over 16 experts of which 2 are held, 128 wide:
    1,024 pairs through buffers of 512 slots, the layer with its kernels in
    interpret mode against itself on XLA's forms."""

    def _layer(self):
        return MoEMLP(num_experts=16, top_k=2, expert_dim=32, held=(0, 2), gated=True,
                      capacity_factor=None)

    def _setup(self, lift):
        layer = self._layer()
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 128)).at[..., 0].set(1.0)
        p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
        p = {**p, "router": {"kernel": p["router"]["kernel"].at[0, :2].add(lift)}}
        co = jax.random.normal(jax.random.PRNGKey(2), x.shape)

        def loss(p, x):
            out, upd = layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
            return jnp.sum(out * co), upd

        return loss, p, x

    @pytest.mark.parametrize("lift, windows", [(0.0, 1), (3.0, 2), (30.0, 2)],
                             ids=["fair", "overflowing", "every_pair_here"])
    def test_value_and_every_gradient_against_xlas_forms(self, lift, windows, monkeypatch):
        loss, p, x = self._setup(lift)
        monkeypatch.delenv("TPUFRAME_PALLAS_INTERPRET", raising=False)
        jax.clear_caches()
        (want, upd), want_grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
        assert float(upd["counters"]["moe/slot_rows"]) == windows * 512
        if lift == 30.0:
            assert float(upd["counters"]["moe/assignments_here"]) == 1024
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        jax.clear_caches()  # the layer's jitted bodies were traced on XLA's forms just now
        try:
            (value, _), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
        finally:
            jax.clear_caches()
        np.testing.assert_allclose(float(value), float(want), rtol=1e-5)
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
                                       err_msg=jax.tree_util.keystr(path))

    def test_the_first_window_alone_runs_the_kernel(self, monkeypatch):
        """Forward and backward each un-sort once through the kernel; the
        further windows' loop bodies keep XLA's gather, as they keep
        ``ragged_dot``; and no (k, n, d) array is made outside a loop."""
        loss, p, x = self._setup(3.0)
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        jax.clear_caches()
        try:
            jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x)[0], argnums=(0, 1)))(p, x)
        finally:
            jax.clear_caches()

        def walk(jaxpr, looped=False):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call" and e.params["name"] == "tpuframe_unsort":
                    yield "kernel", looped
                if any(tuple(v.aval.shape) == (2, 512, 128) for v in e.outvars):
                    yield "a_row_a_pair", looped
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from walk(sub, looped or e.primitive.name == "while")

        found = list(walk(jaxpr.jaxpr))
        assert found.count(("kernel", False)) == 2
        assert ("kernel", True) not in found and ("a_row_a_pair", False) not in found
        assert ("a_row_a_pair", True) in found

    def test_a_layer_that_holds_every_expert_keeps_the_gather(self, monkeypatch):
        layer = MoEMLP(num_experts=4, top_k=2, expert_dim=32, gated=True, capacity_factor=None)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 128))
        p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        jax.clear_caches()
        try:
            text = str(jax.make_jaxpr(jax.grad(
                lambda p: jnp.sum(layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])[0])))(p))
        finally:
            jax.clear_caches()
        assert "tpuframe_unsort" not in text and "tpuframe_grouped_fwd" in text


# -- the engagement reading ------------------------------------------------------
def _metric(name, trace):
    from chipbench import correct

    return correct.load_by_name("layer_metrics", name).read({"trace": trace})


@pytest.mark.parametrize("trace", [
    None,
    {"steps": 0, "kernels": {}},
    {"steps": 16, "kernels": {}},
    # the parent's program: the grouped products, no un-sort kernel
    {"steps": 16, "kernels": {"tpuframe_grouped_fwd": {"seconds": 0.05, "calls": 192},
                              "tpuframe_flash_fwd_select": {"seconds": 0.3, "calls": 64}}},
], ids=["no_trace", "no_steps", "no_kernels", "other_kernels"])
def test_a_trace_without_the_kernel_reads_as_nothing(trace):
    assert _metric("moe.unsort_ms", trace) is None


@pytest.mark.parametrize("reader", ["moe.experts_ms", "qwen3next.experts_ms", "keyevl2.experts_ms"])
def test_the_kernels_time_a_step_and_the_products_readers_leave_it_out(reader):
    trace = {"steps": 16, "kernels": {
        "tpuframe_unsort": {"seconds": 0.032, "calls": 128},
        "tpuframe_grouped_fwd": {"seconds": 0.024, "calls": 192},
        "tpuframe_grouped_drows": {"seconds": 0.024, "calls": 192},
        "tpuframe_grouped_dweights": {"seconds": 0.032, "calls": 192}}}
    assert _metric("moe.unsort_ms", trace) == pytest.approx(2.0)
    assert _metric(reader, trace) == pytest.approx(5.0)


def test_the_benchmark_lists_the_reading_for_the_six_expert_cells():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "moe.unsort_ms"]
    assert entry == {
        "name": "moe.unsort_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "samples_per_s_chip", "workloads": list(CELLS)}
    configs = {w["name"]: w["config"] for w in bench["workloads"]}
    for cell in CELLS:
        with open(os.path.join(root, "chipbench", "configs", f"{configs[cell]}.json")) as f:
            kwargs = json.load(f)["model"]["kwargs"]
        assert "moe" in json.dumps(kwargs), cell
    from tpuframe.ops.registry import map_op_name

    assert map_op_name("tpuframe_unsort") == "unsort"
    assert not "tpuframe_unsort".startswith("tpuframe_grouped")
