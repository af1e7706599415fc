"""The expert layer's route made by counting (``models/moe.py``:
``_count_blocks``, ``_window_plan``, ``_spread_weights``, ``_pair_slots``)
against the route by a stable sort, kept here as the oracle: the lines the
layer ran before it counted (two ``argsort``s, ``bincount``, a gather of a
number a pair; autodiff's scatter-add for the gate values' cotangent).  Equal
element for element over every window's live slots, and the layer with the
oracle route injected equal bit for bit: output, every gradient, counters."""

import hashlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models import moe
from tpuframe.models.moe import MoEMLP, slot_bound

#: (choices a token, held experts, experts) of the six expert cells' layers
CELLS = {
    "dsv2lite_seq4096": (6, 8, 64),
    "sdar_blockdiff_seq4096": (8, 16, 128),
    "lfm2moe_seq4096": (4, 8, 32),
    "mellum2_seq8192": (8, 8, 64),
    "qwen3next_seq8192": (10, 16, 512),
    "keyevl2_seq8192": (8, 8, 128),
}


# -- the oracle: the route by sorting, as the layer made it ----------------------
def sorted_route(gate_idx, gate_vals, first, count):
    """``(sizes, tok, weight, inv, order)`` over all pairs, sorted slots."""
    k = gate_idx.shape[1]
    local = gate_idx.reshape(-1) - first
    here = (local >= 0) & (local < count)
    key = jnp.where(here, local, count)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    return sizes, order // k, (gate_vals.reshape(-1) * here)[order], inv, order


def sorted_plan(key, edges, vals, lo, cap, k):
    """``_window_plan``'s answer by sorting: the oracle a layer is handed."""
    count = edges.shape[0] // moe._blocks_of(key.shape[0])
    order = jnp.argsort(key, stable=True)
    slot = lo + jnp.arange(cap)
    live = slot < jnp.sum(key < count)
    pair = jnp.where(live, order[jnp.minimum(slot, key.shape[0] - 1)], -1)
    weight = jnp.where(live, vals[jnp.maximum(pair, 0)], 0)
    return jnp.where(live, pair // k, 0), weight, pair


def scattered_weights(d_weight, pair, pairs):
    """``_spread_weights``' answer as the scatter-add autodiff emitted."""
    return jnp.zeros((pairs,), d_weight.dtype).at[jnp.where(pair >= 0, pair, pairs)].add(
        d_weight, mode="drop")


def _keys(gate_idx, first, count):
    local = jnp.asarray(gate_idx).reshape(-1) - first
    return jnp.where((local >= 0) & (local < count), local, count)


def _choices(rng, n, k, experts):
    return np.argsort(-rng.standard_normal((n, experts)), axis=1)[:, :k].astype(np.int32)


def _case(name):
    """``(gate_idx (n, k), first, count, cap)`` of a named load."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))  # the same load in every process
    if name in CELLS:
        k, count, experts = CELLS[name]
        n = 384
        return _choices(rng, n, k, experts), 0, count, slot_bound(n * k, count, experts)
    if name == "held_range_not_from_zero":
        return _choices(rng, 300, 4, 32), 11, 6, 512
    if name == "one_held_expert":
        return _choices(rng, 256, 4, 16), 3, 1, 512
    if name == "an_expert_with_no_pair":
        idx = _choices(rng, 256, 3, 16)
        return np.where(idx == 2, 9, idx), 0, 4, 512
    if name == "every_pair_to_one_expert":  # overflow: three windows
        return np.full((320, 4), 1, np.int32), 0, 4, 512
    if name == "pairs_no_multiple_of_a_block":
        return _choices(rng, 333, 3, 16), 0, 4, 512
    if name == "one_choice_a_token":
        return _choices(rng, 1000, 1, 8), 2, 3, 512
    if name == "more_held_than_a_byte_holds":  # keys past 255: two byte planes
        return _choices(rng, 512, 2, 1024), 5, 300, 512
    raise KeyError(name)


CASES = [*CELLS, "held_range_not_from_zero", "one_held_expert", "an_expert_with_no_pair",
         "every_pair_to_one_expert", "pairs_no_multiple_of_a_block", "one_choice_a_token",
         "more_held_than_a_byte_holds"]


def _windows_of(gate_idx, vals, first, count, cap):
    """Each window's counted plan beside the oracle's slice of the sorted slots."""
    n, k = gate_idx.shape
    sizes, tok, weight, inv, order = sorted_route(jnp.asarray(gate_idx), vals, first, count)
    key = _keys(gate_idx, first, count)
    got_sizes, edges = moe._count_blocks(key, count)
    np.testing.assert_array_equal(got_sizes, sizes)
    total = int(jnp.sum(sizes))
    plan = jax.jit(moe._window_plan, static_argnums=(4, 5))
    for i in range(max(1, -(-total // cap))):
        live = min(cap, total - i * cap)
        got = plan(key, edges, vals.reshape(-1), jnp.int32(i * cap), cap, k)
        want = (tok[i * cap:][:live], weight[i * cap:][:live], order[i * cap:][:live])
        yield live, got, want


@pytest.mark.parametrize("name", CASES)
def test_a_windows_plan_is_the_sorted_routes(name):
    """``sizes``, and ``tok``, ``weight`` and the pair over every window's
    live slots, element for element; past them ``weight`` and ``tok`` are 0."""
    gate_idx, first, count, cap = _case(name)
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.uniform(0.01, 1.0, gate_idx.shape).astype(np.float32))
    windows = list(_windows_of(gate_idx, vals, first, count, cap))
    assert len(windows) == (3 if name == "every_pair_to_one_expert" else 1)
    for live, (tok, weight, pair), want in windows:
        for got, ref in zip((tok, weight, pair), want):
            np.testing.assert_array_equal(np.asarray(got)[:live], np.asarray(ref))
        assert not np.asarray(weight)[live:].any() and not np.asarray(tok)[live:].any()
        assert (np.asarray(pair)[live:] == -1).all()
        assert weight.dtype == jnp.float32 and tok.shape == (cap,)


@pytest.mark.parametrize("where", ["routed_elsewhere", "routed_here"])
@pytest.mark.parametrize("what", [np.nan, np.inf, 1e-42])
def test_a_gate_value_is_selected_never_multiplied(where, what):
    """A NaN, an infinity or a number under the normal range stays with its
    own pair: it moves as bits, and no product touches it."""
    gate_idx, first, count, cap = _case("held_range_not_from_zero")
    here = np.asarray(_keys(gate_idx, first, count)) < count
    at = int(np.flatnonzero(here if where == "routed_here" else ~here)[5])
    vals = np.random.default_rng(3).uniform(0.01, 1.0, gate_idx.size).astype(np.float32)
    vals[at] = what
    ((live, (tok, weight, pair), _),) = _windows_of(
        gate_idx, jnp.asarray(vals).reshape(gate_idx.shape), first, count, cap)
    pair, weight = np.asarray(pair), np.asarray(weight)
    np.testing.assert_array_equal(weight[:live].view(np.uint32), vals[pair[:live]].view(np.uint32))
    assert not weight[live:].any()
    assert (at in pair[:live]) == (where == "routed_here")


@pytest.mark.parametrize("name", ["keyevl2_seq8192", "qwen3next_seq8192", "every_pair_to_one_expert",
                                  "pairs_no_multiple_of_a_block", "one_choice_a_token"])
def test_the_gate_values_gradient_is_the_scatter_adds(name):
    """``jax.grad`` to the gate values through the sorted route's lines (a
    gather, whose transpose is a scatter-add) against the windows'
    ``_spread_weights`` summed: each pair from exactly one slot, bit for bit."""
    gate_idx, first, count, cap = _case(name)
    n, k = gate_idx.shape
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.uniform(0.01, 1.0, (n, k)).astype(np.float32))
    windows = max(1, -(-int(np.sum(np.asarray(_keys(gate_idx, first, count)) < count)) // cap))
    d_weight = jnp.asarray(rng.standard_normal((windows, cap)).astype(np.float32))

    def by_sorting(v):
        weight = sorted_route(jnp.asarray(gate_idx), v, first, count)[2]
        weight = jnp.pad(weight, (0, windows * cap))[:windows * cap]
        return jnp.sum(weight.reshape(windows, cap) * d_weight)

    want = jax.grad(by_sorting)(vals).reshape(-1)
    key = _keys(gate_idx, first, count)
    edges = moe._count_blocks(key, count)[1]
    got = jnp.zeros((n * k,), jnp.float32)
    for i in range(windows):
        pair = moe._window_plan(key, edges, vals.reshape(-1), jnp.int32(i * cap), cap, k)[2]
        got = got + jax.jit(moe._spread_weights, static_argnums=2)(d_weight[i], pair, n * k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[np.asarray(key) == count].any()


@pytest.mark.parametrize("name", CASES)
def test_a_pairs_slot_where_xlas_un_sort_asks_for_it(name):
    """``_pair_slots``: ``argsort(argsort(key))`` on the held pairs, counted
    from the window's first slot; every other pair outside every window."""
    gate_idx, first, count, cap = _case(name)
    inv = np.asarray(sorted_route(jnp.asarray(gate_idx), jnp.ones(gate_idx.shape), first, count)[3])
    key = _keys(gate_idx, first, count)
    edges = moe._count_blocks(key, count)[1]
    held = np.asarray(key) < count
    for lo in (0, cap):
        got = np.asarray(moe._pair_slots(moe._Counted(key, edges, jnp.int32(lo))))
        assert got.shape == (gate_idx.size,)
        np.testing.assert_array_equal(got[held], inv[held] - lo)
        assert (got[~held] >= gate_idx.size + cap).all()


# -- the layer, the oracle route injected ---------------------------------------------
def _layer_readings(kwargs, shape, lift):
    layer = MoEMLP(capacity_factor=None, **kwargs)
    x = jax.random.normal(jax.random.PRNGKey(0), shape).at[..., 0].set(1.0)
    p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
    first, count = kwargs["held"]
    p = {**p, "router": {"kernel": p["router"]["kernel"].at[0, first:first + count].add(lift)}}

    def loss(p, x):
        out, upd = layer.apply({"params": p}, x, mutable=["counters", "gauges", "aux_loss"])
        aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd.get("aux_loss", {})))
        return jnp.sum(out ** 2) + aux, (out, upd)

    (_, (out, upd)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True, argnums=(0, 1)))(p, x)
    return jax.tree.map(np.asarray, (out, grads, upd))


@pytest.fixture
def oracle_route(monkeypatch):
    """Switches the layer's plan and its transpose to the sorted oracle's."""
    def switch():
        monkeypatch.setattr(moe, "_window_plan", sorted_plan)
        monkeypatch.setattr(moe, "_spread_weights", scattered_weights)
        jax.clear_caches()  # the layer's jitted bodies were traced on the counting plan
    yield switch
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("experts, overflow", [(8, False), (8, True), (16, False), (16, True)])
def test_the_layer_equals_itself_on_the_sorted_route(experts, overflow, oracle_route):
    """``MoEMLP`` holding 4 of 8 and of 16 experts: forward output, every
    parameter's and the input's gradient and the ``moe/*`` counters bit-equal
    under the counting route and under the oracle route, with one window and
    with a router that overflows the buffers."""
    kwargs = dict(num_experts=experts, top_k=2, expert_dim=32, held=(0, 4), gated=experts == 16)
    shape = (2, 512, 16) if experts == 8 else (2, 1024, 16)
    counted = _layer_readings(kwargs, shape, 8.0 if overflow else 0.0)
    counters = counted[2]["counters"]
    # 4 of 8 held: twice the balanced share is a slot a pair, the sorted route
    assert ("moe/counted_routes" in counters) == (experts == 16)
    assert float(counters["moe/overflow_calls"]) == float(overflow and experts == 16)
    oracle_route()
    by_sorting = _layer_readings(kwargs, shape, 8.0 if overflow else 0.0)
    for a, b in zip(jax.tree.leaves(counted), jax.tree.leaves(by_sorting), strict=True):
        np.testing.assert_array_equal(a, b)


def test_three_steps_of_a_model_equal_on_both_routes(oracle_route):
    """A two-layer expert transformer that holds 2 of 8 experts a layer (the
    shape of the benchmark's cuts), three SGD steps: every loss bit-equal
    under the counting route and under the oracle route."""
    from tpuframe.models import TransformerLM

    model = TransformerLM(vocab_size=64, num_layers=2, num_heads=2, head_dim=8, max_len=512,
                          attn_impl="full", moe_experts=8, moe_top_k=2,
                          moe_kwargs=(("capacity_factor", None), ("held", (0, 2)),
                                      ("expert_dim", 32), ("gated", True)))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 512)), jnp.int32)

    def losses():
        params = model.init(jax.random.PRNGKey(0), toks)["params"]

        def objective(p):
            logits, upd = model.apply({"params": p}, toks, train=True,
                                      mutable=["aux_loss", "counters", "gauges"])
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            nll = -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))
            return nll + sum(jnp.sum(a) for a in jax.tree.leaves(upd["aux_loss"])), upd["counters"]

        step = jax.jit(jax.value_and_grad(objective, has_aux=True))
        out = []
        for _ in range(3):
            (value, counters), grads = step(params)
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
            out.append(np.asarray(value))
        return out, sum(float(v) for path, v in jax.tree_util.tree_flatten_with_path(counters)[0]
                        if "moe/counted_routes" in jax.tree_util.keystr(path))

    counted, calls = losses()
    assert calls == 2.0  # two expert layers, both counted
    oracle_route()
    by_sorting, _ = losses()
    np.testing.assert_array_equal(counted, by_sorting)
    assert counted[0] != counted[2]


def test_a_layer_that_holds_every_expert_lowers_as_it_did():
    """``held=None``: a slot a pair, the sorted route, and the program PR 48's
    commit lowers this layer to (loss and every gradient, StableHLO byte for
    byte; the digest was taken with this very function under jax 0.9.0 on
    that commit).  No counter of the counting route is sown there."""
    layer = MoEMLP(num_experts=8, top_k=2, expert_dim=32, capacity_factor=None, gated=True)
    shape = (2, 64, 16)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(shape)))["params"]

    def objective(p, x):
        out, upd = layer.apply({"params": p}, x, train=True,
                               mutable=["aux_loss", "counters", "gauges"])
        aux = sum(jnp.sum(a) for a in jax.tree.leaves(upd.get("aux_loss", {})))
        return jnp.sum(out.astype(jnp.float32)) + aux, upd

    lowered = jax.jit(jax.value_and_grad(objective, has_aux=True)).lower(
        params, jax.ShapeDtypeStruct(shape, jnp.float32))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == (
        "f4adfd3324ca04e6eaf1ef8fc4294610f5b26a2a182ce8400d99dcac9061af51")
    assert "moe/counted_routes" not in lowered.out_info[0][1]["counters"]


def test_the_counting_route_sorts_gathers_and_scatters_nothing():
    """The jaxpr of a layer whose buffers are shorter than its pairs, loss and
    gradients: under ``tpuframe/moe/route`` no sort, gather or scatter has an
    operand as long as the pairs (``top_k`` over a token's experts and its
    transpose stay: the expert choice is not the route's plan); the sorted
    route (all experts held) has all three."""
    pairs = 2 * 1024 * 2

    def over_the_pairs(held):
        layer = MoEMLP(num_experts=16, top_k=2, expert_dim=32, capacity_factor=None, held=held)
        x = jnp.ones((2, 1024, 16))
        p = layer.init({"params": jax.random.PRNGKey(1)}, x)["params"]
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(layer.apply(
            {"params": p}, x, mutable=["counters", "gauges", "aux_loss"])[0])))(p)
        found = set()

        def walk(jaxpr, scoped=False):  # a jitted callee's name stack starts anew
            for e in jaxpr.eqns:
                name = e.primitive.name.replace("_", "-")
                here = scoped or "tpuframe/moe/route" in str(e.source_info.name_stack)
                if (here and name in ("sort", "gather", "scatter", "scatter-add")
                        and any(v.aval.shape == (pairs,) for v in (*e.invars, *e.outvars))):
                    found.add(name)
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub, here)

        walk(jaxpr.jaxpr)
        return found

    assert over_the_pairs((0, 4)) == set()
    assert over_the_pairs(None) == {"sort", "gather", "scatter-add"}


@pytest.mark.parametrize("steps, counted, want", [(0, 0.0, None), (24, 0.0, None), (24, 96.0, 4.0)])
def test_the_benchmarks_reader_says_counted_calls_a_step(steps, counted, want):
    """``moe.counted_route_calls``: the counter over the ``train/step`` spans'
    count; nothing where the program sows no such counter (every commit
    before this one, and a layer that holds a slot a pair); and its entry
    in ``BENCHMARK.json`` lists the six expert cells."""
    import json
    import os

    from chipbench import correct
    from tpuframe.track import telemetry as T

    T.reset()
    try:
        registry = T.get_telemetry().registry
        for _ in range(steps):
            registry.histogram("span/train/step").observe(0.002)
        if counted:
            registry.counter("moe/counted_routes").inc(counted)
        assert correct.load_by_name("layer_metrics", "moe.counted_route_calls").read({}) == want
    finally:
        T.reset()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == "moe.counted_route_calls"]
    assert entry == {"name": "moe.counted_route_calls", "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "model step",
                     "moves": "samples_per_s_chip", "workloads": list(CELLS)}
