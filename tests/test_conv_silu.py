"""What the gated delta rule reads (`tpuframe.ops.short_conv.conv_silu`): the
four taps, SiLU and the unit norms of q and k as one kernel pair, against
the jnp oracle.  Small sizes, on the CPU: the kernels in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuframe.models import transformer as tr
from tpuframe.ops.short_conv import conv_silu, conv_silu_reference

PARTS = ["q", "k", "v", "dx", "dw"]
#: batch, length, key heads, value heads (each of 128), columns behind [q | k | v], dtype
SHAPES = {
    "batch2_no_whole_tile_f32": (2, 592, 1, 2, 256, jnp.float32),
    "no_whole_group_f32": (2, 600, 2, 4, 0, jnp.float32),
    "shorter_than_a_group_f32": (1, 9, 1, 2, 128, jnp.float32),
    "heads_16_over_32_bf16": (1, 272, 16, 32, 4096, jnp.bfloat16),
    "heads_2_over_4_bf16": (2, 300, 2, 4, 512, jnp.bfloat16),
    "heads_2_over_4_f32": (1, 256, 2, 4, 512, jnp.float32),
}


def _inputs(shape, taps=4):
    b, l, hk, hv, behind, dtype = shape
    keys, channels = hk * 128, (2 * hk + hv) * 128
    ks = jax.random.split(jax.random.PRNGKey(l), 5)
    x = jax.random.normal(ks[0], (b, l, channels + behind), jnp.float32).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, channels), jnp.float32)
    gs = tuple(jax.random.normal(key, (b, l, n), jnp.float32).astype(dtype)
               for key, n in zip(ks[2:], (keys, keys, channels - 2 * keys)))
    return x, w, gs, dict(key_heads=hk, key_dim=128)


@functools.lru_cache(maxsize=None)
def _both_forms(name):
    x, w, gs, kw = _inputs(SHAPES[name])

    def all_parts(op):
        out, vjp = jax.vjp(lambda x, w: op(x, w, **kw), x, w)
        return out + vjp(gs)

    return {"kernels": all_parts(functools.partial(conv_silu, interpret=True)),
            "oracle": all_parts(conv_silu_reference)}


class TestConvSiluOp:
    @pytest.mark.parametrize("part", PARTS)
    @pytest.mark.parametrize("shape", list(SHAPES))
    def test_kernels_match_the_oracle(self, shape, part):
        forms = _both_forms(shape)
        i = PARTS.index(part)
        got, want = (np.asarray(forms[f][i], np.float32) for f in ("kernels", "oracle"))
        assert got.shape == want.shape
        # the kernels keep the cotangent in float32 through the rounding of
        # the taps' sum, where XLA's transpose of the `astype` pair rounds it
        tol = 2e-2 if SHAPES[shape][5] == jnp.bfloat16 else 2e-6
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)

    def test_the_oracle_is_the_equation_by_hand(self):
        # one key head of 2, v of 3, and 3 columns behind them
        rng = np.random.default_rng(0)
        x, w = rng.standard_normal((2, 9, 10)), rng.standard_normal((4, 7))
        conv = np.zeros((2, 9, 7))
        for t in range(9):
            for j in range(4):
                if t - 3 + j >= 0:
                    conv[:, t] += w[j] * x[:, t - 3 + j, :7]
        act = conv / (1.0 + np.exp(-conv))
        unit = lambda a: a / np.sqrt(np.sum(a * a, -1, keepdims=True) + 1e-6)  # noqa: E731
        want = (unit(act[..., :2]) * 2 ** -0.5, unit(act[..., 2:4]), act[..., 4:])
        got = conv_silu_reference(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
                                  key_heads=1, key_dim=2)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), b, rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("t", [0, 1, 255, 256, 257, 599])
    @pytest.mark.parametrize("form", ["oracle", "kernels"])
    def test_changing_a_position_moves_its_own_and_the_next_three(self, form, t):
        op = conv_silu_reference if form == "oracle" else functools.partial(
            conv_silu, interpret=True)
        x, w, _, kw = _inputs((1, 600, 1, 2, 128, jnp.float32))
        # one channel of q, of k and of v, and one behind them, which nothing reads
        there = x.at[0, t, jnp.array([5, 130, 300, 600])].add(1.0)
        for got, was in zip(op(there, w, **kw), op(x, w, **kw)):
            moved = np.asarray(got - was)[0]
            # zeros stand before the row, and the taps reach three positions on
            assert not moved[:t].any() and not moved[t + 4:].any()
            assert moved[t:t + 4].any(axis=1).all()

    def test_rows_of_the_batch_keep_to_themselves(self):
        x, w, _, kw = _inputs((2, 48, 1, 2, 0, jnp.float32))
        run = functools.partial(conv_silu, interpret=True, **kw)
        for got, was in zip(run(x.at[0].add(1.0), w), run(x, w)):
            moved = np.asarray(got - was)
            assert moved[0].any() and not moved[1].any()

    def test_the_columns_behind_are_never_read(self):
        x, w, gs, kw = _inputs((1, 40, 1, 2, 256, jnp.float32))
        poisoned = x.at[..., 512:].set(jnp.nan)

        def loss(x, w):
            out = conv_silu(x, w, interpret=True, **kw)
            return sum(jnp.sum(o * g) for o, g in zip(out, gs))

        dx, dw = jax.grad(loss, (0, 1))(poisoned, w)
        assert np.isfinite(np.asarray(dx)).all() and np.isfinite(np.asarray(dw)).all()
        assert np.asarray(dx)[..., :512].any() and not np.asarray(dx)[..., 512:].any()

    @pytest.mark.parametrize("key_dim, channels, behind, taps", [
        (64, 256, 0, 4),       # a head that is no block of lanes
        (128, 512, 32, 18),    # taps past the neighbours' 16 rows
        (128, 320, 0, 4),      # values that end inside a block of lanes
    ])
    def test_shapes_the_kernels_do_not_take_run_the_oracle(self, key_dim, channels, behind, taps,
                                                           monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        x = jax.random.normal(keys[0], (1, 32, channels + behind), jnp.float32)
        w = jax.random.normal(keys[1], (taps, channels), jnp.float32)
        kw = dict(key_heads=1, key_dim=key_dim)
        text = jax.jit(functools.partial(conv_silu, **kw)).lower(x, w).as_text()
        assert "tpuframe_conv_silu" not in text
        for got, want in zip(conv_silu(x, w, **kw), conv_silu_reference(x, w, **kw)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_and_the_shapes_they_take_run_them(self, monkeypatch):
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        x, w, _, kw = _inputs((1, 32, 1, 2, 256, jnp.float32))
        # interpret mode leaves no custom call: the kernels' grid is a loop
        assert "while" in jax.jit(functools.partial(conv_silu, **kw)).lower(x, w).as_text()
        assert "while" not in jax.jit(
            functools.partial(conv_silu_reference, **kw)).lower(x, w).as_text()

    @pytest.mark.parametrize("width, key_heads", [(384, 2), (512, 2)])
    def test_an_array_without_room_for_q_k_and_v_is_refused(self, width, key_heads):
        with pytest.raises(ValueError, match=r"\[q \| k \| v\]"):
            conv_silu(jnp.zeros((1, 16, width)), jnp.zeros((4, 512)), key_heads=key_heads,
                      key_dim=128)

    def test_per_shard_on_a_mesh(self, monkeypatch):
        from tpuframe.core import MeshSpec

        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        mesh = MeshSpec(data=4, fsdp=2).build()
        x, w, gs, kw = _inputs((8, 32, 1, 2, 128, jnp.float32))

        def loss(op):
            return lambda x, w: sum(jnp.sum(o * g) for o, g in zip(op(x, w, **kw), gs))

        got = jax.jit(jax.grad(loss(functools.partial(conv_silu, mesh=mesh)), (0, 1)))(x, w)
        want = jax.grad(loss(conv_silu_reference), (0, 1))(x, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


class TestTheMixerThroughTheKernels:
    @pytest.fixture(scope="class")
    def layer(self):
        mixer = tr.GatedDeltaNet(num_key_heads=1, num_value_heads=2, key_dim=128, value_dim=128)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 64), jnp.float32)
        params = mixer.init(jax.random.PRNGKey(1), x)["params"]
        return mixer, params, x

    @pytest.mark.parametrize("leaf", ["in_proj_qkvz/kernel", "conv", "in_proj_ba/kernel", "x"])
    def test_gradients_with_the_kernels_are_the_oracles(self, layer, leaf, monkeypatch):
        mixer, params, x = layer

        def grads():
            def loss(p, x):
                return jnp.sum(jnp.sin(mixer.apply({"params": p}, x)))
            gp, gx = jax.grad(loss, (0, 1))(params, x)
            flat = {"/".join(k.key for k in path): v
                    for path, v in jax.tree_util.tree_flatten_with_path(gp)[0]}
            return {**flat, "x": gx}[leaf]

        want = np.asarray(grads())
        monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
        got = np.asarray(grads())
        assert np.linalg.norm(got - want) <= 2e-5 * np.linalg.norm(want) > 0


# -- the engagement reading ------------------------------------------------------
def _inputs_ms(trace):
    from chipbench import correct

    return correct.load_by_name("layer_metrics", "deltanet.inputs_ms").read({"trace": trace})


@pytest.mark.parametrize("trace", [
    None,
    {"steps": 0, "kernels": {}},
    {"steps": 16, "kernels": {}},
    # the parent's program: the rule's kernels and the short convolution's, none of these
    {"steps": 16, "kernels": {"tpuframe_gated_delta_fwd": {"seconds": 0.05, "calls": 48},
                              "tpuframe_short_conv_fwd": {"seconds": 0.01, "calls": 64}}},
], ids=["no_trace", "no_steps", "no_kernels", "other_kernels"])
def test_a_trace_without_the_pair_reads_as_nothing(trace):
    assert _inputs_ms(trace) is None


def test_a_trace_with_the_pair_reads_its_time_a_step():
    trace = {"steps": 16, "kernels": {
        "tpuframe_conv_silu_fwd": {"seconds": 0.032, "calls": 48},
        "tpuframe_conv_silu_bwd": {"seconds": 0.064, "calls": 48},
        "tpuframe_short_conv_fwd": {"seconds": 0.01, "calls": 64},
        "tpuframe_gated_delta_fwd": {"seconds": 0.05, "calls": 48}}}
    assert _inputs_ms(trace) == pytest.approx(6.0)


def test_the_benchmark_lists_the_reading_for_the_one_cell_that_has_the_layers():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "deltanet.inputs_ms"]
    assert entry == {"name": "deltanet.inputs_ms", "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "samples_per_s_chip", "workloads": ["qwen3next_seq8192"]}
    # the other readers of kernels by name leave these to it
    from tpuframe.ops.registry import map_op_name

    for name in ("tpuframe_conv_silu_fwd", "tpuframe_conv_silu_bwd"):
        assert map_op_name(name) == "conv_silu"
        assert not name.startswith(("tpuframe_short_conv", "tpuframe_gated_delta"))


@pytest.mark.parametrize("key_dim, taps", [(64, 4), (128, 18)])
def test_asking_for_the_kernels_at_a_shape_they_do_not_take_is_refused(key_dim, taps):
    with pytest.raises(ValueError, match="do not take"):
        conv_silu(jnp.zeros((1, 32, 4 * key_dim)), jnp.zeros((taps, 4 * key_dim)), key_heads=1,
                  key_dim=key_dim, interpret=True)
