"""Window rates, the percentile rule, the spread, the analytic FLOP counts."""

import json
import os

import pytest

from chipbench import correct, windows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_window_rates_and_overall_rate():
    closes = [10.0, 11.0, 12.0, 14.0]          # two windows of 1 s, one stalled to 2 s
    rates = windows.window_rates(closes, samples_per_window=2048, chips=1)
    assert rates == [2048.0, 2048.0, 1024.0]
    # the run's rate is all samples over all time: the stall lowers it
    assert windows.overall_rate(closes, 2048, 1) == pytest.approx(3 * 2048 / 4.0)
    # the median window (trainer.window_rate_median) is the pace with it left out
    assert windows.median(rates) == 2048.0
    assert windows.overall_rate(closes, 2048, 4) == pytest.approx(3 * 2048 / 4.0 / 4)
    with pytest.raises(ValueError):
        windows.overall_rate([1.0], 8, 1)


@pytest.mark.parametrize("q,want", [(90, 9), (50, 5), (100, 10), (10, 1), (91, 10)])
def test_percentile_is_nearest_rank(q, want):
    assert windows.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_of_few_readings_is_a_reading():
    assert windows.percentile([3.0, 1.0, 2.0], 90) == 3.0
    with pytest.raises(ValueError):
        windows.percentile([], 90)


def test_spread_is_the_drivers_quartile_rule():
    import statistics

    vals = [1893.0, 1894.0, 1892.5, 1893.5, 1862.0, 1893.2]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert windows.spread(vals) == pytest.approx((q3 - q1) / statistics.median(vals))


def test_resnet50_forward_is_4_09_gmac():
    flops = correct.load_by_name("flops", "resnet50-imagenet")
    c = cfg("resnet50-imagenet")
    assert flops.forward_macs(c) == pytest.approx(4.09e9, rel=0.005)
    assert flops.train_flops_per_sample(c) == 6 * flops.forward_macs(c)


def test_gpt2_medium_flops_follow_its_parameter_count():
    flops = correct.load_by_name("flops", "gpt2-medium")
    c = cfg("gpt2-medium")
    # 24 x 12 d^2 + d x vocab
    assert flops.matmul_params(c) == 24 * 12 * 1024 * 1024 + 1024 * 50257
    # the published model has 354.8M parameters with the head tied and
    # biases on the attention projections; untied and without them it is 406.2M
    assert flops.total_params(c) == pytest.approx(406.2e6, rel=0.001)
    per_token = flops.train_flops_per_sample(c) / c["seq_len"]
    assert per_token == pytest.approx(6 * flops.matmul_params(c) + 6 * 24 * 2 * 1024 * 1024)
    assert per_token == pytest.approx(2.42e9, rel=0.01)


def test_reference_parameter_counts_match_the_flops_files():
    for name, want in (("gpt2-medium", None), ("resnet50-imagenet", 25_557_032)):
        c = cfg(name)
        ref = correct.load_by_name("reference", name)
        import jax

        shapes = jax.tree.leaves(ref.param_shapes(c), is_leaf=correct._is_spec)
        n = sum(int(__import__("math").prod(s[0])) for s in shapes)
        if want is None:
            want = correct.load_by_name("flops", name).total_params(c)
        assert n == want


def test_kernel_costs_are_positive_and_bandwidth_bound():
    with open(os.path.join(ROOT, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    for name in ("resnet50-imagenet", "gpt2-medium"):
        c = cfg(name)
        costs = correct.load_by_name("flops", name).kernel_costs(c, c["per_chip_batch"])
        for k, v in costs.items():
            assert k.startswith("tpuframe_")
            assert v["bytes"] / peaks["hbm_bytes_per_s"] > v["flops"] / peaks["bf16_flops_per_s"] > 0
