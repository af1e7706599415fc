"""The reader of the expert layer's grouped-product kernels, and its entry in
BENCHMARK.json: a trace without such kernels (every commit whose expert products
are XLA's ragged-dot kernel, named after its HLO op) reads as nothing; a trace
with them reads their time a step."""

import json
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _read(trace):
    return correct.load_by_name("layer_metrics", "moe.experts_ms").read({"trace": trace})


def test_the_entry_moves_the_rate_in_the_expert_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": "moe.experts_ms", "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "kernels", "moves": "samples_per_s_chip",
                     "workloads": ["dsv2lite_seq4096", "sdar_blockdiff_seq4096",
                                   "lfm2moe_seq4096", "mellum2_seq8192"]}
    with_experts = {w["name"] for w in bench["workloads"] for c in bench["configs"]
                    if c["name"] == w["config"] and "expert" in c["why"]}
    assert set(entry["workloads"]) <= with_experts


@pytest.mark.parametrize("trace", [
    None,
    {"steps": 0, "kernels": {}},
    {"steps": 16, "kernels": {}},
    {"steps": 16, "kernels": {"tpuframe_flash_fwd": {"seconds": 0.2, "calls": 64}}},
], ids=["no_trace", "no_steps", "no_kernels", "other_kernels"])
def test_a_trace_without_the_kernels_reads_as_nothing(trace):
    assert _read(trace) is None


def test_a_trace_with_them_reads_their_time_a_step():
    trace = {"steps": 16, "kernels": {
        "tpuframe_grouped_fwd": {"seconds": 0.064, "calls": 192},
        "tpuframe_grouped_drows": {"seconds": 0.072, "calls": 192},
        "tpuframe_grouped_dweights": {"seconds": 0.056, "calls": 192},
        "tpuframe_flash_fwd": {"seconds": 0.2, "calls": 64}}}
    assert _read(trace) == pytest.approx(12.0)
