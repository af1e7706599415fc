"""The two readers of the head-norm-and-rotary kernels, and their entries in
BENCHMARK.json: a trace without such kernels (every commit before the op, and
a cell whose attention has no head norms) reads as nothing in both; a trace
with them reads their time and their calls a step."""

import json
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("headprologue.kernel_ms", "headprologue.calls")


def _read(name, trace):
    return correct.load_by_name("layer_metrics", name).read({"trace": trace})


def _trace(kernels, steps=16):
    return {"steps": steps, "kernels": kernels}


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_moves_the_rate_in_the_cells_that_norm_their_heads(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["moves"] == "samples_per_s_chip" and entry["source"] == "device_trace"
    assert entry["layer"] == "kernels"
    assert entry["better"] == ("lower" if name.endswith("_ms") else "higher")
    cells = {w["name"]: w["config"] for w in bench["workloads"]}
    assert entry["workloads"] and all(cells[w] in ("sdar-30b-a3b-chat", "lfm2-8b-a1b")
                                      for w in entry["workloads"])


@pytest.mark.parametrize("trace", [
    None,
    _trace({}, steps=0),
    _trace({}),
    _trace({"tpuframe_flash_fwd": {"seconds": 0.2, "calls": 64},
            "tpuframe_short_conv_bwd": {"seconds": 0.02, "calls": 64}}),
], ids=["no_trace", "no_steps", "no_kernels", "other_kernels"])
@pytest.mark.parametrize("name", NAMES)
def test_a_trace_without_the_kernels_reads_as_nothing(name, trace):
    assert _read(name, trace) is None


def test_a_trace_with_them_reads_time_and_calls_a_step():
    trace = _trace({"tpuframe_head_norm_rope_fwd": {"seconds": 0.012, "calls": 128},
                    "tpuframe_head_norm_rope_bwd": {"seconds": 0.020, "calls": 128},
                    "tpuframe_flash_fwd": {"seconds": 0.2, "calls": 64}})
    assert _read("headprologue.kernel_ms", trace) == pytest.approx(2.0)
    assert _read("headprologue.calls", trace) == 16
