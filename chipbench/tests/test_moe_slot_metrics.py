"""The two readers of the expert layer's slot-buffer counters, and their
entries in BENCHMARK.json: a program without ``moe/slot_rows`` (every commit
before the counter) reads as nothing in both, a program with it reads a
ratio and a count that may stand at 0."""

import json
import os

import pytest

from chipbench import correct
from tpuframe.track.telemetry import get_telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("moe.slot_rows_over_routed", "moe.overflow_calls")
COUNTERS = ("moe/slot_rows", "moe/assignments_here", "moe/overflow_calls")


@pytest.fixture()
def counters():
    """``set(slot_rows, assignments_here, overflow_calls)`` on the process's
    registry, put back as it was afterwards."""
    registry = get_telemetry().registry
    was = [registry.counter(n).value for n in COUNTERS]

    def set_to(*values):
        for name, value in zip(COUNTERS, values):
            c = registry.counter(name)
            c.inc(value - c.value)

    yield set_to
    set_to(*was)


def _read(name):
    return correct.load_by_name("layer_metrics", name).read({})


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_lists_both_expert_cells_and_moves_the_rate(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["dsv2lite_seq4096", "sdar_blockdiff_seq4096"]
    assert entry["moves"] == "samples_per_s_chip" and entry["better"] == "lower"
    assert entry["source"] == "program_counter" and entry["layer"] == "model step"
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # new entries go to the end of their list
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NAMES)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_reads_as_nothing(name, counters):
    counters(0, 6144 * 4, 0)
    assert _read(name) is None


def test_a_program_with_it_reads_the_ratio_and_a_zero(counters):
    counters(4 * 12288, 4 * 6100, 0)
    assert _read("moe.slot_rows_over_routed") == pytest.approx(12288 / 6100)
    assert _read("moe.overflow_calls") == 0.0
    counters(3 * 12288 + 24576, 3 * 6100 + 13000, 1)
    assert _read("moe.overflow_calls") == 1.0
    assert _read("moe.slot_rows_over_routed") == pytest.approx(61440 / 31300)
