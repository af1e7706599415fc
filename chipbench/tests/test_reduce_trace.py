"""The trace reducer: interval arithmetic on hand-made events, and the
recorded chip trace kept as its fixture."""

import gzip
import json
import os

import pytest

from chipbench import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "resnet50_cached.trace_sample.json.gz")


def test_union_subtract_overlap():
    assert rt.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert rt.total(rt.union([[0, 2], [1, 3], [5, 7]])) == 5
    assert rt.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert rt.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert rt.overlap(2, 8, [[0, 3], [7, 20]]) == 2


def flat_two_steps():
    """Two 100 us programs on one device with a 20 us gap between them in
    which the host sat in the drain; a kernel, a hidden and an exposed
    collective inside."""
    us = 1000
    ops = [
        ["fusion.1", 0, 40 * us],
        ["tpuframe_normalize", 40 * us, 10 * us],
        ["all-reduce.1", 45 * us, 15 * us],          # 5 us hidden under the kernel, 10 exposed
        ["fusion.2", 60 * us, 40 * us],
        ["fusion.1", 120 * us, 40 * us],
        ["tpuframe_normalize", 160 * us, 10 * us],
        ["fusion.2", 175 * us, 45 * us],             # a 5 us hole inside the program
    ]
    return {
        "planes": ["/device:TPU:0", "/host:CPU"],
        "devices": {"/device:TPU:0": {
            "ops": ops,
            "modules": [["jit_step", 0, 100 * us], ["jit_step", 120 * us, 100 * us]]}},
        "host": [["chipbench/dispatch", 0, 5 * us], ["chipbench/host_block", 90 * us, 28 * us],
                 ["chipbench/dispatch", 118 * us, 4 * us]],
    }


def test_reduce_busy_idle_kernels_collectives_gaps():
    r = rt.reduce(flat_two_steps(), steps=2)
    assert r["window_s"] == pytest.approx(220e-6)
    assert r["busy_s"] == pytest.approx(195e-6)          # 220 - 20 (between) - 5 (inside)
    assert r["kernels"]["tpuframe_normalize"] == {"calls": 2, "seconds": pytest.approx(20e-6)}
    assert r["exposed_collective_s"] == pytest.approx(10e-6)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"host_block": pytest.approx(20e-6), "inside_program": pytest.approx(5e-6)}
    top = r["breakdown"]["device_ops"]
    # operations are summed by kind and shape, the instruction number dropped
    assert top[0] == ["fusion", pytest.approx(165e-6)] and len(top) <= 10
    assert rt.op_name("%fusion.3646 = f32[4,1024,50257]{2,1,0:T(8,128)} fusion(...)") \
        == "fusion.3646_f32[4,1024,50257]"
    assert rt.op_name('%jvp_tpuframe_ce_fwd_.1 = f32[256,1]{1,0} custom-call(...), '
                      'custom_call_target="tpu_custom_call"') == "tpuframe_ce_fwd"


def stalled_trace():
    """Three steps (the window added for the profiler's start-up), then four
    with a stall of 30 s before the last two: an input-bound stretch."""
    ms = 1_000_000
    starts = [0, 130, 260, 1_000, 1_130, 31_260, 31_390]
    dev = {"modules": [["jit_step", s * ms, 129 * ms] for s in starts]
           + [["jit_add", (s + 129) * ms, 1000] for s in starts],
           "ops": [["fusion.1", s * ms, 129 * ms] for s in starts]}
    return {"devices": {"/device:TPU:0": dev}, "host": [], "planes": []}, ms


def test_traced_window_is_counted_and_a_stall_inside_it_reads_as_idle():
    flat, ms = stalled_trace()
    assert rt.traced_window(flat["devices"], steps=4) == (1_000 * ms, 31_519 * ms)
    assert rt.traced_window(flat["devices"], steps=2) == (31_260 * ms, 31_519 * ms)
    r = rt.reduce(flat, 4)
    assert r["steps"] == 4 and r["busy_s"] == pytest.approx(4 * 0.129)
    # the 30 s in which the device waited are inside the window: 98% idle
    assert r["window_s"] == pytest.approx(30.519)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.983, abs=0.001)
    assert dict(r["breakdown"]["idle_gaps"])["between_programs"] == pytest.approx(30.003)


def test_a_trace_that_holds_fewer_steps_than_the_run_made_is_refused():
    flat, _ms = stalled_trace()
    with pytest.raises(ValueError, match="holds 7 execution"):
        rt.reduce(flat, 8)
    flat["devices"]["/device:TPU:1"] = {"modules": [], "ops": [["fusion.1", 0, 5]]}
    with pytest.raises(ValueError, match="TPU:1: the trace holds 0"):
        rt.reduce(flat, 4)


def test_reduce_refuses_a_trace_without_device_operations():
    with pytest.raises(ValueError, match="no device plane"):
        rt.reduce({"devices": {}, "host": [], "planes": ["/host:CPU"]}, 1)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded chip trace in the tree")
def test_recorded_chip_trace_reduces():
    with gzip.open(FIXTURE, "rt") as f:
        flat = json.load(f)
    assert any(p.startswith(rt.DEVICE_PREFIX) for p in flat["planes"])
    r = rt.reduce(flat, steps=1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert "tpuframe_normalize" in r["kernels"]
    assert r["breakdown"]["device_ops"] and len(r["breakdown"]["device_ops"]) <= 10
    # one chip: nothing to exchange
    assert r["exposed_collective_s"] == 0
