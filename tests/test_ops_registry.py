"""The registry of dispatchable ops: shape classes, the profiler-name
map (and the autotune diagnosis that prints through it), the registry
rows, and the one ``ops/kernel_verdict`` event per distinct decision."""

from __future__ import annotations

import os

import pytest

from tpuframe.ops import dispatch
from tpuframe.ops.registry import (
    OPS_REGISTRY,
    map_op_name,
    normalize_top_ops,
    shape_class,
)


@pytest.fixture(autouse=True)
def _fresh_verdicts():
    dispatch._VERDICT_EMITTED.clear()
    yield
    dispatch._VERDICT_EMITTED.clear()


# -- shape classes ------------------------------------------------------------


def test_shape_class_rounds_up_and_sorts():
    assert shape_class(b=200, k=1000) == "b256_k1024"
    assert shape_class(n=512, e=4) == "e4_n512"  # keys sorted, not given order
    assert shape_class(l=8192) == "l8192"  # exact powers stay put
    assert shape_class(n=0) == "n1"  # degenerate dims clamp to 1


def test_shape_class_symbolic_dims_degrade_to_none():
    """Under jax.export shape polymorphism, batch dims are symbolic and
    refuse int() — shape_class must hand the verdict event a None, not
    abort the export trace (the serve-export regression)."""
    from jax.export import symbolic_shape

    (b,) = symbolic_shape("b")
    assert shape_class(n=784 * b) is None
    assert shape_class(n=784 * b, k=32) is None  # one bad dim poisons all


# -- profiler-name map --------------------------------------------------------


def test_map_op_name_pins_fusion_roots():
    assert map_op_name("log_softmax_fusion") == "cross_entropy"
    assert map_op_name("layer_norm.clone") == "layer_norm"
    assert map_op_name("flash_fwd") == "attention"
    assert map_op_name("expert_dispatch_einsum") == "moe_gating"
    assert map_op_name("jit_adamw_step") is None  # optax's: no op of ours
    assert map_op_name("fusion.123") is None  # generic names map to nothing
    assert map_op_name("") is None and map_op_name(None) is None


def test_normalize_top_ops_keeps_raw_and_rewrites_name():
    rows = normalize_top_ops([
        {"name": "log_softmax_fusion", "pct": 41.0, "class": "compute"},
        {"name": "fusion.7", "pct": 12.0, "class": "compute"},
    ])
    assert rows[0]["op"] == "cross_entropy"
    assert rows[0]["name"] == "cross_entropy"  # the actionable name
    assert rows[0]["raw"] == "log_softmax_fusion"  # provenance kept
    assert rows[1]["op"] is None
    assert rows[1]["name"] == "fusion.7"  # unmapped rows keep their raw name


def test_diagnosis_prints_dispatchable_ops_not_hlo_names():
    """A compute-bound diagnosis's top_ops detail must name tpuframe ops
    (registry-normalized), and its kernel move is the one off switch."""
    from tpuframe.autotune.diagnosis import diagnose

    report = {
        "step_time": {"mean": 0.1, "count": 20, "p50": 0.1},
        "per_step": [{"bound": "compute"}] * 20,
        "device_time": {
            "device_step_s": 0.095,
            "exposed_comms_per_step_s": 0.0,
            "top_ops": [
                {"name": "log_softmax_fusion", "pct": 38.0,
                 "class": "compute"},
                {"name": "layer_norm.clone.2", "pct": 21.0,
                 "class": "compute"},
                {"name": "fusion.9", "pct": 4.0, "class": "compute"},
            ],
        },
    }
    diag = diagnose(report)
    assert diag.bound == "compute"
    top = diag.detail["top_ops"]
    assert [r["name"] for r in top[:2]] == ["cross_entropy", "layer_norm"]
    assert top[0]["raw"] == "log_softmax_fusion"
    (move,) = [m for m in diag.moves if m.knob == "TPUFRAME_DISABLE_PALLAS"]
    assert move.value == "0"
    assert "cross_entropy" in move.reason
    assert "log_softmax_fusion" not in move.reason
    assert not [m for m in diag.moves if m.knob.startswith("TPUFRAME_KERNEL")]


# -- the dispatch plane's event -----------------------------------------------


def test_verdict_event_fires_once_per_decision(monkeypatch, tmp_path):
    from tpuframe.track import telemetry as T

    monkeypatch.delenv("TPUFRAME_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    tele = T.configure(str(tmp_path / "events.jsonl"))

    def verdicts():
        return [e for e in tele.recent_events(50)
                if e["name"] == "ops/kernel_verdict"]

    try:
        for _ in range(5):
            dispatch.resolve_interpret(None, False, op="layer_norm",
                                       shape_class="d512")
            dispatch.resolve_interpret(False, False, op="layer_norm",
                                       shape_class="d4096")
        # one loud event per DISTINCT decision, not one per trace
        assert len(verdicts()) == 2
        by_cls = {e["shape_class"]: e for e in verdicts()}
        assert by_cls["d512"]["enable"] is True
        assert by_cls["d512"]["source"] == "default"
        assert by_cls["d512"]["mode"] == "interpret"
        assert by_cls["d4096"]["enable"] is True
        assert by_cls["d4096"]["source"] == "forced"
        # the off switch is another decision of the same op and class
        monkeypatch.setenv("TPUFRAME_DISABLE_PALLAS", "1")
        assert dispatch.resolve_interpret(
            None, False, op="layer_norm", shape_class="d512") is None
        last = verdicts()[-1]
        assert len(verdicts()) == 3
        assert (last["enable"], last["source"], last["mode"]) == (
            False, "forced", None)
        # a call that names no op leaves no event
        dispatch.resolve_interpret(None, False)
        assert len(verdicts()) == 3
    finally:
        T.reset()


# -- registry -----------------------------------------------------------------


def test_registry_rows_resolve_and_have_parity_tests():
    """Runtime mirror of lint OP002/OP003: every registry row resolves
    to importable symbols and an existing parity test."""
    import importlib

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for op, entry in OPS_REGISTRY.items():
        mod = importlib.import_module(entry["module"])
        assert hasattr(mod, entry["symbol"]), (op, entry["symbol"])
        if entry["reference"] is not None:
            assert hasattr(mod, entry["reference"]), (op, entry["reference"])
        path, _, rest = entry["parity_test"].partition("::")
        test_name = rest.split("::")[-1]
        abspath = os.path.join(repo_root, path)
        assert os.path.exists(abspath), (op, path)
        with open(abspath) as f:
            assert f"def {test_name}" in f.read(), (op, test_name)
