"""The one engage rule: which implementation of an op runs is
``dispatch.resolve_interpret``'s answer from what the process can
observe, and from nothing that lives outside the tree."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest

from tpuframe.ops import dispatch

REF = None  # the jnp reference path

# explicit interpret=, what pallas_mode() finds (the backend and
# TPUFRAME_PALLAS_INTERPRET), device count, the caller's shardable, inside a
# manual region, TPUFRAME_DISABLE_PALLAS -> the interpret flag the op's
# kernel runs with (REF: no kernel), and the verdict event's source
_TRUTH_TABLE = [
    # no kernel can run: CPU, no interpret knob
    (None, None, 1, False, False, False, REF, "default"),
    (None, None, 4, True, False, False, REF, "default"),
    (None, None, 1, False, True, False, REF, "default"),
    # one TPU chip: the compiled kernel, whatever else
    (None, "compiled", 1, False, False, False, False, "default"),
    (None, "compiled", 1, True, False, False, False, "default"),
    # several chips under one jit: only per shard
    (None, "compiled", 4, True, False, False, False, "default"),
    (None, "compiled", 4, False, True, False, False, "default"),
    (None, "compiled", 4, True, True, False, False, "default"),
    # ... and gpt2m_dp4's LayerNorm (model dimension, no mesh handed in):
    # a bare custom call there would replicate its operands
    (None, "compiled", 4, False, False, False, REF, "default"),
    # interpret mode engages anywhere
    (None, "interpret", 1, False, False, False, True, "default"),
    (None, "interpret", 4, False, False, False, True, "default"),
    (None, "interpret", 4, True, True, False, True, "default"),
    # the one off switch beats the backend and the interpret knob
    (None, "compiled", 1, False, False, True, REF, "forced"),
    (None, "compiled", 4, True, False, True, REF, "forced"),
    (None, "interpret", 1, False, False, True, REF, "forced"),
    (None, "interpret", 4, False, True, True, REF, "forced"),
    (None, None, 1, False, False, True, REF, "forced"),
    # an explicit interpret= beats everything, the off switch included
    (True, None, 1, False, False, False, True, "forced"),
    (True, "compiled", 4, False, False, False, True, "forced"),
    (True, "interpret", 1, False, False, True, True, "forced"),
    (True, None, 4, False, False, True, True, "forced"),
    (False, None, 1, False, False, False, False, "forced"),
    (False, "compiled", 4, False, False, False, False, "forced"),
    (False, "interpret", 4, True, False, False, False, "forced"),
    (False, "compiled", 1, False, False, True, False, "forced"),
]


def _row_id(row) -> str:
    interpret, mode, devices, shardable, manual, disabled, _, _ = row
    return "-".join([
        f"interpret_{interpret}", str(mode), f"{devices}dev",
        "shardable" if shardable else "unshardable",
        "manual" if manual else "jit", "disabled" if disabled else "enabled"])


@pytest.mark.parametrize("row", _TRUTH_TABLE, ids=_row_id)
def test_resolve_interpret_truth_table(row, monkeypatch, tmp_path):
    from tpuframe.track import telemetry as T

    interpret, mode, devices, shardable, manual, disabled, want, source = row
    monkeypatch.setattr(
        jax, "default_backend", lambda: "tpu" if mode == "compiled" else "cpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: devices)
    monkeypatch.setattr(dispatch, "inside_shard_map", lambda: manual)
    for knob, on in (("TPUFRAME_PALLAS_INTERPRET", mode == "interpret"),
                     ("TPUFRAME_DISABLE_PALLAS", disabled)):
        if on:
            monkeypatch.setenv(knob, "1")
        else:
            monkeypatch.delenv(knob, raising=False)
    assert dispatch.pallas_mode() == (None if disabled else mode)
    dispatch._VERDICT_EMITTED.clear()
    tele = T.configure(str(tmp_path / "events.jsonl"))
    try:
        got = dispatch.resolve_interpret(
            interpret, shardable, op="layer_norm", shape_class="d1024")
        assert got is want
        # a call that names no op decides the same and says nothing
        assert dispatch.resolve_interpret(interpret, shardable) is want
        (event,) = [e for e in tele.recent_events(50)
                    if e["name"] == "ops/kernel_verdict"]
        assert (event["op"], event["shape_class"]) == ("layer_norm", "d1024")
        assert event["enable"] is (want is not REF)
        assert event["source"] == source
    finally:
        T.reset()
        dispatch._VERDICT_EMITTED.clear()


def test_manual_region_is_read_off_the_trace(mesh8):
    from jax.sharding import PartitionSpec as P

    seen = []

    def per_shard(x):
        seen.append(dispatch.inside_shard_map())
        return x

    assert dispatch.inside_shard_map() is False
    axis = mesh8.axis_names[0]
    jax.shard_map(per_shard, mesh=mesh8, in_specs=P(axis), out_specs=P(axis))(
        jnp.zeros((8,)))
    assert seen == [True]
    assert dispatch.effective_mesh(mesh8) is mesh8


# -- nothing outside the tree -------------------------------------------------


def _write_stale_ledger(store_dir: str) -> None:
    """A kernel ledger as ``ops/ledger.py`` persisted it up to PR 28, for
    this host, this backend and the default signature: LayerNorm's kernel
    priced slower, ``blockwise`` priced fastest at 1024 positions."""
    from tpuframe.autotune.config import config_key, default_host

    ident = (default_host(), jax.default_backend(), "unplanned")
    os.makedirs(store_dir, exist_ok=True)
    with open(os.path.join(store_dir, config_key(*ident) + ".json"), "w") as f:
        json.dump({
            "host": ident[0], "backend": ident[1], "signature": ident[2],
            "created_unix": 1.0,
            "verdicts": {
                "layer_norm": {"d1024": {"enable": False, "ratio": 3.0}},
                "attention": {"l1024": {"choice": "blockwise", "p50_s": {}}},
            },
        }, f)


@pytest.mark.parametrize("where", ["default_directory", "TPUFRAME_KERNEL_LEDGER_DIR"])
def test_dispatch_ignores_files_on_the_host(where, monkeypatch, tmp_path):
    """What a step runs depends on no file outside the checkout: a ledger
    left on the host by an earlier tree, where that tree looked for it,
    moves neither the engage rule nor the attention form at 1024."""
    import importlib

    from tpuframe.models import transformer

    # by module path: tpuframe.ops re-exports the function under this name
    bw_module = importlib.import_module("tpuframe.ops.blockwise_attention")

    monkeypatch.setenv("TPUFRAME_AUTOTUNE_DIR", str(tmp_path / "autotune"))
    if where == "default_directory":
        monkeypatch.delenv("TPUFRAME_KERNEL_LEDGER_DIR", raising=False)
        _write_stale_ledger(str(tmp_path / "autotune" / "ledger"))
    else:
        monkeypatch.setenv("TPUFRAME_KERNEL_LEDGER_DIR", str(tmp_path / "kernels"))
        _write_stale_ledger(str(tmp_path / "kernels"))
    monkeypatch.delenv("TPUFRAME_KERNELS", raising=False)
    monkeypatch.delenv("TPUFRAME_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("TPUFRAME_PALLAS_INTERPRET", "1")
    for cache in ("_LEDGER_CACHE", "_VERDICT_EMITTED"):  # the first: PR 28's
        getattr(dispatch, cache, set()).clear()

    assert dispatch.resolve_interpret(
        None, True, op="layer_norm", shape_class="d1024") is True

    # on this backend without the interpret knob no kernel runs, and the
    # rule's answer at 1024 positions is full whatever a ledger priced
    monkeypatch.delenv("TPUFRAME_PALLAS_INTERPRET")
    took = []
    monkeypatch.setattr(transformer, "attention_reference",
                        lambda q, k, v, **kw: took.append("full") or v)
    monkeypatch.setattr(bw_module, "blockwise_attention",
                        lambda q, k, v, **kw: took.append("blockwise") or v)
    qkv = jnp.zeros((1, 1024, 1, 8), jnp.float32)
    transformer._attend(qkv, qkv, qkv, impl="auto", causal=True, num_heads=1,
                        initializing=False)
    assert took == ["full"]
