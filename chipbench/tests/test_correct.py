"""What decides ``correct``, shown to fail: the control (the reference in
the precision below) against the limits, and a whole run with the timed
path broken underneath."""

import json
import os

import pytest

from chipbench import correct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_cfg(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    from chipbench.run import _merge

    return _merge(cfg, cfg["rehearsal"])


def batches_for(cfg, traffic, seed):
    from chipbench.traffic import generator

    ds = generator.make_dataset(generator.load_mix(traffic), cfg, seed, cfg["per_chip_batch"])
    return ds.first_batches(correct.N_STEPS, cfg["per_chip_batch"])


@pytest.mark.parametrize("name,traffic", [("gpt2-medium", "tokens-seq1024"),
                                          ("resnet50-imagenet", "cached-uint8")])
def test_control_in_fp8_fails_where_bf16_passes(name, traffic):
    """At a size a test run holds: rounding every matmul operand to bf16 (the
    configuration's own precision) stays inside limits that rounding to fp8
    (the precision below) breaks, with a factor of three between them."""
    cfg = small_cfg(name)
    ref = correct.load_by_name("reference", name)
    batches = batches_for(cfg, traffic, seed=2**31 + 5)

    exact = correct.follow_reference(ref, cfg, 5, batches)
    sound = correct.follow_reference(ref, cfg, 5, batches, correct.bf16_wrap)
    control = correct.follow_reference(ref, cfg, 5, batches, correct.control_wrap)
    want = exact.pop("_kept")
    sound["grad_diff"] = correct.rel_diff(sound.pop("_kept"), want)
    control["grad_diff"] = correct.rel_diff(control.pop("_kept"), want)
    # the whole-leaf difference sees rounding at first order: leaf by leaf, and
    # in the worst leaf that is the cell's number, fp8 lies three times beyond bf16
    for leaf, d in sound["grad_diff"].items():
        assert control["grad_diff"][leaf] > 3 * d > 0, leaf
    d_sound, d_ctrl = max(sound["grad_diff"].values()), max(control["grad_diff"].values())
    assert d_ctrl > 3 * d_sound
    limits = {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0, "grad_diff": 3 * d_sound}
    assert correct.compare(sound, exact, limits)[0]
    ok, rows = correct.compare(control, exact, limits)
    assert not ok and not all(r["ok"] for r in rows if r["number"] == "grad_diff")
    # a limit for each leaf by name: the control is outside every one of them,
    # and one leaf outside its own is enough
    worst = max(sound["grad_diff"], key=sound["grad_diff"].get)
    by_leaf = {leaf: 3 * d for leaf, d in sound["grad_diff"].items()}
    assert correct.compare(sound, exact, dict(limits, grad_diff=by_leaf))[0]
    rows = correct.compare(control, exact, dict(limits, grad_diff=by_leaf))[1]
    assert not any(r["ok"] for r in rows if r["number"] == "grad_diff")
    by_leaf[worst] = sound["grad_diff"][worst] / 2
    ok, rows = correct.compare(sound, exact, dict(limits, grad_diff=by_leaf))
    assert not ok and [r["at"] for r in rows if not r["ok"]] == [worst]
    with pytest.raises(ValueError, match="no limit"):
        correct.compare(sound, exact, dict(limits, grad_diff={}))


def test_norm_gap_takes_the_worst_leaf_against_the_median_floor():
    want = {"a": 10.0, "b": 1.0, "tiny": 1e-6}
    got = {"a": 10.5, "b": 1.0, "tiny": 3e-6}
    gap, where = correct.norm_gap(got, want)
    # 'tiny' is measured against the median leaf (1.0), not its own norm
    assert where == "a" and gap == pytest.approx(0.05)
    assert correct.norm_gap({"a": float("nan"), "b": 1.0, "tiny": 0.0}, want)[1] == "a"
    unchanged = {k: 0.0 for k in want}
    assert correct.norm_gap(unchanged, want)[0] == pytest.approx(1.0)
    # leaves of up to SMALL_LEAF elements stay out where sizes are given
    sizes = {"a": correct.SMALL_LEAF, "b": 10**6, "tiny": 10**6}
    assert correct.norm_gap(got, want, sizes) == (pytest.approx(4e-6), "tiny")


def test_seed_folding_takes_seeds_past_32_signed_bits():
    a, b = correct.fold_seed(2**31 + 1234567), correct.fold_seed(2**31 + 1234568)
    assert 0 <= a < 2**31 and 0 <= b < 2**31 and a != b


@pytest.mark.parametrize("broken", [False, True])
def test_a_run_with_the_step_broken_underneath_is_not_correct(monkeypatch, tmp_path, broken):
    """Drive everything of a run but the look for a chip (the rehearsal
    sizes on the CPU).  Sound, ``correct`` is true; with a train step that
    returns its parameters unchanged it comes out false."""
    import jax

    from chipbench import run
    from tpuframe.train import trainer as trainer_mod

    if broken:
        real = trainer_mod.Trainer._step_call

        def unchanged(self, kind, fn, state, batch):
            keep = jax.tree.map(lambda a: a.copy(), state.params)
            new_state, metrics = real(self, kind, fn, state, batch)
            return new_state.replace(params=keep), metrics

        monkeypatch.setattr(trainer_mod.Trainer, "_step_call", unchanged)
    out = run.run_cell("gpt2m_seq1024", 2**31 + 99, 0.5, False, rehearsal=True,
                       out_dir=str(tmp_path))
    assert out["attempted"] > 0 and set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is (not broken)
    if broken:
        update = next(r for r in out["extras"]["rows"] if r["number"] == "update_gap")
        assert not update["ok"] and update["value"] == pytest.approx(1.0, abs=0.05)
