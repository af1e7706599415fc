"""BENCHMARK.json against the files it names: every cell is nothing but files."""

import json
import os
import re

from chipbench.traffic import generator

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        conf = configs[w["config"]]
        assert os.path.isfile(os.path.join(ROOT, conf["file"]))
        for kind in ("flops", "reference"):
            assert os.path.isfile(os.path.join(ROOT, "chipbench", kind, f"{conf['name']}.py"))
        mix = generator.load_mix(w["traffic"])
        assert {"input", "label", "cache_batches", "log_interval"} <= set(mix)


def test_every_metric_has_a_reader_and_a_true_moves():
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert NAME.match(m["name"])
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "layer_metrics", f"{m['name']}.py"))
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


def test_the_file_keeps_to_the_contracts_form():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa: E731
    assert 1 <= len(b["command"]) <= 32 and all(line(word) for word in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells has to fit into 43200 seconds
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and line(w["why"])
    for kind, keys in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                       ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for m in b[kind]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and unit.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in ("device_trace", "program_span", "program_counter",
                                   "host_clock")
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in b[kind]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    # a pair of configuration and traffic names one cell; no file serves two configurations
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    assert {w["config"] for w in b["workloads"]} == {c["name"] for c in b["configs"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    b = bench()
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)


def test_traffic_is_the_same_for_the_same_seed_and_differs_across_seeds():
    b = bench()
    with open(os.path.join(ROOT, "chipbench", "configs", "gpt2-medium.json")) as f:
        cfg = json.load(f)
    mix = generator.load_mix("tokens-seq1024")
    a = generator.make_dataset(mix, cfg, 2**31 + 12345, 4)
    again = generator.make_dataset(mix, cfg, 2**31 + 12345, 4)
    other = generator.make_dataset(mix, cfg, 7, 4)
    assert (a.inputs == again.inputs).all() and (a.labels == again.labels).all()
    assert a.inputs.shape == other.inputs.shape and (a.inputs != other.inputs).any()
    # next-token labels; and the first batches are what an unshuffled loader serves
    assert (a.inputs[:, 1:] == a.labels[:, :-1]).all()
    x0, y0 = a.first_batches(2, 4)[1]
    assert (x0[0] == a[4][0]).all() and (y0[3] == a[7][1]).all()
    assert b["run_seconds"] <= 51
