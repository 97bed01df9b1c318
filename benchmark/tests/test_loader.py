"""The harness finds every part of a cell by name, and names none."""
import json
import os
import re

import pytest

import spec

BENCH = spec.HERE


def _bench():
    return spec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_cell_parts_are_found_by_name(cell):
    bench = _bench()
    entry = spec.cell(bench, cell)
    cfg = spec.config(bench, entry["config"])
    traffic = spec.traffic(entry["traffic"])
    assert cfg["name"] == entry["config"]
    assert traffic["seeds_per_unit"] > 0
    ref = spec.reference(cfg)
    assert cfg["control"] in ref.CONTROLS
    e2e = {m["name"] for m in spec.end_to_end_for(bench, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer_for(bench, cell)
    assert layer
    for m in layer:
        assert callable(spec.metric_reader(m["name"]).read)
        assert m["moves"] in e2e


def test_a_cell_loads_by_name_alone():
    for w in _bench()["workloads"]:
        bench, entry, cfg, traffic = spec.load(w["name"])
        assert entry == w and cfg["name"] == w["config"]
        assert traffic == spec.traffic(w["traffic"])


def test_unknown_names_are_refused():
    bench = _bench()
    with pytest.raises(spec.SpecError):
        spec.cell(bench, "no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.traffic("no_such_traffic")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")


def test_per_layer_metrics_follow_their_cells():
    bench = {"workloads": [], "configs": [], "end_to_end": [],
             "per_layer": [{"name": "m", "moves": "a", "workloads": ["x"]},
                           {"name": "n", "moves": "a",
                            "workloads": ["x", "y"]}]}
    assert [m["name"] for m in spec.per_layer_for(bench, "x")] == ["m", "n"]
    assert [m["name"] for m in spec.per_layer_for(bench, "y")] == ["n"]


def test_per_layer_metric_without_cells_is_refused():
    bench = {"workloads": [], "configs": [], "end_to_end": [],
             "per_layer": [{"name": "m", "moves": "a"}]}
    with pytest.raises(spec.SpecError):
        spec.per_layer_for(bench, "x")


def test_harness_code_names_no_cell_config_or_traffic():
    bench = _bench()
    names = {w["name"] for w in bench["workloads"]}
    names |= {c["name"] for c in bench["configs"]}
    names |= {w["traffic"] for w in bench["workloads"]}
    for fname in ("run.py", "workload.py", "check.py", "spec.py",
                  "trace_reduce.py", "control.py"):
        with open(os.path.join(BENCH, fname)) as f:
            src = f.read()
        for name in names:
            assert not re.search(r"[\"']" + re.escape(name) + r"[\"']",
                                 src), (fname, name)


def test_every_config_file_lists_its_cuts_and_source():
    bench = _bench()
    for entry in bench["configs"]:
        with open(os.path.join(spec.ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == entry["reduced"]
        assert cfg["source"] == entry["source"]
        assert cfg["engine"]["n_nodes"] == cfg["raft"]["n"]
        sizes = {**cfg["engine"], **cfg["raft"]}
        # Each cut names what it cut from, and each key it or an
        # assumption names is one the configuration sets.
        assert set(cfg["cuts"]) == set(cfg["reduced"])
        assert set(cfg["cuts"]) | set(cfg["assumed"]) <= set(sizes)
        assert not set(cfg["cuts"]) & set(cfg["assumed"])
