"""BENCHMARK.json against the contract's limits, and file resolution by
name: a cell, a traffic mix and a per-layer metric each arrive as new files
and new entries, with no edit to a file that is there (CPU only)."""

import copy
import json
import os
import re

import pytest

from benchmark.harness import layer, spec
from benchmark_fixtures import bench, grown_root  # noqa: F401  (fixtures)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        # the metric it should move is reported wherever it is
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["name"] in {w["config"] for w in bench["workloads"]}
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            conf = json.load(f)
        # every cut named in BENCHMARK.json is explained in the file
        assert set(c["reduced"]) == set(conf["reduced"])
        assert conf["source"] == c["source"] and conf["guarantees"]


def test_every_cell_resolves_and_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(bench, w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert len(cell.per_layer) >= 1
        assert set(cell.definitions) == {m["name"] for m in cell.per_layer}
        for d in cell.definitions.values():
            assert d["kind"] in layer.KINDS


def test_a_new_cell_resolves_from_new_files_alone(grown_root):
    root, grown = grown_root
    cell = spec.resolve_cell(grown, "ring4.trickle", root)
    assert cell.config["validators"] == 4
    assert cell.traffic["rate_tx_per_s"] == 200.0
    assert [m["name"] for m in cell.per_layer] == ["blocks_in_window.trickle"]
    assert {m["name"] for m in cell.end_to_end} == {
        "commit_p50_ms", "commit_p95_ms", "setup_s"}
    # the ring16 cells arrive by entries alone: their files are in the tree
    fire = spec.resolve_cell(grown, "ring16.firehose", root)
    assert fire.traffic["outstanding_cap"] == 2000
    assert len(fire.per_layer) == 10 and len(fire.end_to_end) == 2
    paced = spec.resolve_cell(grown, "ring16.paced", root)
    assert paced.traffic["loop"] == "open" and len(paced.per_layer) == 9
    # and the cell that was there resolves as before
    old = spec.resolve_cell(grown, "catchup16.backlog8k", root)
    assert old.traffic["backlog_events"] == 8000 and len(old.per_layer) == 8


@pytest.mark.parametrize("breakage,match", [
    (lambda b: b["workloads"].append(
        {"name": "x.y", "config": "nope", "traffic": "firehose", "chips": 1,
         "why": "x"}), "unknown config"),
    (lambda b: b["workloads"].append(
        {"name": "x.y", "config": "catchup16", "traffic": "nope", "chips": 1,
         "why": "x"}), "no traffic/nope.json"),
    (lambda b: b["per_layer"].append(
        {"name": "orphan", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "kernel",
         "moves": "setup_s", "workloads": ["x.y"]}), "neither"),
])
def test_a_missing_file_is_named(bench, breakage, match):
    broken = copy.deepcopy(bench)
    if match == "neither":
        broken["workloads"].append(
            {"name": "x.y", "config": "catchup16", "traffic": "firehose",
             "chips": 1, "why": "x"})
    breakage(broken)
    with pytest.raises(spec.SpecError, match=match):
        spec.resolve_cell(broken, "x.y")
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.resolve_cell(bench, "absent.cell")
