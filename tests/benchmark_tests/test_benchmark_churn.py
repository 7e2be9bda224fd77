"""The cell ``churn16.backlog8k-joinleave`` at a rehearsal's size (4 genesis
validators, 2 joiners, three requests): the command the driver runs, the
control, and the four per-layer metrics the deployment brought. CPU only.

Its driver is ``tests/benchmark_tests/drivers/churn-ingest.py`` — the second
directory of ``paths`` — and not ``benchmark/drivers/``:
``test_benchmark_rehearsal.py``'s ``echo_root`` makes that directory itself
and lists what it finds there, and a PR that adds a cell edits no file the
benchmark has. So the runs here take the checkout's own root, which has
both directories, where the other suites copy ``benchmark/`` alone."""

import json
import os

import pytest

from benchmark.harness import layer, spec
from test_benchmark_control import _control, _failing
from test_benchmark_rehearsal import _run

CELL = "churn16.backlog8k-joinleave"
NEW_METRICS = ("multi_set_sweep_pct.catchup",
               "creator_stall_ms_per_change.catchup",
               "membership_us_per_change.catchup",
               "window_rebuilds_per_change.catchup")


@pytest.fixture(scope="module")
def cell():
    return spec.resolve_cell(spec.load_benchmark(), CELL)


def test_the_cell_resolves_to_its_files(cell):
    bench = spec.load_benchmark()
    assert cell.chips == 1 and cell.config["driver"] == "churn-ingest"
    assert spec.driver_files(spec.ROOT, bench["paths"])["churn-ingest"] == (
        os.path.join(spec.ROOT, "tests/benchmark_tests/drivers",
                     "churn-ingest.py"))
    assert {m["name"] for m in cell.end_to_end} == {
        "catchup_events_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 23 and tuple(names[-4:]) == NEW_METRICS
    assert all(n.endswith(".catchup") for n in names)
    # what catch-up reported before, it still reports, in the same order
    old = spec.resolve_cell(bench, "catchup16.backlog8k")
    assert [m["name"] for m in old.per_layer] == names[:19]
    conf, entry = cell.config, bench["configs"][-1]
    assert entry["name"] == "churn16" and entry["source"] == conf["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "backlog", "transport", "validators"]
    assert "64 -> 16" in conf["reduced"]["validators"]
    assert len(conf["guarantees"]) == 5 and len(conf["assumed"]) == 4
    assert (conf["validators"], conf["joiners"]) == (16, 4)
    traffic = cell.traffic
    assert traffic["requests"] == ["+x0", "-v15", "+x1", "-v14", "+x2",
                                   "-v13", "+x3"]
    assert (traffic["backlog_events"], traffic["sync_events"],
            traffic["distinct_streams"], traffic["warm_passes_max"],
            traffic["first_request_event"], traffic["request_every"]) == (
                8000, 1000, 5, 5, 600, 1000)
    assert traffic["dag_seed"] != spec.resolve_cell(
        bench, "catchup16.backlog8k").traffic["dag_seed"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsal_prints_the_contract_line(cell, trace):
    line, out = _run(CELL, trace=trace)
    compared = line["compared"]
    # three requests at a rehearsal's size, all applied at the reference's
    # rounds, and sweeps whose windows straddle a change
    for name in ("membership_changes_not_applied",
                 "peer_sets_differing_from_oracle",
                 "backlog_events_not_stored", "blocks_differing_from_oracle"):
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}
    assert compared["multi_set_sweeps_in_window"]["value"] >= 1
    assert "3 requests ['+x0', '-v3', '+x1']" in out
    if trace:
        got = line["metrics"]
        assert set(got) <= {m["name"] for m in cell.per_layer}
        # the four the deployment brought, from the rehearsal's own counters
        assert 0 < got["multi_set_sweep_pct.catchup"]["value"] <= 100
        for name in NEW_METRICS[1:]:
            assert got[name]["value"] > 0, name
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


def test_the_altered_sweep_comes_out_not_correct():
    line, err = _control("altered-sweep", CELL, 2, spec.ROOT)
    assert line["correct"] is False
    assert "blocks_differing_from_oracle" in _failing(line), line["compared"]
    assert err.strip().splitlines()[-1] == "correct: false"


COUNTERS = {
    "membership_changes_applied": 7.0, "accel_rebuilds": 14.0,
    "sync_stage_seconds.creator_stall.sum": 0.07,
    "sync_stage_seconds.peer_set_wait.sum": 1.33,
    "sync_stage_seconds.membership.sum": 0.0014,
    "batch_bucket_launches.1x128x1024x16x1x32": 6.0,
    "batch_bucket_launches.1x256x2048x24x2x32": 3.0,
    "accel_bucket_launches.1x256x2048x24x4x64": 1.0,
    "batch_bucket_launches.1x64x256x24x1x16": 0.0,
}


@pytest.mark.parametrize("name,want", zip(NEW_METRICS,
                                          (40.0, 200.0, 200.0, 2.0)))
def test_the_membership_metrics_on_hand_made_counters(cell, name, want):
    ctx = {"counters": COUNTERS, "samples": {}, "trace": None}
    assert layer.evaluate(cell.definitions[name], ctx) == pytest.approx(want)
    # a program that predates the counters: nothing, never a 0
    older = {"counters": {"accel_rebuilds": 14.0, "accel_sweeps": 9.0},
             "samples": {}, "trace": None}
    assert layer.evaluate(cell.definitions[name], older) is None


def test_the_new_files_are_data_but_the_driver_the_generator_and_a_reader():
    """What this cell added under ``paths``, by kind: a PR that claims a
    gain in a new cell may add data only, so the next one knows."""
    bench = spec.load_benchmark()
    code = []
    for p in bench["paths"]:
        for d, _dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            code += [os.path.relpath(os.path.join(d, f), spec.ROOT)
                     for f in files if "churn" in f or "multi_set" in f]
    assert sorted(f for f in code if f.endswith(".py")) == [
        "benchmark/harness/churn.py",
        "benchmark/readers/multi_set_sweep_pct.catchup.py",
        "tests/benchmark_tests/drivers/churn-ingest.py",
        "tests/benchmark_tests/test_benchmark_churn.py"]
    with open(os.path.join(spec.ROOT, "benchmark/configs/churn16.json")) as f:
        assert json.load(f)["name"] == "churn16"
