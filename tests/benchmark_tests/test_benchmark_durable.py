"""The cells ``durable16.backlog8k-store`` and ``durable16.restart8k-bootstrap``
at a rehearsal's size (4 validators, 600 events, syncs of 300, two streams):
the command the driver runs, the control, and the five per-layer metrics the
deployment brought. CPU only.

Their driver is ``tests/benchmark_tests/drivers/durable-ingest.py`` — the
second directory of ``paths``, beside ``churn-ingest.py`` and for the same
reason (``test_benchmark_churn.py``) — so the runs here take the checkout's
own root."""

import json
import os

import pytest

from benchmark.harness import layer, spec
from test_benchmark_control import _control, _failing
from test_benchmark_rehearsal import _run

STORE = "durable16.backlog8k-store"
RESTART = "durable16.restart8k-bootstrap"
NEW_METRICS = {
    "store_write_us_per_event.catchup": [STORE],
    "store_commits_per_event.catchup": [STORE],
    "store_db_reads_per_event.catchup": [STORE, RESTART],
    "bootstrap_us_per_event.catchup": [RESTART],
    "bootstrap_load_us_per_event.catchup": [RESTART],
}
# a replay opens no decode, batch_verify, sync or prepare_sync span
NOT_IN_A_REPLAY = {"verify_us_per_event.catchup", "sync_untimed_pct.catchup",
                   "ingest_cpu_pct.catchup"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def cells(bench):
    return {name: spec.resolve_cell(bench, name) for name in (STORE, RESTART)}


def test_the_cells_resolve_to_their_files(bench, cells):
    assert spec.driver_files(spec.ROOT, bench["paths"])["durable-ingest"] == (
        os.path.join(spec.ROOT, "tests/benchmark_tests/drivers",
                     "durable-ingest.py"))
    entry = next(c for c in bench["configs"] if c["name"] == "durable16")
    conf = cells[STORE].config
    assert conf == cells[RESTART].config
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "backlog", "transport"]
    assert conf["architecture"] is None and conf["store"] is True
    assert conf["driver"] == "durable-ingest"
    assert (conf["validators"], conf["rejoining_validator"], conf["tx_bytes"],
            conf["sync_limit"], conf["cache_size"]) == (16, 0, 100, 1000,
                                                        10000)
    assert conf["database"]["pragmas"] == {
        "journal_mode": "WAL", "synchronous": "NORMAL",
        "auto_vacuum": "INCREMENTAL"}
    assert len(conf["guarantees"]) == 6 and len(conf["assumed"]) == 4
    old = spec.resolve_cell(bench, "catchup16.backlog8k")
    for name, cell in cells.items():
        assert cell.chips == 1
        assert {m["name"] for m in cell.end_to_end} == {
            "catchup_events_per_s", "setup_s"}
        assert all(m["layer"] == "durable store" for m in cell.per_layer
                   if m["name"] in NEW_METRICS)
        # the DAG is backlog8k's, five streams, closed loop
        assert (cell.traffic["dag_seed"], cell.traffic["distinct_streams"],
                cell.traffic["sync_events"], cell.traffic["warm_passes_max"]
                ) == (old.traffic["dag_seed"], 5, 1000, 5)
    assert cells[STORE].traffic["backlog_events"] == 8000
    assert (cells[RESTART].traffic["database_events"],
            cells[RESTART].traffic["replay_batch"]) == (8000, 100)
    # what catch-up reports, the store cell reports too; a replay all but
    # what it opens no span for; and each of the five where it was asked
    shared = [m["name"] for m in old.per_layer]
    assert [m["name"] for m in cells[STORE].per_layer] == shared + [
        n for n, where in NEW_METRICS.items() if STORE in where]
    assert [m["name"] for m in cells[RESTART].per_layer] == [
        n for n in shared if n not in NOT_IN_A_REPLAY] + [
        n for n, where in NEW_METRICS.items() if RESTART in where]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == NEW_METRICS[m["name"]]
            assert m["moves"] == "catchup_events_per_s"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_store_cell_rehearsal_prints_the_contract_line(cells, trace):
    line, out = _run(STORE, trace=trace)
    compared = line["compared"]
    for name in ("backlog_events_not_on_disk", "backlog_events_not_stored",
                 "blocks_on_disk_differing_from_oracle",
                 "blocks_differing_from_oracle", "audited_events_evicted",
                 "oracle_events_the_first_pass_missed", "events_not_ordered"):
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}
    assert compared["device_sweeps_in_window"]["value"] >= 1
    assert "databases under" in out and " on /" in out  # the filesystem
    if trace:
        got = line["metrics"]
        assert set(got) <= {m["name"] for m in cells[STORE].per_layer}
        # from the rehearsal's own counters: several commits an event, and
        # the two parents of an event asked of the database before the
        # batch's overlay
        assert 3 < got["store_commits_per_event.catchup"]["value"] < 8
        assert got["store_write_us_per_event.catchup"]["value"] > 0
        assert 1 < got["store_db_reads_per_event.catchup"]["value"] < 4
        assert "bootstrap_us_per_event.catchup" not in got
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_restart_cell_rehearsal_prints_the_contract_line(cells, trace):
    line, out = _run(RESTART, trace=trace)
    compared = line["compared"]
    for name in ("backlog_events_not_on_disk", "blocks_differing_from_oracle",
                 "oracle_events_the_replay_missed",
                 "undetermined_events_differing_from_oracle",
                 "last_consensus_round_differing",
                 "database_rows_changed_by_replay", "events_not_ordered"):
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}
    assert compared["device_sweeps_in_window"]["value"] >= 1
    assert "database 1: ingest" in out  # set-up made them by the program
    if trace:
        got = line["metrics"]
        assert set(got) <= {m["name"] for m in cells[RESTART].per_layer}
        assert not set(got) & NOT_IN_A_REPLAY
        # a sound replay reads nothing back and writes nothing
        assert got["store_db_reads_per_event.catchup"]["value"] == 0
        assert (got["bootstrap_us_per_event.catchup"]["value"]
                > got["bootstrap_load_us_per_event.catchup"]["value"] > 0)
        assert "store_write_us_per_event.catchup" not in got
        assert got["compile_waits.catchup"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


@pytest.mark.parametrize("fault,cell,caught_by", [
    ("altered-sweep", STORE, "blocks_differing_from_oracle"),
    ("dropped-sync", STORE, "backlog_events_not_on_disk"),
    ("altered-sweep", RESTART, "blocks_differing_from_oracle"),
])
def test_a_planted_fault_comes_out_not_correct(fault, cell, caught_by):
    line, err = _control(fault, cell, 2, spec.ROOT)
    assert line["correct"] is False
    assert caught_by in _failing(line), line["compared"]
    assert err.strip().splitlines()[-1] == "correct: false"


COUNTERS = {
    "sync_stage_seconds.insert.count": 8008.0,
    "sync_stage_seconds.store_write.sum": 4.004,
    "sync_stage_seconds.bootstrap.sum": 2.002,
    "sync_stage_seconds.bootstrap_load.sum": 0.4004,
    "store_commits": 48048.0, "store_db_reads": 16016.0,
}


@pytest.mark.parametrize("name,want", zip(NEW_METRICS,
                                          (500.0, 6.0, 2.0, 250.0, 50.0)))
def test_the_store_metrics_on_hand_made_counters(cells, name, want):
    cell = cells[NEW_METRICS[name][0]]
    ctx = {"counters": COUNTERS, "samples": {}, "trace": None}
    assert layer.evaluate(cell.definitions[name], ctx) == pytest.approx(want)
    # a window with no insert: nothing, never a 0
    empty = {"counters": {"store_commits": 3.0}, "samples": {}, "trace": None}
    assert layer.evaluate(cell.definitions[name], empty) is None


def test_the_new_files_are_data_but_the_driver_and_the_reference():
    """What these cells added under ``paths``, by kind: a PR that claims a
    gain in a new cell may add data only, so the next one knows."""
    bench = spec.load_benchmark()
    found = []
    for p in bench["paths"]:
        for d, _dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            found += [os.path.relpath(os.path.join(d, f), spec.ROOT)
                      for f in files
                      if "__pycache__" not in d and any(
                          word in f for word in ("durable", "store", "restart",
                                                 "bootstrap"))]
    assert sorted(f for f in found if f.endswith(".py")) == [
        "benchmark/harness/durable.py",
        "tests/benchmark_tests/drivers/durable-ingest.py",
        "tests/benchmark_tests/test_benchmark_durable.py"]
    assert sorted(f for f in found if f.endswith(".json")) == [
        "benchmark/configs/durable16.json",
        "benchmark/layer_metrics/bootstrap_load_us_per_event.catchup.json",
        "benchmark/layer_metrics/bootstrap_us_per_event.catchup.json",
        "benchmark/layer_metrics/store_commits_per_event.catchup.json",
        "benchmark/layer_metrics/store_db_reads_per_event.catchup.json",
        "benchmark/layer_metrics/store_write_us_per_event.catchup.json",
        "benchmark/traffic/backlog8k-store.json",
        "benchmark/traffic/restart8k-bootstrap.json"]
    with open(os.path.join(spec.ROOT, "benchmark/configs/durable16.json")) as f:
        assert json.load(f)["name"] == "durable16"
