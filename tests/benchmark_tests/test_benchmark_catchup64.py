"""The cell ``catchup64.backlog8k``: one validator of a 64-validator ring
rejoins through the built-in ``core-ingest`` driver on ``backlog8k``'s
traffic, unchanged. Its rehearsal runs 24 validators (P 24) on the traffic
file's 600 events: at 63 creators a round is about 700 events and nothing
would be ordered (the configuration file says so; the 64-validator
comparison on the CPU is ``tests/test_catchup64.py``'s). It runs on four
host devices, as the cell asks for a four-chip host. CPU only.

Every entry is looked up BY NAME and no list is counted: a later PR appends
its own behind these. The cell reports every per-layer metric
``catchup16.backlog8k`` does but the collector's share, whose cells an
accepted test pins (PERF.md, section 7 (q))."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec
from test_benchmark_control import CONTROL, _failing
from test_benchmark_rehearsal import RUN

CELL, TWIN = "catchup64.backlog8k", "catchup16.backlog8k"
ROWS_AT_ZERO = ("audited_events_evicted", "backlog_events_not_stored",
                "blocks_differing_from_oracle",
                "oracle_events_the_first_pass_missed", "events_not_ordered",
                "device_path_left_in_window")


def _four_devices(*argv):
    """``argv`` (a script of ``benchmark/`` and its arguments) on the cell at
    the rehearsal's size on four host devices: the cell asks for four chips,
    and the harness counts them in a rehearsal too. Returns the last stdout
    line, parsed, and the process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, *argv, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "2", "--rehearsal"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
        preexec_fn=lambda: os.nice(10))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert list(line)[-1] == "compared"
    return line, proc


def _run(trace):
    line, proc = _four_devices(RUN, "--trace", str(trace))
    assert line["correct"] is True and not _failing(line), proc.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    return line, proc.stdout


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_the_cell_resolves_to_its_files_by_name(bench):
    cell = spec.resolve_cell(bench, CELL)
    twin = spec.resolve_cell(bench, TWIN)
    # one chip of a whole four-chip host: the validator holds its window
    # on one chip, nothing sharded; the host is its own for steadiness
    assert cell.chips == 4 and cell.config["driver"] == "core-ingest"
    why = {w["name"]: w for w in bench["workloads"]}[CELL]["why"]
    assert "whole host" in why and len(why) <= 200
    assert "one chip of a four-chip host" in cell.config["layout"]
    assert cell.traffic_name == twin.traffic_name == "backlog8k"
    assert cell.traffic == twin.traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "catchup_events_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in twin.per_layer} - {"gc_us_per_event.catchup"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert CELL not in by_name["gc_us_per_event.catchup"]["workloads"]
    assert CELL in by_name["sweep_roofline_pct.catchup"]["workloads"]
    entry = {c["name"]: c for c in bench["configs"]}["catchup64"]
    conf = cell.config
    assert entry["file"] == "benchmark/configs/catchup64.json"
    assert len(entry["source"]) <= 200 and "config 4" in entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "backlog", "transport"]
    # catchup16's deployment at the ring's published size
    assert (conf["validators"], conf["rejoining_validator"],
            conf["sync_limit"], conf["cache_size"], conf["tx_bytes"]) == (
        64, 0, 1000, 10000, 100)
    assert set(conf) >= set(twin.config)
    assert conf["guarantees"] == twin.config["guarantees"]
    assert conf["rehearsal"]["validators"] == 24


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_prints_the_contract_line(bench, trace):
    line, out = _run(trace)
    compared = line["compared"]
    for name in ROWS_AT_ZERO:
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}, name
    assert compared["device_sweeps_in_window"]["value"] >= 1
    assert compared["distinct_pass_outcomes"]["value"] == 1
    assert "from 23 creators" in out
    cell = spec.resolve_cell(bench, CELL)
    if trace:
        assert set(line["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert line["metrics"]["insert_us_per_event.catchup"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


def test_an_altered_sweep_comes_out_not_correct():
    line, proc = _four_devices(CONTROL, "--fault", "altered-sweep",
                               "--trace", "0", "--root", spec.ROOT)
    assert line["correct"] is False
    assert line["compared"]["blocks_differing_from_oracle"]["value"] > 0
    assert "blocks_differing_from_oracle" in _failing(line)
    assert proc.stderr.strip().splitlines()[-1] == "correct: false"
