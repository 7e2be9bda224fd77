"""The cell ``fastsync16.behind1500-fastforward`` at a rehearsal's size (4
validators, rings of 600 events, the poll answered at event 300): the command
the driver runs, the control, the landing's own negative planted from
outside, and the four per-layer metrics the deployment brought. CPU only.

Its driver is ``tests/benchmark_tests/drivers/fastsync-ingest.py``, beside
``churn-ingest.py`` and ``durable-ingest.py`` (``test_benchmark_churn.py``
says why), so the runs here take the checkout's own root. Every entry is
looked up BY NAME: a later PR appends its own behind these."""

import json
import os
import re

import pytest

from benchmark.harness import layer, spec
from test_benchmark_control import _control, _failing
from test_benchmark_rehearsal import _run

CELL = "fastsync16.behind1500-fastforward"
NEW_METRICS = ("landing_ms_per_fast_forward.catchup",
               "reset_us_per_frame_event.catchup",
               "anchor_check_ms_per_fast_forward.catchup",
               "window_rebuilds_per_fast_forward.catchup")
ROWS_AT_ZERO = ("blocks_differing_from_oracle",
                "state_hashes_differing_from_oracle",
                "landing_block_differing_from_oracle",
                "oracle_events_the_first_pass_missed",
                "tail_events_not_stored", "fast_forwards_not_landed",
                "forged_anchors_accepted", "events_not_ordered",
                "device_path_left_in_window")


@pytest.fixture(scope="module")
def cell():
    return spec.resolve_cell(spec.load_benchmark(), CELL)


def test_the_cell_resolves_to_its_files_by_name(cell):
    bench = spec.load_benchmark()
    assert cell.chips == 1 and cell.config["driver"] == "fastsync-ingest"
    assert spec.driver_files(spec.ROOT, bench["paths"])["fastsync-ingest"] == (
        os.path.join(spec.ROOT, "tests/benchmark_tests/drivers",
                     "fastsync-ingest.py"))
    assert {m["name"] for m in cell.end_to_end} == {
        "catchup_events_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS) <= set(names) and len(names) == 23
    assert all(n.endswith(".catchup") for n in names)
    # everything catch-up reports, this cell reports too
    old = spec.resolve_cell(bench, "catchup16.backlog8k")
    assert {m["name"] for m in old.per_layer} == set(names) - set(NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "catchup_events_per_s"
    entry = {c["name"]: c for c in bench["configs"]}["fastsync16"]
    conf = cell.config
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert entry["file"] == "benchmark/configs/fastsync16.json"
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "history", "peers_answering", "transport"]
    assert (conf["validators"], conf["sync_limit"], conf["cache_size"],
            conf["tx_bytes"], conf["enable_fast_sync"],
            conf["architecture"]) == (16, 1000, 10000, 100, True, None)
    assert len(conf["guarantees"]) == 5 and len(conf["assumed"]) == 3
    traffic = cell.traffic
    assert (traffic["phase"], traffic["history_events"],
            traffic["sync_events"], traffic["warm_passes_max"],
            traffic["poll_at_event"]) == ("fastforward", 1500, 1000, 5, None)
    # over SyncLimit, upstream's trigger; the tails together hold more
    # signatures than the process-wide verdict cache
    assert traffic["history_events"] > conf["sync_limit"]
    assert traffic["distinct_streams"] * traffic["tail_events"] > 32768
    assert (traffic["distinct_streams"] - 1) * traffic["tail_events"] <= 32768


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsal_prints_the_contract_line(cell, trace):
    line, out = _run(CELL, trace=trace)
    compared = line["compared"]
    for name in ROWS_AT_ZERO:
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}, name
    assert compared["blocks_committed_after_landing"]["value"] >= 10
    assert compared["device_sweeps_in_window"]["value"] >= 1
    assert compared["distinct_pass_outcomes"]["value"] == 1
    # the log says what a landing met
    assert "2 streams of 600 events from 3 creators" in out
    assert "with 3 anchor signatures" in out and "frame events in" in out
    assert "tail events carrying" in out
    assert out.count("forged anchor (") == 2 and out.count("): refused") == 2
    # back-to-back: most of the window is inside passes (the set-up's heap
    # is set aside, so the collection between two passes is short)
    took, window = re.search(
        r"passes took ([0-9.]+) of the window's ([0-9.]+) s", out).groups()
    assert float(took) > 0.6 * float(window)
    if trace:
        got = line["metrics"]
        assert set(got) <= {m["name"] for m in cell.per_layer}
        # the four the deployment brought, from the rehearsal's own spans
        for name in NEW_METRICS:
            assert got[name]["value"] > 0, name
        assert got["window_rebuilds_per_fast_forward.catchup"]["value"] >= 1
        # the tail opens the spans catch-up's own metrics read
        for name in ("verify_us_per_event.catchup",
                     "sync_untimed_pct.catchup", "ingest_cpu_pct.catchup"):
            assert got[name]["value"] > 0, name
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


def test_the_altered_sweep_comes_out_not_correct():
    line, err = _control("altered-sweep", CELL, 2, spec.ROOT)
    assert line["correct"] is False
    failing = _failing(line)
    assert "oracle_events_the_first_pass_missed" in failing, line["compared"]
    assert "events_not_ordered" in failing
    # the landing itself is sound under this fault
    assert not failing & {"forged_anchors_accepted",
                          "fast_forwards_not_landed",
                          "landing_block_differing_from_oracle"}
    assert err.strip().splitlines()[-1] == "correct: false"


def test_a_forged_anchor_the_program_accepts_comes_out_not_correct(
        monkeypatch, capsys):
    """The landing's own negative, planted from outside as ``control.py``
    plants its faults: a program whose ``check_block`` lets every block
    through lands on the anchor with a third of the signatures."""
    from babble_tpu.hashgraph.hashgraph import Hashgraph
    from benchmark import run

    monkeypatch.setattr(Hashgraph, "check_block",
                        lambda self, block, peer_set: None)
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "1", "--trace", "0", "--rehearsal"])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["forged_anchors_accepted"]["value"] >= 1
    assert _failing(line) == {"forged_anchors_accepted"}
    assert "forged anchor (signatures cut to a third): ACCEPTED" in out
    # the Frame of another round is caught by the frame hash all the same
    assert "forged anchor (the frame of another round): refused" in out
    assert err.strip().splitlines()[-1] == "correct: false"


COUNTERS = {
    "fast_forwards": 200.0, "fast_forward_failures": 0.0,
    "frame_events_inserted": 53800.0, "anchor_signatures_checked": 3000.0,
    "accel_rebuilds": 400.0, "accel_sweeps": 400.0,
    "sync_stage_seconds.fast_forward.sum": 5.0,
    "sync_stage_seconds.fast_forward.count": 200.0,
    "sync_stage_seconds.ff_reset.sum": 1.345,
    "sync_stage_seconds.ff_reset.count": 200.0,
    "sync_stage_seconds.ff_check.sum": 2.2,
    "sync_stage_seconds.ff_check.count": 200.0,
    "sync_stage_seconds.insert.sum": 6.0,
    "sync_stage_seconds.insert.count": 88000.0,
}


@pytest.mark.parametrize("name,want", zip(NEW_METRICS,
                                          (25.0, 25.0, 11.0, 2.0)))
def test_the_landing_metrics_on_hand_made_counters(cell, name, want):
    ctx = {"counters": COUNTERS, "samples": {}, "trace": None}
    assert layer.evaluate(cell.definitions[name], ctx) == pytest.approx(want)
    # a program without the spans and counters (the parent), or a validator
    # that never fast-forwards: nothing, never a 0
    older = {"counters": {k: v for k, v in COUNTERS.items()
                          if "ff_" not in k and "fast_forward" not in k
                          and "frame_events" not in k and "anchor_" not in k},
             "samples": {}, "trace": None}
    assert older["counters"]["accel_rebuilds"] == 400.0
    assert layer.evaluate(cell.definitions[name], older) is None


def test_what_the_cell_added_under_paths_by_kind():
    """Code this cell added under ``paths`` (a PR that claims a gain in a
    new cell may add data only, so the next one knows); the rest is data."""
    bench = spec.load_benchmark()
    found = []
    for p in bench["paths"]:
        for d, _dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            found += [os.path.relpath(os.path.join(d, f), spec.ROOT)
                      for f in files
                      if "fastsync" in f or "fastforward" in f
                      or "fast_forward" in f or "frame_event.c" in f]
    assert sorted(f for f in found if f.endswith(".py")) == [
        "benchmark/harness/fastsync.py",
        "tests/benchmark_tests/drivers/fastsync-ingest.py",
        "tests/benchmark_tests/test_benchmark_fastsync.py"]
    assert sorted(f for f in found if f.endswith(".json")) == [
        "benchmark/configs/fastsync16.json",
        "benchmark/layer_metrics/anchor_check_ms_per_fast_forward.catchup.json",
        "benchmark/layer_metrics/landing_ms_per_fast_forward.catchup.json",
        "benchmark/layer_metrics/reset_us_per_frame_event.catchup.json",
        "benchmark/layer_metrics/window_rebuilds_per_fast_forward.catchup.json",
        "benchmark/traffic/behind1500-fastforward.json"]
