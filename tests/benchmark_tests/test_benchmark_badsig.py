"""The cell ``badsig16.backlog8k-flood`` at a rehearsal's size (4 validators,
v3 the one flooder, 600 events in syncs of 100, pushes of 100 forged
events): the command the driver runs, a fault planted from outside, and the
driver's reading of the two spans the deployment brought. CPU only.

Its driver is ``tests/benchmark_tests/drivers/flood-ingest.py``, beside the
other added drivers (``test_benchmark_churn.py`` says why). Every entry is
looked up BY NAME and no list is counted: a later PR appends its own behind
these (PERF.md, section 7 (d), (f)).

The two spans have no per-layer metric yet: an accepted test pins the last
three ``per_layer`` entries and ``gc_us_per_event.catchup``'s cells, so the
cell appends none and is left off that list (PERF.md, section 7). The driver
logs each span per inserted event in every pass's split instead."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import spec
from test_benchmark_control import _failing
from test_benchmark_rehearsal import RUN

CELL = "badsig16.backlog8k-flood"
# what the two spans would be read as, and the span each reads
NEW_METRICS = ("verify_fallback_us_per_event.catchup",
               "flood_us_per_event.catchup")
STAGE_OF = dict(zip(NEW_METRICS, ("verify_fallback", "eager_sync_in")))
PER_EVENT = re.compile(r"an inserted event: verify_fallback ([0-9.]+) us, "
                       r"eager_sync_in ([0-9.]+) us")
ROWS_AT_ZERO = ("audited_events_evicted", "backlog_events_not_stored",
                "junk_events_stored", "blocks_differing_from_oracle",
                "oracle_events_the_first_pass_missed",
                "reference_verdicts_differing", "flooders_not_quarantined",
                "honest_peers_scored", "sentry_quarantine_deferrals",
                "junk_syncs_unscored", "junk_syncs_decoded_after_quarantine",
                "honest_syncs_refused", "events_not_ordered",
                "device_path_left_in_window")


def _run(trace):
    """``test_benchmark_rehearsal._run`` on four host devices: the cell asks
    for four chips, and the harness counts them in a rehearsal too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "2", "--trace", str(trace), "--rehearsal"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=240,
        preexec_fn=lambda: os.nice(10))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["correct"] is True, proc.stdout[-3000:]
    assert list(line)[-1] == "compared" and not _failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    return line, proc.stdout


@pytest.fixture(scope="module")
def cell():
    return spec.resolve_cell(spec.load_benchmark(), CELL)


def test_the_cell_resolves_to_its_files_by_name(cell):
    bench = spec.load_benchmark()
    # four chips for a host of its own: the flood is serial host work whose
    # runs spread over half the bound on one chip (PERF.md, section 2)
    assert cell.chips == 4 and cell.config["driver"] == "flood-ingest"
    assert spec.driver_files(spec.ROOT, bench["paths"])["flood-ingest"] == (
        os.path.join(spec.ROOT, "tests/benchmark_tests/drivers",
                     "flood-ingest.py"))
    assert {m["name"] for m in cell.end_to_end} == {
        "catchup_events_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # everything catch-up reports, this cell reports too, but the collector's
    # share, whose cells an accepted test pins to the five before this one
    old = spec.resolve_cell(bench, "catchup16.backlog8k")
    assert names == {m["name"] for m in old.per_layer} - {
        "gc_us_per_event.catchup"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert not set(NEW_METRICS) & set(by_name)
    assert CELL not in by_name["gc_us_per_event.catchup"]["workloads"]
    entry = {c["name"]: c for c in bench["configs"]}["badsig16"]
    conf = cell.config
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert entry["file"] == "benchmark/configs/badsig16.json"
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "backlog", "transport"]
    assert (conf["validators"], conf["sync_limit"], conf["cache_size"],
            conf["tx_bytes"]) == (16, 1000, 10000, 100)
    assert conf["byzantine_validators"] == [f"v{i}" for i in range(11, 16)]
    traffic = cell.traffic
    # backlog8k's DAG, five flooders: f = floor(15 / 3), the framing cap
    assert (traffic["backlog_events"], traffic["dag_seed"],
            traffic["sync_events"], traffic["distinct_streams"],
            traffic["flooders"], traffic["junk_syncs_per_sync"],
            traffic["junk_events_per_sync"], traffic["warm_passes_max"]) == (
        8000, 2147487920, 1000, 5, 5, 2, 1000, 5)
    assert traffic["flooders"] == (conf["validators"] - 1) // 3
    catchup = spec.resolve_cell(bench, "catchup16.backlog8k").traffic
    assert all(traffic[k] == catchup[k] for k in (
        "backlog_events", "dag_seed", "sync_events", "distinct_streams"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsal_prints_the_contract_line(cell, trace):
    line, out = _run(trace)
    compared = line["compared"]
    for name in ROWS_AT_ZERO:
        assert compared[name] == {"value": 0, "rule": "<=", "limit": 0}, name
    assert compared["device_sweeps_in_window"]["value"] >= 1
    assert compared["distinct_pass_outcomes"]["value"] == 1
    # the flooder lands five pushes, then sits in quarantine
    assert "flooders ['v3']: 12 pushes a pass of 100 forged events" in out
    assert re.search(r"5 pushes handled in [0-9]+ ms, 7 refused", out)
    assert "0 verdicts differ" in out
    # every pass's split reads both spans per inserted event
    splits = [tuple(map(float, m)) for m in PER_EVENT.findall(out)]
    assert splits and all(0 < fb < flood for fb, flood in splits), splits
    if trace:
        got = line["metrics"]
        assert set(got) <= {m["name"] for m in cell.per_layer}
        for name in ("verify_us_per_event.catchup",
                     "insert_us_per_event.catchup"):
            assert got[name]["value"] > 0, name
        # the handler's stage 1 is part of what decode + batch verify spent
        # in all; the split logged is the typical pass's, the metric the
        # window's, so compare with room
        _fallback, flood = splits[-1]
        assert flood < 1.5 * got["verify_us_per_event.catchup"]["value"]
    else:
        assert set(line["metrics"]) == {"catchup_events_per_s", "setup_s"}


def test_a_verifier_that_passes_everything_comes_out_not_correct(
        monkeypatch, capsys):
    """The flood's own negative, planted from outside as ``control.py``
    plants its faults: a batch verifier that reports every signature valid,
    so that no event is re-checked and the forged ones are inserted."""
    from babble_tpu.crypto import batch
    from benchmark import run

    def passes_everything(events):
        for ev in events:
            ev.prevalidate(True)
        return True

    monkeypatch.setattr(batch, "prevalidate_events_host", passes_everything)
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "1", "--trace", "0", "--rehearsal"])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["junk_events_stored"]["value"] > 0
    assert {"junk_events_stored", "blocks_differing_from_oracle"} <= _failing(
        line)
    assert err.strip().splitlines()[-1] == "correct: false"


COUNTERS = {
    "sync_stage_seconds.insert.sum": 1.2,
    "sync_stage_seconds.insert.count": 8008.0,
    "sync_stage_seconds.verify_fallback.sum": 4.004,
    "sync_stage_seconds.verify_fallback.count": 25.0,
    "sync_stage_seconds.eager_sync_in.sum": 6.006,
    "sync_stage_seconds.eager_sync_in.count": 25.0,
}


@pytest.mark.parametrize("name,want", zip(NEW_METRICS, (500.0, 750.0)))
def test_the_flood_metrics_on_hand_made_counters(cell, name, want):
    bench = spec.load_benchmark()
    driver = spec.load_module(
        spec.driver_files(spec.ROOT, bench["paths"])["flood-ingest"])
    read = driver.span_us_per_event
    assert read(COUNTERS, STAGE_OF[name]) == pytest.approx(want)
    # a program without the spans (the parent): its denominator is there,
    # so the span reads 0, not nothing
    older = {k: v for k, v in COUNTERS.items() if ".insert." in k}
    assert read(older, STAGE_OF[name]) == 0.0
    # and no insert: nothing to divide by
    assert read({}, STAGE_OF[name]) is None


def test_what_the_cell_added_under_paths_by_kind():
    """Code this cell added under ``paths`` (a PR that claims a gain in a
    new cell may add data only, so the next one knows); the rest is data."""
    bench = spec.load_benchmark()
    found = []
    for p in bench["paths"]:
        for d, _dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            found += [os.path.relpath(os.path.join(d, f), spec.ROOT)
                      for f in files if "__pycache__" not in d
                      and ("flood" in f or "badsig" in f
                           or "verify_fallback" in f)]
    assert sorted(f for f in found if f.endswith(".py")) == [
        "benchmark/harness/flood.py",
        "tests/benchmark_tests/drivers/flood-ingest.py",
        "tests/benchmark_tests/test_benchmark_badsig.py"]
    assert sorted(f for f in found if f.endswith(".json")) == [
        "benchmark/configs/badsig16.json",
        "benchmark/traffic/backlog8k-flood.json"]
