"""The three per-layer metrics that read the collector watcher and the
store's row encoding (``obs/gcwatch.py``, span ``store_encode``): their
entries, their readers on synthetic counters — a program without the
counters (the parent) reads nothing, never 0 — and the traced rehearsals
that print them. CPU only; no existing benchmark file is edited for them.
"""

import pytest

from benchmark.harness import layer, spec
from test_benchmark_rehearsal import _run

STORE = "durable16.backlog8k-store"
RESTART = "durable16.restart8k-bootstrap"
CATCHUP = "catchup16.backlog8k"
ALL = [CATCHUP, "churn16.backlog8k-joinleave", STORE, RESTART,
       "fastsync16.behind1500-fastforward"]
NEW = {
    "gc_us_per_event.catchup": ("collector", "us/event", ALL),
    "store_encode_us_per_event.catchup": ("durable store", "us/event",
                                          [STORE, RESTART]),
    "store_encoded_kb_per_event.catchup": ("durable store", "KB/event",
                                           [STORE]),
}
# a window's counters as the parent's program leaves them: spans and store
# tallies, no watcher, no encode span, no encoded bytes
PARENT = {
    "sync_stage_seconds.insert.count": 8008.0,
    "sync_stage_seconds.insert.sum": 0.5,
    "sync_stage_seconds.store_write.sum": 5.1,
    "store_commits": 32410.0,
    "accel_sweeps": 32.0,
}
CHANGE = dict(PARENT, **{
    "gc_pause_seconds.commit.sum": 0.12,
    "gc_pause_seconds.commit.count": 40.0,
    "gc_pause_seconds.insert.sum": 0.08,
    "gc_pause_seconds.insert.count": 900.0,
    "gc_pause_seconds.none.sum": 0.0002,
    "gc_pause_seconds.none.count": 3.0,
    # the registry's own flattening, the sum again without `.sum`
    "gc_pause_seconds.commit": 0.12,
    "gc_pause_seconds.insert": 0.08,
    "gc_collections_total.0": 900.0,
    "gc_collections_total.2": 43.0,
    "sync_stage_seconds.store_encode.sum": 0.8008,
    "sync_stage_seconds.store_encode.count": 8200.0,
    "store_encoded_bytes": 72072000.0,
    "store_encoded_bytes_by_table.rounds": 56056000.0,
})
WANT = {
    "gc_us_per_event.catchup": 1e6 * 0.2002 / 8008,
    "store_encode_us_per_event.catchup": 100.0,
    "store_encoded_kb_per_event.catchup": 9.0,
}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_a_reader_in_the_cells_it_names(bench, name):
    lay, unit, cells = NEW[name]
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert (entry["layer"], entry["unit"], entry["workloads"]) == (
        lay, unit, cells)
    assert entry["moves"] == "catchup_events_per_s"
    assert entry["better"] == "lower"
    # appended: the accepted entries keep their places
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    for cell_name in ALL:
        cell = spec.resolve_cell(bench, cell_name)
        names = [m["name"] for m in cell.per_layer]
        assert (name in names) == (cell_name in cells)
        if cell_name in cells:
            assert cell.definitions[name]["kind"] == "reader"
            assert cell.definitions[name]["path"].endswith(
                f"benchmark/readers/{name}.py")


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_reader_on_the_parent_s_and_the_change_s_counters(bench, name):
    d = spec.resolve_cell(bench, STORE).definitions[name]

    def ctx(counters):
        return {"counters": counters, "samples": {}, "trace": None}

    assert layer.evaluate(d, ctx(PARENT)) is None
    assert layer.evaluate(d, ctx({})) is None
    assert layer.evaluate(d, ctx(CHANGE)) == pytest.approx(WANT[name])
    # without an insert there is no event to divide by
    no_insert = {k: v for k, v in CHANGE.items() if ".insert.count" not in k}
    assert layer.evaluate(d, ctx(no_insert)) is None


@pytest.mark.parametrize("workload", [STORE, RESTART, CATCHUP])
def test_a_traced_rehearsal_prints_the_new_names(workload):
    line, _out = _run(workload, trace=1)
    got = line["metrics"]
    want = {n for n, (_l, _u, cells) in NEW.items() if workload in cells}
    assert want <= set(got)
    assert all(got[n]["unit"] == NEW[n][1] for n in want)
    assert got["gc_us_per_event.catchup"]["value"] > 0
    if workload == STORE:
        # a round row of 4 validators is a fraction of a 16-ring's
        assert 0.3 < got["store_encoded_kb_per_event.catchup"]["value"] < 20
        assert got["store_encode_us_per_event.catchup"]["value"] > 0
    if workload == RESTART:
        assert got["store_encode_us_per_event.catchup"]["value"] > 0
        assert "store_encoded_kb_per_event.catchup" not in got
