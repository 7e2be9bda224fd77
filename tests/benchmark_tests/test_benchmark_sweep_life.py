"""The per-layer metrics that read the program's span tree, the batcher's
ticket stamps and its bucket counter (PR 24): each metric file and the one
reader on a hand-made context, the roofline reader against a share worked
out by hand and against the trace recorded on a v5e, and the catch-up
rehearsal printing them. CPU only; a rehearsal proves names and control
flow, never a number."""

import gzip
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import layer, peaks, spec, trace
from benchmark_fixtures import bench  # noqa: F401  (fixture)

CELL = "catchup16.backlog8k"
RECORDED = os.path.join(spec.ROOT, "benchmark", "testdata",
                        "sweeps_v5e.xplane.pb.gz")
NEW = (
    "commit_us_per_event.catchup", "sync_untimed_pct.catchup",
    "ingest_cpu_pct.catchup", "sweep_queue_ms.catchup",
    "sweep_launch_ms.catchup", "sweep_launch_cpu_pct.catchup",
    "sweep_read_ms.catchup", "sweep_wake_ms.catchup",
    "sweep_result_idle_ms.catchup", "sweep_roofline_pct.catchup",
)
V5E = "TPU v5 lite"
SMALL = (16, 32, 16, 1, 8)  # the smallest prewarmed bucket at 16 validators
BIG = (1024, 2048, 16, 1, 64)

# one pass as the program reports it: 8,000 inserts, 10 sweeps
COUNTERS = {
    "sync_stage_seconds.insert.count": 8000.0,
    "sync_stage_seconds.commit.sum": 0.4,
    "sync_stage_seconds.sync.sum": 2.0,
    "sync_stage_seconds.prepare_sync.sum": 0.5,
    "sync_stage_self_seconds.sync.sum": 0.2,
    "sync_stage_self_seconds.prepare_sync.sum": 0.05,
    "sync_stage_cpu_seconds.sync.sum": 1.5,
    "batch_windows": 11.0,  # one of them failed: not in the sums
    "batch_stage_windows": 10.0,
    "batch_stage_ms.queue": 60.0,
    "batch_stage_ms.launch": 800.0,
    "batch_stage_ms.read": 300.0,
    "batch_stage_cpu_ms.launch": 40.0,
    "accel_sweeps": 10.0,
    "accel_stage_seconds.wake.sum": 0.02,
    "accel_stage_seconds.wake.count": 10.0,
    "accel_stage_seconds.result_idle.sum": 1.5,
    "accel_stage_seconds.result_idle.count": 10.0,
}
# what the parent commit reports of these: its denominators are there, the
# new numerators are not — no metric but the commit span's may print
PARENT = {k: v for k, v in COUNTERS.items() if k in (
    "sync_stage_seconds.insert.count", "sync_stage_seconds.commit.sum",
    "batch_windows", "accel_sweeps")}
WANT = {
    "commit_us_per_event.catchup": 50.0,
    "sync_untimed_pct.catchup": 10.0,
    "ingest_cpu_pct.catchup": 75.0,
    "sweep_queue_ms.catchup": 6.0,
    "sweep_launch_ms.catchup": 80.0,
    "sweep_launch_cpu_pct.catchup": 5.0,
    "sweep_read_ms.catchup": 30.0,
    "sweep_wake_ms.catchup": 2.0,
    "sweep_result_idle_ms.catchup": 150.0,
}


@pytest.fixture(scope="module")
def cell(bench):  # noqa: F811
    return spec.resolve_cell(bench, CELL)


def _label(key, batch=1):
    return "x".join(str(d) for d in (batch,) + tuple(key))


def _summary(programs):
    return trace.TraceSummary(window_s=1.0, chips=1, programs=programs)


def test_the_cell_reports_the_ten_new_metrics_beside_the_accepted_eight(
        bench, cell):  # noqa: F811
    names = [m["name"] for m in cell.per_layer]
    assert names[-10:] == list(NEW) and len(names) == 18
    layers = {m["layer"] for m in bench["per_layer"][:8]}
    for m in bench["per_layer"][8:]:
        assert m["layer"] in layers  # no layer is named anew
        assert m["moves"] == "catchup_events_per_s"
        assert m["workloads"] == [CELL]
    assert cell.definitions[NEW[-1]]["kind"] == "reader"
    assert all(cell.definitions[n]["kind"] != "reader" for n in NEW[:-1])


@pytest.mark.parametrize("name", NEW[:-1])
def test_metric_file_on_a_synthetic_context(cell, name):
    d = cell.definitions[name]
    ctx = {"counters": COUNTERS, "samples": {}, "trace": None}
    assert layer.evaluate(d, ctx) == pytest.approx(WANT[name])
    # a program that lacks the span or counter (the parent commit): the
    # metric is left out of the line, it does not raise or read 0 — but
    # for the `commit` span, which was there and unread
    parent = layer.evaluate(d, {"counters": PARENT, "samples": {},
                                "trace": None})
    assert parent == (50.0 if name.startswith("commit_us") else None)


def test_roofline_reader_against_a_share_worked_out_by_hand(cell):
    d = cell.definitions["sweep_roofline_pct.catchup"]
    t_small, _ = peaks.sweep_least_seconds(V5E, *SMALL)
    t_big, bound = peaks.sweep_least_seconds(V5E, *BIG)
    t_wave, _ = peaks.sweep_least_seconds(V5E, *SMALL, B=16)
    assert bound == "compute" and t_wave == pytest.approx(16 * t_small)
    counters = {
        "batch_bucket_launches." + _label(SMALL): 3.0,
        "batch_bucket_launches." + _label(SMALL, 16): 1.0,
        "accel_bucket_launches." + _label(BIG): 2.0,
        "batch_bucket_launches." + _label((64, 256, 16, 1, 16)): 0.0,
        "batch_windows": 9.0,  # not a bucket
    }
    least = 3 * t_small + t_wave + 2 * t_big
    device_s = 4 * least  # the device took four times its least
    programs = {"jit_counting_sweep_single": [5, 0.75 * device_s],
                "jit_counting_sweep_batched": [1, 0.25 * device_s],
                "jit__resident_core": [7, 1.0]}  # another program
    said = []
    ctx = {"counters": counters, "trace": _summary(programs),
           "device_kind": V5E, "log": said.append}
    assert layer.evaluate(d, ctx) == pytest.approx(25.0)
    assert said == []  # 6 launches, 6 executions
    programs["jit_counting_sweep_single"][0] = 4
    assert layer.evaluate(d, ctx) == pytest.approx(25.0)
    assert "6 launches counted, 5 executions" in said[0]


def test_roofline_reader_finds_nothing_without_a_trace_or_launches(cell):
    d = cell.definitions["sweep_roofline_pct.catchup"]
    launches = {"batch_bucket_launches." + _label(SMALL): 4.0}
    sweeps = _summary({"jit_counting_sweep_single": [4, 1e-3]})
    for ctx in (
        {"counters": launches, "trace": None},
        {"counters": {}, "trace": sweeps},  # the parent: no counter
        {"counters": launches, "trace": _summary({})},  # a CPU trace
        {"counters": launches,
         "trace": _summary({"jit__resident_core": [4, 1e-3]})},
    ):
        assert layer.evaluate(d, dict(ctx, device_kind=V5E)) is None
    # a device that is not in the table is an error, never a default
    with pytest.raises(KeyError, match="no published peak"):
        layer.evaluate(d, {"counters": launches, "trace": sweeps,
                           "device_kind": "TPU v9 imaginary"})


def test_roofline_on_the_recorded_trace_stays_under_100(cell):
    """The recorded sweeps ran at (64,256,16,1,16) and (128,1024,16,1,32).
    Counted at their own buckets the share is what the chip reached;
    counted — every one — at the SMALLEST prewarmed bucket it can only
    be lower, and either way it cannot pass 100."""
    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        summary = trace.reduce(
            ProfileData.from_serialized_xspace(f.read()), window_s=0.05)
    executions, _s = summary.program_time(re.compile("counting_sweep"))
    assert executions == 6
    d = cell.definitions["sweep_roofline_pct.catchup"]

    def share(counters):
        return layer.evaluate(d, {"counters": counters, "trace": summary,
                                  "device_kind": V5E})

    own = share({
        "batch_bucket_launches." + _label((64, 256, 16, 1, 16)): 3.0,
        "batch_bucket_launches." + _label((128, 1024, 16, 1, 32)): 3.0})
    smallest = share({"batch_bucket_launches." + _label(SMALL): 6.0})
    assert 0 < smallest < own < 100


def test_catchup_rehearsal_prints_the_new_names():
    """The cell end to end on host XLA, taking the path a chip takes
    (pipelined sweeps through the batcher). Nine of the ten print; the
    roofline has no device program to read in a CPU trace and is left
    out, as every device metric is. ``correct`` is not this test's: on a
    loaded host a pipelined pass can grow a window into a bucket that is
    still compiling and leave its tail undecided (PERF.md section 7),
    and the sync-mode rehearsals beside this one hold the cell to it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BABBLE_ACCEL_PIPELINE="1",
               BABBLE_ACCEL_BATCH="1")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=240,
        preexec_fn=lambda: os.nice(10),
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0
    metrics = line["metrics"]
    assert set(NEW[:-1]) <= set(metrics), sorted(metrics)
    assert "sweep_roofline_pct.catchup" not in metrics
    assert "sweep_device_us.catchup" not in metrics
    v = {n: metrics[n]["value"] for n in NEW[:-1]}
    assert 0 <= v["sync_untimed_pct.catchup"] < 100
    assert 0 < v["ingest_cpu_pct.catchup"] <= 105
    assert 0 <= v["sweep_launch_cpu_pct.catchup"] <= 105
    # the identity a sweep's stamps satisfy, as the metrics read them:
    # queue + launch + read + wake runs from submit to the reader's wake,
    # the owner's wait (sweep_wait_ms) from the reader's start to it
    life = (v["sweep_queue_ms.catchup"] + v["sweep_launch_ms.catchup"]
            + v["sweep_read_ms.catchup"] + v["sweep_wake_ms.catchup"])
    wait = metrics["sweep_wait_ms.catchup"]["value"]
    assert life >= 0.9 * wait > 0
    assert v["sweep_queue_ms.catchup"] >= 4.0  # the coalesce interval
