"""Each cell end to end at a tiny size on host XLA (``--rehearsal``): the
command the driver runs, in a process of its own, whose LAST stdout line has
to parse to the contract's keys with ``platform: cpu``. A rehearsal proves
control flow, never a speed."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec
from benchmark_fixtures import bench, grown_root  # noqa: F401  (fixtures)

RUN = os.path.join(spec.ROOT, "benchmark", "run.py")


def _run(workload, *extra, trace=0, seconds=2, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one host device, as the harness counts them
    # niced: a rehearsal ring must not starve the timing-sensitive suites
    # that other workers run beside it
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "3000000019",
         "--seconds", str(seconds), "--trace", str(trace), "--rehearsal",
         *extra],
        cwd=spec.ROOT, env=env, capture_output=True, text=True,
        timeout=timeout, preexec_fn=lambda: os.nice(10),
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    assert "memory_peak_bytes" in line["device"]
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    return line, proc.stdout


@pytest.mark.parametrize("workload,trace", [
    ("catchup16.backlog8k", 0),
    ("catchup16.backlog8k", 1),
    ("ring16.firehose", 0),  # from the grown BENCHMARK.json: entries alone
    ("ring16.paced", 1),
])
def test_cell_rehearsal_prints_the_contract_line(grown_root, workload, trace):
    root, grown = grown_root
    line, out = _run(workload, "--root", root, trace=trace)
    cell = spec.resolve_cell(grown, workload, root)
    if trace:
        want = {m["name"] for m in cell.per_layer}
        assert set(line["metrics"]) <= want and line["metrics"]
        assert set(line["device"]) >= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert line["metrics"]["setup_s"]["value"] > 0
    assert "os.cpu_count()" in out  # on an earlier line of every run


def test_a_cell_added_as_new_files_runs_without_an_edit(grown_root):
    root, _grown = grown_root
    line, _out = _run("ring4.trickle", "--root", root, trace=1)
    assert set(line["metrics"]) == {"blocks_in_window.trickle"}


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "catchup16.backlog8k", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 tpu device" in proc.stderr


def test_an_unknown_cell_exits_non_zero():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "no.such", "--rehearsal"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
