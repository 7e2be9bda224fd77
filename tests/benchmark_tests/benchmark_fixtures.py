"""Fixtures shared by the benchmark's own tests, imported by name (a second
``conftest.py`` under tests/ would shadow the one other suites import from).
CPU only; nothing here describes or touches a TPU at import time."""

import copy
import json
import os
import shutil

import pytest

from benchmark.harness import spec


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


# The entries a later PR would add for the two ring16 cells whose files are
# already in the tree (configs/ring16.json, traffic/firehose.json and
# paced.json, layer_metrics/*.firehose.json and *.paced.json): PR 23 left the
# cells out of BENCHMARK.json because a fifth of their chip runs lost a
# validator (PERF.md, Open questions rows 1-2).
RING16_ENTRIES = {
 "configs": [
  {
   "name": "ring16",
   "source": "sikoba/babble src/config/config.go:39-48 defaults (heartbeat 10ms/1s, SyncLimit 1000, CacheSize 10000, SuspendLimit 100); demo/scripts/bombard.sh load shape; BASELINE.json config 3 (16 nodes)",
   "file": "benchmark/configs/ring16.json",
   "reduced": [
    "chips",
    "network"
   ],
   "why": "the flagship: 16 validators with --accelerator sharing one process and one chip through the sweep batcher"
  }
 ],
 "workloads": [
  {
   "name": "ring16.firehose",
   "config": "ring16",
   "traffic": "firehose",
   "chips": 1,
   "why": "closed loop, 2,000 outstanding, 16 tx per 3 ms cycle: saturation, where windows are largest, the batcher coalesces and the device engages in steady state"
  },
  {
   "name": "ring16.paced",
   "config": "ring16",
   "traffic": "paced",
   "chips": 1,
   "why": "open loop, constant spacing at 0.8 of the highest rate the ring sustained: commit latency below capacity, what a client of a healthy ring feels; at saturation it only measures the queue"
  }
 ],
 "end_to_end": [
  {
   "name": "committed_tx_per_s",
   "unit": "tx/s",
   "better": "higher",
   "bound": 0.1,
   "source": "host_clock",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "commit_p50_ms",
   "unit": "ms",
   "better": "lower",
   "bound": 0.1,
   "source": "host_clock",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "commit_p95_ms",
   "unit": "ms",
   "better": "lower",
   "bound": 0.1,
   "source": "host_clock",
   "workloads": [
    "ring16.paced"
   ]
  }
 ],
 "per_layer": [
  {
   "name": "verify_us_per_event.firehose",
   "unit": "us/event",
   "better": "lower",
   "source": "program_span",
   "layer": "wire / decode / batch verify",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "insert_us_per_event.firehose",
   "unit": "us/event",
   "better": "lower",
   "source": "program_span",
   "layer": "insert + DivideRounds",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "device_flush_pct.firehose",
   "unit": "%",
   "better": "higher",
   "source": "program_counter",
   "layer": "flush gate",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "compile_waits.firehose",
   "unit": "count",
   "better": "lower",
   "source": "program_counter",
   "layer": "flush gate",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "snapshot_ms_per_sweep.firehose",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "snapshot",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "sweep_wait_ms.firehose",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "dispatch",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "windows_per_wave.firehose",
   "unit": "windows/wave",
   "better": "higher",
   "source": "program_counter",
   "layer": "dispatch",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "sweep_device_us.firehose",
   "unit": "us/sweep",
   "better": "lower",
   "source": "device_trace",
   "layer": "kernel",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "apply_ms_per_sweep.firehose",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "apply + commit",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "blocks_in_window.firehose",
   "unit": "count",
   "better": "higher",
   "source": "host_clock",
   "layer": "apply + commit",
   "moves": "committed_tx_per_s",
   "workloads": [
    "ring16.firehose"
   ]
  },
  {
   "name": "device_flush_pct.paced",
   "unit": "%",
   "better": "higher",
   "source": "program_counter",
   "layer": "flush gate",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "compile_waits.paced",
   "unit": "count",
   "better": "lower",
   "source": "program_counter",
   "layer": "flush gate",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "snapshot_ms_per_sweep.paced",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "snapshot",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "sweep_wait_ms.paced",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "dispatch",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "windows_per_wave.paced",
   "unit": "windows/wave",
   "better": "higher",
   "source": "program_counter",
   "layer": "dispatch",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "sweep_device_us.paced",
   "unit": "us/sweep",
   "better": "lower",
   "source": "device_trace",
   "layer": "kernel",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "apply_ms_per_sweep.paced",
   "unit": "ms/sweep",
   "better": "lower",
   "source": "program_span",
   "layer": "apply + commit",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "blocks_in_window.paced",
   "unit": "count",
   "better": "higher",
   "source": "host_clock",
   "layer": "apply + commit",
   "moves": "commit_p50_ms",
   "workloads": [
    "ring16.paced"
   ]
  },
  {
   "name": "generator_late_ms.paced",
   "unit": "ms",
   "better": "lower",
   "source": "host_clock",
   "layer": "load generator",
   "moves": "commit_p95_ms",
   "workloads": [
    "ring16.paced"
   ]
  }
 ]
}


@pytest.fixture()
def grown_root(tmp_path, bench):
    """A checkout that a later PR grew: the ring16 cells by entries alone
    (their files are there), and a cell ``ring4.trickle`` by three new
    files and three new entries. Nothing that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    grown = copy.deepcopy(bench)
    for key, entries in RING16_ENTRIES.items():
        grown[key] = grown[key] + copy.deepcopy(entries)
    (root / "benchmark/configs/ring4.json").write_text(json.dumps({
        "name": "ring4", "driver": "live-ring", "validators": 4,
        "tx_bytes": 100, "source": "upstream 4-node docker demo",
        "reduced": {}, "guarantees": ["as ring16"],
        "rehearsal": {"accel_min_window": 16},
    }))
    (root / "benchmark/traffic/trickle.json").write_text(json.dumps({
        "loop": "open", "rate_tx_per_s": 200.0, "pump_interval_s": 0.002,
        "ramp_quiet_s": 1.0, "ramp_max_s": 6.0, "drain_max_s": 15.0,
    }))
    (root / "benchmark/layer_metrics/blocks_in_window.trickle.json"
     ).write_text(json.dumps(
         {"kind": "counter_ratio", "num": ["harness.blocks_v0"]}))
    grown["configs"].append({
        "name": "ring4", "source": "upstream 4-node docker demo",
        "file": "benchmark/configs/ring4.json", "reduced": [], "why": "x"})
    grown["workloads"].append({
        "name": "ring4.trickle", "config": "ring4", "traffic": "trickle",
        "chips": 1, "why": "x"})
    grown["per_layer"].append({
        "name": "blocks_in_window.trickle", "unit": "count",
        "better": "higher", "source": "host_clock",
        "layer": "apply + commit", "moves": "commit_p50_ms",
        "workloads": ["ring4.trickle"]})
    for m in grown["end_to_end"]:
        if m["name"] in ("commit_p50_ms", "commit_p95_ms"):
            m["workloads"] = m["workloads"] + ["ring4.trickle"]
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return str(root), grown
