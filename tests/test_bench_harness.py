"""The bench harness's own measurement logic — wrong accounting would
silently misreport every round's numbers, so the subtle parts are pinned:

- LatencyState percentile windows (commit-time filtering, the paced
  mode's coordinated-omission guard via min_submit);
- the synthetic gossip stream's determinism and DAG validity;
- the device-description stamp shapes consumed by the capture tooling.
"""

from __future__ import annotations

import os
import sys
import time

# bench.py lives at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_latency_percentiles_filter_on_commit_time():
    st = bench.LatencyState()
    # (submit, commit): one old commit, three in-window
    st.commit_times = [
        (0.0, 5.0),
        (8.0, 10.0),
        (9.0, 11.0),
        (9.5, 12.5),
    ]
    p50, p95, n = st.latency_percentiles(since=9.0)
    # commit >= 9.0 keeps the last three: latencies 2.0, 2.0, 3.0
    assert n == 3
    assert p50 == 2.0
    assert p95 == 3.0


def test_latency_percentiles_min_submit_drops_warmup_stamps():
    st = bench.LatencyState()
    st.commit_times = [
        (1.0, 10.0),  # scheduled during warmup: must be excluded
        (9.0, 10.5),
        (9.5, 11.0),
    ]
    p50, p95, n = st.latency_percentiles(since=10.0, min_submit=9.0)
    assert n == 2
    assert p50 == 1.5


def test_latency_state_parses_lat_stamps():
    st = bench.LatencyState()

    class Block:
        def transactions(self):
            return [b"lat 12.5 7 xxxx", b"not a stamp", b"lat bogus x"]

        def index(self):
            return 0

        def internal_transactions(self):
            return []

    before = time.monotonic()
    st.commit_handler(Block())
    assert len(st.commit_times) == 1
    t0, now = st.commit_times[0]
    assert t0 == 12.5 and now >= before
    # the inner dummy state committed ALL transactions
    assert len(st.committed_txs) == 3


def test_synthetic_stream_is_deterministic_and_valid():
    """Keys are random per call, so hashes differ — but the DAG SHAPE
    (creator sequence + per-creator indexes) must be seed-deterministic,
    and the stream must replay cleanly through a fresh hashgraph."""

    def shape(events):
        # creator ids normalized to first-appearance order, so the shape
        # is independent of the (random) keys and any PeerSet sorting
        first_seen = {}
        out = []
        for e in events:
            c = e.creator()
            if c not in first_seen:
                first_seen[c] = len(first_seen)
            out.append((first_seen[c], e.index()))
        return out

    ev1, peers1 = bench._synthetic_stream(4, 64, seed=9)
    ev2, peers2 = bench._synthetic_stream(4, 64, seed=9)
    assert shape(ev1) == shape(ev2)
    assert len(ev1) == 64
    h = bench._replay_inserts(ev1, peers1)
    assert len(h.undetermined_events) > 0
    assert h.store.last_round() >= 1


def test_model_flops_monotone():
    """The MFU estimator's op model must grow with window size — a
    regression here would silently misreport utilization."""
    small = bench._dag_model_flops(128, 16, 8)
    big = bench._dag_model_flops(512, 16, 8)
    assert big > small > 0


def test_accelerated_capture_without_a_tpu_fails():
    """An accelerated capture never publishes host-XLA numbers: with no
    TPU it fails at device resolution; host-only captures just stamp."""
    import pytest

    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench._resolve_bench_device()
    info = bench._resolve_bench_device(accelerated=False)
    assert info["capture_class"] == "cpu-xla" and info["platform"] == "cpu"
    assert set(info) >= {"platform", "device_kind", "count", "device"}
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.bench_pallas_probe()  # never the interpreter, unasked


def test_peak_flops_table_is_keyed_by_device_kind():
    import pytest

    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no published peak"):
        bench._peak_flops("TPU v99")
