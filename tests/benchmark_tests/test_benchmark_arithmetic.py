"""The benchmark's own arithmetic on hand-made samples (CPU only)."""

import math

import pytest

from benchmark.harness import counters, layer, peaks, stats


@pytest.mark.parametrize("samples,q,want", [
    ([], 50, None),
    ([7.0], 95, 7.0),
    ([1, 2, 3, 4], 50, 2),
    ([1, 2, 3, 4, 5], 50, 3),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 100, 100),
    ([3, 1, math.inf, 2], 50, 2),
    ([3, 1, math.inf, 2], 95, math.inf),  # an uncommitted tx is over any limit
])
def test_percentile_is_nearest_rank_on_raw_samples(samples, q, want):
    assert stats.percentile(samples, q) == want


def test_all_commit_steps_counts_a_block_when_its_last_validator_has_it():
    v0 = [(1.0, 10), (2.0, 5), (9.0, 1)]
    v1 = [(1.5, 10), (1.9, 5)]  # has not committed block 2
    assert stats.all_commit_steps([v0, v1]) == [(1.5, 10), (2.0, 15)]
    assert stats.all_commit_steps([]) == []


def test_block_to_block_rate_ignores_where_the_window_edges_fall():
    steps = [(0.5, 100), (2.0, 300), (4.0, 700), (8.0, 900), (11.0, 2000)]
    # commits inside [1, 10]: t=2 (300), t=4 (700), t=8 (900)
    rate, blocks, span = stats.block_to_block_rate(steps, 1.0, 10.0)
    assert (rate, blocks, span) == ((900 - 300) / 6.0, 2, 6.0)
    # the same commits from a window that opens and closes elsewhere
    assert stats.block_to_block_rate(steps, 1.9, 8.1)[0] == rate
    # one commit inside: no rate, never a division by a window length
    assert stats.block_to_block_rate(steps, 3.0, 5.0) is None
    assert stats.block_to_block_rate([], 0.0, 1.0) is None


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.sweep_least_seconds("cpu", 128, 1024, 16, 1, 32)


def test_sweep_ops_and_bytes_follow_the_bucket():
    W, E, P, S, R = 128, 1024, 16, 1, 32
    ops, nbytes = peaks.sweep_ops_bytes(W, E, P, S, R)
    fame = (R - 1) * (2 * W**3 + 12 * W * W)
    rr = (R - 1) * (3 * W * E + 8 * E)
    assert ops == W * E + 3 * W * W * P + fame + 4 * R * W + rr
    assert nbytes == (13 * E + 8 * W * P + 14 * W + S * P + 4 * S + 11 * R
                      + 4 * (W + E))
    # a vmapped execution of B windows does B times the work
    assert peaks.sweep_ops_bytes(W, E, P, S, R, B=16) == (16 * ops, 16 * nbytes)
    seconds, bound = peaks.sweep_least_seconds("TPU v5 lite", W, E, P, S, R)
    assert bound == "compute" and seconds == ops / 393e12


def test_window_counters_sum_validators_and_take_process_wide_once():
    before = [{"accel_sweeps": 1.0, "batch_windows": 10.0},
              {"accel_sweeps": 2.0, "batch_windows": 10.0}]
    after = [{"accel_sweeps": 4.0, "batch_windows": 25.0,
              "accel_stage_ms.apply": 3.0},
             {"accel_sweeps": 7.0, "batch_windows": 25.0}]
    got = counters.window_counters(before, after)
    assert got == {"accel_sweeps": 8.0, "batch_windows": 15.0,
                   "accel_stage_ms.apply": 3.0}


def test_flatten_keeps_numbers_only():
    out = {}
    counters._flatten("", {"a": 1, "b": {"c": 2.5, "d": "x", "e": True},
                           "f": None}, out)
    assert out == {"a": 1.0, "b.c": 2.5}


CTX = {
    "counters": {"accel_sweeps": 4.0, "accel_small_windows": 12.0,
                 "accel_stage_ms.dispatch": 10.0,
                 "accel_stage_ms.readback": 30.0,
                 "sync_stage_seconds.decode.sum": 0.001,
                 "sync_stage_seconds.batch_verify.sum": 0.003,
                 "sync_stage_seconds.insert.count": 40.0},
    "samples": {"generator_late_s": [0.001 * i for i in range(1, 101)]},
    "trace": None,
}


@pytest.mark.parametrize("definition,want", [
    ({"kind": "counter_ratio", "num": ["accel_sweeps"],
      "den": ["accel_sweeps", "accel_small_windows"], "scale": 100.0}, 25.0),
    ({"kind": "counter_ratio", "num": ["accel_stage_ms.dispatch",
                                       "accel_stage_ms.readback"],
      "den": ["accel_sweeps"]}, 10.0),
    ({"kind": "counter_ratio", "num": ["accel_sweeps"]}, 4.0),
    # nothing to divide by: the metric is left out, never a 0
    ({"kind": "counter_ratio", "num": ["accel_sweeps"], "den": ["nope"]}, None),
    ({"kind": "histogram_sum_per_count", "histogram": "sync_stage_seconds",
      "sum_of": ["decode", "batch_verify"], "count_of": ["insert"],
      "scale": 1e6}, 100.0),
    ({"kind": "harness_samples", "samples": "generator_late_s", "q": 95,
      "scale": 1000.0}, 95.0),
    ({"kind": "harness_samples", "samples": "absent", "q": 95}, None),
    ({"kind": "device_op_time", "match": "counting_sweep"}, None),  # no trace
])
def test_layer_vocabulary(definition, want):
    got = layer.evaluate(definition, CTX)
    assert got == (pytest.approx(want) if want is not None else None)


def test_unknown_source_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown per-layer source kind"):
        layer.evaluate({"kind": "guess"}, CTX)
