"""The benchmark's arithmetic: percentiles of raw samples and the rate
between block commits."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of raw samples (``q`` in 0..100): the
    smallest sample with at least q% of the samples at or below it. An
    infinite sample (a transaction that never committed) sorts last, so it
    is over any limit. None when there is no sample."""
    if not samples:
        return None
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def block_to_block_rate(
    steps: Sequence[Tuple[float, int]], t0: float, t1: float
) -> Optional[Tuple[float, int, float]]:
    """Committed work per second between block commits.

    ``steps`` are the block commits as (time the LAST validator committed
    the block, cumulative count through that block), in block order. The
    rate counts what was committed after the first commit inside
    [t0, t1] up to the last one, over the time between those two commits —
    so neither window edge cuts a block in two, and a window that happens
    to open just before or just after a commit reads the same. Returns
    (rate, blocks between the two commits, seconds between them), or None
    with fewer than two commits inside the window."""
    inside = [(t, c) for t, c in steps if t0 <= t <= t1]
    if len(inside) < 2:
        return None
    (ta, ca), (tb, cb) = inside[0], inside[-1]
    if tb <= ta:
        return None
    return (cb - ca) / (tb - ta), len(inside) - 1, tb - ta


def all_commit_steps(
    per_validator: Sequence[Sequence[Tuple[float, int]]],
) -> List[Tuple[float, int]]:
    """From each validator's (commit time, transactions in block) list, in
    block order, the steps of "committed by ALL validators": block b counts
    when its last validator committed it. Blocks are identical across
    validators (``correct`` checks that), so the cumulative count through
    block b is the same everywhere."""
    if not per_validator:
        return []
    common = min(len(v) for v in per_validator)
    steps, cum = [], 0
    for b in range(common):
        cum += per_validator[0][b][1]
        steps.append((max(v[b][0] for v in per_validator), cum))
    return steps
