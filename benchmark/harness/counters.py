"""The program's counters as one flat namespace, differenced over a window.

A snapshot is ``Node.get_stats_snapshot()`` plus the node's telemetry
registry (``registry.snapshot()``), flattened to dotted names with numeric
values only: ``accel_sweeps``, ``accel_stage_ms.readback``,
``sync_stage_seconds.insert.sum`` / ``.count``. A window's counters are
end minus start, summed over the validators — except the process-wide
tallies every node repeats (the sweep batcher, the codec and the caches),
which are taken once.
"""

from __future__ import annotations

from typing import Dict, Iterable, List

# counted once per process, reported by every node's snapshot
PROCESS_WIDE = ("batch_", "copro_", "codec_", "wire_cache_", "norm_cache_",
                "verify_cache_")


def _flatten(prefix: str, obj, out: Dict[str, float]) -> None:
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)


def node_snapshot(node) -> Dict[str, float]:
    out: Dict[str, float] = {}
    _flatten("", node.get_stats_snapshot(), out)
    _flatten("", node.telemetry.registry.snapshot(), out)
    return out


def window_counters(
    before: List[Dict[str, float]], after: List[Dict[str, float]]
) -> Dict[str, float]:
    """``after`` minus ``before``, node by node, summed (a key a node first
    reports inside the window counts from 0)."""
    total: Dict[str, float] = {}
    for i, (b, a) in enumerate(zip(before, after)):
        for k, v in a.items():
            if k.startswith(PROCESS_WIDE) and i > 0:
                continue
            total[k] = total.get(k, 0.0) + v - b.get(k, 0.0)
    return total


def total(counters: Dict[str, float], names: Iterable[str]) -> float:
    return sum(counters.get(n, 0.0) for n in names)
