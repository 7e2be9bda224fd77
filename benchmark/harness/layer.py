"""Per-layer metrics: a small fixed vocabulary of sources, plus readers.

A metric's file ``layer_metrics/<name>.json`` names one source ``kind`` and
its operands. What a run gathered is handed over as a context dict:

- ``counters``: the window's flat counter differences (``counters.py``);
- ``samples``: named lists of raw samples the harness recorded;
- ``trace``: the reduced device trace (``trace.TraceSummary``) or None;
- ``cell``, ``device_kind``: the resolved cell and ``jax``'s device kind.

A source that finds nothing to read gives None and the metric is left out
of the line — never a 0 that would read as a measurement.

kinds:

- ``counter_ratio``: ``scale * sum(num) / sum(den)``; without ``den`` the
  plain ``scale * sum(num)``. ``num``/``den`` are lists of counter names.
- ``histogram_sum_per_count``: ``scale * sum(<h>.<label>.sum for label in
  sum_of) / sum(<h>.<label>.count for label in count_of)`` over the labelled
  histogram ``histogram`` — sums and counts only, never its bucket
  quantiles.
- ``harness_samples``: ``scale * percentile(samples[<samples>], <q>)``.
- ``device_op_time``: device time of the programs whose name matches the
  regular expression ``match`` in the trace: ``stat`` is ``mean`` (seconds
  per execution), ``total`` (seconds) or ``count``; times ``scale``.
- ``reader``: ``readers/<name>.py`` defines ``read(ctx) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import re
from typing import Callable, Dict, Optional

from . import stats
from .counters import total


def _counter_ratio(d: dict, ctx: dict) -> Optional[float]:
    c = ctx["counters"]
    num = total(c, d["num"])
    scale = float(d.get("scale", 1.0))
    if "den" not in d:
        return scale * num
    den = total(c, d["den"])
    return scale * num / den if den > 0 else None


def _histogram_sum_per_count(d: dict, ctx: dict) -> Optional[float]:
    c, h = ctx["counters"], d["histogram"]
    num = total(c, (f"{h}.{s}.sum" for s in d["sum_of"]))
    den = total(c, (f"{h}.{s}.count" for s in d["count_of"]))
    return float(d.get("scale", 1.0)) * num / den if den > 0 else None


def _harness_samples(d: dict, ctx: dict) -> Optional[float]:
    v = stats.percentile(ctx["samples"].get(d["samples"], ()), float(d["q"]))
    return None if v is None else float(d.get("scale", 1.0)) * v


def _device_op_time(d: dict, ctx: dict) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None:
        return None
    count, seconds = tr.program_time(re.compile(d["match"]))
    if count == 0:
        return None
    value = {"mean": seconds / count, "total": seconds,
             "count": float(count)}[d.get("stat", "mean")]
    return float(d.get("scale", 1.0)) * value


def _reader(d: dict, ctx: dict) -> Optional[float]:
    spec = importlib.util.spec_from_file_location("_layer_reader", d["path"])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


KINDS: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    "counter_ratio": _counter_ratio,
    "histogram_sum_per_count": _histogram_sum_per_count,
    "harness_samples": _harness_samples,
    "device_op_time": _device_op_time,
    "reader": _reader,
}


def evaluate(definition: dict, ctx: dict) -> Optional[float]:
    kind = definition.get("kind")
    if kind not in KINDS:
        raise ValueError(f"unknown per-layer source kind {kind!r} "
                         f"(known: {sorted(KINDS)})")
    return KINDS[kind](definition, ctx)
