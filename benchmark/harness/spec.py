"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A later PR adds a configuration, a traffic mix or a per-layer metric by
adding files and entries, never by editing a file that is there:

- a cell ``{"name", "config", "traffic"}`` names its configuration entry
  (whose ``file`` is the deployment) and its traffic mix, found as
  ``<path>/traffic/<traffic>.json`` under any directory of ``paths``;
- a metric is reported by the cells its ``BENCHMARK.json`` entry lists under
  ``workloads`` (every cell when the key is absent) — the one place that
  pairing is written; the metric's own file holds no cell list;
- a per-layer metric's definition is ``<path>/layer_metrics/<name>.json``
  (a source kind from the vocabulary of ``layer.py`` and its operands) or,
  for a source the vocabulary does not cover, ``<path>/readers/<name>.py``
  with a ``read(ctx)`` function.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    # per-layer metric name -> its definition ({"kind": ...}) or, for a
    # reader, {"kind": "reader", "path": ...}
    definitions: Dict[str, dict] = field(default_factory=dict)


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
    except OSError as err:
        raise SpecError(f"cannot read {path}: {err}") from None
    except ValueError as err:
        raise SpecError(f"{path} is not JSON: {err}") from None
    if not isinstance(obj, dict):
        raise SpecError(f"{path} does not hold a JSON object")
    return obj


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _find(root: str, paths: List[str], *parts: str) -> Optional[str]:
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.isfile(cand):
            return cand
    return None


def _reported_by(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve_cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(
            f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})"
        )
    w = cells[name]
    paths = list(bench.get("paths", []))
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"cell {name!r} names unknown config {w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic_path = _find(root, paths, "traffic", w["traffic"] + ".json")
    if traffic_path is None:
        raise SpecError(
            f"no traffic/{w['traffic']}.json under any of {paths}"
        )
    cell = Cell(
        name=name,
        chips=int(w.get("chips", 1)),
        config_name=w["config"],
        config=config,
        traffic_name=w["traffic"],
        traffic=_load_json(traffic_path),
        end_to_end=[m for m in bench.get("end_to_end", [])
                    if _reported_by(m, name)],
        per_layer=[m for m in bench.get("per_layer", [])
                   if _reported_by(m, name)],
    )
    for m in cell.per_layer:
        path = _find(root, paths, "layer_metrics", m["name"] + ".json")
        if path is not None:
            cell.definitions[m["name"]] = _load_json(path)
            continue
        path = _find(root, paths, "readers", m["name"] + ".py")
        if path is None:
            raise SpecError(
                f"per-layer metric {m['name']!r} has neither "
                f"layer_metrics/{m['name']}.json nor readers/{m['name']}.py"
            )
        cell.definitions[m["name"]] = {"kind": "reader", "path": path}
    return cell
