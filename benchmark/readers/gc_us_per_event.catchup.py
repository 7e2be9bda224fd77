"""``gc_us_per_event.catchup``: the collector's pauses charged to the
validator, per event inserted:

    1e6 * sum over stages of gc_pause_seconds.<stage>.sum
        / sync_stage_seconds.insert.count

``gc_pause_seconds.<stage>`` is the program's (``obs/gcwatch.py``): each
collection's pause, charged once to the node whose span it interrupted and
labelled with that span (``none`` outside any). None without those counters
(a program without the watcher) or without an insert — never a 0.
"""

from __future__ import annotations

from typing import Optional


def read(ctx: dict) -> Optional[float]:
    c = ctx["counters"]
    paused = [v for k, v in c.items()
              if k.startswith("gc_pause_seconds.") and k.endswith(".sum")]
    inserts = c.get("sync_stage_seconds.insert.count", 0.0)
    if not paused or inserts <= 0:
        return None
    return 1e6 * sum(paused) / inserts
