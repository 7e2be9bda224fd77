"""``store_encoded_kb_per_event.catchup``: the bytes of round, frame and
block rows a ``--store`` validator serialised and committed, in KB (1,000
bytes) per event inserted:

    store_encoded_bytes / 1000 / sync_stage_seconds.insert.count

``store_encoded_bytes_by_table.<rounds|frames|blocks>`` has the split. None
without the counter (a program that lacks it) or without an insert — never
a 0.
"""

from __future__ import annotations

from typing import Optional


def read(ctx: dict) -> Optional[float]:
    c = ctx["counters"]
    encoded = c.get("store_encoded_bytes")
    inserts = c.get("sync_stage_seconds.insert.count", 0.0)
    if encoded is None or inserts <= 0:
        return None
    return encoded / 1000.0 / inserts
