"""``store_encode_us_per_event.catchup``: a ``--store`` validator's time
serialising round, frame and block rows, per event inserted:

    1e6 * sync_stage_seconds.store_encode.sum
        / sync_stage_seconds.insert.count

The span ``store_encode`` (``PersistentStore._write_derived``) covers a
row's ``to_dict()`` and ``canonical_dumps``; in a ``--bootstrap`` replay the
``to_dict()`` alone, built before the write gate. None without the span (a
program that lacks it) or without an insert — never a 0.
"""

from __future__ import annotations

from typing import Optional


def read(ctx: dict) -> Optional[float]:
    c = ctx["counters"]
    encode = c.get("sync_stage_seconds.store_encode.sum")
    inserts = c.get("sync_stage_seconds.insert.count", 0.0)
    if encode is None or inserts <= 0:
        return None
    return 1e6 * encode / inserts
