"""``multi_set_sweep_pct.catchup``: the share of sweep launches whose shape
bucket holds two or more validator-set slots (S >= 2) — windows that
straddle a membership change.

    100 * launches at a bucket with S >= 2 / all launches

over the ``batch_bucket_launches.<BxWxExPxSxR>`` and
``accel_bucket_launches.<...>`` counters. None without a counted launch (a
program that predates the counters).
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import churn


def read(ctx: dict) -> Optional[float]:
    multi, launches = churn.multi_set_launches(ctx["counters"])
    if launches == 0:
        return None
    return 100.0 * multi / launches
