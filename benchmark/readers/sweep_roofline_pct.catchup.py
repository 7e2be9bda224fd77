"""``sweep_roofline_pct.catchup``: the share of its roofline the fused sweep
reached, over every execution in the traced window.

    100 * sum over buckets of launches * least seconds(bucket)
        / device seconds of the programs named ``counting_sweep``

The program counts each launch under the shape bucket that RAN
(``batch_bucket_launches.<BxWxExPxSxR>`` by the sweep batcher,
``accel_bucket_launches.<...>`` by an engine launching its own); the least
time a bucket could take is ``peaks.sweep_least_seconds``; the device
seconds come from the trace's ``XLA Modules`` line. None without a trace,
without a matching program in it, or without a counted launch (a program
that predates the counters) — never a CPU number.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmark.harness import peaks

PROGRAM = re.compile("counting_sweep")
COUNTERS = ("batch_bucket_launches.", "accel_bucket_launches.")


def read(ctx: dict) -> Optional[float]:
    trace = ctx.get("trace")
    if trace is None:
        return None
    executions, seconds = trace.program_time(PROGRAM)
    if executions == 0 or seconds <= 0:
        return None
    launches, least = 0, 0.0
    for name, n in ctx["counters"].items():
        if n <= 0 or not name.startswith(COUNTERS):
            continue
        B, W, E, P, S, R = (int(d) for d in name.split(".", 1)[1].split("x"))
        t, _bound = peaks.sweep_least_seconds(
            ctx["device_kind"], W, E, P, S, R, B)
        launches += int(n)
        least += n * t
    if launches == 0:
        return None
    if launches != executions and "log" in ctx:
        ctx["log"](f"sweep_roofline_pct: {launches} launches counted, "
                   f"{executions} executions in the trace")
    return 100.0 * least / seconds
