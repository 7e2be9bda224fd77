"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
time of each compiled program and device operation, and the longest idle
gaps with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else. What the reducer
relies on in a trace (checked on the recorded one beside the tests):

- a device is a plane named ``/device:TPU:<n>``; its line ``XLA Modules``
  has one event per execution of a compiled program, named
  ``<jit name>(<fingerprint>)``, and its line ``XLA Ops`` one event per
  operation executed;
- the host is the plane ``/host:CPU``, one line per thread; a
  ``jax.profiler.TraceAnnotation`` of the harness shows there under the name
  it was given (the harness prefixes its own with ``bench:``);
- every event carries ``start_ns`` and ``duration_ns`` on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
# a gap shorter than this is launch spacing, not something the host did
MIN_GAP_NS = 100_000
# gaps attributed one by one; the rest are summed under one label
MAX_GAPS = 300

Interval = Tuple[int, int]  # (start_ns, end_ns)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class TraceSummary:
    window_s: float
    chips: int = 0
    busy_s: float = 0.0  # union of device-op intervals, mean over chips
    # program name (fingerprint stripped) -> [executions, seconds], all chips
    programs: Dict[str, List[float]] = field(default_factory=dict)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def program_time(self, pattern: "re.Pattern") -> Tuple[int, float]:
        """(executions, device seconds) of the programs matching."""
        count, seconds = 0, 0.0
        for name, (n, s) in self.programs.items():
            if pattern.search(name):
                count += int(n)
                seconds += s
        return count, seconds

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.device_ops[:10]],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]],
        }


def _line(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _strip_fingerprint(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


class _HostEvents:
    """Every host event of the trace as arrays, for overlap queries."""

    def __init__(self, events: List[Tuple[str, int, int]]):
        self.names = [n for n, _s, _e in events]
        self.starts = np.array([s for _n, s, _e in events], dtype=np.int64)
        self.ends = np.array([e for _n, _s, e in events], dtype=np.int64)

    def dominant(self, gap: Interval) -> Tuple[Optional[str], int]:
        """The event name whose events cover most of ``gap``, and how many
        ns of it they cover. A nested event never outweighs the one around
        it, so this names the outermost thing the host was in."""
        if not self.names:
            return None, 0
        ov = np.minimum(self.ends, gap[1]) - np.maximum(self.starts, gap[0])
        hit = np.nonzero(ov > 0)[0]
        by_name: Dict[str, int] = {}
        for i in hit:
            by_name[self.names[i]] = by_name.get(self.names[i], 0) + int(ov[i])
        if not by_name:
            return None, 0
        name = max(by_name, key=by_name.get)
        return name, min(by_name[name], gap[1] - gap[0])


def _attribute(gap: Interval, spans: _HostEvents, others: _HostEvents) -> str:
    """What the host was doing in ``gap``: the harness span that covers
    at least half of it, else the traced host activity that covers most of
    it (the threads of a Python process all carry the process's name, so
    "by thread" is by what the thread was in), else nothing traced."""
    s_name, s_ns = spans.dominant(gap)
    if s_name is not None and 2 * s_ns >= gap[1] - gap[0]:
        return "span " + s_name
    o_name, o_ns = others.dominant(gap)
    if o_name is not None and o_ns >= s_ns and 10 * o_ns >= gap[1] - gap[0]:
        return "host " + o_name
    if s_name is not None:
        return "span " + s_name
    return "host: nothing traced (Python)"


def reduce(profile, window_s: float) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    out = TraceSummary(window_s=window_s)
    op_time: Dict[str, float] = {}
    busy_by_chip: List[List[Interval]] = []
    host = None
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            host = plane
        if not DEVICE_PLANE.match(plane.name):
            continue
        mods, ops = _line(plane, MODULES_LINE), _line(plane, OPS_LINE)
        if mods is not None:
            for ev in mods.events:
                rec = out.programs.setdefault(
                    _strip_fingerprint(ev.name), [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns * 1e-9
        busy: List[Interval] = []
        for ev in (ops.events if ops is not None else ()):
            s = int(ev.start_ns)
            busy.append((s, s + int(ev.duration_ns)))
            op_time[ev.name] = op_time.get(ev.name, 0.0) + ev.duration_ns * 1e-9
        if not busy and mods is not None:
            # no per-op line: a program's execution is the busy interval
            busy = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in mods.events]
        if busy:
            busy_by_chip.append(merge(busy))
    out.chips = len(busy_by_chip)
    if busy_by_chip:
        out.busy_s = sum(
            sum(e - s for s, e in b) for b in busy_by_chip
        ) * 1e-9 / len(busy_by_chip)
    out.device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])

    span_events: List[Tuple[str, int, int]] = []
    other_events: List[Tuple[str, int, int]] = []
    if host is not None:
        for ln in host.lines:
            for ev in ln.events:
                s0 = int(ev.start_ns)
                e0 = s0 + int(ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    name = ev.name[len(SPAN_PREFIX):]
                    span_events.append((name, s0, e0))
                elif e0 > s0:
                    other_events.append((ev.name, s0, e0))
    if busy_by_chip:
        # the gaps of the first chip (one chip in every cell so far): the
        # MAX_GAPS longest, summed by what the host was doing in them
        b = busy_by_chip[0]
        gaps = [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)
                if b[i + 1][0] - b[i][1] >= MIN_GAP_NS]
        gaps.sort(key=lambda g: g[0] - g[1])
        spans, others = _HostEvents(span_events), _HostEvents(other_events)
        by_label: Dict[str, float] = {}
        for g in gaps[:MAX_GAPS]:
            label = _attribute(g, spans, others)
            by_label[label] = by_label.get(label, 0.0) + (g[1] - g[0]) * 1e-9
        rest = sum(g[1] - g[0] for g in gaps[MAX_GAPS:]) * 1e-9
        if rest > 0:
            by_label["shorter gaps, not attributed"] = rest
        out.idle_gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
    return out


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_file(path: str, window_s: float) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), window_s)
