"""Span tracer: follow one sync (or one self-originating gossip round)
through the pipeline, as a TREE of spans.

A ``Tracer`` keeps one stack of open spans per thread. A span records
its name, start, end and parent; when it closes it feeds

- ``sync_stage_seconds{stage}`` — its whole duration (inclusive), and
- ``sync_stage_self_seconds{stage}`` — its duration minus what its
  child spans covered,

so a parent's self time is the work no finer span names. The ``staged``
decorator (hashgraph stages), ``Tracer.span`` (the hand-written spans of
``node/core.py``, the accel stages of ``hashgraph/accel.py``) and
``Tracer.observe`` (a duration measured elsewhere, recorded as a leaf)
all go through this one primitive.

COARSE spans — ``catalog.COARSE_STAGES``, those that open at most a few
times per sync — also read the thread's CPU clock
(``sync_stage_cpu_seconds{stage}``: wall minus CPU is the time the
thread did not run — GIL, sleep, device wait) and enter a
``jax.profiler.TraceAnnotation("babble:<stage>", owner=...)`` when jax
is already imported, which puts the span into the profiler's trace on
the device's clock. Per-event spans (``insert``, ``divide_rounds``) get
neither. A tracer on a simulated clock is given no CPU sink and no
owner, so it reads no real clock at all.

A ``SyncTrace`` is opened by the node around a gossip leg; spans closed
anywhere below it (core decode/verify, hashgraph insert/voting/commit)
attach to the ACTIVE trace through a thread-local, so the deep consensus
code needs no span plumbing. Finishing a trace appends a compact record
to a bounded ring served at ``/telemetry`` (``recent_syncs``): trace id,
peer, total wall time, ordered stage list, and each stage's self time.

Overhead: two clock reads, one small object and a list push/pop per
span — and with ``BABBLE_OBS=0`` the observers are ``None`` and the
instrumented code reads no clock at all.
"""

from __future__ import annotations

import functools
import itertools
import sys
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Tuple

from .catalog import COARSE_STAGES

StageSink = Callable[[str, float], None]
_COARSE = frozenset(COARSE_STAGES)


def annotation(stage: str, owner: Optional[str]):
    """``jax.profiler.TraceAnnotation("babble:<stage>", owner=<owner>)``
    when jax is already imported (a host-only node never imports it for
    a span's sake), else None. Idle — no trace running — it costs well
    under a microsecond."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation("babble:" + stage, owner=owner or "")


def staged(stage: str):
    """Method decorator running one pipeline stage as a span of the
    owning object's ``stage_observer`` (a ``Tracer``). When the observer
    is None (telemetry disabled, or a bare object outside a node) the
    original method runs with no clock reads — only one attribute check."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            obs = self.stage_observer
            if obs is None:
                return fn(self, *args, **kwargs)
            with obs.span(stage):
                return fn(self, *args, **kwargs)

        return wrapper

    return deco


class _NullStage:
    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_STAGE = _NullStage()


class NullTrace:
    """Stand-in when tracing is disabled; safe to call everywhere."""

    __slots__ = ()
    trace_id = 0

    def stage(self, name: str):
        return NULL_STAGE

    def add(self, stage: str, seconds: float, self_seconds: float) -> None:
        pass

    def finish(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_TRACE = NullTrace()


class SyncTrace:
    """One gossip round's span. Not thread-safe by design: a trace is
    owned by the gossip thread that opened it (stages recorded from
    other threads attach to THEIR active trace, or none).

    Stage recordings are AGGREGATED per stage name (first-seen order,
    count + total seconds + self seconds): a 1000-event sync observes
    ``insert`` once per event, and appending raw tuples would balloon
    each ring record to sync_limit entries and every /telemetry response
    to multi-MB."""

    __slots__ = ("trace_id", "kind", "peer_id", "t0", "_agg", "_tracer")

    def __init__(self, tracer: "Tracer", kind: str, peer_id: int):
        # ids and the clock come from the OWNING tracer (not process
        # globals) so two identical simulated runs in one process produce
        # identical trace records (docs/simulation.md determinism).
        self.trace_id = next(tracer._ids)
        self.kind = kind
        self.peer_id = peer_id
        self.t0 = tracer.clock()
        # stage -> [count, total_seconds, self_seconds]; dicts preserve
        # insertion order
        self._agg: dict = {}
        self._tracer = tracer

    def stage(self, name: str):
        return self._tracer.span(name)

    def add(self, stage: str, seconds: float, self_seconds: float) -> None:
        agg = self._agg.get(stage)
        if agg is None:
            self._agg[stage] = [1, seconds, self_seconds]
        else:
            agg[0] += 1
            agg[1] += seconds
            agg[2] += self_seconds

    @property
    def stages(self) -> List[Tuple[str, float]]:
        """(stage, total_seconds) in first-observation order."""
        return [(name, agg[1]) for name, agg in self._agg.items()]

    @property
    def self_stages(self) -> List[Tuple[str, float]]:
        """(stage, self_seconds): each stage's time outside its children."""
        return [(name, agg[2]) for name, agg in self._agg.items()]

    def stage_counts(self) -> List[Tuple[str, int]]:
        return [(name, agg[0]) for name, agg in self._agg.items()]

    def finish(self) -> None:
        self._tracer._finish(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.finish()
        return False


class Span:
    """One open (then closed) span of a ``Tracer``; a context manager.
    After exit ``seconds`` is its duration and ``self_seconds`` that
    minus the durations of the spans opened (or leaves observed) inside
    it on the same thread."""

    __slots__ = ("name", "parent", "t0", "t1", "child_s", "_tracer",
                 "_sink", "_cpu0", "_ann", "_thread")

    def __init__(self, tracer: "Tracer", name: str,
                 sink: Optional[StageSink]):
        self._tracer = tracer
        self.name = name
        self._sink = sink
        self.child_s = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def self_seconds(self) -> float:
        return max(0.0, self.t1 - self.t0 - self.child_s)

    def __enter__(self):
        tr = self._tracer
        th = self._thread = tr._thread()
        stack = th.stack
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self._cpu0 = self._ann = None
        if self.name in _COARSE:
            if tr.owner is not None:
                self._ann = annotation(self.name, tr.owner)
                if self._ann is not None:
                    self._ann.__enter__()
            if tr.cpu_sink is not None:
                self._cpu0 = time.thread_time()
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        self.t1 = tr.clock()
        if self._cpu0 is not None:
            tr.cpu_sink(self.name, time.thread_time() - self._cpu0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._thread.stack
        # LIFO by construction; an abandoned inner span is dropped with us
        while stack and stack.pop() is not self:
            pass
        seconds = self.t1 - self.t0
        if self.parent is not None:
            self.parent.child_s += seconds
        if self._sink is not None:
            self._sink(self.name, seconds)
        else:
            tr._record(self.name, seconds,
                       max(0.0, seconds - self.child_s), self._thread.trace)
        return False


class _Thread:
    """What a tracer keeps per thread: the stack of open spans and the
    active trace."""

    __slots__ = ("stack", "trace")

    def __init__(self):
        self.stack: List[Span] = []
        self.trace: Optional[SyncTrace] = None


class Tracer:
    """Owns the per-thread span stacks and active traces, and the
    recent-trace ring. The sinks are the telemetry callbacks feeding
    ``sync_stage_seconds`` (``stage_sink``, inclusive),
    ``sync_stage_self_seconds`` (``self_sink``) and
    ``sync_stage_cpu_seconds`` (``cpu_sink``, coarse spans only).
    ``owner`` (the validator's moniker) switches on the coarse spans'
    profiler annotations. A tracer on a simulated clock is given neither
    ``cpu_sink`` nor ``owner``."""

    def __init__(self, stage_sink: Optional[StageSink] = None,
                 ring: int = 64, clock=time.perf_counter,
                 self_sink: Optional[StageSink] = None,
                 cpu_sink: Optional[StageSink] = None,
                 owner: Optional[str] = None):
        self._local = threading.local()
        self._ring: Deque[dict] = deque(maxlen=ring)
        self.stage_sink = stage_sink
        self.self_sink = self_sink
        self.cpu_sink = cpu_sink
        self.owner = owner
        # per-tracer id stream + clock: deterministic under the sim
        # engine's virtual time (module-global state would leak between
        # runs in one process)
        self._ids = itertools.count(1)
        self.clock = clock

    # -- lifecycle ----------------------------------------------------------

    def _thread(self) -> _Thread:
        try:
            return self._local.thread
        except AttributeError:
            th = self._local.thread = _Thread()
            return th

    def start(self, kind: str, peer_id: int) -> SyncTrace:
        tr = SyncTrace(self, kind, peer_id)
        self._thread().trace = tr
        return tr

    def active(self) -> Optional[SyncTrace]:
        return self._thread().trace

    def _finish(self, tr: SyncTrace) -> None:
        th = self._thread()
        if th.trace is tr:
            th.trace = None
        self._ring.append(
            {
                "id": tr.trace_id,
                "kind": tr.kind,
                "peer": tr.peer_id,
                "total_ms": round(
                    1e3 * (self.clock() - tr.t0), 3
                ),
                "stages": [
                    [name, round(1e3 * s, 3)] for name, s in tr.stages
                ],
                "self_ms": [
                    [name, round(1e3 * s, 3)] for name, s in tr.self_stages
                ],
            }
        )

    # -- span recording -----------------------------------------------------

    def span(self, stage: str,
             sink: Optional[StageSink] = None) -> Span:
        """A span to enter with ``with``. ``sink`` replaces the sync
        histograms for stages of another family (the accel stages feed
        ``accel_stage_seconds``): such a span still counts as a child of
        the span around it, and a coarse one still feeds the CPU sink."""
        return Span(self, stage, sink)

    def observe(self, stage: str, seconds: float) -> None:
        """Record a duration measured elsewhere as a LEAF span closed
        now: histograms always, the open span around it (if any) counts
        it as a child, the active trace when one is open on this
        thread."""
        th = self._thread()
        if th.stack:
            th.stack[-1].child_s += seconds
        self._record(stage, seconds, seconds, th.trace)

    def _record(self, stage: str, seconds: float, self_seconds: float,
                trace: Optional[SyncTrace]) -> None:
        sink = self.stage_sink
        if sink is not None:
            sink(stage, seconds)
        sink = self.self_sink
        if sink is not None:
            sink(stage, self_seconds)
        if trace is not None:
            trace.add(stage, seconds, self_seconds)

    def recent(self) -> List[dict]:
        return list(self._ring)
