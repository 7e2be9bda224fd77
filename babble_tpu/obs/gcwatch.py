"""The collector as part of the span tracer: every garbage collection's pause
charged once, to one node, under the span it interrupted.

ONE ``gc.callbacks`` entry per process (``WATCHER``), however many nodes
the process holds. A node's ``NodeTelemetry`` — enabled, on the wall clock
— adds its ``GcTally`` and takes it out at ``close()`` (``Node.shutdown``)
or when it is collected; the entry leaves ``gc.callbacks`` with the last
tally. A simulated clock or ``BABBLE_OBS=0`` adds none, so such a process
never registers the entry.

At a collection's ``start`` the pause is attributed, in this order, to

1. the node whose tracer has a span open on the collecting thread, under
   the name of the innermost (latest-opened) such span: the span the pause
   interrupted;
2. else the node whose moniker prefixes the thread's name
   (``<moniker>:sweep-reader``, ...), under ``none``;
3. else the only node, if the process holds exactly one, under ``none``;
4. else ``WATCHER.process``: a tally no node reports (a process-wide
   thread such as the sweep batcher's, or any thread between two spans
   while several nodes live).

At ``stop`` the pause — ``perf_counter`` at start to ``perf_counter`` at
stop — is added to that tally: ``gc_pause_seconds{stage}`` (seconds and
pauses) and ``gc_collections_total{generation}``. Where the node's tracer
has an owner and jax is imported, ``babble:gc`` (``trace.annotation``) is
open from start to stop, on the device trace's clock beside the other
``babble:`` spans.

The lock rule: the callback runs inside an arbitrary allocation — of any
thread, inside any code, the metrics registry's own included — so it takes
NO lock and calls nothing that does. It reads the tracers' thread-local
stacks and adds to plain dicts that a snapshot copies. A pause is NOT added
to the interrupted span's children: every span's inclusive and self time
read as without the watcher; the label says where the pause hid.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref
from typing import Dict, Optional, Tuple

from .trace import Tracer, annotation

_INF = float("inf")


class GcTally:
    """One node's collector tallies: plain dicts the watcher adds to and a
    snapshot copies (``dict.copy`` allocates no tracked object midway, so a
    collection cannot change a dict while it is being copied)."""

    __slots__ = ("tracer", "prefixes", "pause_s", "pauses", "collections",
                 "__weakref__")

    def __init__(self, tracer: Optional[Tracer] = None,
                 prefixes: Tuple[str, ...] = ()):
        self.tracer = tracer
        self.prefixes = prefixes  # thread-name prefixes of this node
        self.pause_s: Dict[str, float] = {}  # stage -> seconds paused
        self.pauses: Dict[str, int] = {}  # stage -> pauses
        self.collections: Dict[int, int] = {}  # generation -> collections

    def charge(self, stage: str, generation: int, seconds: float) -> None:
        self.pause_s[stage] = self.pause_s.get(stage, 0.0) + seconds
        self.pauses[stage] = self.pauses.get(stage, 0) + 1
        self.collections[generation] = self.collections.get(generation, 0) + 1

    def pause_seconds(self) -> Dict[str, Dict[str, float]]:
        """``{stage: {"sum": seconds, "count": pauses}}``."""
        seconds, pauses = self.pause_s.copy(), self.pauses.copy()
        return {stage: {"sum": s, "count": pauses.get(stage, 0)}
                for stage, s in sorted(seconds.items())}

    def collections_by_generation(self) -> Dict[str, int]:
        return {str(g): n for g, n in sorted(self.collections.copy().items())}


def _thread_name() -> Optional[str]:
    # threading.current_thread() would build (and lock for) a dummy Thread
    # on a thread the threading module never started
    th = threading._active.get(threading.get_ident())
    return None if th is None else th.name


class _Watcher:
    def __init__(self):
        # held by add/remove/_prune only, never by the callback; re-entrant
        # because a tally's weakref callback can run inside a collection
        # that an allocation under this very lock started
        self._lock = threading.RLock()
        self._members: tuple = ()  # weakrefs to the live tallies
        self._pending = None  # (tally, stage, annotation, t0) of a collection
        self._hook = self._on_gc
        self.process = GcTally()

    def add(self, tally: GcTally) -> None:
        self._update(add=tally)

    def remove(self, tally: GcTally) -> None:
        self._update(drop=tally)

    def _prune(self, _ref=None) -> None:
        self._update()

    def _update(self, add: Optional[GcTally] = None,
                drop: Optional[GcTally] = None) -> None:
        with self._lock:
            live = [r for r in self._members
                    if r() is not None and r() is not drop]
            if add is not None:
                live.append(weakref.ref(add, self._prune))
            self._members = tuple(live)
            hooked = self._hook in gc.callbacks
            if live and not hooked:
                gc.callbacks.append(self._hook)
            elif not live and hooked and self._pending is None:
                gc.callbacks.remove(self._hook)

    def _attribute(self) -> Tuple[Optional[GcTally], str]:
        members = self._members
        best, best_t0, stage = None, -_INF, "none"
        for ref in members:
            tally = ref()
            if tally is None:
                continue
            th = getattr(tally.tracer._local, "thread", None)
            if th is not None and th.stack:
                span = th.stack[-1]
                # a span is on the stack a moment before its t0 is stamped
                t0 = getattr(span, "t0", _INF)
                if best is None or t0 > best_t0:
                    best, best_t0, stage = tally, t0, span.name
        if best is not None:
            return best, stage
        name = _thread_name()
        only, live = None, 0
        for ref in members:
            tally = ref()
            if tally is None:
                continue
            if name is not None and name.startswith(tally.prefixes):
                return tally, "none"
            only, live = tally, live + 1
        return (only if live == 1 else None), "none"

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            tally, stage = self._attribute()
            ann = None
            if tally is not None and tally.tracer.owner is not None:
                ann = annotation("gc", tally.tracer.owner)
                if ann is not None:
                    ann.__enter__()
            self._pending = (tally, stage, ann, time.perf_counter())
            return
        t1 = time.perf_counter()
        pending, self._pending = self._pending, None
        if pending is None:  # hooked while this collection ran
            return
        tally, stage, ann, t0 = pending
        (tally or self.process).charge(stage, info["generation"], t1 - t0)
        if ann is not None:
            ann.__exit__(None, None, None)


WATCHER = _Watcher()
