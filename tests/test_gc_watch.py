"""The collector watcher (``obs/gcwatch.py``): one ``gc.callbacks`` entry per
process charges each collection's pause once, to one node, under the span it
interrupted — without touching any span's times, and without a lock.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time

import pytest

from babble_tpu.common.clock import Clock
from babble_tpu.crypto.keys import PrivateKey
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.node.core import Core
from babble_tpu.node.validator import Validator
from babble_tpu.obs import metrics
from babble_tpu.obs.gcwatch import WATCHER, GcTally
from babble_tpu.obs.trace import Tracer
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet
from benchmark.harness.counters import node_snapshot
from test_durable_catchup import EVENTS, _ingest, _node


def _core(moniker="g0", clock=None):
    key = PrivateKey(0x6C0 + sum(map(ord, moniker)))
    peers = PeerSet([Peer(f"inmem://{moniker}", key.public_key.hex(),
                          moniker)])
    return Core(Validator(key, moniker), peers, peers, InmemStore(100),
                lambda block: None, clock=clock)


def _hooks():
    return sum(cb == WATCHER._hook for cb in gc.callbacks)


def _live():
    """Every live tally the watcher holds, kept alive by the caller."""
    return [t for t in (r() for r in WATCHER._members) if t is not None]


def _collections(tallies):
    out = {}
    for t in list(tallies) + [WATCHER.process]:
        for g, n in t.collections.copy().items():
            out[g] = out.get(g, 0) + n
    return out


class _Watched:
    """A bare tracer's tally on the watcher, for as long as the block runs,
    with automatic collections off: only the block's own ``gc.collect()``
    runs."""

    def __init__(self, prefix="w0"):
        self.tracer = Tracer(self_sink=self._self)
        self.tally = GcTally(self.tracer, (prefix + ":",))
        self.self_s = {}

    def _self(self, stage, seconds):
        self.self_s[stage] = self.self_s.get(stage, 0.0) + seconds

    def __enter__(self):
        WATCHER.add(self.tally)
        gc.disable()
        return self

    def __exit__(self, *exc):
        gc.enable()
        WATCHER.remove(self.tally)
        return False


def test_a_collection_inside_a_span_is_charged_once_under_its_name():
    with _Watched() as w:
        with w.tracer.span("sync"):
            with w.tracer.span("commit") as commit:
                gc.collect()
    assert w.tally.pauses == {"commit": 1}
    assert w.tally.collections == {2: 1}
    paused = w.tally.pause_s["commit"]
    assert paused > 0
    # the pause is labelled, not subtracted: inside the span's own time
    assert commit.child_s == 0.0
    assert commit.self_seconds >= paused
    assert w.self_s["commit"] >= paused
    assert w.self_s["sync"] < commit.seconds
    assert w.tally.pause_seconds() == {"commit": {"sum": paused, "count": 1}}
    assert w.tally.collections_by_generation() == {"2": 1}


def test_the_thread_name_then_the_only_node_take_a_pause_outside_spans():
    held = _live()
    before = _collections(held)
    with _Watched("w7") as w:
        done = threading.Thread(target=gc.collect, name="w7:sweep-reader")
        done.start()
        done.join()
        gc.collect(1)  # MainThread: the tally is the process's only one?
    assert w.tally.pauses.get("none", 0) == (2 if not held else 1)
    assert w.tally.collections.get(2) == 1
    after = _collections(held + [w.tally])
    assert sum(after.values()) - sum(before.values()) == 2


def test_two_telemetries_in_one_process_count_a_collection_once():
    hooks = _hooks()
    a, b = _core("ga"), _core("gb")
    held = _live()
    assert a.obs.gc in held and b.obs.gc in held
    assert _hooks() == 1  # one entry, however many nodes
    before = _collections(held)
    gc.disable()
    try:
        with a.obs.tracer.span("insert"):
            gc.collect()
        gc.collect()  # two live tallies and no span: the process's
    finally:
        gc.enable()
    after = _collections(held)
    assert {g: n - before.get(g, 0) for g, n in after.items() if g != 2} == {
        g: 0 for g in after if g != 2}
    assert after[2] - before.get(2, 0) == 2
    assert a.obs.gc.pauses == {"insert": 1}
    assert b.obs.gc.pauses == {}
    a.obs.close()
    b.obs.close()
    del held
    assert _hooks() == (1 if _live() else 0) <= max(hooks, 1)


def test_the_collections_of_an_ingest_add_up_to_the_collector_s_own():
    gc.collect()
    held = _live()
    node, wires, from_id = _node(InmemStore(10000))
    tallies = held + [node.telemetry.gc]
    snap0 = node_snapshot(node)
    gc.disable()  # no collection between the two readings
    g0 = [s["collections"] for s in gc.get_stats()]
    before = _collections(tallies)
    gc.enable()
    _ingest(node.core, wires, from_id)
    gc.disable()
    g1 = [s["collections"] for s in gc.get_stats()]
    after = _collections(tallies)
    gc.enable()
    snap = node_snapshot(node)
    for g in range(len(g0)):
        assert after.get(g, 0) - before.get(g, 0) == g1[g] - g0[g]
    # most of them were the ingesting node's, under its spans
    mine = sum(snap.get(f"gc_collections_total.{g}", 0)
               - snap0.get(f"gc_collections_total.{g}", 0) for g in (0, 1, 2))
    assert mine > 0 and mine >= (sum(g1) - sum(g0)) // 2
    stages = {k.split(".")[1] for k in snap
              if k.startswith("gc_pause_seconds.") and k.endswith(".sum")}
    spans = stages & {"insert", "divide_rounds", "sync", "prepare_sync",
                      "commit", "decode", "flush"}
    assert spans
    assert node.core.get_consensus_events_count() > EVENTS - 100
    # and the registry reads what the snapshot does
    stage = sorted(spans)[0]
    assert snap[f"gc_pause_seconds.{stage}"] == snap[
        f"gc_pause_seconds.{stage}.sum"] > 0
    node.shutdown()
    assert node.telemetry.gc not in _live()


class _Virtual(Clock):
    def monotonic(self):
        return 0.0

    def time(self):
        return 0.0

    def sleep(self, seconds):
        pass


@pytest.mark.parametrize("how", ["BABBLE_OBS=0", "simulated clock"])
def test_an_unwatched_node_leaves_gc_callbacks_as_it_found_them(how):
    callbacks = list(gc.callbacks)
    if how == "BABBLE_OBS=0":
        metrics.set_enabled(False)
        try:
            core = _core("gq")
        finally:
            metrics.set_enabled(True)
    else:
        core = _core("gs", clock=_Virtual())
    assert gc.callbacks == callbacks
    assert core.obs.gc not in _live()
    gc.collect()
    assert core.obs.gc.collections == {}
    assert core.obs.registry.get("gc_collections_total", generation="2") == 0
    core.obs.close()  # closing what was never watched is harmless
    assert gc.callbacks == callbacks


def test_labelled_observes_under_a_collection_per_allocation_do_not_hang():
    """The callback takes no lock: with a collection at nearly every
    allocation, inside the registry's labelled-children lock too, 10,000
    observes finish."""
    registry = metrics.Registry(enabled=True)
    hist = registry.histogram("h", "h", metrics.STAGE_BUCKETS, ("stage",))
    result = []

    def work():
        for i in range(10_000):
            hist.labels(stage=f"s{i % 97}").observe(1e-4)
            if i % 1000 == 0:
                registry.snapshot()
        result.append(registry.snapshot()["h"]["s0"]["count"])

    tracer = Tracer()
    tally = GcTally(tracer, ("gl:",))
    threshold = gc.get_threshold()
    WATCHER.add(tally)
    before = sum(_collections(_live()).values())
    gc.set_threshold(1)
    try:
        with tracer.span("observe"):
            t = threading.Thread(target=work, daemon=True, name="gl:obs")
            t0 = time.monotonic()
            t.start()
            t.join(timeout=120)
    finally:
        gc.set_threshold(*threshold)
        WATCHER.remove(tally)
    assert not t.is_alive(), "10,000 observes did not finish: a lock?"
    assert result == [104]  # 10,000 / 97, rounded up
    assert time.monotonic() - t0 < 120
    # the worker's are charged by its name (the span is open on the main
    # thread, which only allocates while it starts the worker)
    assert tally.pauses["none"] > 100 * tally.pauses.get("observe", 1)
    assert sum(_collections(_live() + [tally]).values()) - before > 100


def test_collections_of_more_threads_than_cores_add_up_and_stay_apart():
    """Each worker is a node with a span open on its own thread; with a
    collection at nearly every allocation and the switch interval cut, every
    collection is charged once, and only ever to the collecting thread's
    node: under its span, or by the thread's name before it opens."""
    workers = (os.cpu_count() or 4) + 2
    nodes = [_Watched(f"gt{i}") for i in range(workers)]
    held = _live()
    gate = threading.Barrier(workers)

    def work(i):
        tracer = nodes[i].tracer
        gate.wait(timeout=60)
        with tracer.span(f"s{i}"):
            junk = []
            for k in range(3000):
                junk.append([k])  # kept: the young generation grows

    threshold, interval = gc.get_threshold(), sys.getswitchinterval()
    for n in nodes:
        WATCHER.add(n.tally)
    threads = [threading.Thread(target=work, args=(i,), daemon=True,
                                name=f"gt{i}:worker")
               for i in range(workers)]
    try:
        gc.disable()
        g0 = sum(s["collections"] for s in gc.get_stats())
        before = sum(_collections(held + [n.tally for n in nodes]).values())
        gc.enable()
        gc.set_threshold(1)
        sys.setswitchinterval(1e-5)
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        gc.disable()
        g1 = sum(s["collections"] for s in gc.get_stats())
        after = sum(_collections(held + [n.tally for n in nodes]).values())
    finally:
        gc.enable()
        gc.set_threshold(*threshold)
        sys.setswitchinterval(interval)
        for n in nodes:
            WATCHER.remove(n.tally)
    assert not any(t.is_alive() for t in threads)
    assert after - before == g1 - g0 > workers * 100
    # which threads the interpreter lets collect is its own affair; those
    # that did were charged under their own span, or by name outside it
    own = [n.tally.pauses.get(f"s{i}", 0) for i, n in enumerate(nodes)]
    for i, n in enumerate(nodes):
        assert set(n.tally.pauses) <= {f"s{i}", "none"}
    assert sum(own) > workers * 100
