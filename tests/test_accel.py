"""Differential tests for the accelerated (device) consensus path.

The same signed event streams are replayed through two Hashgraphs — one
driven by the oracle pipeline per insert, one with TensorConsensus attached
(fame + round-received coming off the device in batched sweeps) — and every
consensus output must be identical: rounds, witnesses, lamport timestamps,
fame, round-received, and committed block bodies byte for byte.

This is the proof VERDICT round-2 item 1 asks for: with --accelerator on,
consensus decisions come off the device in the live insert path and match
the oracle (which itself is pinned to the reference's golden DAGs by
tests/test_hashgraph.py).
"""

from __future__ import annotations

import json

import pytest

from babble_tpu.common.trilean import Trilean
from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.accel import TensorConsensus

from tests.test_hashgraph import (
    BASIC_PLAYS,
    CONSENSUS_PLAYS,
    ROUND_PLAYS,
    _js_bytes,
    init_full,
    init_funky,
    init_sparse,
)

BUILDERS = {
    "basic": lambda: init_full(BASIC_PLAYS, 3),
    "round": lambda: init_full(ROUND_PLAYS, 3),
    "consensus": lambda: init_full(CONSENSUS_PLAYS, 3),
    "funky": lambda: init_funky(False),
    "funky_full": lambda: init_funky(True),
    "sparse": lambda: init_sparse(),
}


def _replay(ordered, peer_set, sweep_events=None, chip=False):
    """Re-insert fresh copies of the signed events through the live driver.

    sweep_events=None runs the oracle pipeline per insert; an int attaches
    TensorConsensus with that mid-batch sweep threshold (plus the final
    flush, mirroring core.sync's cadence). ``chip`` makes it the lane a
    chip resolves — pipelined sweeps launched by the batcher's thread —
    and ends with the drain that brings deferred voting level."""
    h = Hashgraph(InmemStore(1000))
    h.init(peer_set)
    if sweep_events is not None:
        # async_compile off: tests need deterministic device sweeps, not
        # oracle-carried ones while a background compile warms up.
        # min_window=0 forces the device path regardless of window size.
        h.accel = TensorConsensus(sweep_events=sweep_events,
                                  async_compile=False, min_window=0,
                                  pipeline=chip, batcher=chip)
    for ev in ordered:
        h.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                         set_wire_info=True)
    h.flush_consensus()
    if chip:
        h.drain_consensus()
    return h


def _consensus_state(h: Hashgraph):
    """Everything consensus decides, keyed by event hash / round / block."""
    store = h.store
    events = {}
    seen = set()
    for pk in store.repertoire_by_pub_key():
        try:
            hashes = store.participant_events(pk, -1)
        except Exception:
            continue
        for eh in hashes:
            if eh in seen:
                continue
            seen.add(eh)
            ev = store.get_event(eh)
            events[eh] = (ev.round, ev.lamport_timestamp, ev.round_received)
    rounds = {}
    for r in range(store.last_round() + 1):
        try:
            ri = store.get_round(r)
        except Exception:
            continue
        rounds[r] = (
            {x: (e.witness, int(e.famous)) for x, e in ri.created_events.items()},
            sorted(ri.received_events),
        )
    blocks = {}
    for b in range(store.last_block_index() + 1):
        blk = store.get_block(b)
        blocks[b] = json.dumps(blk.body.to_dict(), default=_js_bytes,
                               sort_keys=True)
    return events, rounds, blocks, sorted(h.undetermined_events)


@pytest.mark.parametrize("graph", list(BUILDERS))
@pytest.mark.parametrize("sweep_events", [1, 7, 10_000])
def test_accel_matches_oracle(graph, sweep_events):
    h, index, nodes, peer_set = BUILDERS[graph]()
    # The builder's hashgraph only holds raw inserts; pull the signed events
    # back out in topological order and replay through both drivers.
    ordered = _ordered_events(h)
    oracle = _replay(ordered, peer_set)
    accel = _replay(ordered, peer_set, sweep_events=sweep_events)
    assert accel.accel.sweeps > 0, "device sweep never ran"
    assert accel.accel.fallbacks == 0, "device path fell back to oracle"

    o_events, o_rounds, o_blocks, o_undet = _consensus_state(oracle)
    a_events, a_rounds, a_blocks, a_undet = _consensus_state(accel)

    assert a_events == o_events
    assert a_rounds == o_rounds
    assert a_blocks == o_blocks
    assert a_undet == o_undet


def drain_pipelined(hg, max_iters: int = 200) -> None:
    """Flush a pipelined-accelerator hashgraph until nothing is in flight
    and the consensus state has stopped changing: each flush applies one
    in-flight sweep's results and may launch another."""
    prev = None
    for _ in range(max_iters):
        inf = hg.accel._inflight
        if inf is not None:
            inf.done.wait(10.0)
        hg._accel_pending = max(hg._accel_pending, 1)
        hg.flush_consensus()
        if hg.accel.busy():
            continue
        cur = _consensus_state(hg)
        if cur == prev:
            return
        prev = cur


@pytest.mark.parametrize("batcher", [False, True])
@pytest.mark.parametrize("graph", list(BUILDERS))
def test_accel_pipelined_matches_oracle(graph, batcher):
    """The non-blocking pipelined mode (the real-accelerator default, where
    flushes apply the PREVIOUS sweep's results while the next computes)
    must converge to the oracle's exact consensus state. Forced on the CPU
    mesh here; each insert's flush may defer, so drain at the end. With
    ``batcher`` on this is the lane a chip resolves and both benchmark
    cells run: every launch goes through the sweep batcher's thread."""
    h, index, nodes, peer_set = BUILDERS[graph]()
    ordered = _ordered_events(h)
    oracle = _replay(ordered, peer_set)

    hp = Hashgraph(InmemStore(1000))
    hp.init(peer_set)
    hp.accel = TensorConsensus(sweep_events=3, async_compile=False,
                               min_window=0, pipeline=True, batcher=batcher)
    for ev in ordered:
        hp.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                          set_wire_info=True)
    drain_pipelined(hp)
    assert hp.accel.sweeps > 0
    assert hp.accel.fallbacks == 0
    assert hp.accel.stats()["accel_batcher"] is batcher
    assert _consensus_state(hp) == _consensus_state(oracle)


def _ordered_events(h: Hashgraph):
    store = h.store
    events = []
    seen = set()
    for pk in store.repertoire_by_pub_key():
        try:
            hashes = store.participant_events(pk, -1)
        except Exception:
            continue
        for eh in hashes:
            if eh not in seen:
                seen.add(eh)
                events.append(store.get_event(eh))
    events.sort(key=lambda e: e.topological_index)
    return events


def test_accel_stats_surface():
    """The node-facing stats report the device engine and sweep counters."""
    h, index, nodes, peer_set = BUILDERS["consensus"]()
    accel = _replay(_ordered_events(h), peer_set, sweep_events=5)
    s = accel.accel.stats()
    assert s["consensus_engine"] == "device"
    assert s["accel_sweeps"] >= 1
    assert s["accel_last_window_events"] > 0
    assert sum(s["accel_stage_ms"].values()) > 0


def test_flock_slots_cross_process_exclusion(tmp_path):
    """BABBLE_ACCEL_SLOT_DIR admission slots exclude across PROCESSES:
    with 2 slot files, two holders in a child process leave none for this
    one; releases hand them back (accel.py _FlockSlots)."""
    import os
    import subprocess
    import sys
    import textwrap

    from babble_tpu.hashgraph.accel import _FlockSlots

    slot_dir = str(tmp_path / "slots")
    mine = _FlockSlots(slot_dir, 2)

    # a child process grabs both slots and holds them until told to exit
    child = subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(f"""
            import sys
            from babble_tpu.hashgraph.accel import _FlockSlots
            s = _FlockSlots({slot_dir!r}, 2)
            assert s.acquire() and s.acquire()
            print("held", flush=True)
            sys.stdin.readline()  # wait for the parent
            s.release()
            print("one-free", flush=True)
            sys.stdin.readline()
        """)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        assert child.stdout.readline().strip() == "held"
        assert mine.acquire() is False, "child's flocks not visible"

        child.stdin.write("\n")
        child.stdin.flush()
        assert child.stdout.readline().strip() == "one-free"
        assert mine.acquire() is True, "released slot not acquirable"
        assert mine.acquire() is False, "child still holds the other slot"
        mine.release()
    finally:
        child.stdin.close()
        child.wait(timeout=10)


def test_flock_slots_thread_exclusion(tmp_path):
    """The same slot files exclude across threads of ONE process too (each
    acquire opens its own fd; Linux flock treats separate fds as
    independent lockers)."""
    from babble_tpu.hashgraph.accel import _FlockSlots

    s = _FlockSlots(str(tmp_path / "slots"), 2)
    assert s.acquire() and s.acquire()
    assert s.acquire() is False
    s.release()
    assert s.acquire() is True
    s.release()
    s.release()
    s.release()  # over-release is a no-op


@pytest.mark.parametrize("lane", ["synchronous", "chip"])
@pytest.mark.parametrize("seed,n_peers", [(11, 4), (12, 6), (13, 9)])
def test_accel_matches_oracle_random_streams(seed, n_peers, lane):
    """Randomized differential: seeded random gossip streams (not just the
    hand-drawn golden DAGs) through the oracle and the device sweep must
    produce identical consensus state — fame, round-received, and block
    bodies. Catches shape/mask bugs the fixed fixtures can't reach
    (padding buckets, larger peer counts, deeper round structure). The
    ``chip`` lane is what a chip resolves (pipelined, through the batcher),
    brought level with ``Hashgraph.drain_consensus()``."""
    from babble_tpu.parallel.voting_shard import synthetic_voting_window

    h, _ = synthetic_voting_window(
        n_peers=n_peers, n_events=120, seed=seed, peer_change=False
    )
    ordered = _ordered_events(h)
    peer_set = h.store.get_peer_set(0)
    oracle = _replay(ordered, peer_set)
    accel = _replay(ordered, peer_set, sweep_events=13, chip=lane == "chip")
    assert accel.accel.sweeps > 0
    assert accel.accel.fallbacks == 0
    assert accel.accel.stats()["accel_batcher"] is (lane == "chip")
    assert _consensus_state(accel) == _consensus_state(oracle)


def _drain_as_the_benchmark_does(hg, seconds: float = 30.0) -> None:
    """benchmark/harness/ingest.py ``_Pass._drain``, and in effect a quiet
    node's heartbeat: flush while the engine says it is busy, stop when it
    is not and the consensus count stands still. Unlike drain_pipelined it
    never forces a flush the engine did not ask for."""
    import time

    prev = -1
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        hg.flush_consensus()
        if hg.accel.busy():
            time.sleep(0.0005)
            continue
        cur = hg.store.consensus_events_count()
        if cur == prev:
            return
        prev = cur
    raise AssertionError("the drain never quiesced")


@pytest.mark.parametrize("batcher", [False, True])
@pytest.mark.parametrize("outcome", ["compiled", "failed"])
def test_drain_that_meets_a_compile_wait_ends_decided(monkeypatch, outcome,
                                                      batcher):
    """async_compile on, one bucket left uncompiled: a flush applies a
    sweep's result and its relaunch finds the window's bucket not ready. It
    has reported "handled", nothing is in flight and nothing is pending —
    only busy() can tell a drain to flush again, and it does while the
    bucket compiles. The drain ends with the tail decided as the oracle
    decides it, and busy() is False once the compile is over or has
    failed."""
    import threading

    from babble_tpu.ops import voting
    from babble_tpu.parallel.voting_shard import synthetic_voting_window

    src, _ = synthetic_voting_window(n_peers=4, n_events=200, seed=7,
                                     peer_change=False)
    ordered = _ordered_events(src)
    peer_set = src.store.get_peer_set(0)
    oracle = _replay(ordered, peer_set)

    withheld = threading.Event()  # from now on the next bucket is not ready
    release = threading.Event()  # lets the background compile end
    compiled = threading.Event()

    def bucket_ready(key):
        return compiled.is_set() or not withheld.is_set()

    def precompile(*key):
        assert release.wait(30.0)
        if outcome == "failed":
            raise RuntimeError("compile failed (injected)")
        compiled.set()

    monkeypatch.setattr(voting, "bucket_ready", bucket_ready)
    monkeypatch.setattr(voting, "precompile", precompile)

    hp = Hashgraph(InmemStore(1000))
    hp.init(peer_set)
    accel = hp.accel = TensorConsensus(
        sweep_events=10_000, async_compile=True, min_window=0, pipeline=True,
        batcher=batcher)
    half = len(ordered) // 2
    for ev in ordered[:half]:
        hp.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                          set_wire_info=True)
    hp.flush_consensus()  # launches the first sweep
    assert accel._inflight is not None and accel.compile_waits == 0
    assert accel._inflight.done.wait(30.0)
    for ev in ordered[half:]:
        hp.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                          set_wire_info=True)

    withheld.set()
    hp.flush_consensus()  # applies the first sweep; the relaunch must wait
    assert accel.sweeps == 1 and accel.compile_waits == 1
    assert accel._inflight is None and hp._accel_pending == 0
    assert _consensus_state(hp) != _consensus_state(oracle)  # tail undecided
    assert accel.busy(), "nothing would flush the undecided tail"

    threading.Timer(0.2, release.set).start()
    _drain_as_the_benchmark_does(hp)
    assert release.is_set(), "the drain ended while the bucket compiled"
    assert not accel.busy()
    assert not accel._compiling
    assert accel.fallbacks == 0
    assert _consensus_state(hp) == _consensus_state(oracle)
    if outcome == "compiled":
        # the bucket is there now: the next growth of the DAG sweeps again
        assert voting.bucket_ready(None)
    else:
        assert not voting.bucket_ready(None)


def test_prewarm_is_a_span_and_counts_what_it_compiled(monkeypatch):
    """``prewarm_buckets``' work is the coarse span ``prewarm`` of the
    tracer it is given, and ``accel_prewarm_programs`` /
    ``accel_prewarm_seconds`` count it: a program already ready is not
    counted again, the span opens all the same."""
    from babble_tpu.hashgraph import accel as accel_mod
    from babble_tpu.obs.trace import Tracer
    from babble_tpu.ops import voting

    key = (16, 32, 8, 1, 8)
    monkeypatch.setenv("BABBLE_ACCEL_BATCH", "0")
    monkeypatch.setenv("BABBLE_ACCEL_RESIDENT", "0")
    monkeypatch.setattr(accel_mod, "prewarm_keys", lambda n: [key])
    stages, cpu = [], []
    tracer = Tracer(stage_sink=lambda s, sec: stages.append((s, sec)),
                    cpu_sink=lambda s, sec: cpu.append(s))
    before = TensorConsensus().stats()
    ready = voting.bucket_ready(key)
    assert accel_mod.prewarm_buckets(4, background=False,
                                     spans=tracer) is None
    assert voting.bucket_ready(key)
    t = accel_mod.prewarm_buckets(4, spans=tracer)  # on its own thread
    t.join()
    after = TensorConsensus().stats()
    assert [s for s, _sec in stages] == ["prewarm", "prewarm"]
    assert cpu == ["prewarm", "prewarm"]  # a coarse span
    assert (after["accel_prewarm_programs"]
            - before["accel_prewarm_programs"]) == (0 if ready else 1)
    assert (after["accel_prewarm_seconds"]
            - before["accel_prewarm_seconds"]) == pytest.approx(
                sum(sec for _s, sec in stages), abs=1e-3)


@pytest.mark.parametrize("n_peers,floor", [
    (4, (64, 512, 8, 1, 16)), (16, (128, 1024, 16, 1, 32)), (64, None),
])
def test_prewarm_seeds_the_batcher_floor_up_to_16_validators(
        monkeypatch, n_peers, floor):
    """Up to 16 validators prewarm compiles the batcher's B=MAX_BATCH
    floor and pins it; no floor was measured for a larger ring, so there
    the first wave sets the batcher's target."""
    from babble_tpu.hashgraph import accel as accel_mod
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher
    from babble_tpu.ops import voting

    monkeypatch.setenv("BABBLE_ACCEL_BATCH", "1")
    monkeypatch.setattr(accel_mod, "prewarm_keys", lambda n: [])
    compiled = []
    monkeypatch.setattr(voting, "precompile_batched",
                        lambda b, *key: compiled.append((b, key)))
    svc = SweepBatcher.instance()
    monkeypatch.setattr(svc, "floor_key", None)
    accel_mod.prewarm_buckets(n_peers, background=False)
    if floor is None:
        assert compiled == [] and svc.floor_key is None
    else:
        assert compiled == [(SweepBatcher.MAX_BATCH, floor)]
        assert svc.floor_key == floor
