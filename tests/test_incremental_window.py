"""Incremental, device-resident voting windows (ops/window_state.py).

Pinned properties:

- **Rebuild equivalence (the correctness oracle)**: after EVERY mutation
  step (events deciding, witnesses settling, fd updates, peer-set
  changes), the incremental WindowState mirrors equal a from-scratch
  ``build_voting_window`` rebuild field by field — modulo row placement
  (the free-list recycles rows, the fresh build packs them contiguously)
  and the frozen floor (the state may keep settled witnesses below the
  fresh build's floor; those must be provably inert). The sweep decisions
  computed from both snapshots must be identical per hash.
- **Buffer-donation / generation safety**: a sweep launched from
  generation N whose readback lands after generation N+1 mutated the
  resident state is detected by the generation check and DISCARDED, never
  applied through moved row maps; the batcher refuses stale-generation
  windows at dispatch.
- **Rebuild triggers**: repertoire changes and store evictions fall back
  to a from-scratch rebuild without consensus divergence.
"""

from __future__ import annotations

import numpy as np
import pytest

from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.accel import TensorConsensus
from babble_tpu.ops import voting
from babble_tpu.ops import window_state as ws

from tests.test_accel import BUILDERS, _consensus_state, _ordered_events
from tests.test_accel import _replay, drain_pipelined  # noqa: F401


def _stream(n_peers=6, n_events=160, seed=3, peer_change=False):
    """Signed random-gossip events + the peer set (optionally with a
    mid-stream peer-set change recorded at round 3, so windows carry
    multiple peer-set slots)."""
    import random

    from babble_tpu.crypto.keys import generate_key
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet

    rng = random.Random(seed)
    keys = [generate_key() for _ in range(n_peers)]
    peers = PeerSet(
        [Peer(f"inmem://p{i}", k.public_key.hex(), f"p{i}")
         for i, k in enumerate(keys)]
    )
    heads = [""] * n_peers
    seqs = [-1] * n_peers
    events = []
    order = list(range(n_peers))
    while len(events) < n_events:
        rng.shuffle(order)
        for i in order:
            if len(events) >= n_events:
                break
            op = ""
            if events:
                j = rng.randrange(n_peers - 1)
                j = j if j < i else j + 1
                op = heads[j]
                if op == "":
                    continue
            idx = seqs[i] + 1
            e = Event.new(
                [b"t"] if idx else [], [], [], [heads[i], op],
                keys[i].public_key.bytes(), idx, timestamp=len(events),
            )
            e.sign(keys[i])
            e.prevalidate(True)
            heads[i] = e.hex()
            seqs[i] = idx
            events.append(e)
    return events, peers, keys


def _assert_equiv(state: ws.WindowState, snap_win, hg) -> None:
    """The incremental mirrors vs a fresh build_voting_window rebuild:
    field-by-field equality per hash, inertness of the extra rows the
    frozen floor keeps, round/peer-set metadata equality over the fresh
    span, and identical sweep decisions."""
    fresh = voting.build_voting_window(hg)
    assert fresh is not None
    m = state.mirror
    P_real = len(state.pub_keys)
    assert tuple(sorted(hg.store.repertoire_by_pub_key())) == state.pub_keys
    assert fresh.base >= state.base  # the floor only rises between rebuilds

    # every fresh E row exists with identical content (absolute rounds)
    for h, fi in fresh.row.items():
        i = state.row.get(h)
        assert i is not None, f"missing E row {h}"
        assert int(m["creator"][i]) == int(fresh.creator[fi])
        assert int(m["index"][i]) == int(fresh.index[fi])
        assert (int(m["rounds"][i]) + state.base
                == int(fresh.rounds[fi]) + fresh.base)
        assert bool(m["undet"][i]) == bool(fresh.undet[fi]), h

    # every fresh W row exists with identical coordinates/fame/coin bits
    for h, fw in fresh.wit_row.items():
        w = state.wit_row.get(h)
        assert w is not None, f"missing W row {h}"
        assert bool(m["valid_w"][w]) and bool(fresh.valid_w[fw])
        assert (int(m["rounds_w"][w]) + state.base
                == int(fresh.rounds_w[fw]) + fresh.base)
        assert int(m["fame0_w"][w]) == int(fresh.fame0_w[fw]), h
        assert bool(m["mid_w"][w]) == bool(fresh.mid_w[fw])
        np.testing.assert_array_equal(
            m["la_w"][w][:P_real], fresh.la_w[fw][:P_real]
        )
        np.testing.assert_array_equal(
            m["fd_w"][w][:P_real], fresh.fd_w[fw][:P_real]
        )
        # wit_idx resolves to the same hash's E row in both
        assert int(m["wit_idx"][w]) == state.row[h]
        assert int(fresh.wit_idx[fw]) == fresh.row[h]

    # extras the frozen floor keeps must be inert: settled witnesses of
    # rounds below the fresh floor, never receivable
    for h in set(state.row) - set(fresh.row):
        w = state.wit_row.get(h)
        assert w is not None, f"extra non-witness row {h}"
        assert int(m["rounds_w"][w]) + state.base < fresh.base
        assert int(m["fame0_w"][w]) != 0, f"undecided extra witness {h}"
        assert not bool(m["undet"][state.row[h]])

    # round/peer-set metadata over the fresh build's real span
    for a in range(fresh.base, hg.store.last_round() + 2):
        rf, rs = a - fresh.base, a - snap_win.base
        assert bool(fresh.exists_r[rf]) == bool(snap_win.exists_r[rs]), a
        assert bool(fresh.prior_dec_r[rf]) == bool(snap_win.prior_dec_r[rs])
        assert bool(fresh.lb_gate_r[rf]) == bool(snap_win.lb_gate_r[rs])
        assert int(fresh.sm_r[rf]) == int(snap_win.sm_r[rs]), a
        np.testing.assert_array_equal(
            fresh.member[int(fresh.psi[rf])][:P_real],
            snap_win.member[int(snap_win.psi[rs])][:P_real],
        )

    # and the decisions computed from either snapshot are identical
    fame_f, rr_f = voting.run_sweep(fresh)
    fame_s, rr_s = voting.run_sweep(snap_win)
    for h, fw in fresh.wit_row.items():
        assert int(fame_f[fw]) == int(fame_s[state.wit_row[h]]), h
    for h, fi in fresh.row.items():
        af = int(rr_f[fi])
        ai = int(rr_s[state.row[h]])
        af = af + fresh.base if af >= 0 else -1
        ai = ai + snap_win.base if ai >= 0 else -1
        assert af == ai, h


def _replay_checked(events, peers, sweep_every=8):
    """Replay a stream through a resident TensorConsensus, asserting
    incremental == rebuild after EVERY snapshot (i.e. every mutation
    step a sweep observes)."""
    acc = TensorConsensus(sweep_events=sweep_every, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = acc

    checked = {"count": 0}
    orig = ws.WindowState.snapshot

    def snapshot_checked(self, hg, timers, copy_rows=False):
        snap = orig(self, hg, timers, copy_rows)
        if snap is not None:
            _assert_equiv(self, snap.win, hg)
            checked["count"] += 1
        return snap

    ws.WindowState.snapshot = snapshot_checked
    try:
        for ev in events:
            e = Event(ev.body, ev.signature)
            e.prevalidate(True)
            h.insert_event_and_run_consensus(e, set_wire_info=True)
        h.flush_consensus()
    finally:
        ws.WindowState.snapshot = orig
    return h, acc, checked["count"]


def test_incremental_equals_rebuild_under_churn():
    """Random DAG with churn (events deciding, witnesses settling, rows
    releasing and recycling): the incremental snapshot equals a fresh
    rebuild after every mutation step, and the final consensus equals the
    oracle's."""
    events, peers, _keys = _stream(n_peers=6, n_events=160, seed=11)
    h, acc, n_checked = _replay_checked(events, peers)
    assert acc.fallbacks == 0
    assert n_checked >= 10, "property was barely exercised"
    assert acc.rows_reused_total > acc.rows_delta_total, (
        "incremental path never amortized rows"
    )
    assert acc.window_state.rebuilds < acc.sweeps, "every sweep rebuilt"

    oracle = Hashgraph(InmemStore(100000))
    oracle.init(peers)
    for ev in events:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        oracle.insert_event_and_run_consensus(e, set_wire_info=True)
    assert _consensus_state(h) == _consensus_state(oracle)


def test_incremental_equals_rebuild_with_peer_set_change():
    """A mid-stream peer-set change (recorded at round 3) exercises the
    multi-slot psi/member machinery through the incremental path."""
    events, peers, _keys = _stream(n_peers=6, n_events=140, seed=12)
    acc = TensorConsensus(sweep_events=7, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.store.set_peer_set(3, peers.with_removed_peer(peers.peers[-1]))
    h.accel = acc

    orig = ws.WindowState.snapshot
    seen_slots = {"max": 0}

    def snapshot_checked(self, hg, timers, copy_rows=False):
        snap = orig(self, hg, timers, copy_rows)
        if snap is not None:
            _assert_equiv(self, snap.win, hg)
            seen_slots["max"] = max(
                seen_slots["max"], len(set(np.asarray(snap.win.psi)))
            )
        return snap

    ws.WindowState.snapshot = snapshot_checked
    try:
        for ev in events:
            e = Event(ev.body, ev.signature)
            e.prevalidate(True)
            h.insert_event_and_run_consensus(e, set_wire_info=True)
        h.flush_consensus()
    finally:
        ws.WindowState.snapshot = orig
    assert acc.fallbacks == 0
    assert seen_slots["max"] >= 2, "peer-set change never reached a window"


def test_repertoire_change_triggers_rebuild_without_divergence():
    """Adding a peer to the repertoire renumbers peer columns: the next
    snapshot must rebuild (not delta) and still equal the fresh build."""
    from babble_tpu.crypto.keys import generate_key
    from babble_tpu.peers.peer import Peer

    events, peers, _keys = _stream(n_peers=6, n_events=120, seed=13)
    acc = TensorConsensus(sweep_events=10, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = acc
    for ev in events:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    state = acc.window_state
    assert state.mirror is not None
    r0 = state.rebuilds

    joiner = Peer("inmem://joiner", generate_key().public_key.hex(), "j")
    h.store.set_peer_set(
        h.store.last_round() + 1, peers.with_new_peer(joiner)
    )
    snap = state.snapshot(h, {})
    assert snap is not None and snap.rebuilt
    assert state.rebuilds == r0 + 1
    assert joiner.pub_key_hex in state.pub_keys
    _assert_equiv(state, snap.win, h)


def test_round_eviction_triggers_rebuild():
    """A round readable at the last snapshot vanishing from the store (LRU
    eviction) must force a rebuild — a fresh build would have dropped its
    witnesses, so the delta mirrors no longer match."""
    events, peers, _keys = _stream(n_peers=6, n_events=120, seed=14)
    acc = TensorConsensus(sweep_events=10, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = acc
    for ev in events:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    state = acc.window_state
    assert state.mirror is not None
    # evict a round the window watched (between the frozen floor and top)
    evict = state.base + 1
    assert h.store._round_cache.remove(evict)
    r0 = state.rebuilds
    snap = state.snapshot(h, {})
    assert state.rebuilds == r0 + 1
    assert snap is None or snap.rebuilt


@pytest.mark.parametrize("batcher", [False, True])
def test_stale_generation_readback_discarded(batcher):
    """Donation safety: a pipelined sweep launched from generation N whose
    readback lands after generation N+1 mutated the resident state is
    discarded by the generation check (accel_stale_drops), the oracle
    carries the flush, and consensus converges to the oracle's exact
    state. With ``batcher`` on (the lane a chip resolves) the window went
    out as ``snapshot(copy_rows=True)`` copies, which no other lane takes."""
    h0, index, nodes, peer_set = BUILDERS["consensus"]()
    ordered = _ordered_events(h0)
    oracle = _replay(ordered, peer_set)

    h = Hashgraph(InmemStore(1000))
    h.init(peer_set)
    h.accel = TensorConsensus(sweep_events=3, async_compile=False,
                              min_window=0, pipeline=True, resident=True,
                              batcher=batcher)
    for ev in ordered:
        h.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                         set_wire_info=True)
    if h.accel._inflight is None:
        # make sure a sweep is in flight to poison
        h.accel._last_snapshot_topo = -1
        h._accel_pending = 1
        h.run_consensus_sweep()
    inf = h.accel._inflight
    assert inf is not None, "no sweep in flight"
    assert inf.done.wait(30.0)
    # generation N+1 mutates the resident state before the apply
    h.accel.window_state.mark_dirty("test-mutation")
    h._accel_pending = 1
    h.run_consensus_sweep()
    assert h.accel.stale_drops >= 1, "stale readback was not detected"

    drain_pipelined(h)
    assert _consensus_state(h) == _consensus_state(oracle)


def test_batcher_refuses_stale_generation():
    """The sweep batcher keys dispatch on the resident-state generation: a
    submitted window whose state moved on is failed with StaleWindowError
    instead of being computed and applied through moved row maps."""
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    events, peers, _keys = _stream(n_peers=6, n_events=100, seed=15)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    for ev in events:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event(e, set_wire_info=True)
        h.divide_rounds()
    state = ws.WindowState()
    snap = state.snapshot(h, {}, copy_rows=True)
    assert snap is not None
    state.mark_dirty("test-mutation")  # generation moves on

    svc = SweepBatcher()
    t = svc.submit(snap.win)
    assert t is not None and t.done.wait(30.0)
    assert isinstance(t.error, ws.StaleWindowError)


def test_skipped_dispatch_reseeds_residency():
    """A snapshot whose delta was committed to the mirrors but never
    dispatched (compile wait / admission loss) leaves the device buffers
    trailing. drop_residency() must force the next dispatch onto the
    full-upload path — a delta dispatch over the stale buffers would
    compute a window missing the skipped rows."""
    events, peers, _keys = _stream(n_peers=6, n_events=120, seed=18)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h._accel_track_delta = True
    state = ws.WindowState()

    # big first chunk, small increments after: the increments must fit the
    # first snapshot's bucket headroom, or a rebuild (legitimately) fires
    # and bypasses the path under test
    cuts = (90, 100, 110)
    for ev in events[:cuts[0]]:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event(e, set_wire_info=True)
        h.divide_rounds()
    snap = state.snapshot(h, {})
    assert snap is not None
    out, used_delta = state.dispatch(snap)
    np.asarray(out)
    assert state.device is not None

    # second snapshot commits a delta, but its dispatch is skipped
    for ev in events[cuts[0]:cuts[1]]:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event(e, set_wire_info=True)
        h.divide_rounds()
    snap2 = state.snapshot(h, {})
    assert snap2 is not None and not snap2.rebuilt
    state.drop_residency()
    assert state.device is None

    # third snapshot: the dispatch must reseed via full upload and its
    # decisions must equal a from-scratch window's
    for ev in events[cuts[1]:cuts[2]]:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event(e, set_wire_info=True)
        h.divide_rounds()
    snap3 = state.snapshot(h, {})
    assert snap3 is not None
    out3, used_delta3 = state.dispatch(snap3)
    assert used_delta3 is False, "stale residency was not reseeded"
    fame_s, rr_s = voting.read_sweep(out3, snap3.win)
    fresh = voting.build_voting_window(h)
    fame_f, rr_f = voting.run_sweep(fresh)
    for hsh, fw in fresh.wit_row.items():
        assert int(fame_f[fw]) == int(fame_s[state.wit_row[hsh]])
    for hsh, fi in fresh.row.items():
        af = int(rr_f[fi])
        ai = int(rr_s[state.row[hsh]])
        assert (af + fresh.base if af >= 0 else -1) == (
            ai + snap3.win.base if ai >= 0 else -1
        )


@pytest.mark.parametrize("batcher", [False, True])
def test_resident_pipelined_matches_oracle(batcher):
    """The pipelined resident path (deltas + donated buffers + deferred
    applies; through the batcher, host mirrors and copied rows) converges
    to the oracle's exact consensus on the golden DAGs."""
    h0, index, nodes, peer_set = BUILDERS["funky_full"]()
    ordered = _ordered_events(h0)
    oracle = _replay(ordered, peer_set)

    hp = Hashgraph(InmemStore(1000))
    hp.init(peer_set)
    hp.accel = TensorConsensus(sweep_events=3, async_compile=False,
                               min_window=0, pipeline=True, resident=True,
                               batcher=batcher)
    for ev in ordered:
        hp.insert_event_and_run_consensus(Event(ev.body, ev.signature),
                                          set_wire_info=True)
    drain_pipelined(hp)
    assert hp.accel.sweeps > 0
    assert _consensus_state(hp) == _consensus_state(oracle)


def test_resident_stats_surface():
    """The new counters ride TensorConsensus.stats() (and therefore node
    get_stats): rows_delta/rows_reused/rebuilds, the stale-drop counter,
    and the per-stage breakdown keys the benchmark reads."""
    events, peers, _keys = _stream(n_peers=6, n_events=120, seed=16)
    acc = TensorConsensus(sweep_events=8, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = acc
    for ev in events:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    s = acc.stats()
    assert s["accel_resident"] is True
    assert s["accel_rebuilds"] >= 1
    assert s["accel_rows_delta"] > 0
    assert s["accel_rows_reused"] > 0
    assert s["accel_stale_drops"] == 0
    for stage in ("build", "delta_scan", "pack", "dispatch", "readback",
                  "apply"):
        assert stage in s["accel_stage_ms"], stage
    snapshot_ms = (
        s["accel_stage_ms"]["build"]
        + s["accel_stage_ms"]["delta_scan"]
        + s["accel_stage_ms"]["pack"]
    )
    assert snapshot_ms > 0


def test_oracle_pass_marks_state_dirty():
    """Any flush the oracle carries (here: the min_window gate) must mark
    the resident state dirty — the next engaged snapshot rebuilds instead
    of trusting mirrors the oracle mutated behind."""
    events, peers, _keys = _stream(n_peers=6, n_events=100, seed=17)
    head, tail = events[:60], events[60:]
    acc = TensorConsensus(sweep_events=10, async_compile=False,
                          min_window=0, pipeline=False, batcher=False,
                          resident=True)
    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = acc
    for ev in head:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    state = acc.window_state
    assert not state.dirty
    acc.min_window = 10**9  # every later flush rides the oracle
    h._accel_pending = 1
    h.run_consensus_sweep()
    assert state.dirty, "oracle pass did not invalidate the mirrors"

    # while the oracle carries every flush, the hashgraph's delta
    # channels must be drained per flush, not accumulate forever
    for ev in tail:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    h.flush_consensus()
    assert h._accel_new_witnesses == []
    assert h._accel_fd_dirty == set()
