"""DivideRounds' per-round witness matrices (``Hashgraph._round_ctx``) are
kept current in place: the insert-time walk writes the one entry a cached
witness's new first descendant changes, a new witness gets its row. Two
checks after EVERY insert of five signed DAGs:

- the plain definition: the round and witness flag the hashgraph gave the
  event equal those of the per-pair, per-peer ``Hashgraph._strongly_see``
  (parent round, + 1 on a super-majority of that round's witnesses under
  that round's peer-set). The benchmark cannot give this guard: its
  references run the same ``Hashgraph._round``.
- the shadow: every cached matrix equals, cell for cell, what
  ``_build_round_ctx`` builds from the store at that moment.

The DAGs: random gossip at 16 creators; a creator that falls silent and
catches up (witnesses minted into OLD rounds); joins and leaves (peer-sets
that differ from round to round, creators without a column); a hashgraph
that prunes; one that lands on a frame and goes on from there.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.event import FrameEvent
from babble_tpu.peers.peer_set import PeerSet
from benchmark.harness import churn, data

SEED, DAG_SEED = 3000000019, 2147487920
N = 16


# -- the two checks -----------------------------------------------------------

def shadow_check(hg: Hashgraph) -> int:
    """Every cached ctx against a fresh build. Returns how many it held.
    A round below the prune floor is left out: its witnesses may be gone
    from the store, so nothing could build it again, and nothing reads it."""
    for r, ctx in hg._round_ctx.items():
        wits = hg.store.get_round(r).witnesses()
        if hg.prune_floor is not None and r < hg.prune_floor:
            continue
        assert ctx.wits == wits, f"round {r}: witness list"
        fresh = hg._build_round_ctx(ctx.peer_set, wits, 0)
        assert ctx.fd.shape == fresh.fd.shape, f"round {r}: shape"
        assert np.array_equal(ctx.fd, fresh.fd), f"round {r}: matrix"
        assert ctx.row == fresh.row and ctx.col == fresh.col
    return len(hg._round_ctx)


def plain_round_and_witness(hg: Hashgraph, x: str):
    """The definition, from the coordinates as they stand, one pair of
    events at a time: no matrix, no cache."""
    ev = hg.store.get_event(x)
    parents = [p for p in (ev.self_parent(), ev.other_parent()) if p != ""]
    parent_round = max(
        (hg.store.get_event(p).round for p in parents), default=-1)
    round_ = 0
    if parent_round >= 0:
        peers = hg.store.get_peer_set(parent_round)
        seen = sum(
            hg._strongly_see(x, w, peers)
            for w in hg.store.get_round(parent_round).witnesses() if w != x)
        round_ = parent_round + (seen >= peers.super_majority())
    sp_round = -1
    if ev.self_parent() != "":
        sp_round = hg.store.get_event(ev.self_parent()).round
    member = ev.creator() in hg.store.get_peer_set(round_).by_pub_key
    return round_, member and round_ > sp_round


def definition_check(hg: Hashgraph, x: str) -> None:
    ev = hg.store.get_event(x)
    flag = hg.store.get_round(ev.round).created_events[x].witness
    assert (ev.round, flag) == plain_round_and_witness(hg, x), x


def insert_checked(hg: Hashgraph, ev: Event, consensus: bool) -> None:
    if consensus:
        hg.insert_event_and_run_consensus(ev, set_wire_info=True)
    else:
        hg.insert_event(ev, set_wire_info=True)
        hg.divide_rounds()
    definition_check(hg, ev.hex())
    shadow_check(hg)


def assignments(hg: Hashgraph, hashes) -> list:
    out = []
    for h in hashes:
        ev = hg.store.get_event(h)
        out.append((ev.round, ev.lamport_timestamp,
                    hg.store.get_round(ev.round).created_events[h].witness))
    return out


# -- the DAGs -----------------------------------------------------------------

def _ring(n=N, seed=SEED):
    keys = data.seeded_keys(n, seed)
    return keys, data.peer_set(keys, [f"inmem://v{i}" for i in range(n)])


def _fresh(peers) -> Hashgraph:
    hg = Hashgraph(InmemStore(100000))
    hg.init(peers)
    return hg


@pytest.fixture(scope="module")
def gossip16():
    """1,600 events of the benchmark's own backlog shape, all 16 creating."""
    keys, peers = _ring()
    wires = data.backlog_wire_events(
        keys, peers, list(range(N)), 1600, DAG_SEED, 100)
    return keys, peers, wires


def laggard_events(keys, n_events, seed, lag, silent_from, back_at):
    """Random gossip in which creator ``lag`` falls silent at event
    ``silent_from`` and returns at ``back_at`` the way a slow sync does: its
    events take as other-parent creator 0's events of long ago, two steps
    forward each time, so it climbs through rounds the others left behind
    and is a late witness of each."""
    rng = random.Random(seed)
    m = len(keys)
    chains = [[] for _ in range(m)]
    events = []

    def emit(i, op):
        idx = len(chains[i])
        e = Event.new(
            [b"tx %d" % len(events)] if idx else [], [], [],
            [chains[i][-1] if idx else "", op],
            keys[i].public_key.bytes(), idx, timestamp=len(events))
        data.sign_event(e, keys[i])
        chains[i].append(e.hex())
        events.append(e)

    cursor = 0  # into creator 0's chain: how far the laggard has caught up
    while len(events) < n_events:
        n = len(events)
        if n == silent_from:
            cursor = len(chains[0])
        catching_up = n >= back_at and cursor < len(chains[0]) - 1
        if catching_up and n % 3 == 0:
            emit(lag, chains[0][cursor])
            cursor += 2
            continue
        away = silent_from <= n and (n < back_at or catching_up)
        i = rng.randrange(m)
        if i == lag and away:
            continue
        others = [c for c in range(m)
                  if c != i and chains[c] and not (c == lag and away)]
        if events and not others:
            continue
        emit(i, chains[rng.choice(others)][-1] if events else "")
    return events


def _churn_backlog(eager):
    n_genesis, n_joiners = 4, 2
    keys = data.seeded_keys(n_genesis + n_joiners, SEED)
    peers = churn.all_peers(keys, n_genesis)
    genesis = PeerSet(peers[:n_genesis])
    requests = churn.parse_requests(["+x0", "-v3", "+x1"], n_genesis)
    _script, wires = churn.churn_script(
        keys, peers, genesis, [1, 2, 3], requests, 600, 2147489957,
        40, 180, 100, eager_joiners=eager)
    return genesis, wires


# -- (a) + (b): after every insert -------------------------------------------

def test_random_gossip_at_16_creators_and_the_counters(gossip16):
    _keys, peers, wires = gossip16
    hg = _fresh(peers)
    held = 0
    for we in wires:
        insert_checked(hg, hg.read_wire_info(we), consensus=False)
        held = max(held, len(hg._round_ctx))
    assert hg.store.last_round() >= 12 and held >= 12
    # (c) a matrix is built once a round and patched from then on
    assert hg.round_ctx_rebuilds / len(wires) < 0.05
    assert hg.round_ctx_rebuilds >= hg.store.last_round()
    # every entry a witness gained while cached, every row but a round's first
    assert hg.round_ctx_patches > 10 * hg.store.last_round()


def test_a_silent_creator_that_returns_mints_witnesses_into_old_rounds():
    keys, peers = _ring(seed=SEED + 2)
    lag = 5
    events = laggard_events(keys, 1600, DAG_SEED + 1, lag, 150, 1100)
    hg = _fresh(peers)
    late = 0
    for ev in events:
        before = hg.store.last_round()
        insert_checked(hg, ev, consensus=False)
        stored = hg.store.get_event(ev.hex())
        if (hg.store.get_round(stored.round).created_events[ev.hex()].witness
                and stored.round < before - 2):
            late += 1
            # the late witness has a row in a matrix cached long before
            assert ev.hex() in hg._round_ctx[stored.round].row
    assert late >= 4
    assert hg.round_ctx_rebuilds / len(events) < 0.05


@pytest.mark.parametrize("eager", [False, True], ids=["at-round", "eager"])
def test_joins_and_leaves_change_the_columns_from_round_to_round(eager):
    genesis, wires = _churn_backlog(eager)
    hg, plus_six = churn.sequential_hashgraph(genesis, len(wires))
    no_column = 0
    for we in wires:
        ev = hg.read_wire_info(we)
        hg.insert_event_and_run_consensus(ev, set_wire_info=False)
        definition_check(hg, ev.hex())
        shadow_check(hg)
        stored = hg.store.get_event(ev.hex())
        if ev.creator() not in hg.store.get_peer_set(stored.round).by_pub_key:
            no_column += 1
    assert len(plus_six.changes) == 3
    widths = {len(ctx.col) for ctx in hg._round_ctx.values()}
    assert len(widths) >= 2  # matrices of different peer-sets side by side
    if eager:
        assert no_column >= 1  # a joiner's events before it is a member
    assert hg.round_ctx_rebuilds / len(wires) < 0.1


def test_a_peer_set_swapped_under_a_cached_matrix_is_rebuilt(gossip16):
    keys, peers, wires = gossip16
    hg = _fresh(peers)
    for we in wires[:400]:
        hg.insert_event(hg.read_wire_info(we))
        hg.divide_rounds()
    r = max(hg._round_ctx)
    old = hg._round_ctx[r]
    # the same members as another object, as a store that reloads gives
    hg.store.set_peer_set(r, PeerSet(list(peers.peers)))
    rebuilds = hg.round_ctx_rebuilds
    for we in wires[400:500]:
        insert_checked(hg, hg.read_wire_info(we), consensus=False)
    assert hg._round_ctx[r] is not old
    assert hg._round_ctx[r].peer_set is hg.store.get_peer_set(r)
    assert hg.round_ctx_rebuilds > rebuilds


def test_a_hashgraph_that_prunes(gossip16):
    _keys, peers, wires = gossip16
    hg, control = _fresh(peers), _fresh(peers)
    pruned = 0
    for k, we in enumerate(wires[:1200]):
        insert_checked(hg, hg.read_wire_info(we), consensus=True)
        control.insert_event_and_run_consensus(
            control.read_wire_info(we), set_wire_info=True)
        if k % 300 == 299 and hg.last_consensus_round is not None:
            got = hg.prune_below(hg.last_consensus_round - 1)
            pruned += got["rounds_pruned"]
            shadow_check(hg)  # a dropped round left no matrix behind
    assert pruned > 0 and hg.prune_floor > 3
    tail = list(control.undetermined_events)
    assert tail and assignments(hg, tail) == assignments(control, tail)


def test_a_hashgraph_that_lands_on_a_frame_and_goes_on(gossip16):
    _keys, peers, wires = gossip16
    h = _fresh(peers)
    for we in wires[:900]:
        h.insert_event_and_run_consensus(h.read_wire_info(we),
                                         set_wire_info=True)
    block = h.store.get_block(h.store.last_block_index() // 2)
    frame = h.get_frame(block.round_received())
    h2 = Hashgraph(InmemStore(100000))
    h2.reset(block, frame)
    assert shadow_check(h2) == 0  # reset leaves no matrix behind
    diff = []
    for id_, ct in h2.store.known_events().items():
        pk = peers.by_id[id_].pub_key_hex
        diff += [h.store.get_event(x) for x in h.store.participant_events(pk, ct)]
    diff.sort(key=lambda e: e.topological_index)
    assert len(diff) > 300
    trusted = 0
    for k, orig in enumerate(diff):
        ev = h2.read_wire_info(orig.to_wire())
        flag = h.store.get_round(orig.round).created_events[orig.hex()].witness
        if k > 100 and flag and trusted < 6 and orig.round in h2._round_ctx:
            # a trusted insert into a round whose matrix is cached: the
            # frame path appends the row too
            h2.insert_frame_event(FrameEvent(
                ev, orig.round, orig.lamport_timestamp, True))
            assert ev.hex() in h2._round_ctx[orig.round].row
            trusted += 1
        else:
            h2.insert_event(ev, set_wire_info=False)
            h2.divide_rounds()
            definition_check(h2, ev.hex())
        shadow_check(h2)
    assert trusted == 6
    hashes = [e.hex() for e in diff]
    assert assignments(h2, hashes) == assignments(h, hashes)


# -- (c) the counters reach the node's snapshot -------------------------------

def test_the_counters_are_in_the_nodes_snapshot(gossip16):
    from babble_tpu.config.config import Config
    from babble_tpu.dummy.state import State as DummyState
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.node import Node
    from babble_tpu.node.validator import Validator
    from babble_tpu.proxy.proxy import InmemProxy

    keys, peers, _wires = gossip16
    wires = data.backlog_wire_events(
        keys, peers, list(range(1, N)), 300, DAG_SEED, 100)
    conf = Config(bind_addr="inmem://v0", moniker="v0", log_level="error",
                  no_service=True)
    node = Node(conf, Validator(keys[0], "v0"), peers, peers,
                InmemStore(conf.cache_size),
                InmemNetwork().new_transport("inmem://v0"),
                InmemProxy(DummyState()))
    node.init()
    try:
        from_id = peers.by_pub_key[keys[1].public_key.hex()].id
        with node.core_lock:
            node.core.sync(from_id, wires, node.core.prepare_sync(wires))
        snap = node.get_stats_snapshot()
        hg = node.core.hg
        assert snap["round_ctx_patches"] == hg.round_ctx_patches > 0
        assert snap["round_ctx_rebuilds"] == hg.round_ctx_rebuilds > 0
        assert snap["round_ctx_rebuilds"] < 0.1 * hg.topological_index
        # the coordinate rows' counters ride beside them: the entries the
        # first-descendant walk wrote, and a ring of 16 fills rows of 16
        assert snap["fd_walk_steps"] == hg.fd_walk_steps > 5 * len(wires)
        # every ancestor the walk reached carried its witness flag
        assert snap["fd_walk_flag_misses"] == hg.fd_walk_flag_misses == 0
        assert snap["coord_row_regrows"] == hg.coord_row_regrows == 0
        assert "peer_set_waits" in snap
        shadow_check(hg)
    finally:
        node.shutdown()


# -- (d) the requeue path -----------------------------------------------------

@pytest.mark.parametrize("stage", ["witness", "lamport_timestamp"])
def test_an_error_in_the_middle_of_a_batch_leaves_the_matrices_whole(
        gossip16, stage, monkeypatch):
    """``witness`` raises before the event is touched, ``lamport_timestamp``
    after its round is set and its row appended; either way the batch's
    rest is requeued and every cached matrix still equals its build."""
    _keys, peers, wires = gossip16
    hg, control = _fresh(peers), _fresh(peers)
    for we in wires[:600]:
        hg.insert_event(hg.read_wire_info(we))
        hg.divide_rounds()
        control.insert_event(control.read_wire_info(we))
        control.divide_rounds()
    batch = []
    for we in wires[600:800]:  # one batch of two hundred, divided at once
        ev = hg.read_wire_info(we)
        hg.insert_event(ev)
        batch.append(ev.hex())
        control.insert_event(control.read_wire_info(we))
    control.divide_rounds()
    # the first witness past the middle of the batch
    victim = next(
        h for h in batch[60:]
        if control.store.get_round(
            control.store.get_event(h).round).created_events[h].witness)
    real = getattr(hg, stage)

    def failing(x):
        if x == victim:
            raise RuntimeError("store went away")
        return real(x)

    monkeypatch.setattr(hg, stage, failing)
    with pytest.raises(RuntimeError):
        hg.divide_rounds()
    assert hg._round_pending[0] == victim
    shadow_check(hg)
    monkeypatch.setattr(hg, stage, real)
    hg.divide_rounds()
    assert not hg._round_pending
    shadow_check(hg)
    assert assignments(hg, batch) == assignments(control, batch)
    for we in wires[800:860]:
        insert_checked(hg, hg.read_wire_info(we), consensus=False)
