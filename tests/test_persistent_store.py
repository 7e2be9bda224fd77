"""PersistentStore tests: DB round-trips of every object, cache-miss →
DB fallback, and the kill/restart/bootstrap recycle scenario.

Modeled on the reference's store and bootstrap suites
(/root/reference/src/hashgraph/badger_store_test.go:452 cache-miss
fallback; /root/reference/src/node/node_test.go:238 TestBootstrapAllNodes
kill-all/recycle/resume)."""

from __future__ import annotations

import json
import random
import shutil
import sqlite3
import time
from typing import List

import pytest

from babble_tpu.common.errors import StoreError, StoreErrorKind
from babble_tpu.config.config import Config
from babble_tpu.crypto.canonical import canonical_dumps
from babble_tpu.crypto.keys import generate_key
from babble_tpu.dummy.state import State as DummyState
from babble_tpu.hashgraph.block import Block, BlockBody
from babble_tpu.hashgraph.event import Event
from babble_tpu.hashgraph.frame import Frame, Root
from babble_tpu.hashgraph.persistent_store import PersistentStore
from babble_tpu.hashgraph.round_info import RoundInfo
from babble_tpu.net.inmem import InmemNetwork
from babble_tpu.node.node import Node
from babble_tpu.node.validator import Validator
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.proxy.proxy import InmemProxy


def make_peers(keys):
    return PeerSet(
        [
            Peer(f"inmem://n{i}", k.public_key.hex(), f"n{i}")
            for i, k in enumerate(keys)
        ]
    )


def test_event_round_trip_and_fallback(tmp_path):
    """Events survive a cache wipe: reads fall back to SQLite."""
    k = generate_key()
    store = PersistentStore(cache_size=100, path=str(tmp_path / "s.db"))
    peers = make_peers([k])
    store.set_peer_set(0, peers)

    ev = Event.new([b"tx"], [], [], ["", ""], k.public_key.bytes(), 0)
    ev.sign(k)
    store.set_event(ev)

    # fresh store over the same DB: the cache is cold, DB must serve
    store.close()
    store2 = PersistentStore(cache_size=100, path=str(tmp_path / "s.db"))
    got = store2.get_event(ev.hex())
    assert got.hex() == ev.hex()
    assert got.signature == ev.signature
    assert got.verify()
    assert store2.participant_event(peers.peers[0].pub_key_hex, 0) == ev.hex()
    evs = store2.topological_events(0, 10)
    assert [e.hex() for e in evs] == [ev.hex()]
    store2.close()


def test_round_block_frame_round_trip(tmp_path):
    k = generate_key()
    store = PersistentStore(cache_size=100, path=str(tmp_path / "s.db"))
    peers = make_peers([k])
    store.set_peer_set(0, peers)

    ri = RoundInfo()
    ri.add_created_event("0Xdead", witness=True)
    store.set_round(2, ri)

    block = Block.new(3, 2, b"fh", peers, [b"a", b"b"], [], 7)
    store.set_block(block)

    frame = Frame(
        round=2,
        peers=peers,
        roots={peers.peers[0].pub_key_hex: Root()},
        events=[],
        peer_sets={0: list(peers.peers)},
        timestamp=7,
    )
    store.set_frame(frame)
    store.close()

    s2 = PersistentStore(cache_size=100, path=str(tmp_path / "s.db"))
    assert s2.get_round(2).to_dict() == ri.to_dict()
    assert s2.get_block(3).body.hash() == block.body.hash()
    assert s2.get_frame(2).hash() == frame.hash()
    assert s2.db_last_block_index() == 3
    s2.close()


def make_persistent_cluster(n, network, tmp_path, bootstrap=False, keys=None):
    keys = keys or [generate_key() for _ in range(n)]
    peers = make_peers(keys)
    addr = {p.pub_key_hex: p.net_addr for p in peers.peers}
    nodes: List[Node] = []
    proxies = []
    states = []
    for i, k in enumerate(keys):
        conf = Config(
            heartbeat_timeout=0.02,
            slow_heartbeat_timeout=0.2,
            moniker=f"n{i}",
            log_level="warning",
            bootstrap=bootstrap,
        )
        st = DummyState()
        pr = InmemProxy(st)
        store = PersistentStore(
            cache_size=conf.cache_size, path=str(tmp_path / f"node{i}.db")
        )
        node = Node(
            conf,
            Validator(k, f"n{i}"),
            peers,
            peers,
            store,
            network.new_transport(addr[k.public_key.hex()]),
            pr,
        )
        node.init()
        nodes.append(node)
        proxies.append(pr)
        states.append(st)
    return nodes, proxies, states, keys


def test_bootstrap_recycle_reproduces_chain(tmp_path):
    """Kill all nodes, restart from their DBs with bootstrap, verify the
    same chain, then resume gossip to a further block
    (reference: node_test.go:238 TestBootstrapAllNodes)."""
    network = InmemNetwork()
    nodes, proxies, states, keys = make_persistent_cluster(3, network, tmp_path)
    for n in nodes:
        n.run_async()
    deadline = time.monotonic() + 60
    i = 0
    while (
        min(n.get_last_block_index() for n in nodes) < 2
        and time.monotonic() < deadline
    ):
        proxies[i % 3].submit_tx(f"tx {i}".encode())
        i += 1
        time.sleep(0.005)
    reached = min(n.get_last_block_index() for n in nodes)
    assert reached >= 2, f"cluster only reached block {reached}"
    chain = [nodes[0].get_block(j).body.hash() for j in range(3)]
    for n in nodes:
        n.shutdown()

    # recycle: same keys, same DBs, fresh everything else
    network2 = InmemNetwork()
    nodes2, proxies2, states2, _ = make_persistent_cluster(
        3, network2, tmp_path, bootstrap=True, keys=keys
    )
    try:
        for n in nodes2:
            # replayed chain must match byte-for-byte
            assert n.get_last_block_index() >= 2
            for j in range(3):
                assert n.get_block(j).body.hash() == chain[j], f"block {j}"
        # the app state was rebuilt through replay
        for st in states2:
            assert len(st.committed_txs) > 0

        # resume: the recycled cluster keeps committing
        for n in nodes2:
            n.run_async()
        base = min(n.get_last_block_index() for n in nodes2)
        deadline = time.monotonic() + 60
        while (
            min(n.get_last_block_index() for n in nodes2) < base + 1
            and time.monotonic() < deadline
        ):
            proxies2[i % 3].submit_tx(f"tx {i}".encode())
            i += 1
            time.sleep(0.005)
        assert min(n.get_last_block_index() for n in nodes2) >= base + 1
    finally:
        for n in nodes2:
            n.shutdown()


def test_maintenance_mode_blocks_disk_writes(tmp_path):
    """Maintenance mode disables DB writes while the cache keeps working
    (reference: badger_store.go:848-855 maintenanceMode)."""
    keys = [generate_key() for _ in range(2)]
    peers = make_peers(keys)
    db = str(tmp_path / "m.db")
    store = PersistentStore(100, db)
    store.set_peer_set(0, peers)

    ev = Event.new([b"live"], [], [], ["", ""],
                   keys[0].public_key.bytes(), 0)
    ev.sign(keys[0])
    ev.topological_index = 0
    store.set_event(ev)

    store.set_maintenance_mode(True)
    ev2 = Event.new([b"maint"], [], [], [ev.hex(), ""],
                    keys[0].public_key.bytes(), 1)
    ev2.sign(keys[0])
    ev2.topological_index = 1
    store.set_event(ev2)
    # visible through the cache...
    assert store.get_event(ev2.hex()).transactions() == [b"maint"]
    store.close()

    # ...but never persisted: a fresh store sees only the pre-maintenance
    # event
    store2 = PersistentStore(100, db)
    store2.set_peer_set(0, peers)
    assert store2.get_event(ev.hex()).transactions() == [b"live"]
    with pytest.raises(Exception):
        store2.get_event(ev2.hex())
    store2.close()


def test_peer_set_rows_persist_for_bootstrap(tmp_path):
    """Per-round peer-set rows persist across restart and are readable via
    the raw DB accessor; the live interval cache is deliberately NOT
    preloaded (membership must be reconstructed by bootstrap replay — the
    reference's cache-only design, badger_store.go:109-118), so a fresh
    re-registration of the same rounds must not collide."""
    keys = [generate_key() for _ in range(3)]
    peers = make_peers(keys)
    db = str(tmp_path / "ps.db")
    store = PersistentStore(100, db)
    store.set_peer_set(0, peers)
    smaller = peers.with_removed_peer(peers.peers[-1])
    store.set_peer_set(5, smaller)
    store.close()

    store2 = PersistentStore(100, db)
    # raw rows are there for the replay to rebuild from
    assert store2.db_peer_set(0).hash() == peers.hash()
    assert store2.db_peer_set(5).hash() == smaller.hash()
    with pytest.raises(Exception):
        store2.db_peer_set(3)  # no interval semantics on the raw accessor
    # the live cache starts empty: replay re-registers without collision
    store2.set_peer_set(0, peers)
    store2.set_peer_set(5, smaller)
    assert store2.get_peer_set(3).hash() == peers.hash()  # interval
    assert store2.get_peer_set(9).hash() == smaller.hash()
    store2.close()


def test_participant_events_too_late_db_fallback(tmp_path):
    """When the rolling cache has evicted old indexes, participant_events
    falls back to the DB instead of erroring (reference:
    badger_store.go:293-310 TooLate fallback)."""
    keys = [generate_key() for _ in range(1)]
    peers = make_peers(keys)
    db = str(tmp_path / "tl.db")
    cache_size = 4  # tiny: rolling index evicts aggressively
    store = PersistentStore(cache_size, db)
    store.set_peer_set(0, peers)

    k = keys[0]
    prev = ""
    hashes = []
    for i in range(12):
        ev = Event.new([f"tx{i}".encode()], [], [], [prev, ""],
                       k.public_key.bytes(), i)
        ev.sign(k)
        ev.topological_index = i
        store.set_event(ev)
        prev = ev.hex()
        hashes.append(ev.hex())

    # skip=-1 wants the full history; the cache only holds a suffix
    full = store.participant_events(k.public_key.hex(), -1)
    assert full == hashes
    # an old single index resolves through the DB too
    assert store.participant_event(k.public_key.hex(), 1) == hashes[1]
    store.close()


def test_bootstrap_replays_membership_change(tmp_path):
    """A cluster that accepted a JOIN (persisting a new peer-set row) must
    bootstrap from its DBs without colliding on the replayed peer-set
    registration, ending with the same validator set and chain."""
    from babble_tpu.node.state import State as NState

    from test_node_dyn import Bombardier, make_extra_node, wait_until

    network = InmemNetwork()
    nodes, proxies, states, keys = make_persistent_cluster(
        3, network, tmp_path
    )
    genesis = nodes[0].core.genesis_peers
    bomb = Bombardier(proxies).start()
    joiner = None
    jdir = tmp_path / "joiner.db"
    try:
        for n in nodes:
            n.run_async()
        jkey = generate_key()
        joiner, jp = make_extra_node(
            network, nodes[0].core.peers, genesis, "joiner", key=jkey
        )
        joiner.run_async()
        wait_until(
            lambda: joiner.get_state() == NState.BABBLING,
            60.0,
            "joiner never reached BABBLING",
        )
        jid = joiner.get_id()
        wait_until(
            lambda: all(jid in n.core.validators.by_id for n in nodes),
            60.0,
            "join never committed",
        )
        # let a couple more blocks commit so the membership block is
        # durably followed by ordinary ones
        base = min(n.get_last_block_index() for n in nodes)
        wait_until(
            lambda: min(n.get_last_block_index() for n in nodes) >= base + 1,
            60.0,
            "no blocks after join",
        )
    finally:
        bomb.stop()
        for n in nodes:
            n.shutdown()
        if joiner is not None:
            joiner.shutdown()

    chain_len = min(n.get_last_block_index() for n in nodes)
    chain = [nodes[0].get_block(j).body.hash() for j in range(chain_len + 1)]

    # recycle the 3 original nodes from their DBs: bootstrap must replay
    # the PEER_ADD without KEY_ALREADY_EXISTS and rebuild the validators
    network2 = InmemNetwork()
    nodes2, proxies2, states2, _ = make_persistent_cluster(
        3, network2, tmp_path, bootstrap=True, keys=keys
    )
    try:
        for n in nodes2:
            assert n.get_last_block_index() >= chain_len
            for j in range(chain_len + 1):
                assert n.get_block(j).body.hash() == chain[j], f"block {j}"
            jid2 = jkey.public_key.id()
            assert jid2 in n.core.validators.by_id, (
                "replay lost the accepted join"
            )
    finally:
        for n in nodes2:
            n.shutdown()


def test_closed_store_refuses_event_writes(tmp_path):
    """A closed store FAILS event writes instead of dropping them: events
    must be durable before they become visible to gossip, or a node can
    gossip an event, lose it at shutdown, and re-sign a different event at
    the same index after bootstrap — a cross-incarnation self-fork that
    permanently wedges peers holding the first incarnation's event."""
    key = generate_key()
    store = PersistentStore(cache_size=100, path=str(tmp_path / "c.db"))
    peers = make_peers([key])
    store.set_peer_set(0, peers)

    e0 = Event.new([b"pre"], [], [], ["", ""], key.public_key.bytes(), 0)
    e0.sign(key)
    store.set_event(e0)
    store.close()

    e1 = Event.new([b"post"], [], [], [e0.hex(), ""], key.public_key.bytes(), 1)
    e1.sign(key)
    with pytest.raises(StoreError) as err:
        store.set_event(e1)
    assert err.value.kind == StoreErrorKind.CLOSED
    # the refused event is invisible: not even in the in-memory cache, so
    # it can never become this node's head or be gossiped
    with pytest.raises(StoreError):
        store.get_event(e1.hex())
    assert store.known_events()[peers.peers[0].id] == 0

    # the durable prefix survives for the next incarnation (fresh store:
    # empty cache, so this read proves the DB row exists)
    store2 = PersistentStore(cache_size=100, path=str(tmp_path / "c.db"))
    assert store2.get_event(e0.hex()).body.hash() == e0.body.hash()
    with pytest.raises(StoreError):
        store2.get_event(e1.hex())
    store2.close()


# -- an event's row is written once; its annotations are integer columns ----


def _chain(store, n):
    """``n`` signed events, each on the one before, of one creator that
    ``store`` is told of."""
    key = generate_key()
    store.set_peer_set(0, make_peers([key]))
    events, prev = [], ""
    for i in range(n):
        ev = Event.new([f"tx{i}".encode()], [], [], [prev, ""],
                       key.public_key.bytes(), i)
        ev.sign(key)
        events.append(ev)
        prev = ev.hex()
    return events


def _file_rows(path):
    """The events and participant_events tables, read from outside the
    program: {hash: (topo, data bytes, round, lamport, round received)} and
    {(participant, index, hash)}."""
    db = sqlite3.connect(path)
    try:
        events = {r[0]: r[1:] for r in db.execute(
            "SELECT key, topo, CAST(data AS BLOB), round, lamport, "
            "round_received FROM events")}
        index = set(db.execute(
            "SELECT participant, idx, hash FROM participant_events"))
    finally:
        db.close()
    return events, index


def _annotations(ev):
    return ev.round, ev.lamport_timestamp, ev.round_received


# what an event's three write-throughs carry, in the order the hashgraph
# makes them: insert_event, DivideRounds, a sweep's result applied
WRITES = [{}, {"round": 3, "lamport_timestamp": 11}, {"round_received": 5}]


@pytest.mark.parametrize("writes", [1, 2, 3])
def test_an_event_row_is_written_once_and_annotated_in_place(tmp_path, writes):
    path = str(tmp_path / "s.db")
    store = PersistentStore(100, path)
    first, ev = _chain(store, 2)
    store.set_event(first)  # the row under test is not the table's first
    seen = []
    for write in WRITES[:writes]:
        for annotation, value in write.items():
            setattr(ev, annotation, value)
        commits = store.commits
        store.set_event(ev)
        # one committed transaction a call, fresh or re-set: a second
        # connection reads what it carried as soon as the call returns
        assert store.commits == commits + 1
        events, index = _file_rows(path)
        seen.append(events[ev.hex()])
    topo, data = seen[0][:2]
    assert topo == 1 and set(json.loads(data)) == {"Body", "Signature"}
    assert all(row[:2] == (topo, data) for row in seen)
    assert seen[-1][2:] == [(None,) * 3, (3, 11, None), (3, 11, 5)][writes - 1]
    assert index == {(ev.creator(), 0, first.hex()),
                     (ev.creator(), 1, ev.hex())}
    assert (store.event_inserts, store.event_updates) == (2, writes - 1)
    store.close()


def test_an_evicted_event_reloads_annotated_and_a_replay_loads_none(tmp_path):
    store = PersistentStore(4, str(tmp_path / "s.db"))
    events = _chain(store, 12)
    for i, ev in enumerate(events):
        store.set_event(ev)
        ev.set_round(i // 3)
        ev.set_lamport_timestamp(i)
        store.set_event(ev)
        if i < 9:  # the last three stay undetermined
            ev.set_round_received(i // 3 + 1)
            store.set_event(ev)
    reads = store.db_reads
    for i in (0, 4, 10):  # the cache holds 4: the first two come from disk
        got = store.get_event(events[i].hex())
        assert got.hex() == events[i].hex() and got.verify()
        assert _annotations(got) == _annotations(events[i])
    assert store.db_reads == reads + 2
    assert _annotations(events[10]) == (3, 10, None)
    replayed = store.topological_events(0, 100)
    assert [e.hex() for e in replayed] == [e.hex() for e in events]
    assert all(_annotations(e) == (None,) * 3 for e in replayed)
    store.close()


@pytest.mark.parametrize("refused", ["fresh", "re-set"])
def test_an_event_the_cache_refuses_after_the_database_took_it(
        tmp_path, refused):
    """The database is written before memory. A fresh event the cache then
    refuses loses both its rows again; a re-set it refuses leaves the
    durable row where it is."""
    path = str(tmp_path / "s.db")
    store = PersistentStore(4, path)
    events = _chain(store, 14)
    for ev in events[:12]:
        store.set_event(ev)
    before = _file_rows(path)
    if refused == "fresh":
        with pytest.raises(StoreError) as err:
            store.set_event(events[13])  # index 12 never came
        assert err.value.kind == StoreErrorKind.SKIPPED_INDEX
        assert _file_rows(path) == before
        assert (store.event_inserts, store.event_updates) == (13, 0)
    else:
        ev = events[0]  # let go by the cache, behind its rolling window
        ev.set_round(2)
        with pytest.raises(StoreError) as err:
            store.set_event(ev)
        assert err.value.kind == StoreErrorKind.TOO_LATE
        rows, index = _file_rows(path)
        assert index == before[1] and rows.keys() == before[0].keys()
        assert rows[ev.hex()] == before[0][ev.hex()][:2] + (2, None, None)
        assert (store.event_inserts, store.event_updates) == (12, 1)
    store.close()


def _as_written_before_the_columns(path):
    """Rewrite the file at ``path`` (closed) into the format before an
    event's annotations were columns: the events table of that time, each
    row's round, Lamport time and round received inside its JSON."""
    db = sqlite3.connect(path)
    try:
        rows = db.execute(
            "SELECT key, topo, data, round, lamport, round_received "
            "FROM events").fetchall()
        db.executescript("""
            DROP INDEX events_topo; DROP TABLE events;
            CREATE TABLE events (
                key TEXT PRIMARY KEY, topo INTEGER NOT NULL, data TEXT NOT NULL);
            CREATE INDEX events_topo ON events(topo);
        """)
        for key, topo, data, *annotations in rows:
            d = json.loads(data)
            d.update({k: v for k, v in
                      zip(("Round", "Lamport", "RoundReceived"), annotations)
                      if v is not None})
            db.execute("INSERT INTO events VALUES (?, ?, ?)",
                       (key, topo, canonical_dumps(d).decode()))
        db.commit()
    finally:
        db.close()


def test_a_file_written_before_the_columns_opens_reloads_and_replays(tmp_path):
    """An operator who upgrades a ``--store`` validator: the older file gains
    the columns on open, a row not yet re-set is read by its JSON keys, a
    re-set lands in the columns, and ``--bootstrap`` replays the file."""
    from benchmark.harness import durable
    from test_durable_catchup import _backlog, _core, _ingest

    keys, peers, wires, from_id = _backlog()
    path = str(tmp_path / "babble.db")
    store = PersistentStore(10000, path)
    core = _core(keys, peers, store, "host")
    _ingest(core, wires, from_id)
    want = durable.state_of(core.hg)
    store.close()
    written, _index = _file_rows(path)
    _as_written_before_the_columns(path)
    db = sqlite3.connect(path)
    assert [r[1] for r in db.execute("PRAGMA table_info(events)")] == [
        "key", "topo", "data"]
    db.close()

    store = PersistentStore(10000, path)  # opens, and adds the columns
    older, _index = _file_rows(path)
    assert older.keys() == written.keys()
    assert all(row[2:] == (None,) * 3 for row in older.values())
    assert any("RoundReceived" in json.loads(row[1]) for row in older.values())
    for key, row in written.items():  # a cold cache: every one from the file
        assert _annotations(store.get_event(key)) == row[2:]
    store.set_peer_set(0, peers)
    undetermined = next(k for k, row in written.items() if row[4] is None)
    ev = store.get_event(undetermined)
    ev.set_round_received(99)
    store.set_event(ev)
    assert (store.event_inserts, store.event_updates) == (0, 1)
    store.close()
    rows, _index = _file_rows(path)
    assert rows[undetermined] == older[undetermined][:2] + (
        written[undetermined][2:4] + (99,))
    del rows[undetermined], older[undetermined]
    assert rows == older

    store = PersistentStore(10000, path)
    assert _annotations(store.get_event(undetermined))[2] == 99
    core = _core(keys, peers, store, "host")
    core.bootstrap()
    assert durable.state_of(core.hg) == want
    assert core.hg.bootstrap_events_replayed == len(written)
    store.close()


def _round_key(rng):
    """A created event's key: an event hash as the hashgraph writes it, now
    and then one that JSON escapes or that sorts unlike its bytes."""
    if rng.random() < 0.9:
        return "0X%064X" % rng.getrandbits(256)
    return rng.choice(['0X"q"', "0X\\b", "0X\u00e9", "0x\u2603", "0X\n", "0Xa"]) + (
        "%x" % rng.getrandbits(16))


@pytest.mark.parametrize("seed", range(12))
def test_a_round_row_is_the_plain_encoding_after_every_set_round(
        tmp_path, seed):
    """Random sequences of a round's mutators, with rounds rebuilt by
    ``from_dict``, received lists cut or replaced and created entries
    dropped or altered in place between writes: after
    every ``set_round`` the row in the file is ``canonical_dumps(to_dict())``
    byte for byte, and every entry written was either reused or encoded."""
    rng = random.Random(seed)
    path = str(tmp_path / "s.db")
    store = PersistentStore(cache_size=4, path=path)
    outside = sqlite3.connect(path)
    rounds = {r: RoundInfo() for r in range(3)}
    seen = {r: [] for r in rounds}  # keys a round was ever given
    entries = writes = 0
    for _step in range(300):
        r = rng.randrange(len(rounds))
        ri, keys = rounds[r], seen[r]
        op = rng.random()
        if op < 0.3 or not keys:
            key = rng.choice(keys) if keys and rng.random() < 0.2 else (
                _round_key(rng))  # first write wins on a known key
            ri.add_created_event(key, rng.random() < 0.3)
            keys.append(key)
        elif op < 0.45:
            if rng.random() < 0.2:  # a key the round never created
                keys.append(_round_key(rng))
            ri.set_fame(rng.choice(keys), rng.random() < 0.5)
        elif op < 0.6:
            ri.add_received_event(rng.choice(keys))
        elif op < 0.64:
            rounds[r] = RoundInfo.from_dict(json.loads(canonical_dumps(
                ri.to_dict())))
        elif op < 0.67:
            del ri.received_events[rng.randrange(len(ri.received_events) + 1):]
        elif op < 0.69:
            ri.received_events = list(reversed(ri.received_events))
        elif op < 0.71 and ri.created_events:  # edits no mutator makes
            key = rng.choice(list(ri.created_events))
            if rng.random() < 0.5:
                del ri.created_events[key]
            else:
                ri.created_events[key].witness ^= True
        else:
            store.set_round(r, ri)
            writes += 1
            entries += len(ri.created_events) + len(ri.received_events)
            (row,) = outside.execute(
                "SELECT data FROM rounds WHERE idx = ?", (r,)).fetchone()
            assert row.encode() == canonical_dumps(ri.to_dict())
    outside.close()
    assert writes > 50
    assert store.round_entries_reused + store.round_entries_encoded == entries
    assert store.round_entries_reused > store.round_entries_encoded > 0
    assert store.encoded_bytes == store.encoded_bytes_by_table["rounds"]
    store.close()
