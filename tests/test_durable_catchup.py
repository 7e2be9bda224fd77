"""A ``--store`` validator catches up onto disk, is stopped, and recovers by
``--bootstrap`` (deployment ``durable16``) at a small size on the CPU: 4
validators, 600 events, the flush gate at 16 events.

- the program — ``Core.prepare_sync`` / ``Core.sync`` / ``process_sig_pool``
  on a ``PersistentStore`` — against the benchmark's plain reference
  (``benchmark/harness/durable.py``: the database FILE read with ``sqlite3``
  and ``json`` alone, its events through a sequential host hashgraph), before
  the stop and after the replay, on the host path and on the lane a chip
  resolves (pipelined sweeps through the batcher);
- what the deployment forced in the program: a replay reads no derived row of
  the previous incarnation back (``PersistentStore._fetch_derived``), applies
  the last deferred sweep before the write gate reopens
  (``Hashgraph.bootstrap`` drains), and so leaves the file as it found it;
- the spans and counters the durable store brought, which a validator with
  an ``InmemStore`` never opens;
- a replay verifies each batch it loads in one native call before the
  batch's first insert, as a sync does (``Core.bootstrap`` hands
  ``Hashgraph.bootstrap`` ``Core._batch_prevalidate``), or by singles at
  insert where there is no batch verifier: the same state, every signature
  verified, and a row altered from outside refused where it stands.
"""

from __future__ import annotations

import base64
import json
import os
import shutil
import sqlite3

import pytest

from babble_tpu import native_crypto
from babble_tpu.common.errors import StoreError
from babble_tpu.crypto import batch as sig_batch
from babble_tpu.crypto.canonical import canonical_dumps
from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.block import Block
from babble_tpu.hashgraph.frame import Frame
from babble_tpu.hashgraph.errors import InvalidSignatureError
from babble_tpu.hashgraph.persistent_store import PersistentStore
from babble_tpu.hashgraph.round_info import RoundInfo
from babble_tpu.node.core import Core
from babble_tpu.node.validator import Validator
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.proxy.proxy import dummy_commit_response
from benchmark.harness import churn, data, durable
from benchmark.harness.counters import node_snapshot

N, ME, EVENTS, SYNC = 4, 0, 600, 300
SEED, DAG_SEED = 3000000019, 2147487920


def _backlog():
    keys = data.seeded_keys(N, SEED)
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(N)])
    creators = [i for i in range(N) if i != ME]
    wires = data.backlog_wire_events(keys, peers, creators, EVENTS, DAG_SEED,
                                     100)
    from_id = peers.by_pub_key[keys[creators[0]].public_key.hex()].id
    return keys, peers, wires, from_id


def _core(keys, peers, store, mode):
    """v0's core as ``Node`` builds it, with ``--accelerator`` in mode
    ``chip-lane``: the flush gate scaled to a 4-validator window, compiles
    inline, pipelined sweeps through the batcher."""
    core = Core(Validator(keys[ME], "v0"), peers, peers, store,
                dummy_commit_response, accelerated_verify=mode != "host")
    tc = core.hg.accel
    if tc is not None:
        tc.min_window, tc.async_compile = 16, False
        tc.pipeline, tc.batcher = True, True
    return core


def _sampled(hg, every=100):
    """The undetermined window after every ``every``-th insert."""
    samples, inserts, insert = [], [0], hg.insert_event_and_run_consensus

    def counted(event, set_wire_info=False):
        insert(event, set_wire_info)
        inserts[0] += 1
        if inserts[0] % every == 0:
            samples.append(len(hg.undetermined_events))

    hg.insert_event_and_run_consensus = counted
    return samples


def _ingest(core, wires, from_id):
    for chunk in data.chunks(wires, SYNC):
        prepared = core.prepare_sync(chunk)
        core.sync(from_id, chunk, prepared)
        core.process_sig_pool()
    core.hg.drain_consensus()


class _Run:
    """One validator's life: ingest, the file read from outside, a clean
    stop, the replay — everything the tests below look at."""

    def __init__(self, path, mode):
        keys, self.peers, wires, from_id = _backlog()
        self.own = keys[ME].public_key.hex()
        self.path = path
        store = PersistentStore(10000, path)
        core = _core(keys, self.peers, store, mode)
        self.ingest_window = _sampled(core.hg)
        _ingest(core, wires, from_id)
        self.before = durable.read(path)  # a second connection, no close yet
        self.ingested = durable.state_of(core.hg)
        self.ingest_commits = store.commits
        self.ingest_reads = store.db_reads
        self.event_writes = (store.event_inserts, store.event_updates)
        self.ingest_sweeps = (core.hg.accel.stats()["accel_sweeps"]
                              if core.hg.accel is not None else 0)
        store.close()

        store = PersistentStore(10000, path)
        core = _core(keys, self.peers, store, mode)
        self.replay_window = _sampled(core.hg)
        # Hashgraph.init has written the genesis peer-set row again, as it
        # was: one commit before the replay, none inside it
        commits, reads = store.commits, store.db_reads
        core.bootstrap()
        core.set_head_and_seq()
        self.replay_commits = store.commits - commits
        self.replay_reads = store.db_reads - reads
        self.replayed = durable.state_of(core.hg)
        self.events_replayed = core.hg.bootstrap_events_replayed
        self.events_batch_verified = core.hg.bootstrap_events_batch_verified
        self.head_seq = (core.head, core.seq)
        self.after = durable.read(path)
        store.close()
        self.want = durable.replay(self.before, self.peers)


@pytest.fixture(scope="module", params=["host", "chip-lane"])
def run(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("durable") / "babble.db"
    return _Run(str(path), request.param), request.param


def test_acknowledged_means_durable(run):
    r, _mode = run
    assert EVENTS - r.before.events_from_others(r.own) == 0
    counts = r.before.row_counts()
    assert counts["events"] == counts["participant_events"] > EVENTS
    assert r.before.max_topo == counts["events"] - 1
    assert counts["blocks"] == len(r.ingested.blocks) > 50
    assert durable.blocks_on_disk_differing(r.before, r.want.blocks) == 0


def test_the_validator_equals_the_reference_before_the_stop(run):
    r, mode = run
    assert r.ingested == r.want
    assert r.want.ordered > EVENTS - 50 and len(r.want.undetermined) > 10
    if mode != "host":
        assert r.ingest_sweeps > 0


def test_bootstrap_recomputes_what_a_sequential_validator_reaches(run):
    r, _mode = run
    assert r.events_replayed == len(r.before.event_rows)
    if sig_batch.available():  # `Core` then verifies by the batch
        assert r.events_batch_verified == r.events_replayed
    assert durable.blocks_differing(r.replayed.blocks, r.want.blocks) == 0
    assert r.replayed.ordered == r.want.ordered
    assert r.replayed.last_consensus_round == r.want.last_consensus_round
    assert r.replayed.undetermined == r.want.undetermined  # the SET
    assert r.replayed.pending_rounds == r.want.pending_rounds
    # the validator's own events came back too: it resumes after them
    assert r.head_seq[1] == r.before.row_counts()["events"] - EVENTS - 1


def test_the_replay_leaves_the_database_as_it_found_it(run):
    r, _mode = run
    assert durable.rows_changed(r.before, r.after) == 0
    assert r.replay_commits == 0
    # and reads nothing of the previous incarnation's back
    assert r.replay_reads == 0


def test_the_replay_window_stays_as_bounded_as_the_ingest_window(run):
    """With the previous incarnation's rounds read back, the host path's
    window read 301, 801, 1,502 after 300, 800, 1,500 replayed events."""
    r, _mode = run
    assert len(r.replay_window) == len(r.ingest_window) == 6
    assert max(r.replay_window) <= max(r.ingest_window) + 100


def test_ingest_commits_and_reads(run):
    r, _mode = run
    n = r.before.row_counts()["events"]
    # an event is written at insert and again as its round, Lamport time
    # and round received are assigned; rounds, blocks and frames besides
    assert 3 * n < r.ingest_commits < 8 * n
    # a parent that came in the same sync is asked of the store first
    # (by index), and a new round's and frame's first lookups: no more
    assert EVENTS < r.ingest_reads < 3 * n


# SQLite transactions an ingest commits, as read on the parent of the PR
# that made the annotations columns (host: the same count every time; on the
# chip's lane a round is set once a sweep, and the sweeps, 2 to 3, are the
# pipeline's timing: 2,787 and 2,813)
PARENT_COMMITS = {"host": (4003, 4003), "chip-lane": (2700, 2900)}


def test_an_event_row_is_inserted_once_and_annotated_in_place_twice(run):
    """One commit a ``set_event`` as before: only what it carries changed."""
    r, mode = run
    n = r.before.row_counts()["events"]
    inserts, updates = r.event_writes
    assert inserts == n
    # the round and the Lamport time at the insert's own DivideRounds, the
    # round received once a sweep's result (host: the pass) is applied
    assert updates == n + r.want.ordered
    least, most = PARENT_COMMITS[mode]
    assert least <= r.ingest_commits <= most
    # and the file holds them as columns, the row's JSON as first written
    db = sqlite3.connect(r.path)
    try:
        rows = db.execute(
            "SELECT data, round, lamport, round_received FROM events"
        ).fetchall()
    finally:
        db.close()
    assert all(set(json.loads(data)) == {"Body", "Signature"}
               and rnd is not None and lamport is not None
               for data, rnd, lamport, _received in rows)
    assert sum(rr is not None for *_, rr in rows) == r.want.ordered


def test_a_store_that_drops_every_other_event_write_reads_not_on_disk(
        tmp_path):
    class Dropping(PersistentStore):
        """Every other event it is given is acknowledged and never
        persisted, at insert or when its annotations are set."""

        def __init__(self, *args):
            super().__init__(*args)
            self.seen = {}

        def _persist_event(self, event):
            drop = self.seen.setdefault(event.hex(), len(self.seen) % 2 == 1)
            return False if drop else super()._persist_event(event)

    keys, peers, wires, from_id = _backlog()
    path = str(tmp_path / "babble.db")
    store = Dropping(10000, path)
    _ingest(_core(keys, peers, store, "host"), wires, from_id)
    db = durable.read(path)
    store.close()
    own = keys[ME].public_key.hex()
    assert EVENTS - db.events_from_others(own) > EVENTS // 3


def test_a_replay_reads_a_derived_row_back_only_once_it_has_set_it(tmp_path):
    path = str(tmp_path / "babble.db")
    store = PersistentStore(2, path)
    for r in range(3):
        store.set_round(r, RoundInfo())
    store.close()

    store = PersistentStore(2, path)  # the next incarnation: a cold cache
    assert store.db_reads == 0
    store.set_maintenance_mode(True)
    with pytest.raises(StoreError):
        store.get_round(0)  # the previous incarnation's: not this one's
    assert store.db_reads == 0
    for r in range(3):
        store.set_round(r, RoundInfo())  # recomputed; round 0 leaves the LRU
    assert isinstance(store.get_round(0), RoundInfo)
    assert store.db_reads == 1 and store.commits == 0
    with pytest.raises(StoreError):
        store.get_block(0)
    store.set_maintenance_mode(False)
    assert isinstance(store.get_round(1), RoundInfo)
    store.close()


# -- a replay's signatures: one native call a loaded batch, or singles -------

LANES = pytest.mark.parametrize("lane", ["batch", "singles"])


class _Stopped:
    """A database a host-path validator left behind, read from outside,
    with what the reference makes of it (``want`` is None for a file the
    reference is not asked about)."""

    def __init__(self, path, keys, peers, wires, from_id, referee=True):
        self.path, self.keys, self.peers = path, keys, peers
        store = PersistentStore(10000, path)
        _ingest(_core(keys, peers, store, "host"), wires, from_id)
        self.db = durable.read(path)
        store.close()
        self.want = durable.replay(self.db, peers) if referee else None

    def restart(self, lane, path=None):
        """v0's next incarnation on ``path``, before its replay."""
        if lane == "batch" and not sig_batch.available():
            pytest.skip("no native batch verifier on this host")
        store = PersistentStore(10000, path or self.path)
        core = _core(self.keys, self.peers, store, "host")
        if lane == "singles":
            core._host_batch_verify = False  # a host without the library
        return core, store


@pytest.fixture(scope="module")
def stopped(tmp_path_factory):
    path = tmp_path_factory.mktemp("stopped") / "babble.db"
    keys, peers, wires, from_id = _backlog()
    return _Stopped(str(path), keys, peers, wires, from_id)


@pytest.fixture(scope="module")
def stopped_after_changes(tmp_path_factory):
    """A file whose history holds three membership changes: 4 genesis
    validators, 2 joiners, one leaver (``test_churn_catchup.py``'s)."""
    path = tmp_path_factory.mktemp("changes") / "babble.db"
    keys = data.seeded_keys(6, SEED)
    peers = churn.all_peers(keys, N)
    genesis = PeerSet(peers[:N])
    _script, wires = churn.churn_script(
        keys, peers, genesis, [i for i in range(N) if i != ME],
        churn.parse_requests(["+x0", "-v3", "+x1"], N), EVENTS, 2147489957,
        40, 180, 100)
    return _Stopped(str(path), keys, genesis, wires, peers[1].id,
                    referee=False)


@LANES
def test_a_replay_verifies_every_signature_in_either_lane(
        stopped, lane, monkeypatch):
    core, store = stopped.restart(lane)
    singles, verify_one = [0], native_crypto.verify_one

    def counted(*args):
        singles[0] += 1
        return verify_one(*args)

    monkeypatch.setattr(native_crypto, "verify_one", counted)
    with sig_batch._VERDICTS_LOCK:
        sig_batch._VERDICTS.purge()  # a cold verdict cache, as a restart's
    misses = sig_batch.VERIFY_CACHE.misses
    core.bootstrap()
    got = durable.state_of(core.hg)
    store.close()
    n = len(stopped.db.event_rows)  # one signature each: no request rides
    # v0's own events carry its signatures of the blocks it committed:
    # the sig pool verifies each alone, in a replay as after a sync
    block_sigs = sum(len(json.loads(row)["Body"]["BlockSignatures"])
                     for row in stopped.db.event_rows)
    # the same blocks, last round, ordered count and SET of undetermined
    # events as the sequential host hashgraph, so as the other lane
    assert got == stopped.want
    assert core.hg.bootstrap_events_replayed == n > EVENTS
    # nothing skipped: every signature went to the verifier, once
    if lane == "batch":
        assert core.hg.bootstrap_events_batch_verified == n
        assert sig_batch.VERIFY_CACHE.misses - misses == n
        assert core.ingest_batch_verifies == -(-n // 100)  # one a batch
        assert core.ingest_fallback_singles == 0
        assert singles[0] == block_sigs > 0
    else:
        assert core.hg.bootstrap_events_batch_verified == 0
        assert sig_batch.VERIFY_CACHE.misses - misses == 0
        assert core.ingest_batch_verifies == 0
        assert singles[0] == n + block_sigs


def _alter_signature(path, internal):
    """From outside, with ``sqlite3`` alone: one character of a row's
    ``Signature`` — the creator's, or its internal transaction's — becomes
    another. An event's hash covers its internal transactions' signatures,
    so the second alters both verdicts; nothing else of the file moves.
    Returns the row's place in the replay and who made it."""
    db = sqlite3.connect(path)
    try:
        rows = db.execute("SELECT topo, data FROM events ORDER BY topo")
        rows = [(k, json.loads(d)) for k, d in rows]
        if internal:
            k, row = [(k, r) for k, r in rows
                      if r["Body"]["InternalTransactions"]][-1]
            holder = row["Body"]["InternalTransactions"][0]
        else:
            k, row = rows[250]
            holder = row
        sig = holder["Signature"]
        holder["Signature"] = sig[:-1] + ("1" if sig[-1] == "0" else "0")
        db.execute("UPDATE events SET data = ? WHERE topo = ?",
                   (json.dumps(row), k))
        db.commit()
    finally:
        db.close()
    return k, (row["Body"]["Creator"], row["Body"]["Index"])


@LANES
@pytest.mark.parametrize("altered", ["creator", "internal-transaction"])
def test_a_row_altered_from_outside_stops_the_replay_where_it_stands(
        stopped, stopped_after_changes, altered, lane, tmp_path):
    internal = altered != "creator"
    origin = stopped_after_changes if internal else stopped
    # a store that was closed leaves one file, no `-wal` beside it
    path = shutil.copy(origin.path, str(tmp_path / "babble.db"))
    k, (creator, index) = _alter_signature(path, internal)
    assert k % 100 and k > 100  # inside a batch, whole batches before it
    found = durable.read(path)
    core, store = origin.restart(lane, path)
    with pytest.raises(InvalidSignatureError) as refused:
        core.bootstrap()
    ev = refused.value.event
    assert (base64.b64encode(ev.body.creator).decode(), ev.index()) == (
        creator, index)
    # every earlier event of its batch is in, none after it
    assert core.hg.topological_index == k
    assert sum(i + 1 for i in core.hg.store.known_events().values()) == k
    assert core.hg.bootstrap_events_replayed == k - k % 100  # whole batches
    # the batch flagged it; the scalar verifier had the last word
    assert core.ingest_fallback_singles == (1 if lane == "batch" else 0)
    assert core.ingest_fallback_skipped == 0
    assert durable.rows_changed(found, durable.read(path)) == 0
    store.close()


def _node(store, bootstrap=False):
    """A ``Node`` as ``engine.py`` builds it around ``store``, host path,
    through ``Node.init()`` (which replays when ``bootstrap``), not started."""
    from babble_tpu.config.config import Config
    from babble_tpu.dummy.state import State as DummyState
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.node import Node
    from babble_tpu.proxy.proxy import InmemProxy

    keys, peers, wires, from_id = _backlog()
    durable_store = isinstance(store, PersistentStore)
    conf = Config(
        bind_addr="inmem://v0", moniker="v0", log_level="error",
        no_service=True, store=durable_store, bootstrap=bootstrap,
        database_dir=(os.path.dirname(store.store_path())
                      if durable_store else ""))
    node = Node(conf, Validator(keys[ME], "v0"), peers, peers, store,
                InmemNetwork().new_transport("inmem://v0"),
                InmemProxy(DummyState()))
    node.init()
    return node, wires, from_id


def test_the_store_spans_and_counters_reach_a_node_snapshot(tmp_path):
    path = str(tmp_path / "babble.db")
    node, wires, from_id = _node(PersistentStore(10000, path))
    _ingest(node.core, wires, from_id)
    snap = node_snapshot(node)
    store = node.core.hg.store
    assert snap["store_commits"] == store.commits > 3 * EVENTS
    assert snap["store_db_reads"] == store.db_reads > EVENTS
    assert snap["store_event_inserts"] == store.event_inserts > EVENTS
    assert snap["store_event_updates"] == store.event_updates > (
        store.event_inserts)
    # every commit is inside a `store_write` span, a child of what wrote,
    # but the genesis peer-set's, written before the core has a tracer
    assert snap["sync_stage_seconds.store_write.count"] == store.commits - 1
    assert 0 < snap["sync_stage_seconds.store_write.sum"] < (
        snap["sync_stage_seconds.sync.sum"])
    # a derived row is serialised in `store_encode`, then written
    assert snap["store_encoded_bytes"] == sum(
        snap[f"store_encoded_bytes_by_table.{t}"] for t in DERIVED) > 0
    # most of a round row's entries come from its last encoding
    assert snap["store_round_entries_reused"] > (
        snap["store_round_entries_encoded"]) > 0
    assert 0 < snap["sync_stage_seconds.store_encode.count"] < (
        snap["sync_stage_seconds.store_write.count"])
    assert snap["bootstrap_events_replayed"] == 0
    assert "sync_stage_seconds.bootstrap.count" not in snap
    node.shutdown()

    # the restart, as engine.py does it: bootstrap=True on the same file
    again, _wires, _from_id = _node(PersistentStore(10000, path),
                                    bootstrap=True)  # replays inside init
    snap = node_snapshot(again)
    n = snap["bootstrap_events_replayed"]
    assert n == snap["sync_stage_seconds.insert.count"] > EVENTS
    if sig_batch.available():  # each loaded batch in one `batch_verify`
        assert snap["bootstrap_events_batch_verified"] == n
        assert snap["sync_stage_seconds.batch_verify.count"] == -(-n // 100)
        assert snap["sync_stage_seconds.batch_verify.sum"] < (
            snap["sync_stage_seconds.bootstrap.sum"])
    assert snap["sync_stage_seconds.bootstrap.count"] == 1
    assert snap["sync_stage_seconds.bootstrap_load.count"] == n // 100 + 1
    assert snap["sync_stage_seconds.bootstrap_load.sum"] < (
        snap["sync_stage_seconds.bootstrap.sum"])
    # the genesis peer-set row, written again as it was before the replay
    assert snap["store_commits"] == 1 and snap["store_db_reads"] == 0
    assert snap["store_event_inserts"] == snap["store_event_updates"] == 0
    assert "sync_stage_seconds.store_write.count" not in snap
    # the replay's rounds, frames and blocks: noted, never built
    assert snap["sync_stage_seconds.store_encode.count"] > 0
    assert snap["store_encoded_bytes"] == 0
    assert snap["store_round_entries_reused"] == 0
    assert snap["store_round_entries_encoded"] == 0
    assert again.core.seq == n - EVENTS - 1
    again.shutdown()


# the table a derived row's `_write` commits to, and its key column
DERIVED = {"rounds": "idx", "frames": "round", "blocks": "idx"}


@pytest.mark.parametrize("mode", ["host", "chip-lane"])
def test_the_encoded_bytes_are_those_of_the_rows_committed(tmp_path, mode):
    """``store_encoded_bytes_by_table`` against the rows ``_write``
    committed, each read back from the file by a connection of its own
    right after its commit (a round row is rewritten many times)."""
    path = str(tmp_path / "babble.db")
    keys, peers, wires, from_id = _backlog()
    store = PersistentStore(10000, path)
    core = _core(keys, peers, store, mode)
    outside = sqlite3.connect(path)
    committed = {table: [0, 0] for table in DERIVED}  # writes, bytes
    write = store._write

    def read_back(sql, args):
        write(sql, args)
        table = sql.split()[4]  # INSERT OR REPLACE INTO <table> ...
        if table in DERIVED:
            (n,) = outside.execute(
                f"SELECT length(CAST(data AS BLOB)) FROM {table} "
                f"WHERE {DERIVED[table]} = ?", (args[0],)).fetchone()
            committed[table][0] += 1
            committed[table][1] += n

    store._write = read_back
    _ingest(core, wires, from_id)
    outside.close()
    spans = core.obs.registry.snapshot()["sync_stage_seconds"]
    store.close()
    got = store.encoded_bytes_by_table
    assert got == {table: b for table, (_w, b) in committed.items()}
    assert store.encoded_bytes == sum(got.values())
    assert all(b > 0 for b in got.values())
    # a round row is the largest share, and written once a DivideRounds
    # that changed it: about once an event
    assert got["rounds"] > got["blocks"]
    assert committed["rounds"][0] > EVENTS / 2
    # one `store_encode` a derived row, beside its `store_write`
    assert spans["store_encode"]["count"] == sum(
        w for w, _b in committed.values())


@LANES
def test_a_replay_opens_store_encode_and_encodes_nothing(stopped, lane):
    """The write gate shuts before anything is built: the span opens for
    each round, frame and block the replay sets, around the note of its
    key, and no byte is serialised or written."""
    core, store = stopped.restart(lane)
    core.bootstrap()
    spans = core.obs.registry.snapshot()["sync_stage_seconds"]
    store.close()
    assert spans["store_encode"]["count"] >= (
        stopped.db.row_counts()["blocks"] + stopped.db.row_counts()["rounds"])
    assert spans["store_encode"]["sum"] > 0
    assert "store_write" not in spans
    assert store.encoded_bytes == 0
    assert store.encoded_bytes_by_table == dict.fromkeys(DERIVED, 0)


@pytest.mark.parametrize("mode", ["host", "chip-lane"])
def test_every_derived_row_is_the_plain_encoding_and_a_replay_builds_none(
        tmp_path, mode, monkeypatch):
    """Every rounds / frames / blocks row an ingest commits equals
    ``canonical_dumps(obj.to_dict())`` of the object it was set from, as the
    parent encoded it, and ``store_encoded_bytes`` adds up the same bytes;
    most of a round row's entries are taken from its last encoding. Then a
    replay of the file builds no row's ``to_dict()`` behind the shut write
    gate and leaves the file as it found it."""
    path = str(tmp_path / "babble.db")
    keys, peers, wires, from_id = _backlog()
    store = PersistentStore(10000, path)
    outside = sqlite3.connect(path)
    plain = {table: [0, 0, 0] for table in DERIVED}  # rows, bytes, differing
    entries = [0]
    write_derived = store._write_derived

    def beside_the_parent(table, sql, key, obj, *encode):
        want = canonical_dumps(obj.to_dict())
        write_derived(table, sql, key, obj, *encode)
        (row,) = outside.execute(
            f"SELECT data FROM {table} WHERE {DERIVED[table]} = ?",
            (key,)).fetchone()
        tally = plain[table]
        tally[0] += 1
        tally[1] += len(want)
        tally[2] += row.encode() != want
        if table == "rounds":
            entries[0] += len(obj.created_events) + len(obj.received_events)

    store._write_derived = beside_the_parent
    _ingest(_core(keys, peers, store, mode), wires, from_id)
    outside.close()
    assert all(rows > 0 and differing == 0
               for rows, _b, differing in plain.values())
    assert store.encoded_bytes_by_table == {
        table: b for table, (_r, b, _d) in plain.items()}
    reused, encoded = store.round_entries_reused, store.round_entries_encoded
    assert reused + encoded == entries[0]
    assert reused > 2 * encoded  # rounds of ≈ 15 events here, ≈ 100 in the cell
    before = durable.read(path)
    store.close()

    store = PersistentStore(10000, path)
    core = _core(keys, peers, store, mode)
    built = []
    for cls in (RoundInfo, Frame, Block):
        def counted(obj, _to_dict=cls.to_dict, _name=cls.__name__):
            if store._maintenance:
                built.append(_name)
            return _to_dict(obj)
        monkeypatch.setattr(cls, "to_dict", counted)
    core.bootstrap()
    spans = core.obs.registry.snapshot()["sync_stage_seconds"]
    after = durable.read(path)
    store.close()
    assert built == []
    assert durable.rows_changed(before, after) == 0
    assert spans["store_encode"]["count"] >= before.row_counts()["rounds"]
    assert store.round_entries_reused == store.round_entries_encoded == 0
    assert store.encoded_bytes == 0


def test_a_validator_with_an_inmem_store_opens_none_of_it():
    node, wires, from_id = _node(InmemStore(10000))
    _ingest(node.core, wires, from_id)
    snap = node_snapshot(node)
    assert snap["store_commits"] == snap["store_db_reads"] == 0
    assert snap["store_event_inserts"] == snap["store_event_updates"] == 0
    assert snap["bootstrap_events_replayed"] == 0
    assert snap["bootstrap_events_batch_verified"] == 0
    assert snap["store_encoded_bytes"] == 0
    assert snap["store_round_entries_reused"] == 0
    assert snap["store_round_entries_encoded"] == 0
    assert not [k for k in snap if "store_write" in k or "bootstrap." in k
                or "bootstrap_load" in k or ".store_encode." in k]
    assert snap["sync_stage_seconds.insert.count"] > EVENTS
    node.shutdown()
