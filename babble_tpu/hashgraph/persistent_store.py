"""PersistentStore — write-through durable store over SQLite.

The tpu-native equivalent of the reference's BadgerStore
(/root/reference/src/hashgraph/badger_store.go:28-100): an InmemStore
LRU cache in front, with every event/round/block/frame/peer-set written
through to an embedded KV (SQLite, stdlib — this image ships no badger).
Reads fall back to the DB on cache miss or rolling-index eviction
(TooLate), mirroring badger_store.go:293-310.

Bootstrap (`--bootstrap`) replays the whole DB topologically through
consensus to rebuild in-memory state — "WE CAN ONLY BOOTSTRAP FROM 0"
(reference: hashgraph.go:1481-1536); Hashgraph.bootstrap drives it via
``topological_events`` and flips ``set_maintenance_mode`` so the replay
doesn't rewrite the DB — nor read it as its own: while the mode is on, a
round, frame or block comes back from the DB only once the replay has set
that key itself (``_fetch_derived``).
"""

from __future__ import annotations

import json
import os
import sqlite3
import threading
from bisect import bisect_left
from json.encoder import encode_basestring_ascii
from operator import attrgetter
from typing import Dict, List, NamedTuple, Optional

from babble_tpu.common.errors import StoreError, StoreErrorKind
from babble_tpu.crypto.canonical import (
    PreNormalized,
    canonical_dumps,
    canonical_loads,
    canonical_scalar,
)
from babble_tpu.hashgraph.block import Block
from babble_tpu.hashgraph.event import Event, EventBody
from babble_tpu.hashgraph.frame import Frame, Root
from babble_tpu.hashgraph.round_info import RoundInfo
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.obs.trace import NULL_STAGE
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet

# An event's row is written once: `data` holds its body and signature and
# is never touched again; the consensus annotations (write-once once
# assigned, NULL until then) are integer columns set in place. Beside each
# column, the key a row written before the columns existed carries in its
# JSON instead.
_EVENT_ANNOTATIONS = (
    ("round", "Round"),
    ("lamport", "Lamport"),
    ("round_received", "RoundReceived"),
)
_SCHEMA = """
CREATE TABLE IF NOT EXISTS events (
    key TEXT PRIMARY KEY, topo INTEGER NOT NULL, data TEXT NOT NULL,
    round INTEGER, lamport INTEGER, round_received INTEGER);
CREATE INDEX IF NOT EXISTS events_topo ON events(topo);
CREATE TABLE IF NOT EXISTS participant_events (
    participant TEXT NOT NULL, idx INTEGER NOT NULL, hash TEXT NOT NULL,
    PRIMARY KEY (participant, idx));
CREATE TABLE IF NOT EXISTS rounds (idx INTEGER PRIMARY KEY, data TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS blocks (idx INTEGER PRIMARY KEY, data TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS frames (round INTEGER PRIMARY KEY, data TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS peer_sets (round INTEGER PRIMARY KEY, data TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS roots (participant TEXT PRIMARY KEY, data TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS evidence (key TEXT PRIMARY KEY, data TEXT NOT NULL);
"""

# A round's row is `canonical_dumps(round_info.to_dict())`; the store keeps
# on the RoundInfo (``store_row``) what it encoded that row from, and encodes
# the next one from the entries that changed since (``_encode_round``).
_ENTRY_STATE = attrgetter("witness", "famous")


class _RoundRow(NamedTuple):
    keys: list  # created events, in the dict's order
    states: list  # their (witness, famous), in the same order
    sorted_keys: list  # the same keys, in the row's (sorted) order
    fragments: list  # '"<hash>":{"Famous":f,"Witness":w}', in that order
    created_text: str  # the fragments joined
    received: list  # the received events encoded
    received_text: str  # ... joined


_EMPTY_ROW = _RoundRow([], [], [], [], "", [], "")


def _entry_fragment(key: str, state: tuple) -> str:
    """One created event as ``canonical_dumps`` writes it inside the row."""
    witness, famous = state
    return (
        f'{encode_basestring_ascii(key)}:{{"Famous":{int(famous)},'
        f'"Witness":{canonical_scalar(witness).decode()}}}'
    )


class PersistentStore:
    """Write-through store: InmemStore cache + SQLite persistence."""

    def __init__(self, cache_size: int = 10000, path: str = "babble.db"):
        self._path = path
        self._inmem = InmemStore(cache_size)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._db = sqlite3.connect(path, check_same_thread=False)
        self._db_lock = threading.Lock()
        with self._db_lock:
            # Declared BEFORE the first table exists so a fresh DB gets
            # incremental vacuum (checkpoint-prune frees pages back to the
            # OS without a full rebuild). On a pre-existing DB this is a
            # no-op until a full VACUUM — vacuum(incremental=False) covers
            # that upgrade path.
            self._db.execute("PRAGMA auto_vacuum=INCREMENTAL")
            self._db.executescript(_SCHEMA)
            # A file written before the annotations were columns keeps
            # them inside each row's JSON: it gains the columns here, and
            # a row whose column is NULL is read by its JSON key
            # (_event_from_row).
            have = {r[1] for r in self._db.execute("PRAGMA table_info(events)")}
            for column, _legacy_key in _EVENT_ANNOTATIONS:
                if column not in have:
                    self._db.execute(
                        f"ALTER TABLE events ADD COLUMN {column} INTEGER"
                    )
            self._db.execute("PRAGMA journal_mode=WAL")
            self._db.execute("PRAGMA synchronous=NORMAL")
            row = self._db.execute("SELECT MAX(topo) FROM events").fetchone()
        self._next_topo = (row[0] + 1) if row and row[0] is not None else 0
        # maintenanceMode disables DB writes during bootstrap replay
        # (reference: badger_store.go:848-855)
        self._maintenance = False
        # Derived rows (table -> keys) the replay has itself recomputed.
        # While the write gate is shut the rounds / frames / blocks tables
        # still hold the PREVIOUS incarnation's decisions: a cache miss may
        # fall back to such a row only once this incarnation has set the
        # same key (it then lost it to the LRU, long decided) — never
        # before, or the replay would take a round's witnesses and their
        # fame from a future it has not inserted yet.
        self._replayed: Dict[str, set] = {}
        # A node's span tracer (obs/trace.py; Core hands it over), or None:
        # `store_write` spans around every write-through, `store_encode`
        # around a derived row's serialisation. The tallies are plain ints
        # read by Node.get_stats_snapshot().
        self.stage_observer = None
        self.commits = 0  # SQLite transactions committed by a write
        self.db_reads = 0  # reads that fell through the cache to the DB
        self.event_inserts = 0  # set_event calls that wrote the event's row
        self.event_updates = 0  # ... that set a durable row's annotations
        # bytes of the derived rows serialised and committed, and by table
        self.encoded_bytes = 0
        self.encoded_bytes_by_table = {"rounds": 0, "frames": 0, "blocks": 0}
        # of the round rows' entries (created and received events), those
        # taken from the kept state and those encoded anew (_encode_round)
        self.round_entries_reused = 0
        self.round_entries_encoded = 0
        # NOTE: persisted peer-sets are deliberately NOT preloaded into the
        # interval cache. The reference's design comment
        # (badger_store.go:109-118) applies verbatim: membership state must
        # be reconstructed by replaying events through consensus
        # (Bootstrap), which re-registers each peer-set at its effective
        # round — preloading would make that replay collide with
        # KEY_ALREADY_EXISTS. db_peer_set() exposes the raw rows.

    # -- maintenance --------------------------------------------------------

    def set_maintenance_mode(self, on: bool) -> None:
        self._maintenance = on
        self._replayed = {}

    def _fetch_derived(self, table: str, sql: str, key: int) -> Optional[tuple]:
        """DB fallback for a rounds / frames / blocks row; during a
        bootstrap replay only for a key the replay has already set."""
        if self._maintenance and key not in self._replayed.get(table, ()):
            return None
        return self._fetch(sql, (key,))

    def _write_derived(
        self, table: str, sql: str, key: int, obj, encode=None
    ) -> None:
        """Write a rounds / frames / blocks row through: its encoding (the
        span ``store_encode``; ``canonical_dumps(obj.to_dict())`` unless
        ``encode`` is given), then ``_write``. Gated off during a bootstrap
        replay, which only notes the key as recomputed and builds nothing."""
        with self._span("store_encode"):
            if self._maintenance:
                self._replayed.setdefault(table, set()).add(key)
                return
            if encode is None:
                text = canonical_dumps(obj.to_dict()).decode()
            else:
                text = encode(obj)
        self._write(sql, (key, text))
        # canonical JSON is ASCII: a character is a byte
        self.encoded_bytes += len(text)
        self.encoded_bytes_by_table[table] += len(text)

    def _encode_round(self, ri: RoundInfo) -> str:
        """``canonical_dumps(ri.to_dict())``, byte for byte, from what
        ``ri.store_row`` kept of the last encoding: a created event is
        encoded again only if it is new or its (witness, famous) differs
        from the kept state, a received event only past the kept prefix.
        Where the round no longer extends what was kept (a created key
        gone, a received list that is not the kept prefix followed by
        more), that part is encoded from nothing."""
        created = ri.created_events
        keys = list(created)
        states = list(map(_ENTRY_STATE, created.values()))
        received = list(ri.received_events)
        kept = ri.store_row or _EMPTY_ROW
        n, kept_states = len(kept.keys), kept.states
        if keys[:n] != kept.keys:
            n, kept_states = 0, []
        sorted_keys, fragments = kept.sorted_keys, kept.fragments
        created_text = kept.created_text
        changed = ()
        if states[:n] != kept_states:
            changed = [
                i for i, state in enumerate(kept_states) if states[i] != state
            ]
        encoded = len(keys) - n + len(changed)
        if n == 0:
            order = sorted(range(len(keys)), key=keys.__getitem__)
            sorted_keys = [keys[i] for i in order]
            fragments = [_entry_fragment(keys[i], states[i]) for i in order]
        elif encoded:
            # copies, so that the kept state is always one whole encoding
            sorted_keys, fragments = sorted_keys[:], fragments[:]
            for i in changed:
                at = bisect_left(sorted_keys, keys[i])
                fragments[at] = _entry_fragment(keys[i], states[i])
            for i in range(n, len(keys)):
                at = bisect_left(sorted_keys, keys[i])
                sorted_keys.insert(at, keys[i])
                fragments.insert(at, _entry_fragment(keys[i], states[i]))
        if encoded:
            created_text = ",".join(fragments)
        m = len(kept.received)
        received_text = kept.received_text
        if received[:m] != kept.received:
            m, received_text = 0, ""
        if len(received) > m:
            more = canonical_dumps(received[m:]).decode()[1:-1]
            received_text = received_text + "," + more if m else more
            encoded += len(received) - m
        ri.store_row = _RoundRow(
            keys, states, sorted_keys, fragments, created_text,
            received, received_text,
        )
        self.round_entries_encoded += encoded
        self.round_entries_reused += len(keys) + len(received) - encoded
        return (
            f'{{"CreatedEvents":{{{created_text}}},'
            f'"ReceivedEvents":[{received_text}]}}'
        )

    # -- passthroughs to the cache -----------------------------------------

    def cache_size(self) -> int:
        return self._inmem.cache_size()

    def get_all_peer_sets(self) -> Dict[int, List[Peer]]:
        return self._inmem.get_all_peer_sets()

    def first_round(self, participant_id: int):
        return self._inmem.first_round(participant_id)

    def repertoire_by_pub_key(self) -> Dict[str, Peer]:
        return self._inmem.repertoire_by_pub_key()

    def repertoire_by_id(self) -> Dict[int, Peer]:
        return self._inmem.repertoire_by_id()

    def known_events(self) -> Dict[int, int]:
        return self._inmem.known_events()

    def consensus_events(self) -> List[str]:
        return self._inmem.consensus_events()

    def consensus_events_count(self) -> int:
        return self._inmem.consensus_events_count()

    def add_consensus_event(self, event: Event) -> None:
        self._inmem.add_consensus_event(event)

    def last_event_from(self, participant: str) -> str:
        return self._inmem.last_event_from(participant)

    def last_consensus_event_from(self, participant: str) -> str:
        return self._inmem.last_consensus_event_from(participant)

    def last_round(self) -> int:
        return self._inmem.last_round()

    def last_block_index(self) -> int:
        return self._inmem.last_block_index()

    def round_witnesses(self, round_index: int) -> List[str]:
        try:
            return self.get_round(round_index).witnesses()
        except StoreError:
            return []

    def round_events(self, round_index: int) -> int:
        try:
            return len(self.get_round(round_index).created_events)
        except StoreError:
            return 0

    def get_root(self, participant: str) -> Root:
        try:
            return self._inmem.get_root(participant)
        except StoreError:
            row = self._fetch(
                "SELECT data FROM roots WHERE participant = ?", (participant,)
            )
            if row is None:
                raise
            return Root.from_dict(json.loads(row[0]))

    # -- peer sets (write-through) -----------------------------------------

    def get_peer_set(self, round: int) -> PeerSet:
        return self._inmem.get_peer_set(round)

    def set_peer_set(self, round: int, peer_set: PeerSet) -> None:
        self._inmem.set_peer_set(round, peer_set)
        self._write(
            "INSERT OR REPLACE INTO peer_sets (round, data) VALUES (?, ?)",
            (round, canonical_dumps([p.to_dict() for p in peer_set.peers]).decode()),
        )

    # -- events -------------------------------------------------------------

    def get_event(self, hash_: str) -> Event:
        try:
            return self._inmem.get_event(hash_)
        except StoreError:
            row = self._fetch(
                "SELECT data, round, lamport, round_received FROM events "
                "WHERE key = ?",
                (hash_,),
            )
            if row is None:
                raise
            return _event_from_row(row[0], row[1:])

    def set_event(self, event: Event) -> None:
        # DB first, memory second: an event must be DURABLE before it can
        # become visible to gossip. A silently dropped disk write during the
        # shutdown race let a node gossip an event, lose it at close, then
        # re-sign a different event at the same index after bootstrap — a
        # cross-incarnation self-fork that wedges every peer still holding
        # the first incarnation's event (observed as the recycle tests'
        # "invalid event signature" livelock). Failing the insert instead
        # keeps the event out of this node's head chain entirely.
        if self._maintenance:
            self._inmem.set_event(event)
            return
        fresh = self._persist_event(event)
        try:
            self._inmem.set_event(event)
        except BaseException:
            if fresh:
                # the cache rejected an event the DB just gained (e.g. a
                # trusted frame-event insert hitting an index gap): roll
                # the fresh rows back so the next incarnation's bootstrap
                # never replays an event this one refused. Pre-existing
                # rows (annotation re-sets) are left untouched.
                self._unpersist_event(event)
            raise

    def on_event_evicted(self, fn) -> None:
        self._inmem.on_event_evicted(fn)

    def _persist_event(self, event: Event) -> bool:
        """Write through to the DB; returns True when the rows are new
        (vs. a re-set of an already-durable event)."""
        key = event.hex()
        with self._span("store_write"), self._db_lock:
            if self._db is None:
                raise StoreError(
                    "PersistentStore", StoreErrorKind.CLOSED, key
                )
            # Consensus annotations (write-once once assigned) are durable
            # so a cache-evicted event reloads with its round/lamport
            # intact — after compaction the recursive recomputation may no
            # longer have the parents to rebuild them from. Bootstrap
            # replay leaves them behind (topological_events) so the
            # from-zero recompute stays pristine.
            annotations = (
                event.round, event.lamport_timestamp, event.round_received
            )
            # The database says whether the event is durable already: a
            # re-set changes three integers of the row it finds, and only
            # an event it does not find is serialised and inserted.
            fresh = self._db.execute(
                "UPDATE events SET round = ?, lamport = ?, round_received = ? "
                "WHERE key = ?",
                (*annotations, key),
            ).rowcount == 0
            if fresh:
                self._db.execute(
                    "INSERT OR REPLACE INTO participant_events "
                    "(participant, idx, hash) VALUES (?, ?, ?)",
                    (event.creator(), event.index(), key),
                )
                # memoized body form: reuses the normalization the
                # insert-path hash already paid for
                data = canonical_dumps({
                    "Body": PreNormalized(event.body.normalized()),
                    "Signature": event.signature,
                })
                self._db.execute(
                    "INSERT INTO events "
                    "(key, topo, data, round, lamport, round_received) "
                    "VALUES (?, ?, ?, ?, ?, ?)",
                    (key, self._next_topo, data.decode(), *annotations),
                )
                self._next_topo += 1
                self.event_inserts += 1
            else:
                self.event_updates += 1
            self._db.commit()
            self.commits += 1
            return fresh

    def _unpersist_event(self, event: Event) -> None:
        key = event.hex()
        with self._db_lock:
            if self._db is None:
                return
            self._db.execute("DELETE FROM events WHERE key = ?", (key,))
            self._db.execute(
                "DELETE FROM participant_events WHERE participant = ? "
                "AND idx = ? AND hash = ?",
                (event.creator(), event.index(), key),
            )
            self._db.commit()

    def participant_events(self, participant: str, skip: int) -> List[str]:
        try:
            return self._inmem.participant_events(participant, skip)
        except StoreError as err:
            if err.kind != StoreErrorKind.TOO_LATE:
                raise
            self.db_reads += 1
            with self._db_lock:
                if self._db is None:
                    raise err  # shutdown race: surface the original miss
                rows = self._db.execute(
                    "SELECT hash FROM participant_events "
                    "WHERE participant = ? AND idx > ? ORDER BY idx",
                    (participant, skip),
                ).fetchall()
            return [r[0] for r in rows]

    def participant_event(self, participant: str, index: int) -> str:
        """Cache first; DB fallback on eviction (badger_store.go:293-310)."""
        try:
            return self._inmem.participant_event(participant, index)
        except StoreError:
            row = self._fetch(
                "SELECT hash FROM participant_events "
                "WHERE participant = ? AND idx = ?",
                (participant, index),
            )
            if row is None:
                raise
            return row[0]

    # -- rounds -------------------------------------------------------------

    def get_round(self, round_index: int) -> RoundInfo:
        try:
            return self._inmem.get_round(round_index)
        except StoreError:
            row = self._fetch_derived(
                "rounds", "SELECT data FROM rounds WHERE idx = ?", round_index
            )
            if row is None:
                raise
            return RoundInfo.from_dict(json.loads(row[0]))

    def set_round(self, round_index: int, round_info: RoundInfo) -> None:
        self._inmem.set_round(round_index, round_info)
        self._write_derived(
            "rounds", "INSERT OR REPLACE INTO rounds (idx, data) VALUES (?, ?)",
            round_index, round_info, self._encode_round,
        )

    # -- blocks -------------------------------------------------------------

    def get_block(self, index: int) -> Block:
        try:
            return self._inmem.get_block(index)
        except StoreError:
            row = self._fetch_derived(
                "blocks", "SELECT data FROM blocks WHERE idx = ?", index
            )
            if row is None:
                raise
            return Block.from_dict(json.loads(row[0]))

    def set_block(self, block: Block) -> None:
        self._inmem.set_block(block)
        self._write_derived(
            "blocks", "INSERT OR REPLACE INTO blocks (idx, data) VALUES (?, ?)",
            block.index(), block,
        )

    # -- frames -------------------------------------------------------------

    def get_frame(self, round_received: int) -> Frame:
        try:
            return self._inmem.get_frame(round_received)
        except StoreError:
            row = self._fetch_derived(
                "frames", "SELECT data FROM frames WHERE round = ?",
                round_received,
            )
            if row is None:
                raise
            return Frame.from_dict(json.loads(row[0]))

    def set_frame(self, frame: Frame) -> None:
        self._inmem.set_frame(frame)
        self._write_derived(
            "frames", "INSERT OR REPLACE INTO frames (round, data) VALUES (?, ?)",
            frame.round, frame,
        )

    # -- bootstrap support ---------------------------------------------------

    def topological_events(self, skip: int, count: int) -> List[Event]:
        """Events in insert order, for bootstrap replay
        (reference: badger_store.go dbTopologicalEvents / hashgraph.go:1481)."""
        with self._db_lock:
            if self._db is None:
                return []  # shutdown race: nothing left to replay
            rows = self._db.execute(
                "SELECT data FROM events ORDER BY topo LIMIT ? OFFSET ?",
                (count, skip),
            ).fetchall()
        return [_event_from_row(r[0]) for r in rows]

    def db_peer_set(self, round: int) -> PeerSet:
        """The persisted peer-set registered at EXACTLY this round (raw DB
        row, no interval semantics — reference: badger_store.go
        dbGetPeerSet). Bootstrap replay, not this accessor, rebuilds the
        live interval cache."""
        row = self._fetch(
            "SELECT data FROM peer_sets WHERE round = ?", (round,)
        )
        if row is None:
            raise StoreError(
                "PeerSetDB", StoreErrorKind.KEY_NOT_FOUND, str(round)
            )
        return PeerSet(
            [Peer.from_dict(d) for d in canonical_loads(row[0].encode())]
        )

    def db_last_block_index(self) -> int:
        row = self._fetch("SELECT MAX(idx) FROM blocks", ())
        return row[0] if row and row[0] is not None else -1

    # -- evidence ------------------------------------------------------------

    def set_evidence(self, key: str, data: dict) -> None:
        """Durable misbehavior evidence (equivocation proofs): written
        through even in maintenance mode — evidence is NOT derived state
        that a bootstrap replay rebuilds, so the replay's write gate
        (which protects events/rounds/blocks from being re-written) must
        not silently drop a proof recorded while it is open."""
        self._inmem.set_evidence(key, data)
        with self._db_lock:
            if self._db is None:
                raise StoreError(
                    "PersistentStore", StoreErrorKind.CLOSED, "evidence"
                )
            self._db.execute(
                "INSERT OR REPLACE INTO evidence (key, data) VALUES (?, ?)",
                (key, canonical_dumps(data).decode()),
            )
            self._db.commit()

    def all_evidence(self) -> Dict[str, dict]:
        with self._db_lock:
            if self._db is None:
                return self._inmem.all_evidence()
            rows = self._db.execute("SELECT key, data FROM evidence").fetchall()
        out = dict(self._inmem.all_evidence())
        for key, data in rows:
            out[key] = json.loads(data)
        return out

    # -- lifecycle -----------------------------------------------------------

    def reset(self, frame: Frame) -> None:
        """Reset the cache from a frame; the DB keeps accumulating (the
        reference's badger Reset also only clears the in-memory half)."""
        self._inmem.reset(frame)
        for participant, root in frame.roots.items():
            self._write(
                "INSERT OR REPLACE INTO roots (participant, data) VALUES (?, ?)",
                (participant, canonical_dumps(root.to_dict()).decode()),
            )
        self.set_frame(frame)

    # -- compaction ----------------------------------------------------------

    def prune_below(
        self,
        floor_round: int,
        drop_events: List[str],
        drop_rounds: List[int],
        participant_floors: Dict[str, int],
    ) -> None:
        """Durable half of checkpoint-prune: delete the compacted rows.
        Blocks, peer-sets, roots and evidence are never touched — evidence
        in particular is NOT replay-derived state (see set_evidence) and
        must survive compaction."""
        self._inmem.prune_below(
            floor_round, drop_events, drop_rounds, participant_floors
        )
        with self._db_lock:
            if self._db is None:
                raise StoreError(
                    "PersistentStore", StoreErrorKind.CLOSED, "prune"
                )
            self._db.executemany(
                "DELETE FROM events WHERE key = ?",
                [(h,) for h in drop_events],
            )
            self._db.executemany(
                "DELETE FROM rounds WHERE idx = ?",
                [(r,) for r in drop_rounds],
            )
            self._db.execute(
                "DELETE FROM frames WHERE round < ?", (floor_round,)
            )
            for participant, floor in participant_floors.items():
                self._db.execute(
                    "DELETE FROM participant_events "
                    "WHERE participant = ? AND idx < ?",
                    (participant, floor),
                )
            self._db.commit()

    def vacuum(self, incremental: bool = True) -> None:
        """Hand freed pages back to the OS. Incremental is cheap and the
        default (the DB is created with auto_vacuum=INCREMENTAL); a full
        VACUUM rebuild also upgrades DBs that predate that pragma."""
        with self._db_lock:
            if self._db is None:
                return
            if incremental:
                self._db.execute("PRAGMA incremental_vacuum")
            else:
                self._db.execute("VACUUM")
            self._db.commit()

    def size_stats(self) -> Dict[str, int]:
        stats = dict(self._inmem.size_stats())
        with self._db_lock:
            if self._db is None:
                return stats
            ev = self._db.execute("SELECT COUNT(*) FROM events").fetchone()[0]
            rd = self._db.execute("SELECT COUNT(*) FROM rounds").fetchone()[0]
            bl = self._db.execute("SELECT COUNT(*) FROM blocks").fetchone()[0]
            fr = self._db.execute("SELECT COUNT(*) FROM frames").fetchone()[0]
            page_count = self._db.execute("PRAGMA page_count").fetchone()[0]
            page_size = self._db.execute("PRAGMA page_size").fetchone()[0]
            freelist = self._db.execute("PRAGMA freelist_count").fetchone()[0]
        stats["events"] = ev
        stats["rounds"] = rd
        stats["blocks"] = bl
        stats["frames"] = fr
        stats["store_bytes"] = page_count * page_size
        stats["free_bytes"] = freelist * page_size
        return stats

    def close(self) -> None:
        with self._db_lock:
            if self._db is None:
                return
            self._db.commit()
            self._db.close()
            self._db = None

    def store_path(self) -> str:
        return self._path

    # -- helpers -------------------------------------------------------------

    def _span(self, stage: str):
        obs = self.stage_observer
        return NULL_STAGE if obs is None else obs.span(stage)

    def _fetch(self, sql: str, args: tuple) -> Optional[tuple]:
        self.db_reads += 1
        with self._db_lock:
            if self._db is None:
                # a gossip thread outliving shutdown's bounded wait must
                # get a typed miss, not an AttributeError
                raise StoreError(
                    "PersistentStore", StoreErrorKind.KEY_NOT_FOUND, "closed"
                )
            return self._db.execute(sql, args).fetchone()

    def _write(self, sql: str, args: tuple) -> None:
        if self._maintenance:
            return
        with self._span("store_write"), self._db_lock:
            if self._db is None:
                # Same fail-closed policy as events: a silently dropped
                # write leaves the durable history behind what this
                # incarnation advertised to the network. Derived objects
                # (rounds/blocks/frames) replay from events, but a loud
                # failure is strictly safer than a silent gap — the dying
                # caller handles it like any other store error.
                raise StoreError(
                    "PersistentStore", StoreErrorKind.CLOSED, sql.split()[2]
                )
            self._db.execute(sql, args)
            self._db.commit()
            self.commits += 1


def _event_from_row(data: str, annotations: Optional[tuple] = None) -> Event:
    """An event from its row: body and signature from ``data`` and, where
    the caller selected them, the annotation columns (in
    ``_EVENT_ANNOTATIONS``' order). A NULL column falls back to the key a
    row written before the columns existed carries in its JSON."""
    d = json.loads(data)
    ev = Event(EventBody.from_dict(d["Body"]), signature=d["Signature"])
    if annotations is not None:
        ev.round, ev.lamport_timestamp, ev.round_received = (
            d.get(legacy_key) if value is None else value
            for (_column, legacy_key), value
            in zip(_EVENT_ANNOTATIONS, annotations)
        )
    return ev
