"""The plain reference of a durable deployment: what is in the database FILE,
read from outside the program, and the state a sequential validator reaches
from it.

The file is read with the standard library's ``sqlite3`` and ``json`` on a
connection of its own — none of ``hashgraph/persistent_store.py`` runs here —
while the validator that wrote it may still hold it open (WAL: a second
connection reads what has been committed, which is what "acknowledged means
durable" has to show). Its events, in ``topo`` order, go one at a time
through a host ``Hashgraph(InmemStore)`` with no accelerator
(``reference.oracle_replay``): the blocks, the count of ordered events, the
last consensus round, the undetermined events and the pending rounds of that
hashgraph are what a validator has to hold after ingesting those events, and
again after a ``--bootstrap`` replay of the same file.
"""

from __future__ import annotations

import json
import sqlite3
from typing import Dict, FrozenSet, List, NamedTuple, Tuple

from . import reference

# every table of the store's schema: (name, the column(s) that key a row)
TABLES = (
    ("events", "key"), ("participant_events", "participant, idx"),
    ("rounds", "idx"), ("blocks", "idx"), ("frames", "round"),
    ("peer_sets", "round"), ("roots", "participant"), ("evidence", "key"),
)


class Database(NamedTuple):
    """One reading of a database file."""

    event_rows: List[str]  # every event row's JSON as stored, by ``topo``
    max_topo: int  # MAX(topo), -1 of an empty table
    rows: Dict[str, Dict[tuple, int]]  # table -> {row key: hash of the row}
    blocks: Dict[int, dict]  # block index -> its body under ORACLE_BLOCK_KEYS

    def row_counts(self) -> Dict[str, int]:
        return {table: len(rows) for table, rows in self.rows.items()}

    def events(self) -> List:
        """The event rows as ``Event``s: body and signature, and none of the
        annotations (round, Lamport time, round received) a row carries."""
        from babble_tpu.hashgraph.event import Event, EventBody

        out = []
        for data in self.event_rows:
            d = json.loads(data)
            out.append(Event(EventBody.from_dict(d["Body"]),
                             signature=d["Signature"]))
        return out

    def events_from_others(self, own_pub_hex: str) -> int:
        """Event rows whose creator is not the validator itself: it holds no
        other key, so each of them came in by a sync."""
        return sum(1 for ev in self.events() if ev.creator() != own_pub_hex)


class State(NamedTuple):
    """What consensus has decided, and what it has left open."""

    blocks: List[dict]  # each body under ORACLE_BLOCK_KEYS, as JSON gives it
    ordered: int  # events ordered into blocks
    last_consensus_round: int  # -1 before the first
    undetermined: FrozenSet[str]  # the SET: its order is an engine's own
    pending_rounds: Tuple[Tuple[int, bool], ...]  # (round, decided)


def read(path: str) -> Database:
    """The whole file through one read transaction of a connection of its
    own: a snapshot of what has been committed."""
    db = sqlite3.connect(path)
    try:
        db.execute("BEGIN")
        rows: Dict[str, Dict[tuple, int]] = {}
        for table, key in TABLES:
            n_key = key.count(",") + 1
            rows[table] = {
                tuple(r[:n_key]): hash(tuple(r[n_key:]))
                for r in db.execute(f"SELECT {key}, * FROM {table}")
            }
        events = [data for (data,) in
                  db.execute("SELECT data FROM events ORDER BY topo")]
        max_topo = db.execute("SELECT MAX(topo) FROM events").fetchone()[0]
        blocks = {}
        for idx, data in db.execute("SELECT idx, data FROM blocks"):
            body = json.loads(data)["Body"]
            blocks[idx] = {k: body[k] for k in reference.ORACLE_BLOCK_KEYS}
        db.execute("ROLLBACK")
    finally:
        db.close()
    return Database(events, -1 if max_topo is None else max_topo, rows, blocks)


def rows_changed(before: Database, after: Database) -> int:
    """Rows added, removed or rewritten between two readings, over every
    table, and 1 more if MAX(topo) moved."""
    changed = int(before.max_topo != after.max_topo)
    for table, _key in TABLES:
        b, a = before.rows[table], after.rows[table]
        changed += len(b.keys() ^ a.keys())
        changed += sum(1 for k in b.keys() & a.keys() if b[k] != a[k])
    return changed


def state_of(hg) -> State:
    """The five things of any ``Hashgraph``, a validator's or the
    reference's."""
    store = hg.store
    blocks = [
        json.loads(reference.block_bytes(store.get_block(b),
                                         reference.ORACLE_BLOCK_KEYS))
        for b in range(store.last_block_index() + 1)
    ]
    lcr = hg.last_consensus_round
    return State(
        blocks, store.consensus_events_count(), -1 if lcr is None else lcr,
        frozenset(hg.undetermined_events),
        tuple((p.index, p.decided)
              for p in hg.pending_rounds.get_ordered_pending_rounds()),
    )


def replay(db: Database, peers) -> State:
    """The file's events, in ``topo`` order, through a sequential host
    hashgraph: the state a validator has to hold."""
    return state_of(reference.oracle_replay(db.events(), peers))


def blocks_differing(got: List[dict], want: List[dict]) -> int:
    """Blocks of ``got`` that ``want`` lacks or has otherwise."""
    return sum(1 for i, b in enumerate(got)
               if i >= len(want) or want[i] != b)


def blocks_on_disk_differing(db: Database, want: List[dict]) -> int:
    """Block rows of the file that the reference lacks or has otherwise,
    and 1 for each index below the highest that has no row."""
    if not db.blocks:
        return 0
    differing = sum(1 for i, b in db.blocks.items()
                    if not 0 <= i < len(want) or want[i] != b)
    return differing + (max(db.blocks) + 1 - len(db.blocks))
