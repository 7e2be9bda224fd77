"""Driver kind ``durable-ingest``: one validator run with ``--store`` catches
up onto disk (traffic ``phase`` ``store``), or is restarted on the database
such a catch-up left and recovers by ``--bootstrap`` (``phase`` ``restart``).

The validator is a ``Node`` built as ``engine.py`` builds a ``--store`` one:
``Config(accelerator=True, store=True)``, ``PersistentStore(cache_size,
<fresh directory>/babble.db)``, ``InmemProxy`` + dummy app, through
``Node.init()`` and not started. A ``store`` pass is ``core-ingest``'s
(``harness/ingest.py``: ``prepare_sync`` outside the core lock, ``sync`` +
``process_sig_pool`` under it, then flushes until nothing is in flight) on a
fresh database, timed as that driver times it. A ``restart`` pass builds the
``Node`` with ``bootstrap=True`` on one of the databases that set-up made BY
such ingests, and is timed from before ``Node.init()`` — inside which the
program replays the whole file — to the end of the drain. Nothing of the
program is replaced or stubbed. A pass that raises ends there: its seconds
count, its events do not.

``correct`` compares the validator with ``harness/durable.py``: the FILE,
read from outside before any ``close()``, and its events through a sequential
host hashgraph.
"""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

from benchmark.harness import data, durable, reference
from benchmark.harness.counters import node_snapshot, window_counters
from benchmark.harness.ingest import _add, _Pass

NO_STATE = durable.State([], 0, -1, frozenset(), ())


def filesystem_of(path: str) -> str:
    """``<type> on <mount point>`` of the filesystem ``path`` lives on."""
    best = ("", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, fstype = line.split()[:3]
                at = mount.rstrip("/") + "/"
                if (path + "/").startswith(at) and len(at) > len(best[0]):
                    best = (at, fstype)
    except OSError:
        pass
    return f"{best[1]} on {best[0] or '?'}"


def database_home():
    """Where the databases live: ``/dev/shm`` where the machine has one (a
    memory filesystem, local to the machine), else the default ``tempfile``
    directory. ``durable16.json`` ``assumed`` says why: on the chip's
    machine TMPDIR is a ``9p`` mount whose commit latency drifts."""
    shm = "/dev/shm"
    if os.path.isdir(shm) and os.access(shm, os.W_OK | os.X_OK):
        return shm
    return None


class _DurablePass(_Pass):
    """One ``--store`` validator on the database in ``db_dir``: it ingests
    a backlog onto it, or (``bootstrap``) recovers from it."""

    error: Optional[BaseException] = None

    def __init__(self, env, keys, peers, me: int, conf: dict, db_dir: str,
                 bootstrap: bool = False):
        from babble_tpu.config.config import Config
        from babble_tpu.dummy.state import State as DummyState
        from babble_tpu.hashgraph.persistent_store import PersistentStore
        from babble_tpu.net.inmem import InmemNetwork
        from babble_tpu.node.node import Node
        from babble_tpu.node.validator import Validator
        from babble_tpu.proxy.proxy import InmemProxy

        moniker, addr = f"v{me}", f"inmem://v{me}"
        node_conf = Config(
            bind_addr=addr, moniker=moniker, log_level="error",
            no_service=True, accelerator=True, store=True,
            bootstrap=bootstrap, database_dir=db_dir,
        )
        self.path = os.path.join(db_dir, "babble.db")
        self.node = Node(
            node_conf, Validator(keys[me], moniker), peers, peers,
            PersistentStore(node_conf.cache_size, self.path),
            InmemNetwork().new_transport(addr), InmemProxy(DummyState()))
        env.scale_gate(self.node, conf)
        self.core = self.node.core
        self.env = env
        self.seconds = 0.0
        self.counters: Dict[str, float] = {}

    def init(self) -> None:
        """``Node.init()`` with the prewarm thread joined, as
        ``harness/nodes.py`` does for an in-memory validator."""
        self.node.init()
        self._join_prewarm()

    def _join_prewarm(self) -> None:
        warm = getattr(self.node, "_prewarm_thread", None)
        if warm is not None:
            warm.join()

    def _timed(self, work: Callable[[], None]) -> None:
        """``work`` between two snapshots; what stops it is kept."""
        before = node_snapshot(self.node)
        t0 = time.perf_counter()
        try:
            work()
        except Exception as err:  # a refused sync, a drain that never ends
            self.error = err
        self.seconds = time.perf_counter() - t0
        self.counters = window_counters([before], [node_snapshot(self.node)])

    def ingest(self, wires: List, from_id: int, sync_events: int) -> None:
        self.init()
        self._timed(lambda: _Pass.ingest(self, wires, from_id, sync_events))

    def recover(self) -> None:
        """The restart: ``Node.init()`` replays the database, then the
        drain. The prewarm thread ``Node.init`` started runs beside the
        replay, as it does in a validator that restarts."""
        span = self.env.span

        def work() -> None:
            with span("bootstrap"):
                self.node.init()
            with self.node.core_lock, span("drain"):
                self._drain()

        self._timed(work)
        self._join_prewarm()

    def summary(self) -> tuple:
        # a pass that was stopped ordered nothing WHOLE
        return (0, 0, 0) if self.error is not None else super().summary()

    def file_bytes(self) -> int:
        return sum(os.path.getsize(self.path + ext)
                   for ext in ("", "-wal") if os.path.exists(self.path + ext))


def _buckets(counters: Dict[str, float]) -> str:
    """The sweep programs that ran, ``BxWxExPxSxR:launches``."""
    return " ".join(sorted(
        f"{k.split('.', 1)[1]}:{v:.0f}" for k, v in counters.items()
        if v > 0 and "_bucket_launches." in k))


class _Window:
    """The timed passes of a run and what they add up to."""

    def __init__(self) -> None:
        self.summaries: List[tuple] = []
        self.seconds: List[float] = []
        self.errors: List[str] = []
        self.counters: Dict[str, float] = {}

    def add(self, p: _DurablePass) -> None:
        _add(self.counters, p.counters)
        self.summaries.append(p.summary())
        self.seconds.append(p.seconds)
        if p.error is not None:
            self.errors.append(repr(p.error))

    def result(self, env, checks, notes: List[str], expected: int,
               chosen: dict) -> dict:
        ordered = [s[0] for s in self.summaries]
        failed = sum(max(0, expected - c) for c in ordered)
        checks.at_most("events_not_ordered", failed)
        if self.errors:
            notes.append(f"{len(self.errors)} of {len(self.seconds)} passes "
                         f"were stopped, the first by {self.errors[0]}")
        if not checks.at_most("distinct_pass_outcomes",
                              len(set(self.summaries)), 1):
            notes.append("passes disagree on (ordered, blocks, transactions)"
                         f": {sorted(set(self.summaries))}")
        notes.extend(reference.device_path(checks, self.counters))
        c = self.counters
        env.log(f"{len(self.seconds)} passes: seconds "
                f"{[round(s, 3) for s in self.seconds]}, ordered {ordered}; "
                f"buckets launched {_buckets(c)}; "
                f"{c.get('accel_small_windows', 0):.0f} flushes under the "
                "gate")
        env.log("the store inside the window: "
                f"{c.get('store_commits', 0):.0f} commits, "
                f"{c.get('store_db_reads', 0):.0f} reads that fell through "
                f"to the database, {c.get('bootstrap_events_replayed', 0):.0f}"
                " events replayed, "
                f"{c.get('sync_stage_seconds.insert.count', 0):.0f} inserts")
        return {
            "correct": checks.ok,
            "compared": checks.as_dict(),
            "attempted": expected * len(self.seconds),
            "failed": failed,
            "notes": notes,
            "end_to_end": {
                "catchup_events_per_s": sum(ordered) / sum(self.seconds),
            },
            "counters": self.counters,
            "samples": {},
            "chosen": chosen,
        }


def _reference_state(db: durable.Database, peers, notes: List[str]):
    """The file's events through the sequential hashgraph; a file it cannot
    replay (a parent that is not there) gives the empty state, against
    which every block of the validator differs."""
    t_ref = time.monotonic()
    try:
        want = durable.replay(db, peers)
    except Exception as err:
        notes.append(f"the reference could not replay the file: {err!r}")
        return NO_STATE
    notes.append(
        f"the reference replayed the file's {len(db.event_rows)} events (MAX(topo)"
        f" {db.max_topo}; rows {db.row_counts()}): {len(want.blocks)} blocks, "
        f"{want.ordered} ordered, last consensus round "
        f"{want.last_consensus_round}, {len(want.undetermined)} undetermined, "
        f"{len(want.pending_rounds)} pending rounds "
        f"({time.monotonic() - t_ref:.1f}s)")
    return want


def _chosen(env, node) -> dict:
    snap = node.get_stats_snapshot()
    return {k: snap.get(k) for k in env.CHOICE_KEYS}


class _Deployment:
    """The validator's keys, the backlog's streams and the directory the
    databases live in, with the passes both cells are made of."""

    def __init__(self, cell, env):
        self.env = env
        self.conf, self.traffic = env.sized(cell.config), env.sized(cell.traffic)
        conf, traffic = self.conf, self.traffic
        n = int(conf["validators"])
        self.me = int(conf.get("rejoining_validator", 0))
        self.keys = data.seeded_keys(n, env.seed)
        self.peers = data.peer_set(self.keys,
                                   [f"inmem://v{i}" for i in range(n)])
        self.own = self.keys[self.me].public_key.hex()
        creators = [i for i in range(n) if i != self.me]
        self.from_id = self.peers.by_pub_key[
            self.keys[creators[0]].public_key.hex()].id
        self.backlog = int(traffic.get("backlog_events",
                                       traffic.get("database_events")))
        self.streams = [
            data.backlog_wire_events(self.keys, self.peers, creators,
                                     self.backlog, int(traffic["dag_seed"]),
                                     int(conf["tx_bytes"]), tag=k)
            for k in range(int(traffic["distinct_streams"]))
        ]
        self.sync_events = int(traffic["sync_events"])
        self.root = tempfile.mkdtemp(prefix="babble_durable_",
                                     dir=database_home())
        self.turn = 0
        env.log(f"backlog: {len(self.streams)} streams of {self.backlog} wire "
                f"events from {len(creators)} creators, keys from seed "
                f"{env.seed}, DAG shape from dag_seed {traffic['dag_seed']}; "
                f"databases under {self.root} ({filesystem_of(self.root)})")

    def new_dir(self) -> str:
        return tempfile.mkdtemp(dir=self.root)

    def validator(self, db_dir: str, bootstrap: bool = False) -> _DurablePass:
        gc.collect()
        return _DurablePass(self.env, self.keys, self.peers, self.me,
                            self.conf, db_dir, bootstrap)

    def ingest(self, k: int, db_dir: str) -> _DurablePass:
        """A fresh ``--store`` validator ingests stream ``k`` onto a fresh
        database in ``db_dir``."""
        p = self.validator(db_dir)
        if self.sync_events > p.node.conf.sync_limit:
            raise ValueError(f"sync_events {self.sync_events} is over the "
                             f"node's SyncLimit {p.node.conf.sync_limit}")
        p.ingest(self.streams[k % len(self.streams)], self.from_id,
                 self.sync_events)
        return p

    def warm_up(self, one_pass: Callable[[], _DurablePass],
                end: Callable[[_DurablePass], None]) -> None:
        """Set-up: untimed passes until one meets every bucket compiled."""
        for i in range(int(self.traffic.get("warm_passes_max", 3))):
            p = one_pass()
            waits = p.counters.get("accel_compile_waits", 0.0)
            self.env.log(
                f"warm pass {i}: {p.seconds:.2f}s, ordered/blocks/txs "
                f"{p.summary()}, sweeps "
                f"{p.counters.get('accel_sweeps', 0):.0f}, compile waits "
                f"{waits:.0f}, buckets {_buckets(p.counters)}, file "
                f"{p.file_bytes()} bytes"
                + (f", stopped by {p.error!r}" if p.error else ""))
            end(p)
            if waits == 0 and p.error is None:
                break

    def timed(self, one_pass: Callable[[], _DurablePass],
              end: Callable[[_DurablePass], None], audit) -> tuple:
        """The window: passes start until ``--seconds`` has elapsed and the
        last one finishes. Returns (the window, what ``audit`` took from
        the first timed pass before it ended)."""
        env = self.env
        env.window_open()
        t_open = time.monotonic()
        window, audited = _Window(), None
        while time.monotonic() - t_open < env.seconds:
            p = one_pass()
            window.add(p)
            if audited is None:
                audited = audit(p)
            end(p)
        env.window_close()
        return window, audited


def run(cell, env) -> dict:
    d = _Deployment(cell, env)
    try:
        return {"store": _run_store, "restart": _run_restart}[
            d.traffic["phase"]](d)
    finally:
        shutil.rmtree(d.root, ignore_errors=True)


def _run_store(d: _Deployment) -> dict:
    def one_pass() -> _DurablePass:
        p = d.ingest(d.turn, d.new_dir())
        d.turn += 1
        return p

    def end(p: _DurablePass) -> None:
        p.close()
        shutil.rmtree(os.path.dirname(p.path), ignore_errors=True)

    def audit(p: _DurablePass) -> tuple:
        # acknowledged means durable: the file as a second connection reads
        # it after the last sync's drain and BEFORE any close()
        hg = p.core.hg
        return (durable.read(p.path), durable.state_of(hg),
                hg.topological_index - len(reference.stored_events(hg.store)),
                reference.stored_from_others(hg.store, d.own),
                _chosen(d.env, p.node))

    d.warm_up(one_pass, end)
    window, (db, got, evicted, stored, chosen) = d.timed(one_pass, end, audit)

    notes: List[str] = []
    checks = reference.Checks()
    want = _reference_state(db, d.peers, notes)
    checks.at_most("audited_events_evicted", evicted)
    checks.at_most("backlog_events_not_stored", d.backlog - stored)
    checks.at_most("backlog_events_not_on_disk",
                   d.backlog - db.events_from_others(d.own))
    checks.at_most("blocks_differing_from_oracle",
                   durable.blocks_differing(got.blocks, want.blocks))
    checks.at_most("blocks_on_disk_differing_from_oracle",
                   durable.blocks_on_disk_differing(db, want.blocks))
    if not checks.at_most("oracle_events_the_first_pass_missed",
                          abs(want.ordered - window.summaries[0][0])):
        notes.append(f"the reference ordered {want.ordered} events, the "
                     f"validator {window.summaries[0][0]}")
    return window.result(d.env, checks, notes, want.ordered, chosen)


def _run_restart(d: _Deployment) -> dict:
    # set-up: the databases, each made by the program's own durable ingest,
    # read from outside, and only then stopped cleanly
    files: List[durable.Database] = []
    dirs: List[str] = []
    for k in range(len(d.streams)):
        p = d.ingest(k, d.new_dir())
        files.append(durable.read(p.path))
        d.env.log(f"database {k}: ingest {p.seconds:.2f}s, ordered/blocks/txs "
                  f"{p.summary()}, compile waits "
                  f"{p.counters.get('accel_compile_waits', 0):.0f}, file "
                  f"{p.file_bytes()} bytes, rows {files[-1].row_counts()}"
                  + (f", stopped by {p.error!r}" if p.error else ""))
        p.close()  # Node.shutdown(), which closes the store
        dirs.append(os.path.dirname(p.path))

    def one_pass() -> _DurablePass:
        p = d.validator(dirs[d.turn % len(dirs)], bootstrap=True)
        p.recover()
        d.turn += 1
        return p

    def audit(p: _DurablePass) -> tuple:
        # which file, and the file again before this incarnation closes it
        return (files[(d.turn - 1) % len(dirs)], durable.read(p.path),
                durable.state_of(p.core.hg), _chosen(d.env, p.node))

    d.warm_up(one_pass, _DurablePass.close)
    window, audited = d.timed(one_pass, _DurablePass.close, audit)
    # `before`: as set-up read it, before any replay had touched it
    before, after, got, chosen = audited

    notes: List[str] = []
    checks = reference.Checks()
    want = _reference_state(before, d.peers, notes)
    checks.at_most("backlog_events_not_on_disk",
                   d.backlog - before.events_from_others(d.own))
    checks.at_most("blocks_differing_from_oracle",
                   durable.blocks_differing(got.blocks, want.blocks))
    if not checks.at_most("oracle_events_the_replay_missed",
                          abs(want.ordered - got.ordered)):
        notes.append(f"the reference ordered {want.ordered} events, the "
                     f"replay {got.ordered}")
    checks.at_most("undetermined_events_differing_from_oracle",
                   len(got.undetermined ^ want.undetermined))
    checks.at_most("last_consensus_round_differing",
                   abs(got.last_consensus_round - want.last_consensus_round))
    if not checks.at_most("database_rows_changed_by_replay",
                          durable.rows_changed(before, after)):
        notes.append(f"the file held {before.row_counts()} before the "
                     f"replays and {after.row_counts()} after the first "
                     f"timed one (MAX(topo) {before.max_topo} and "
                     f"{after.max_topo})")
    return window.result(d.env, checks, notes, want.ordered, chosen)
