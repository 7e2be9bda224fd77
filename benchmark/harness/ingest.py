"""Driver kind ``core-ingest``: one validator of a ring that was down
re-ingests the ring's backlog, one sync after another, on one thread.

Each pass builds a ``Node`` as ``engine.py`` builds it (``Config(
accelerator=True)``, ``InmemStore(cache_size)``, ``InmemProxy`` + dummy
app), takes it through ``Node.init()`` — device resolution,
``require_accelerator()``, prewarm — and, without starting it, feeds its
``core`` the backlog in syncs of ``sync_limit`` wire events exactly as the
node's own sync handlers do: ``prepare_sync`` outside the core lock,
``sync`` + ``process_sig_pool`` under it. Nothing of the program is
replaced or stubbed; the self-events the core records are part of the work.

The process-wide signature-verdict cache (``crypto/batch.py``, 32,768
entries) would answer every signature of a repeated backlog; a validator
that really rejoins has a cold one. So the passes cycle through
``distinct_streams`` copies of the backlog — the same DAG from the seed, a
different tag in every payload, hence different hashes and signatures —
whose total is larger than that cache: every pass verifies every signature.

``--seed`` makes the validators' keys (hence every hash, signature, peer id
and tie-break); the DAG's SHAPE comes from the traffic file's ``dag_seed``,
so every seed does the same work: with the shape drawn from ``--seed`` the
undecidable tail of the backlog, and with it the rate, moved by 4 % from
seed to seed against 0.5 % between two runs of one seed (PR 23, on the chip).
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

from . import data, reference
from .counters import node_snapshot, window_counters
from .nodes import build_node


def _add(total: Dict[str, float], part: Dict[str, float]) -> None:
    for k, v in part.items():
        total[k] = total.get(k, 0.0) + v


class _Pass:
    """One fresh validator ingesting one backlog."""

    def __init__(self, env, keys, peers, me: int, conf: dict):
        from babble_tpu.dummy.state import State as DummyState

        self.node, _proxy = build_node(
            env, conf, keys[me], f"v{me}", peers, f"inmem://v{me}",
            DummyState(), tcp=False)
        self.core = self.node.core
        self.env = env
        self.seconds = 0.0
        self.counters: Dict[str, float] = {}

    def ingest(self, wires: List, from_id: int, sync_events: int) -> None:
        core, lock, span = self.core, self.node.core_lock, self.env.span
        before = node_snapshot(self.node)
        t0 = time.perf_counter()
        for chunk in data.chunks(wires, sync_events):
            with span("prepare_sync"):
                prepared = core.prepare_sync(chunk)
            with lock, span("sync"):
                core.sync(from_id, chunk, prepared)
                core.process_sig_pool()
        with lock, span("drain"):
            self._drain()
        self.seconds = time.perf_counter() - t0
        self.counters = window_counters([before], [node_snapshot(self.node)])

    def _drain(self) -> None:
        """Flush until nothing is in flight and the consensus count has
        stopped rising: each flush applies one in-flight sweep's results
        and may launch another."""
        hg, accel = self.core.hg, self.core.hg.accel
        prev = -1
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            hg.flush_consensus()
            if accel.busy():
                time.sleep(0.0005)
                continue
            cur = self.core.get_consensus_events_count()
            if cur == prev:
                return
            prev = cur
        raise RuntimeError("catch-up pass never quiesced")

    @property
    def ordered(self) -> int:
        return self.core.get_consensus_events_count()

    def summary(self) -> tuple:
        return (self.ordered, self.core.get_last_block_index() + 1,
                self.core.get_consensus_transactions_count())

    def close(self) -> None:
        self.node.shutdown()


def run(cell, env) -> dict:
    conf, traffic = env.sized(cell.config), env.sized(cell.traffic)
    n = int(conf["validators"])
    me = int(conf.get("rejoining_validator", 0))
    keys = data.seeded_keys(n, env.seed)
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(n)])
    creators = [i for i in range(n) if i != me]
    from_id = peers.by_pub_key[keys[creators[0]].public_key.hex()].id
    streams = [
        data.backlog_wire_events(
            keys, peers, creators, int(traffic["backlog_events"]),
            int(traffic["dag_seed"]), int(conf["tx_bytes"]), tag=k,
        )
        for k in range(int(traffic["distinct_streams"]))
    ]
    env.log(f"backlog: {len(streams)} streams of {len(streams[0])} wire "
            f"events from {len(creators)} creators, keys from seed {env.seed}, "
            f"DAG shape from dag_seed {traffic['dag_seed']}")
    sync_events = int(traffic["sync_events"])
    turn = [0]

    def one_pass() -> _Pass:
        gc.collect()
        p = _Pass(env, keys, peers, me, conf)
        if sync_events > p.node.conf.sync_limit:
            raise ValueError(f"sync_events {sync_events} is over the "
                             f"node's SyncLimit {p.node.conf.sync_limit}")
        p.ingest(streams[turn[0] % len(streams)], from_id, sync_events)
        turn[0] += 1
        return p

    # set-up: untimed passes until one meets every bucket compiled
    for i in range(int(traffic.get("warm_passes_max", 3))):
        p = one_pass()
        waits = p.counters.get("accel_compile_waits", 0.0)
        env.log(f"warm pass {i}: {p.seconds:.2f}s, ordered/blocks/txs "
                f"{p.summary()}, sweeps "
                f"{p.counters.get('accel_sweeps', 0):.0f}, compile waits "
                f"{waits:.0f}")
        p.close()
        if waits == 0:
            break

    env.window_open()
    t_open = time.monotonic()
    audited = None  # the first timed pass, kept for the audit
    summaries: List[tuple] = []
    seconds: List[float] = []
    counters: Dict[str, float] = {}
    while time.monotonic() - t_open < env.seconds:
        p = one_pass()
        _add(counters, p.counters)
        summaries.append(p.summary())
        seconds.append(p.seconds)
        if audited is None:
            audited = p
        else:
            p.close()
    env.window_close()

    notes: List[str] = []
    ok, note, _blocks, expected = reference.audit_against_oracle(
        audited.core.hg, peers
    )
    notes.append("audit of the first timed pass: " + note)
    if ok and expected != summaries[0][0]:
        ok = False
        notes.append(f"the oracle ordered {expected} events, the validator "
                     f"{summaries[0][0]}")
    chosen = {k: audited.node.get_stats_snapshot().get(k)
              for k in env.CHOICE_KEYS}
    audited.close()
    ordered = [s[0] for s in summaries]
    failed = sum(max(0, expected - c) for c in ordered)
    if len(set(summaries)) != 1:
        ok = False
        notes.append("passes disagree on (ordered, blocks, transactions): "
                     f"{sorted(set(summaries))}")
    d_ok, d_notes = reference.device_path_held(counters)
    notes.extend(d_notes)
    ok = ok and d_ok
    env.log(f"{len(seconds)} passes: seconds {[round(s, 3) for s in seconds]}, "
            f"ordered {ordered}")
    return {
        "correct": ok and failed == 0,
        "attempted": expected * len(seconds),
        "failed": failed,
        "notes": notes,
        "end_to_end": {
            "catchup_events_per_s": sum(ordered) / sum(seconds),
        },
        "counters": counters,
        "samples": {},
        "chosen": chosen,
    }
