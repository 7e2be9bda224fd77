"""Driver kind ``churn-ingest``: one validator that was down while the
validator set changed re-ingests that history, one sync after another, on
one thread.

The passes are ``core-ingest``'s (``harness/ingest.py``: a fresh ``Node``
through ``Node.init()``, ``prepare_sync`` outside the core lock, ``sync`` +
``process_sig_pool`` under it, then flushes until nothing is in flight),
built on the GENESIS validator set. The backlog is ``harness/churn.py``'s:
signed join and leave requests ride in its events, joiners' first events
follow their admission, leavers fall silent. Nothing of the program is
replaced or stubbed. A pass whose ``Core.sync`` (or drain) raises ends
there and is a failed pass: its events count as not ordered, its seconds
count.

``correct`` compares what ``core-ingest`` compares — against a reference
that has a +6 commit step of its own — and three numbers more: the
validator set of every round, the changes applied, and that a sweep with
two or more validator-set slots ran inside the window.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

from benchmark.harness import churn, data, reference
from benchmark.harness.counters import node_snapshot, window_counters
from benchmark.harness.ingest import _add, _Pass


class _ChurnPass(_Pass):
    """A pass that keeps what stopped it instead of raising."""

    error = None

    def ingest(self, wires: List, from_id: int, sync_events: int) -> None:
        before = node_snapshot(self.node)
        t0 = time.perf_counter()
        try:
            super().ingest(wires, from_id, sync_events)
        except Exception as err:  # a refused sync, a drain that never ends
            self.error = err
            self.seconds = time.perf_counter() - t0
            self.counters = window_counters(
                [before], [node_snapshot(self.node)])


def run(cell, env) -> dict:
    from babble_tpu.peers.peer_set import PeerSet

    conf, traffic = env.sized(cell.config), env.sized(cell.traffic)
    n = int(conf["validators"])
    me = int(conf.get("rejoining_validator", 0))
    keys = data.seeded_keys(n + int(conf["joiners"]), env.seed)
    peers = churn.all_peers(keys, n)
    genesis = PeerSet(peers[:n])
    creators = [i for i in range(n) if i != me]
    requests = churn.parse_requests(traffic["requests"], n)
    from_id = peers[creators[0]].id
    t_gen = time.monotonic()
    script, first = churn.churn_script(
        keys, peers, genesis, creators, requests,
        int(traffic["backlog_events"]), int(traffic["dag_seed"]),
        int(traffic["first_request_event"]), int(traffic["request_every"]),
        int(conf["tx_bytes"]))
    streams = [first] + [
        churn.wire_events(keys, peers, requests, script,
                          int(conf["tx_bytes"]), tag=k)
        for k in range(1, int(traffic["distinct_streams"]))
    ]
    final_set = churn.schedule_final_set(genesis, peers, requests)
    env.log(f"backlog: {len(streams)} streams of {len(first)} wire events, "
            f"{len(requests)} requests {list(traffic['requests'])}, "
            f"{len({s.creator for s in script})} creators of "
            f"{len(keys)} keys, keys from seed {env.seed}, DAG shape and "
            f"schedule from dag_seed {traffic['dag_seed']} "
            f"({time.monotonic() - t_gen:.1f}s)")
    sync_events = int(traffic["sync_events"])
    turn = [0]

    def one_pass() -> _ChurnPass:
        gc.collect()
        p = _ChurnPass(env, keys, genesis, me, conf)
        if sync_events > p.node.conf.sync_limit:
            raise ValueError(f"sync_events {sync_events} is over the "
                             f"node's SyncLimit {p.node.conf.sync_limit}")
        p.ingest(streams[turn[0] % len(streams)], from_id, sync_events)
        turn[0] += 1
        return p

    def buckets(counters: Dict[str, float]) -> str:
        return " ".join(sorted(
            f"{k.split('.', 1)[1]}:{v:.0f}" for k, v in counters.items()
            if v > 0 and "_bucket_launches." in k))

    def compiling(node) -> float:
        """Programs the validator's engine still has to compile ahead of
        need (0 from a program that compiles none)."""
        return node.get_stats_snapshot().get("accel_variant_backlog", 0)

    # set-up: untimed passes until one meets every bucket compiled, then
    # until the program compiles nothing in the background either
    for i in range(int(traffic.get("warm_passes_max", 3))):
        p = one_pass()
        waits = p.counters.get("accel_compile_waits", 0.0)
        behind = compiling(p.node)
        env.log(f"warm pass {i}: {p.seconds:.2f}s, ordered/blocks/txs "
                f"{p.summary()}, sweeps "
                f"{p.counters.get('accel_sweeps', 0):.0f}, compile waits "
                f"{waits:.0f}, compiling ahead {behind:.0f}, buckets "
                f"{buckets(p.counters)}"
                + (f", stopped by {p.error!r}" if p.error else ""))
        done = waits == 0 and behind == 0 and p.error is None
        if done or i + 1 == int(traffic.get("warm_passes_max", 3)):
            t_quiet = time.monotonic()
            while compiling(p.node) > 0 and time.monotonic() - t_quiet < 180:
                time.sleep(0.2)
            env.log(f"compiles ahead of need: "
                    f"{p.node.get_stats_snapshot().get('accel_variant_compiles', 0)}"
                    f" made, {compiling(p.node):.0f} left after "
                    f"{time.monotonic() - t_quiet:.1f}s more")
        p.close()
        if done:
            break

    env.window_open()
    t_open = time.monotonic()
    audited = None  # the first timed pass, kept for the audit
    summaries: List[tuple] = []
    seconds: List[float] = []
    errors: List[str] = []
    counters: Dict[str, float] = {}
    while time.monotonic() - t_open < env.seconds:
        p = one_pass()
        _add(counters, p.counters)
        # a pass that was stopped ordered nothing WHOLE
        summaries.append(p.summary() if p.error is None else (0, 0, 0))
        seconds.append(p.seconds)
        if p.error is not None:
            errors.append(repr(p.error))
        if audited is None:
            audited = p
        else:
            p.close()
    env.window_close()

    notes: List[str] = []
    checks = reference.Checks()
    t_ref = time.monotonic()
    audit = churn.audit(audited.core.hg, genesis, final_set, len(requests))
    notes.append("audit of the first timed pass: " + audit.note
                 + f" (the reference took {time.monotonic() - t_ref:.1f}s)")
    checks.at_most("audited_events_evicted", audit.blocks.missing_events)
    checks.at_most("backlog_events_not_stored",
                   len(first) - reference.stored_from_others(
                       audited.core.hg.store, keys[me].public_key.hex()))
    checks.at_most("blocks_differing_from_oracle",
                   audit.blocks.differing_blocks)
    checks.at_most("peer_sets_differing_from_oracle",
                   audit.peer_sets_differing)
    checks.at_most("membership_changes_not_applied",
                   audit.changes_not_applied)
    expected = audit.blocks.ordered
    if not checks.at_most("oracle_events_the_first_pass_missed",
                          abs(expected - summaries[0][0])) and audit.blocks.ok:
        notes.append(f"the reference ordered {expected} events, the "
                     f"validator {summaries[0][0]}")
    chosen = {k: audited.node.get_stats_snapshot().get(k)
              for k in env.CHOICE_KEYS}
    audited.close()
    ordered = [s[0] for s in summaries]
    failed = sum(max(0, expected - c) for c in ordered)
    checks.at_most("events_not_ordered", failed)
    if errors:
        notes.append(f"{len(errors)} of {len(seconds)} passes were stopped, "
                     f"the first by {errors[0]}")
    if not checks.at_most("distinct_pass_outcomes", len(set(summaries)), 1):
        notes.append("passes disagree on (ordered, blocks, transactions): "
                     f"{sorted(set(summaries))}")
    notes.extend(reference.device_path(checks, counters))
    multi, launches = churn.multi_set_launches(counters)
    if not checks.at_least("multi_set_sweeps_in_window", multi, 1):
        notes.append(f"none of {launches:.0f} launches inside the window had "
                     "two validator-set slots")
    env.log(f"{len(seconds)} passes: seconds {[round(s, 3) for s in seconds]}, "
            f"ordered {ordered}; buckets launched {buckets(counters)}")
    prefix = "accel_rebuilds_by_reason."
    env.log("membership inside the window: "
            f"{counters.get('membership_changes_applied', 0):.0f} changes, "
            f"{counters.get('peer_set_waits', 0):.0f} peer-set waits, "
            f"{counters.get('sync_creator_stalls', 0):.0f} creator stalls, "
            "rebuilds " + " ".join(sorted(
                f"{k[len(prefix):]}:{v:.0f}" for k, v in counters.items()
                if k.startswith(prefix) and v > 0)))
    return {
        "correct": checks.ok,
        "compared": checks.as_dict(),
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
