"""Driver kind ``flood-ingest``: a validator of a 16-validator ring rejoins
while a third of the ring floods it with syncs of forged events.

Each pass is a fresh ``Node`` built as ``core-ingest`` builds
``catchup16``'s (``harness/nodes.py``: ``Config(accelerator=True)``,
``InmemStore(cache_size)``, ``InmemProxy`` + dummy app, through
``Node.init()``, prewarm joined, not started; its sentry is the one
``Node`` builds from the config's defaults). Timed, from the first honest
sync to the end of the drain:

1. the ring's backlog in syncs of ``sync_events`` wire events from an
   honest peer, exactly as ``core-ingest`` feeds them: ``prepare_sync``
   outside the core lock, ``sync`` + ``process_sig_pool`` under it (the
   inline pull leg, ``Node._pull``); a sync the program refuses is scored
   as ``Node._gossip`` scores it;
2. after each honest sync, each flooder's pushes (``harness/flood.py``):
   every one an ``EagerSyncRequest`` handed to ``Node._process_rpc``, the
   node's own handler — state gate, quarantine refusal,
   ``_process_eager_sync_request``, ``prepare_sync``, the insert tail on
   the node's inserter thread (the pipeline ``Node`` builds on the wall
   clock), the sentry — and waited for; the response is discarded;
3. the drain (``ingest.py::_Pass._drain``).

Nothing of the program is replaced or stubbed. The forged events are made
in set-up and given fresh signatures between passes, outside every pass's
seconds. ``correct`` holds the validator to its configuration's guarantees:
the blocks against the host oracle fed what it stored, its store against
the honest stream's hashes, the sentry's ledger against the pushes.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

from benchmark.harness import data, flood, reference
from benchmark.harness.counters import node_snapshot, window_counters
from benchmark.harness.ingest import _add, _Pass

PUSH_WAIT_S = 120.0  # a push unanswered this long is a hung handler
JUNK_STAGES = ("decode", "batch_verify")  # split off the honest syncs' own
# the flood's own spans, logged per inserted event; no metric reads them yet
# (PERF.md, section 7)
FLOOD_STAGES = ("verify_fallback", "eager_sync_in")


class _FloodedPass(_Pass):
    """One fresh validator ingesting one backlog under the flood."""

    def __init__(self, env, keys, peers, me: int, conf: dict, flooders):
        super().__init__(env, keys, peers, me, conf)
        self.flooders = list(flooders)
        self.honest_refused: List[str] = []
        # (flooder id, quarantined before the push, reached the batch verify)
        self.pushes: List[tuple] = []
        self.refused_s = self.handled_s = self.drain_s = 0.0
        self.junk_s = dict.fromkeys(JUNK_STAGES, 0.0)

    def _stage_sums(self) -> Dict[str, float]:
        reg = self.node.telemetry.registry
        out = {}
        for stage in JUNK_STAGES:
            h = reg.histogram_summary("sync_stage_seconds", stage=stage)
            out[stage] = h["sum"] if h else 0.0
        return out

    def ingest(self, wires, from_id: int, sync_events: int,
               pushes: flood.Flood) -> None:
        from babble_tpu.net.rpc import RPC, EagerSyncRequest

        node, core, lock, span = self.node, self.core, self.node.core_lock, \
            self.env.span
        sentry = core.sentry
        before = node_snapshot(node)
        t0 = time.perf_counter()
        for k, chunk in enumerate(data.chunks(wires, sync_events)):
            try:
                with span("prepare_sync"):
                    prepared = core.prepare_sync(chunk)
                with lock, span("sync"):
                    core.sync(from_id, chunk, prepared)
                    core.process_sig_pool()
            except Exception as err:
                self.honest_refused.append(repr(err))
                sentry.observe_rejection(err, from_id)
            sums = self._stage_sums()
            for fid, events in pushes.after[k]:
                quarantined = sentry.is_quarantined(fid)
                verifies = core.ingest_batch_verifies
                rpc = RPC(EagerSyncRequest(fid, events))
                t_push = time.perf_counter()
                with span("flood"):
                    node._process_rpc(rpc)
                    rpc.wait(timeout=PUSH_WAIT_S)
                decoded = core.ingest_batch_verifies > verifies
                if decoded:
                    self.handled_s += time.perf_counter() - t_push
                else:
                    self.refused_s += time.perf_counter() - t_push
                self.pushes.append((fid, quarantined, decoded))
            for stage, v in self._stage_sums().items():
                self.junk_s[stage] += v - sums[stage]
        t_drain = time.perf_counter()
        with lock, span("drain"):
            self._drain()
        self.seconds = time.perf_counter() - t0
        self.drain_s = self.seconds - (t_drain - t0)
        self.counters = window_counters([before], [node_snapshot(node)])

    @property
    def handled(self) -> int:
        return sum(decoded for _f, _q, decoded in self.pushes)

    def summary(self) -> tuple:
        return super().summary() + (self.handled,
                                    len(self.pushes) - self.handled)

    def sentry_rows(self) -> Dict[str, int]:
        """The guarantees the sentry's ledger keeps in this pass."""
        sentry = self.core.sentry
        scored = {int(pid) for pid in sentry.suspects()["peers"]}
        return {
            "flooders_not_quarantined": sum(
                not sentry.is_quarantined(f) for f in self.flooders),
            "honest_peers_scored": len(scored - set(self.flooders)),
            "sentry_quarantine_deferrals": sentry.quarantine_deferrals,
            "junk_syncs_unscored": abs(
                self.handled - sentry.rejects.get("invalid_signature", 0)),
            "junk_syncs_decoded_after_quarantine": sum(
                q and decoded for _f, q, decoded in self.pushes),
        }


def span_us_per_event(counters: Dict[str, float], stage: str):
    """1e6 x the span's seconds / the ``insert`` span's count: 0 where the
    program has no such span, None where nothing was inserted."""
    inserted = counters.get("sync_stage_seconds.insert.count", 0.0)
    if not inserted:
        return None
    return 1e6 * counters.get(f"sync_stage_seconds.{stage}.sum", 0.0) / inserted


def _split(p: _FloodedPass) -> str:
    """Where one pass's seconds went, from the program's own spans."""
    c = p.counters
    per_event = {stage: span_us_per_event(c, stage) or 0.0
                 for stage in FLOOD_STAGES}

    def ms(stage: str) -> float:
        return 1000.0 * c.get(f"sync_stage_seconds.{stage}.sum", 0.0)

    junk = {k: 1000.0 * v for k, v in p.junk_s.items()}
    gc_s = sum(v for k, v in c.items()
               if k.startswith("gc_pause_seconds.") and k.endswith(".sum"))
    wait_ms = (c.get("accel_stage_ms.dispatch", 0.0)
               + c.get("accel_stage_ms.readback", 0.0))
    return (f"{p.seconds:.3f}s: prepare_sync {ms('prepare_sync'):.0f} ms "
            f"(decode {ms('decode'):.0f}, of it the pushes' "
            f"{junk['decode']:.0f}; batch_verify {ms('batch_verify'):.0f}, of "
            f"it the pushes' {junk['batch_verify']:.0f}, of that "
            f"verify_fallback {ms('verify_fallback'):.0f}), sync "
            f"{ms('sync'):.0f} (insert {ms('insert'):.0f}, divide_rounds "
            f"{ms('divide_rounds'):.0f}, commit {ms('commit'):.0f}), "
            f"eager_sync_in {ms('eager_sync_in'):.0f}, flushes "
            f"{ms('flush'):.0f}, the drain {1000.0 * p.drain_s:.0f}; "
            f"{p.handled} pushes handled in {1000.0 * p.handled_s:.0f} ms, "
            f"{len(p.pushes) - p.handled} refused in "
            f"{1000.0 * p.refused_s:.1f} ms; "
            f"{c.get('ingest_fallback_singles', 0):.0f} events re-checked "
            f"alone; collector {1000.0 * gc_s:.0f} ms, "
            f"{c.get('accel_sweeps', 0):.0f} sweeps waited for {wait_ms:.0f} ms"
            f"; an inserted event: verify_fallback "
            f"{per_event['verify_fallback']:.1f} us, eager_sync_in "
            f"{per_event['eager_sync_in']:.1f} us")


def run(cell, env) -> dict:
    conf, traffic = env.sized(cell.config), env.sized(cell.traffic)
    n = int(conf["validators"])
    me = int(conf.get("rejoining_validator", 0))
    keys = data.seeded_keys(n, env.seed)
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(n)])
    ids = [peers.by_pub_key[k.public_key.hex()].id for k in keys]
    creators = [i for i in range(n) if i != me]
    flooders = creators[-int(traffic["flooders"]):]
    from_id = ids[creators[0]]
    sync_events = int(traffic["sync_events"])
    if sync_events > int(conf["sync_limit"]):
        raise ValueError(f"sync_events {sync_events} is over SyncLimit "
                         f"{conf['sync_limit']}")
    t_gen = time.monotonic()
    streams = [
        data.backlog_wire_events(
            keys, peers, creators, int(traffic["backlog_events"]),
            int(traffic["dag_seed"]), int(conf["tx_bytes"]), tag=k)
        for k in range(int(traffic["distinct_streams"]))
    ]
    t_flood = time.monotonic()
    pushes = flood.Flood(
        streams[0], [ids[f] for f in flooders], sync_events,
        int(traffic["junk_syncs_per_sync"]),
        int(traffic["junk_events_per_sync"]), int(conf["tx_bytes"]), env.seed)
    t_sample = time.monotonic()
    sample = flood.sample_items(streams[0], pushes, peers,
                                int(traffic["reference_sample_events"]))
    differing = flood.verdicts_differing(sample)
    pushes.refresh()  # the sample's signatures are never sent
    env.log(
        f"backlog: {len(streams)} streams of {len(streams[0])} wire events "
        f"from {len(creators)} creators, keys from seed {env.seed}, DAG shape "
        f"from dag_seed {traffic['dag_seed']} ({t_flood - t_gen:.1f}s); "
        f"flooders {['v%d' % f for f in flooders]}: "
        f"{sum(len(a) for a in pushes.after)} pushes a pass of "
        f"{traffic['junk_events_per_sync']} forged events "
        f"({t_sample - t_flood:.1f}s to make, fresh signatures before every "
        f"pass); {len(sample)} events checked by the curve's own arithmetic: "
        f"{differing} verdicts differ ({time.monotonic() - t_sample:.1f}s)")

    turn = [0]
    flooder_ids = [ids[f] for f in flooders]

    def one_pass() -> _FloodedPass:
        pushes.refresh()
        gc.collect()
        p = _FloodedPass(env, keys, peers, me, conf, flooder_ids)
        p.ingest(streams[turn[0] % len(streams)], from_id, sync_events,
                 pushes)
        turn[0] += 1
        return p

    # set-up: untimed passes until one meets every bucket compiled
    for i in range(int(traffic.get("warm_passes_max", 3))):
        p = one_pass()
        waits = p.counters.get("accel_compile_waits", 0.0)
        env.log(f"warm pass {i}: ordered/blocks/txs/pushes handled/refused "
                f"{p.summary()}, sweeps {p.counters.get('accel_sweeps', 0):.0f}"
                f", compile waits {waits:.0f}; {_split(p)}")
        p.close()
        if waits == 0:
            break

    # the set-up's heap (the streams, the forged events) is set aside, so a
    # collection inside a pass walks the validator's objects, not the harness's
    gc.collect()
    gc.freeze()
    try:
        env.window_open()
        t_open = time.monotonic()
        audited, audited_stream = None, 0
        summaries: List[tuple] = []
        seconds: List[float] = []
        splits: List[str] = []
        rows: Dict[str, int] = {}
        honest_refused: List[str] = []
        counters: Dict[str, float] = {}
        while time.monotonic() - t_open < env.seconds:
            k = turn[0] % len(streams)
            p = one_pass()
            _add(counters, p.counters)
            summaries.append(p.summary())
            seconds.append(p.seconds)
            splits.append(_split(p))
            for name, v in p.sentry_rows().items():
                rows[name] = rows.get(name, 0) + v
            honest_refused += p.honest_refused
            if audited is None:
                audited, audited_stream = p, k
            else:
                p.close()
        in_window = time.monotonic() - t_open
        env.window_close()
    finally:
        gc.unfreeze()

    notes: List[str] = []
    checks = reference.Checks()
    t_ref = time.monotonic()
    honest = set(flood.honest_hashes(streams[audited_stream], peers).values())
    # what the validator stored of the others' events, against the stream
    own = keys[me].public_key.bytes()
    stored = {ev.hex() for ev in reference.stored_events(audited.core.hg.store)
              if ev.body.creator != own}
    junk_stored = len(stored - honest)
    try:
        audit = reference.audit_against_oracle(audited.core.hg, peers)
        notes.append("audit of the first timed pass: " + audit.note + " (the "
                     f"reference took {time.monotonic() - t_ref:.1f}s)")
        evicted, blocks_diff, expected = (audit.missing_events,
                                          audit.differing_blocks, audit.ordered)
    except Exception as err:  # a stored event the oracle refuses
        blocks = audited.core.get_last_block_index() + 1
        notes.append(f"the host oracle refused the events the first timed "
                     f"pass stored ({err!r}): its {blocks} blocks count as "
                     "differing")
        evicted, blocks_diff, expected = 0, max(1, blocks), 0
    checks.at_most("audited_events_evicted", evicted)
    checks.at_most("backlog_events_not_stored", len(honest - stored))
    if not checks.at_most("junk_events_stored", junk_stored):
        notes.append(f"the first timed pass stored {junk_stored} events that "
                     "are not the honest stream's")
    checks.at_most("blocks_differing_from_oracle", blocks_diff)
    checks.at_most("oracle_events_the_first_pass_missed",
                   abs(expected - summaries[0][0]))
    checks.at_most("reference_verdicts_differing", differing)
    for name in ("flooders_not_quarantined", "honest_peers_scored",
                 "sentry_quarantine_deferrals", "junk_syncs_unscored",
                 "junk_syncs_decoded_after_quarantine"):
        checks.at_most(name, rows.get(name, 0))
    if not checks.at_most("honest_syncs_refused", len(honest_refused)):
        notes.append(f"{len(honest_refused)} honest syncs were refused, the "
                     f"first by {honest_refused[0]}")
    chosen = {k: audited.node.get_stats_snapshot().get(k)
              for k in env.CHOICE_KEYS}
    audited.close()
    ordered = [s[0] for s in summaries]
    failed = sum(max(0, expected - c) for c in ordered)
    checks.at_most("events_not_ordered", failed)
    if not checks.at_most("distinct_pass_outcomes", len(set(summaries)), 1):
        notes.append("passes disagree on (ordered, blocks, transactions, "
                     "pushes handled, pushes refused): "
                     f"{sorted(set(summaries))}")
    notes.extend(reference.device_path(checks, counters))
    env.log(f"{len(seconds)} passes took {sum(seconds):.1f} of the window's "
            f"{in_window:.1f} s: seconds {[round(s, 3) for s in seconds]}, "
            f"ordered/blocks/txs/pushes handled/refused "
            f"{sorted(set(summaries))}")
    typical = sorted(seconds)[len(seconds) // 2]
    env.log(f"a typical pass, {splits[seconds.index(typical)]}")
    for i, t in enumerate(seconds):
        if t > 1.08 * typical:
            env.log(f"slow pass {i} of {len(seconds)}, {splits[i]}")
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
