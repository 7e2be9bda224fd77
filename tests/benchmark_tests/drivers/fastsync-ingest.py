"""Driver kind ``fastsync-ingest``: a validator of the genesis set that starts
more than SyncLimit events behind with ``--fast-sync`` does not replay. It
polls its peers for the latest block that more than a third of the
validators signed, restores the application from the peer's snapshot,
throws its hashgraph away, rebuilds it from that block's Frame and orders
the ring's tail on top.

Each pass is a fresh ``Node`` built as ``harness/nodes.py`` builds
``catchup16``'s with ``enable_fast_sync=True`` beside ``accelerator=True``
(``InmemStore(cache_size)``, ``InmemProxy`` + dummy app, through
``Node.init()``, which leaves it CATCHING_UP; prewarm joined, not started),
on an in-memory network where ONE peer answers ``FastForwardRequest`` and
the other addresses are not connected. Timed, from just before the landing
to the end of the drain:

1. ``node._fast_forward()``, the method the node's run loop calls in
   CATCHING_UP: the poll, ``proxy.restore``, ``Core.fast_forward``
   (``check_block``, the frame hash, ``Hashgraph.reset``), the transition
   to BABBLING. The peer's answer is the stream's marshalled response
   decoded anew for every request, as a socket transport hands it over: a
   landing never shares an object with the donor or an earlier landing;
2. the tail in syncs of at most ``sync_events`` wire events:
   ``prepare_sync`` outside the core lock, ``sync`` + ``process_sig_pool``
   under it (``ingest.py::_Pass.ingest``);
3. the drain (``ingest.py::_Pass._drain``).

Nothing of the program is replaced or stubbed. Responses and tails are made
in set-up BY the program (``harness/fastsync.py``: a donor ``Core``).
``correct`` compares the validator's chain from its landing block on with
``fastsync.replay``: a host hashgraph that never reset, fed the whole
history from genesis.
"""

from __future__ import annotations

import gc
import os
import threading
import time
from typing import Dict, List, Optional

from benchmark.harness import data, fastsync, reference
from benchmark.harness.counters import node_snapshot, window_counters
from benchmark.harness.ingest import _add, _Pass

VERDICT_CACHE_ENTRIES = 32768  # crypto/batch.py: process-wide
WORKERS_MAX = 8  # processes that make streams in set-up


class _Peer:
    """The one peer of the ring that answers: a thread on its transport's
    consumer queue, as ``Node._do_background_work`` is on a validator's."""

    def __init__(self, network, addr: str):
        self.trans = network.new_transport(addr)
        self.response: Optional[bytes] = None
        self.answered = 0
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="fastsync-peer")
        self._thread.start()

    def _serve(self) -> None:
        from babble_tpu.net.rpc import FastForwardRequest

        while True:
            rpc = self.trans.consumer().get()
            if rpc is None:
                return
            if not isinstance(rpc.command, FastForwardRequest):
                rpc.respond(None, "this peer answers fast-forward alone")
            elif self.response is None:
                rpc.respond(None, "no anchor block")
            else:
                self.answered += 1
                rpc.respond(fastsync.decode_response(self.response), None)

    def close(self) -> None:
        self.trans.consumer().put(None)
        self._thread.join(timeout=5.0)
        self.trans.close()


class _Landing(_Pass):
    """One fresh ``--fast-sync`` validator: it lands, then ingests a tail."""

    error: Optional[BaseException] = None

    def __init__(self, env, keys, peers, me: int, conf: dict, network):
        from babble_tpu.config.config import Config
        from babble_tpu.dummy.state import State as DummyState
        from babble_tpu.hashgraph.store import InmemStore
        from babble_tpu.node.node import Node
        from babble_tpu.node.state import State
        from babble_tpu.node.validator import Validator
        from babble_tpu.proxy.proxy import InmemProxy

        moniker, addr = f"v{me}", f"inmem://v{me}"
        node_conf = Config(
            bind_addr=addr, moniker=moniker, log_level="critical",
            no_service=True, accelerator=True, enable_fast_sync=True,
        )
        self.node = Node(
            node_conf, Validator(keys[me], moniker), peers, peers,
            InmemStore(node_conf.cache_size), network.new_transport(addr),
            InmemProxy(DummyState()))
        env.scale_gate(self.node, conf)
        self.node.init()
        warm = getattr(self.node, "_prewarm_thread", None)
        if warm is not None:
            warm.join()
        self.core = self.node.core
        self.babbling = State.BABBLING
        self.own_id = self.node.get_id()
        self.env = env
        self.seconds = 0.0
        self.counters: Dict[str, float] = {}
        # right after the landing: the consensus count (InmemStore.reset
        # keeps it and insert_frame_event adds to it, so the raw count is
        # not the work), the events of other creators, the last block
        self.base_ordered = 0
        self.base_stored = 0
        self.landed_on = -1
        self.state_after_landing = None

    def land(self) -> None:
        with self.env.span("fast_forward"):
            self.node._fast_forward()
        self.state_after_landing = self.node.get_state()
        self.landed_on = self.core.get_last_block_index()
        self.base_ordered = self.core.get_consensus_events_count()
        self.base_stored = self._indexes_of_others()

    def run(self, stream, from_id: int, sync_events: int) -> None:
        """The timed pass; what stops it is kept, its seconds count."""
        core, lock, span = self.core, self.node.core_lock, self.env.span
        tail = fastsync.decode_events(stream.tail)  # as off the wire
        before = node_snapshot(self.node)
        t0 = time.perf_counter()
        try:
            self.land()
            if self.landed():
                for chunk in data.chunks(tail, sync_events):
                    with span("prepare_sync"):
                        prepared = core.prepare_sync(chunk)
                    with lock, span("sync"):
                        core.sync(from_id, chunk, prepared)
                        core.process_sig_pool()
                with lock, span("drain"):
                    self._drain()
        except Exception as err:  # a refused sync, a drain that never ends
            self.error = err
        self.seconds = time.perf_counter() - t0
        self.counters = window_counters([before], [node_snapshot(self.node)])

    def landed(self) -> bool:
        return self.state_after_landing == self.babbling and self.landed_on >= 0

    @property
    def ordered(self) -> int:
        """Events ordered AFTER the landing; a pass that was stopped
        ordered nothing whole."""
        if self.error is not None or not self.landed():
            return 0
        return self.core.get_consensus_events_count() - self.base_ordered

    def _indexes_of_others(self) -> int:
        """The sum of the other creators' last known indexes: it rises by
        one for every event of theirs the store takes (after a reset the
        store can no longer list a creator's events from its first)."""
        return sum(index for pid, index in self.core.known_events().items()
                   if pid != self.own_id)

    def tail_stored(self) -> int:
        return self._indexes_of_others() - self.base_stored

    def summary(self) -> tuple:
        return (self.ordered, self.core.get_last_block_index() - self.landed_on)


def _buckets(counters: Dict[str, float]) -> str:
    return " ".join(sorted(
        f"{k.split('.', 1)[1]}:{v:.0f}" for k, v in counters.items()
        if v > 0 and "_bucket_launches." in k))


def _split(counters: Dict[str, float]) -> str:
    """Where one pass's seconds went, from the program's own spans."""
    def ms(stage: str) -> float:
        return 1000.0 * counters.get(f"sync_stage_seconds.{stage}.sum", 0.0)

    return (f"landing {ms('fast_forward'):.0f} ms (poll {ms('ff_poll'):.0f}, "
            f"check {ms('ff_check'):.0f}, reset {ms('ff_reset'):.0f}), "
            f"prepare_sync {ms('prepare_sync'):.0f}, sync {ms('sync'):.0f}, "
            f"sig pool {ms('process_sig_pool'):.0f}, flushes in the sync "
            f"and the drain {ms('flush'):.0f}; "
            f"{counters.get('accel_sweeps', 0):.0f} sweeps waited for "
            f"{counters.get('accel_stage_ms.dispatch', 0) + counters.get('accel_stage_ms.readback', 0):.0f}"
            f" ms, {counters.get('accel_compile_waits', 0):.0f} compile waits")


def _rebuilds(counters: Dict[str, float]) -> str:
    prefix = "accel_rebuilds_by_reason."
    return " ".join(sorted(
        f"{k[len(prefix):]}:{v:.0f}" for k, v in counters.items()
        if k.startswith(prefix) and v > 0)) or "none"


def run(cell, env) -> dict:
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.state import State

    conf, traffic = env.sized(cell.config), env.sized(cell.traffic)
    n = int(conf["validators"])
    me = int(conf.get("rejoining_validator", 0))
    ring = fastsync.ring_of(n, me, env.seed)
    keys, peers, creators = ring.keys, ring.peers, ring.creators
    addrs = [f"inmem://v{i}" for i in range(n)]
    from_id = peers.by_pub_key[keys[creators[0]].public_key.hex()].id
    history_events = int(traffic["history_events"])
    sync_events = int(traffic["sync_events"])
    poll_at = traffic.get("poll_at_event")

    def job(tag: int, **more) -> fastsync.Job:
        return fastsync.Job(
            n, me, env.seed, history_events, int(traffic["dag_seed"]),
            int(conf["tx_bytes"]), None if poll_at is None else int(poll_at),
            tag, **more)

    # set-up: the streams, each ring's history through a donor of its own,
    # side by side in worker processes; a history is dropped once its
    # response and tail are made (the one the reference needs is made
    # again after the window)
    t_gen = time.monotonic()
    jobs = [job(k, forged=(k == 0))
            for k in range(int(traffic["distinct_streams"]))]
    workers = (1 if len(jobs) <= 2 else
               min(WORKERS_MAX, len(jobs), max(1, (os.cpu_count() or 2) - 2)))
    made = fastsync.make_streams(jobs, workers)
    streams: List[fastsync.Stream] = [stream for stream, _forged in made]
    forged = made[0][1]
    first = streams[0]
    tail_signatures = sum(s.tail_events for s in streams)
    env.log(
        f"fast-sync: {len(streams)} streams of {history_events} events from "
        f"{len(creators)} creators, keys from seed {env.seed}, DAG shape from "
        f"dag_seed {traffic['dag_seed']} ({time.monotonic() - t_gen:.1f}s in "
        f"{workers} processes); "
        f"anchor block {first.anchor_index} of round {first.anchor_round} "
        f"with {first.anchor_signatures} anchor signatures, a Frame of "
        f"{first.frame_events} frame events in {first.frame_bytes} bytes "
        f"(response {len(first.response)} bytes), {first.tail_events} tail "
        f"events carrying {first.tail_block_signatures} block signatures; "
        f"the tails hold {tail_signatures} event signatures in all (the "
        f"verdict cache {VERDICT_CACHE_ENTRIES}); the donor ordered "
        f"{first.ordered_after} events in {first.blocks_after} blocks "
        "above the anchor")
    if tail_signatures <= VERDICT_CACHE_ENTRIES and not env.rehearsal:
        raise ValueError(
            f"the tails hold {tail_signatures} signatures, no more than the "
            f"verdict cache's {VERDICT_CACHE_ENTRIES}: raise distinct_streams")
    if sync_events > int(conf["sync_limit"]):
        raise ValueError(f"sync_events {sync_events} is over SyncLimit "
                         f"{conf['sync_limit']}")

    network = InmemNetwork()
    peer = _Peer(network, addrs[creators[0]])
    turn = [0]

    def validator() -> _Landing:
        gc.collect()
        return _Landing(env, keys, peers, me, conf, network)

    def one_pass() -> _Landing:
        p = validator()
        stream = streams[turn[0] % len(streams)]
        peer.response = stream.response
        p.run(stream, from_id, sync_events)
        turn[0] += 1
        return p

    try:
        # set-up: the landing's own negatives. A fresh validator is offered
        # each forged response; it has to refuse it and hold no block.
        accepted = 0
        for what, payload in forged:
            p = validator()
            peer.response = payload
            p.land()
            # (a program that does not count its refusals yet is judged
            # by its state and its store alone)
            refused = (p.node.get_state() == State.CATCHING_UP
                       and p.landed_on < 0
                       and getattr(p.node, "fast_forward_failures", 1) == 1)
            env.log(f"forged anchor ({what}): "
                    + ("refused" if refused else
                       f"ACCEPTED, state {p.node.get_state()}, last block "
                       f"{p.landed_on}"))
            accepted += 0 if refused else 1
            p.close()

        # set-up: untimed passes until one meets every bucket compiled
        for i in range(int(traffic.get("warm_passes_max", 3))):
            p = one_pass()
            waits = p.counters.get("accel_compile_waits", 0.0)
            env.log(f"warm pass {i}: {p.seconds:.3f}s, ordered/blocks after "
                    f"the landing {p.summary()}, sweeps "
                    f"{p.counters.get('accel_sweeps', 0):.0f}, compile waits "
                    f"{waits:.0f}, buckets {_buckets(p.counters)}"
                    + (f", stopped by {p.error!r}" if p.error else ""))
            p.close()
            if waits == 0 and p.error is None:
                break

        # Back-to-back landings: what stood between two passes was the full
        # collection before each fresh validator, 60 ms of walking the
        # process's old heap (the imported modules, the compiled programs,
        # the streams) against a pass of 120. That heap is set aside, so the
        # collection between two passes walks the last validator's garbage
        # alone (12 ms) and the window holds a third more passes; inside a
        # pass nothing changes (no full collection fell into one either way).
        gc.collect()
        gc.freeze()
        env.window_open()
        t_open = time.monotonic()
        audited, audited_stream = None, 0
        summaries: List[tuple] = []
        expected: List[int] = []
        seconds: List[float] = []
        errors: List[str] = []
        not_landed = 0
        counters: Dict[str, float] = {}
        splits: List[str] = []
        while time.monotonic() - t_open < env.seconds:
            k = turn[0] % len(streams)
            p = one_pass()
            _add(counters, p.counters)
            splits.append(_split(p.counters))
            summaries.append(p.summary())
            expected.append(streams[k].ordered_after)
            seconds.append(p.seconds)
            if p.error is not None:
                errors.append(repr(p.error))
            if not p.landed() or p.landed_on != streams[k].anchor_index:
                not_landed += 1
            if audited is None:
                audited, audited_stream = p, k
            else:
                p.close()
        in_window = time.monotonic() - t_open
        env.window_close()
    finally:
        gc.unfreeze()
        peer.close()

    # the reference: the first timed pass's ring from genesis, never reset
    notes: List[str] = []
    checks = reference.Checks()
    t_ref = time.monotonic()
    again, _none = fastsync.make_stream(job(audited_stream, history=True))
    stream = streams[audited_stream]
    if again.response != stream.response:
        notes.append("the generator made another response from the same seeds")
    want = fastsync.replay(again.history, peers)
    hg = audited.core.hg
    got = fastsync.chain_of(hg, max(0, audited.landed_on))
    after = [b for b in got.blocks if b > stream.anchor_index]
    blocks_diff = fastsync.differing(got.blocks, want.blocks, after)
    hashes_diff = fastsync.differing(got.state_hashes, want.state_hashes, after)
    landing_diff = fastsync.differing(got.blocks, want.blocks,
                                      [stream.anchor_index])
    want_ordered = want.ordered_after(stream.anchor_round)
    notes.append(
        f"audit of the first timed pass (stream {audited_stream}): landed on "
        f"block {audited.landed_on} (anchor {stream.anchor_index}), "
        f"{len(after)} blocks after it against the reference's "
        f"{len(want.blocks) - stream.anchor_index - 1}: {len(blocks_diff)} "
        f"differ, {len(hashes_diff)} state hashes differ; ordered "
        f"{audited.ordered} against the reference's {want_ordered} in rounds "
        f"above {stream.anchor_round} (its {want.events} events from genesis "
        f"gave {len(want.blocks)} blocks; it took "
        f"{time.monotonic() - t_ref:.1f}s)")
    checks.at_most("blocks_differing_from_oracle", len(blocks_diff))
    checks.at_least("blocks_committed_after_landing", len(after), 1)
    checks.at_most("state_hashes_differing_from_oracle", len(hashes_diff))
    checks.at_most("landing_block_differing_from_oracle", len(landing_diff))
    checks.at_most("oracle_events_the_first_pass_missed",
                   abs(want_ordered - audited.ordered))
    checks.at_most("tail_events_not_stored",
                   stream.tail_events - audited.tail_stored())
    if not checks.at_most("fast_forwards_not_landed", not_landed):
        notes.append(f"{not_landed} of {len(seconds)} passes were not "
                     "BABBLING on the anchor block right after the landing")
    checks.at_most("forged_anchors_accepted", accepted)
    if want_ordered != stream.ordered_after:
        notes.append(f"the reference ordered {want_ordered} events above the "
                     f"anchor, the donor {stream.ordered_after}")
    chosen = {k: audited.node.get_stats_snapshot().get(k)
              for k in env.CHOICE_KEYS}
    audited.close()

    ordered = [s[0] for s in summaries]
    failed = sum(max(0, e - c) for e, c in zip(expected, ordered))
    checks.at_most("events_not_ordered", failed)
    if errors:
        notes.append(f"{len(errors)} of {len(seconds)} passes were stopped, "
                     f"the first by {errors[0]}")
    # the streams share dag_seed: their counts agree by shape
    if not checks.at_most("distinct_pass_outcomes", len(set(summaries)), 1):
        notes.append("passes disagree on (ordered, blocks) after the "
                     f"landing: {sorted(set(summaries))}")
    notes.extend(reference.device_path(checks, counters))
    c = counters
    env.log(f"{len(seconds)} passes took {sum(seconds):.1f} of the window's "
            f"{in_window:.1f} s: seconds {[round(s, 3) for s in seconds]}, "
            f"ordered after the landing {ordered}; buckets launched "
            f"{_buckets(c)}; {c.get('accel_small_windows', 0):.0f} flushes "
            "under the gate")
    typical = sorted(seconds)[len(seconds) // 2]
    slow = [i for i, t in enumerate(seconds) if t > 1.5 * typical]
    env.log(f"a typical pass ({typical:.3f}s): {splits[seconds.index(typical)]}")
    for i in slow[:8]:
        env.log(f"slow pass {i} of {len(seconds)} ({seconds[i]:.3f}s): "
                f"{splits[i]}")
    env.log("fast-sync inside the window: "
            f"{c.get('fast_forwards', 0):.0f} landings, "
            f"{c.get('fast_forward_failures', 0):.0f} refused, "
            f"{c.get('frame_events_inserted', 0):.0f} frame events inserted, "
            f"{c.get('anchor_signatures_checked', 0):.0f} anchor signatures "
            f"checked, {peer.answered} polls answered in the run, window "
            f"rebuilds {_rebuilds(c)}")
    return {
        "correct": checks.ok,
        "compared": checks.as_dict(),
        "attempted": sum(expected),
        "failed": failed,
        "notes": notes,
        "end_to_end": {
            "catchup_events_per_s": sum(ordered) / sum(seconds),
        },
        "counters": counters,
        "samples": {},
        "chosen": chosen,
    }
