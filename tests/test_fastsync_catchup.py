"""A validator that lands on an anchor block's Frame and orders the ring's
tail on top (deployment ``fastsync16``) at a small size on the CPU: 4
validators, the flush gate at 16 events, seeded keys.

- the benchmark's generator (``benchmark/harness/fastsync.py``) makes rings
  whose events carry block signatures, whose donor reaches an anchor block,
  the same bytes for the same seeds;
- the program — ``Node._fast_forward`` (poll, restore, ``check_block``, the
  frame hash, ``Hashgraph.reset``) and then ``prepare_sync`` / ``sync`` /
  ``process_sig_pool`` — with ``--accelerator`` on, in the lane a chip
  resolves, and on the host path, against the benchmark's plain reference: a
  host hashgraph that never reset, fed the whole history from genesis. From
  the anchor on the two hold the same chain;
- the landing's negatives: an anchor with a third of the signatures, or
  beside another round's Frame, is refused and counted;
- a landing while a sweep is in flight drops it and rebuilds the window
  state from the Frame.
"""

from __future__ import annotations

import threading

import pytest

from babble_tpu.config.config import Config
from babble_tpu.dummy.state import State as DummyState
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.net.inmem import InmemNetwork
from babble_tpu.net.rpc import FastForwardRequest
from babble_tpu.node.node import Node
from babble_tpu.node.state import State
from babble_tpu.node.validator import Validator
from babble_tpu.proxy.proxy import InmemProxy
from benchmark.harness import data, fastsync
from benchmark.harness.counters import node_snapshot

N, ME = 4, 0
SEEDS = [3000000019, 2400000011, 3500000311]
DAG_SEED = 2147483659
HISTORY, POLL_AT = 600, 300

SPANS = ("fast_forward", "ff_poll", "ff_restore", "ff_check", "ff_reset")

LANES = {
    # accelerator, pipeline, batcher
    "host": (False, False, False),
    "sync": (True, False, False),
    "chip": (True, True, True),  # the lane a chip resolves
}


def _job(seed=SEEDS[0], **more):
    more.setdefault("poll_at", POLL_AT)
    return fastsync.Job(N, ME, seed, more.pop("history_events", HISTORY),
                        more.pop("dag_seed", DAG_SEED), 100,
                        more.pop("poll_at"), more.pop("tag", 0), **more)


@pytest.fixture(scope="module")
def ring():
    """One ring, its forged offers and the reference's replay of it."""
    stream, forged = fastsync.make_stream(_job(forged=True, history=True))
    peers = fastsync.ring_of(N, ME, SEEDS[0]).peers
    return stream, forged, fastsync.replay(stream.history, peers)


class _Ring:
    """An in-memory network on which ONE peer answers fast-forward with a
    marshalled response, decoded anew for every request."""

    def __init__(self, seed):
        self.who = fastsync.ring_of(N, ME, seed)
        self.network = InmemNetwork()
        self.trans = self.network.new_transport("inmem://v1")
        self.response = None
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        while True:
            rpc = self.trans.consumer().get()
            if rpc is None:
                return
            assert isinstance(rpc.command, FastForwardRequest)
            rpc.respond(fastsync.decode_response(self.response), None)

    def validator(self, lane: str) -> Node:
        """v0 as ``engine.py`` builds it with ``--fast-sync``, the flush
        gate scaled to a 4-validator window and compiles inline."""
        accelerator, pipeline, batcher = LANES[lane]
        key = self.who.keys[ME]
        conf = Config(bind_addr="inmem://v0", moniker="v0",
                      log_level="critical", no_service=True,
                      accelerator=accelerator, enable_fast_sync=True)
        node = Node(conf, Validator(key, "v0"), self.who.peers,
                    self.who.peers, InmemStore(conf.cache_size),
                    self.network.new_transport("inmem://v0"),
                    InmemProxy(DummyState()))
        if accelerator:
            tc = node.core.hg.accel
            tc.min_window, tc.async_compile = 16, False
            tc.pipeline, tc.batcher = pipeline, batcher
        node.init()
        assert node.get_state() == State.CATCHING_UP
        return node

    @property
    def from_id(self) -> int:
        return self.who.peers.by_pub_key[
            self.who.keys[1].public_key.hex()].id

    def close(self):
        self.trans.consumer().put(None)
        self._thread.join(timeout=5.0)


def _ingest(node: Node, ring: _Ring, wires, sync_events=300, drain=True):
    core = node.core
    for chunk in data.chunks(wires, sync_events):
        prepared = core.prepare_sync(chunk)
        with node.core_lock:
            core.sync(ring.from_id, chunk, prepared)
            core.process_sig_pool()
    if drain:
        core.hg.drain_consensus()


def _assert_chain_is_the_references(node: Node, stream, want):
    """From the anchor on the validator holds the reference's chain."""
    hg = node.core.hg
    got = fastsync.chain_of(hg, stream.anchor_index)
    after = [b for b in got.blocks if b > stream.anchor_index]
    assert len(after) == len(want.blocks) - stream.anchor_index - 1 > 10
    every = [stream.anchor_index] + after
    assert fastsync.differing(got.blocks, want.blocks, every) == []
    assert fastsync.differing(got.state_hashes, want.state_hashes, after) == []
    with pytest.raises(Exception):  # the store starts at the anchor
        hg.store.get_block(stream.anchor_index - 1)


def test_the_rings_events_carry_block_signatures_and_the_donor_has_an_anchor(
        ring):
    stream, forged, want = ring
    assert len(stream.history) == HISTORY
    carried = sum(len(w.body.block_signatures) for w in stream.history)
    # three creators sign every block the ring commits
    assert carried >= 3 * (len(want.blocks) - 2) > 100
    assert stream.anchor_index >= 1 and stream.anchor_signatures == 3
    tail = fastsync.decode_events(stream.tail)
    assert len(tail) == stream.tail_events > 100
    assert stream.tail_block_signatures == sum(
        len(w.body.block_signatures) for w in tail) > 0
    # the tail is the history's own events, in its order
    assert [w.signature for w in tail] == [
        w.signature for w in stream.history if w.signature in
        {t.signature for t in tail}]
    assert stream.ordered_after == want.ordered_after(stream.anchor_round) > 200
    assert stream.blocks_after == len(want.blocks) - stream.anchor_index - 1
    # the response is the program's own: the anchor with its own Frame
    resp = fastsync.decode_response(stream.response)
    assert resp.block.frame_hash() == resp.frame.hash()
    assert resp.snapshot == want.state_hashes[stream.anchor_index]
    assert stream.frame_events == len(resp.frame.sorted_frame_events())
    assert len(forged) == 2


def test_a_history_too_short_for_an_anchor_fails_loudly():
    with pytest.raises(ValueError, match="no anchor block at index 1"):
        fastsync.make_stream(_job(history_events=12, poll_at=None))


def test_the_same_seeds_give_the_same_bytes(ring):
    stream = ring[0]
    again, _ = fastsync.make_stream(_job())
    assert again.history == [] and again.response == stream.response
    assert again.tail == stream.tail
    # the shape is dag_seed's alone; keys and a tag change every byte
    for other in (_job(seed=SEEDS[1]), _job(tag=1)):
        s, _ = fastsync.make_stream(other)
        assert s.response != stream.response
        assert (s.tail_events, s.anchor_index, s.frame_events,
                s.ordered_after, s.blocks_after) == (
            stream.tail_events, stream.anchor_index, stream.frame_events,
            stream.ordered_after, stream.blocks_after)
        assert not ({w.signature for w in fastsync.decode_events(s.tail)}
                    & {w.signature for w in fastsync.decode_events(stream.tail)})


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("lane", ["chip", "sync"])
def test_an_accelerated_landing_holds_the_references_chain(seed, lane):
    stream, _ = fastsync.make_stream(_job(seed, history=True))
    net = _Ring(seed)
    try:
        want = fastsync.replay(stream.history, net.who.peers)
        node = net.validator(lane)
        # a validator that has not fast-forwarded has opened no span of it
        flat = node_snapshot(node)
        assert not any(flat.get(f"sync_stage_seconds.{s}.count") for s in SPANS)
        net.response = stream.response
        node._fast_forward()
        assert node.get_state() == State.BABBLING
        assert node.get_last_block_index() == stream.anchor_index
        flat = node_snapshot(node)
        assert [flat[f"sync_stage_seconds.{s}.count"] for s in SPANS] == [1] * 5
        assert flat["sync_stage_seconds.fast_forward.sum"] >= sum(
            flat[f"sync_stage_seconds.{s}.sum"] for s in SPANS[1:])
        base = node.core.get_consensus_events_count()
        _ingest(node, net, fastsync.decode_events(stream.tail))
        tc = node.core.hg.accel
        assert tc.sweeps > 0 and tc.fallbacks == 0 and tc.mesh_fallbacks == 0
        _assert_chain_is_the_references(node, stream, want)
        assert (node.core.get_consensus_events_count() - base
                == want.ordered_after(stream.anchor_round))
        snap = node.get_stats_snapshot()
        assert snap["fast_forwards"] == 1 and snap["fast_forward_failures"] == 0
        assert snap["frame_events_inserted"] == stream.frame_events
        assert snap["anchor_signatures_checked"] == stream.anchor_signatures
        node.shutdown()
    finally:
        net.close()


def test_a_host_path_landing_holds_the_references_chain(ring):
    stream, _forged, want = ring
    net = _Ring(SEEDS[0])
    try:
        node = net.validator("host")
        net.response = stream.response
        node._fast_forward()
        assert node.get_state() == State.BABBLING
        _ingest(node, net, fastsync.decode_events(stream.tail))
        _assert_chain_is_the_references(node, stream, want)
        node.shutdown()
    finally:
        net.close()


@pytest.mark.parametrize("lane", ["chip", "host"])
def test_a_forged_anchor_is_refused_and_counted(ring, lane):
    stream, forged, _want = ring
    net = _Ring(SEEDS[0])
    try:
        for what, payload in forged:
            node = net.validator(lane)
            net.response = payload
            node._fast_forward()
            assert node.get_state() == State.CATCHING_UP, what
            assert node.get_last_block_index() == -1, what
            snap = node.get_stats_snapshot()
            assert snap["fast_forward_failures"] == 1, what
            assert snap["fast_forwards"] == 0, what
            assert snap["frame_events_inserted"] == 0, what
            # refused in ff_check: the reset never opened
            flat = node_snapshot(node)
            assert flat["sync_stage_seconds.fast_forward.count"] == 1
            assert flat["sync_stage_seconds.ff_check.count"] == 1
            assert not flat.get("sync_stage_seconds.ff_reset.count")
            node.shutdown()
        # the first offer's block has exactly trust_count signatures
        resp = fastsync.decode_response(forged[0][1])
        assert len(resp.block.signatures) == resp.frame.peers.trust_count()
        # the second's Frame is another round's
        resp = fastsync.decode_response(forged[1][1])
        assert resp.frame.round != resp.block.round_received()
    finally:
        net.close()


def test_a_landing_while_a_sweep_is_in_flight_drops_it_and_rebuilds(ring):
    """v0 was up for the ring's first 200 events, fell behind and lands:
    the sweep its last sync left in flight describes a store that is gone."""
    stream, _forged, want = ring
    net = _Ring(SEEDS[0])
    try:
        node = net.validator("chip")
        _ingest(node, net, stream.history[:200], sync_events=100, drain=False)
        tc = node.core.hg.accel
        assert tc.busy(), "no sweep in flight to drop"
        before = tc.stats()["accel_rebuilds_by_reason"].get("invalidate", 0)
        net.response = stream.response
        node._fast_forward()
        assert node.get_state() == State.BABBLING and not tc.busy()
        assert node.get_last_block_index() == stream.anchor_index
        _ingest(node, net, fastsync.decode_events(stream.tail))
        by_reason = tc.stats()["accel_rebuilds_by_reason"]
        assert by_reason.get("invalidate", 0) >= before + 1
        assert tc.fallbacks == 0
        _assert_chain_is_the_references(node, stream, want)
        node.shutdown()
    finally:
        net.close()
