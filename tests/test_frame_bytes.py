"""A Frame's hash is assembled from its events' frame forms (FrameForm,
hashgraph/event.py); these tests hold it, byte for byte, to the plain
``canonical_dumps(frame.to_dict())`` — FrameHash goes into every block that
every validator signs, so a different byte is a fork.

One random-gossip DAG with real signatures among five participants, with an
event that carries an internal transaction and block signatures and a
peer-set change registered mid-stream, is replayed through three Hashgraphs:
a plain one, one that drops every memo before each ``get_frame``, and (for
the counters) one that counts ``_create_frame_event`` calls.
"""

from __future__ import annotations

import random

import pytest

from babble_tpu.crypto.canonical import canonical_dumps, canonical_loads
from babble_tpu.crypto.hashing import sha256
from babble_tpu.crypto.keys import decode_signature, generate_key
from babble_tpu.hashgraph import (
    BlockSignature,
    Event,
    Frame,
    FrameEvent,
    Hashgraph,
    InmemStore,
    InternalTransaction,
    sort_frame_events,
)
from babble_tpu.hashgraph.event import FrameForm, _signature_r
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet

from tests.test_accel import _ordered_events as _stored_events

N_PEERS = 5
N_EVENTS = 700
MIN_ROUNDS = 12
LOADED_AT = 40  # this event carries the internal transaction + signatures
CHANGE_AT = N_EVENTS // 2  # after this insert the smaller peer-set is set


class _NoMemoHashgraph(Hashgraph):
    """Every memo the frame path could answer from is dropped before each
    get_frame: frame forms, and the bodies' kept encodings."""

    def get_frame(self, round_received: int) -> Frame:
        for ev in _stored_events(self):
            ev._frame = None
            ev.body.invalidate_normalized()
        return super().get_frame(round_received)


class _CountingHashgraph(Hashgraph):
    calls = 0

    def _create_frame_event(self, x: str) -> FrameEvent:
        self.calls += 1
        return super()._create_frame_event(x)


class _Dag:
    def __init__(self) -> None:
        rng = random.Random(26)
        self.keys = [generate_key() for _ in range(N_PEERS)]
        self.peers = PeerSet([
            Peer(f"inmem://p{i}", k.public_key.hex(), f"p{i}")
            for i, k in enumerate(self.keys)
        ])
        # PeerSet sorts by public key: the leaver is the last KEY's peer
        self.leaver = self.peers.by_pub_key[self.keys[-1].public_key.hex()]
        self.smaller = self.peers.with_removed_peer(self.leaver)
        self.events = []
        heads, seqs = [""] * N_PEERS, [-1] * N_PEERS
        order = list(range(N_PEERS))
        while len(self.events) < N_EVENTS:
            rng.shuffle(order)
            for i in order:
                if len(self.events) >= N_EVENTS:
                    break
                op = ""
                if self.events:
                    j = rng.randrange(N_PEERS - 1)
                    j = j if j < i else j + 1
                    op = heads[j]
                    if op == "":
                        continue
                n, idx = len(self.events), seqs[i] + 1
                itxs, sigs = [], []
                if n == LOADED_AT:
                    itx = InternalTransaction.leave(self.leaver)
                    itx.sign(self.keys[-1])
                    itxs = [itx]
                    sigs = [BlockSignature(self.keys[i].public_key.bytes(),
                                           k, f"{k + 10:x}|{k + 77:x}")
                            for k in range(2)]
                e = Event.new(
                    [b"tx %d" % n] if idx else [], itxs, sigs,
                    [heads[i], op], self.keys[i].public_key.bytes(), idx,
                    timestamp=n,
                )
                e.sign(self.keys[i])
                heads[i], seqs[i] = e.hex(), idx
                self.events.append(e)
        self.plain, self.change_round = self._replay(Hashgraph)
        self.no_memo, _ = self._replay(_NoMemoHashgraph, self.change_round)
        # the plain replay's blocks by the round they were received in
        self.blocks = {
            blk.round_received(): blk for blk in (
                self.plain.store.get_block(b)
                for b in range(self.plain.store.last_block_index() + 1))
        }
        self.rounds = sorted(self.blocks)

    def _replay(self, cls, change_round=None):
        """The events through a fresh hashgraph of class ``cls``, each its
        own Event on the shared body (as the benchmark's oracle makes
        them); the smaller peer-set is registered mid-stream, two rounds
        ahead of the newest (``change_round`` repeats an earlier replay's)."""
        h = cls(InmemStore(100000))
        h.init(self.peers)
        for n, ev in enumerate(self.events):
            h.insert_event_and_run_consensus(
                Event(ev.body, ev.signature), set_wire_info=True)
            if n == CHANGE_AT:
                if change_round is None:
                    change_round = h.store.last_round() + 2
                h.store.set_peer_set(change_round, self.smaller)
        return h, change_round


@pytest.fixture(scope="module")
def dag() -> _Dag:
    d = _Dag()
    assert len(d.rounds) >= MIN_ROUNDS, "the DAG decides too few rounds"
    return d


def _plain_bytes(frame: Frame) -> bytes:
    return canonical_dumps(frame.to_dict())


def _fields(fe: FrameEvent) -> tuple:
    return (fe.core.hex(), fe.core.signature, fe.round, fe.lamport_timestamp,
            fe.witness)


def _frame_fields(frame: Frame) -> tuple:
    return (
        frame.round, frame.timestamp,
        [_fields(fe) for fe in frame.events],
        {p: [_fields(fe) for fe in r.events] for p, r in frame.roots.items()},
        [p.to_dict() for p in frame.peers.peers],
        {r: [p.to_dict() for p in ps] for r, ps in frame.peer_sets.items()},
    )


def _round_trip(frame: Frame) -> Frame:
    return Frame.from_dict(canonical_loads(_plain_bytes(frame)))


@pytest.mark.parametrize("how", ["memoised", "memos-dropped", "from-dict",
                                 "from-dict-twice"])
def test_frame_hash_is_the_plain_encoding(dag, how):
    """(a), (b): every frame, whoever built it, encodes to the bytes of
    canonical_dumps(to_dict()) and hashes to their sha256; and the frames
    of a hashgraph that keeps memos equal, field for field, those of one
    that has none."""
    for rr in dag.rounds:
        kept = dag.plain.store.get_frame(rr)
        frame = {
            "memoised": lambda: kept,
            "memos-dropped": lambda: dag.no_memo.store.get_frame(rr),
            "from-dict": lambda: _round_trip(kept),
            "from-dict-twice": lambda: _round_trip(_round_trip(kept)),
        }[how]()
        plain = _plain_bytes(frame)
        assert frame.canonical_bytes() == plain, f"frame {rr}"
        assert frame.hash() == sha256(plain)
        # and a second time, now that every event carries a form
        assert frame.canonical_bytes() == plain
        assert _frame_fields(frame) == _frame_fields(kept)
        assert frame.hash() == kept.hash()
        assert dag.blocks[rr].body.frame_hash == sha256(plain)


def test_frames_carry_roots_of_every_participant(dag):
    """What the reuse rests on: beyond the first frames, every Root holds
    ROOT_DEPTH + 1 events, each a frame event of an earlier Frame."""
    from babble_tpu.hashgraph.hashgraph import ROOT_DEPTH

    seen = set()
    for rr in dag.rounds:
        frame = dag.plain.store.get_frame(rr)
        assert len(frame.roots) == N_PEERS
        if rr != dag.rounds[0]:
            for root in frame.roots.values():
                assert {fe.core.hex() for fe in root.events} <= seen
        seen.update(fe.core.hex() for fe in frame.events)
    assert all(len(r.events) == ROOT_DEPTH + 1 for r in frame.roots.values())


def test_frame_with_internal_transaction_and_block_signatures(dag):
    """(c): the one loaded event's body goes through every Frame it is in —
    as an event once, as a Root event after — with its internal
    transaction and block signatures in the text."""
    loaded = dag.events[LOADED_AT]
    assert loaded.internal_transactions() and loaded.block_signatures()
    as_event = as_root = 0
    for rr in dag.rounds:
        frame = dag.plain.store.get_frame(rr)
        in_events = [fe for fe in frame.events if fe.core.hex() == loaded.hex()]
        in_roots = [fe for r in frame.roots.values() for fe in r.events
                    if fe.core.hex() == loaded.hex()]
        for fe in in_events + in_roots:
            assert b'"InternalTransactions":[{' in fe.canonical_text()
            assert b'"BlockSignatures":[{' in fe.canonical_text()
            assert fe.canonical_text() == canonical_dumps(fe.to_dict())
            assert frame.canonical_bytes() == _plain_bytes(frame)
        as_event += len(in_events)
        as_root += len(in_roots)
    assert as_event == 1 and as_root >= 1


def test_frame_after_a_peer_set_change(dag):
    """(c): ``Peers`` and ``PeerSets`` are encoded per frame, not once per
    process: frames before and after the change differ in both."""
    before = [rr for rr in dag.rounds if rr < dag.change_round]
    after = [rr for rr in dag.rounds if rr >= dag.change_round]
    assert before and after, (dag.rounds, dag.change_round)
    first = dag.plain.store.get_frame(before[0])
    last = dag.plain.store.get_frame(after[-1])
    assert len(first.peers.peers) == N_PEERS
    assert len(last.peers.peers) == N_PEERS - 1
    assert set(first.peer_sets) == {0}
    assert set(last.peer_sets) == {0, dag.change_round}
    for frame in (first, last):
        assert frame.canonical_bytes() == _plain_bytes(frame)
        again = _round_trip(frame)
        assert again.canonical_bytes() == _plain_bytes(frame)


def test_a_mutated_frame_event_is_not_answered_from_the_form(dag):
    """The form on the Event answers only for the annotations it was made
    from: a FrameEvent that differs (another round, a 1 where the flag was
    True) gets its own text, and the first still gets its own after."""
    frame = dag.plain.store.get_frame(dag.rounds[3])
    fe = frame.events[0]
    text = fe.canonical_text()
    for other in (
        FrameEvent(fe.core, fe.round + 1, fe.lamport_timestamp, fe.witness),
        FrameEvent(fe.core, fe.round, fe.lamport_timestamp + 1, fe.witness),
        FrameEvent(fe.core, fe.round, fe.lamport_timestamp, not fe.witness),
        FrameEvent(fe.core, fe.round, fe.lamport_timestamp, int(fe.witness)),
        FrameEvent(fe.core, True, fe.lamport_timestamp, fe.witness),
    ):
        assert other.canonical_text() == canonical_dumps(other.to_dict())
        assert other.canonical_text() != text
    assert fe.canonical_text() == text == canonical_dumps(fe.to_dict())


def test_sort_frame_events_order_is_lamport_then_signature_r(dag):
    """(d): on equal Lamport timestamps the order is that of R as
    decode_signature reads it."""
    events = _stored_events(dag.plain)
    for e in events:
        assert _signature_r(e) == decode_signature(e.signature)[0]
        assert _signature_r(Event(e.body, e.signature)) == (
            decode_signature(e.signature)[0])  # no form on this one
    fes = [FrameEvent(e, round=1, lamport_timestamp=7 + (k % 3), witness=False)
           for k, e in enumerate(events)]
    random.Random(5).shuffle(fes)
    want = sorted(fes, key=lambda fe: (
        fe.lamport_timestamp, decode_signature(fe.core.signature)[0]))
    assert [fe.core.hex() for fe in sort_frame_events(fes)] == [
        fe.core.hex() for fe in want]


@pytest.mark.parametrize("sig", [
    "", "|", "abc", "a|b|c", "a|", "|b", "a|-", "-|a", "g!|1", "1|g!",
    "+1|2", "1_0|2", "1|2_0", "\u0663|1", "1.0|2", "0x1f|2",
    # accepted by decode_signature, and not what encode_signature writes
    " 1a | 2b ", "-1a|2b", "1A|2B", "\u212a|1", "0" * 5000 + "1z|2", "0|0",
])
def test_signature_r_is_decode_signature_or_zero(sig):
    """(d): R for every signature decode_signature accepts, 0 for one it
    rejects — with and without a frame form on the event."""
    try:
        want = decode_signature(sig)[0]
    except ValueError:
        want = 0
    e = Event.new([], [], [], ["", ""], b"\x04" + b"\x01" * 64, 0)
    e.signature = sig
    assert _signature_r(e) == want
    FrameEvent(e, 1, 2, False).canonical_text()
    assert e._frame is not None and _signature_r(e) == want
    assert _signature_r(e) == want  # from the form's memo


def test_invalidate_hash_drops_the_frame_form(dag):
    """(e), and what else unseats a form: a new signature."""
    key = dag.keys[0]
    e = Event.new([b"a"], [], [], ["", ""], key.public_key.bytes(), 0)
    e.sign(key)
    fe = FrameEvent(e, 1, 2, True)
    before = fe.canonical_text()
    assert isinstance(e._frame, FrameForm)
    e.body.transactions = [b"b"]
    e.invalidate_hash()
    assert e._frame is None and e.body._json is None
    after = fe.canonical_text()
    assert after != before and after == canonical_dumps(fe.to_dict())
    e.sign(key)
    assert e._frame is None
    assert fe.canonical_text() == canonical_dumps(fe.to_dict())
    # a signature set by hand is seen too: the form is stamped with it
    e.signature = "1|2"
    assert fe.canonical_text() == canonical_dumps(fe.to_dict())
    assert _signature_r(e) == 1


def test_frame_event_counters(dag):
    """(f): hits + misses = _create_frame_event calls; an event misses in
    its first Frame and hits in every Root after; the counters are per
    hashgraph."""
    h, _ = dag._replay(_CountingHashgraph, dag.change_round)
    assert h.calls > 0
    assert h.frame_event_hits > 0
    assert h.frame_event_hits + h.frame_event_misses == h.calls
    assert h.frame_event_misses == h.store.consensus_events_count()
    assert (dag.plain.frame_event_hits, dag.plain.frame_event_misses) == (
        h.frame_event_hits, h.frame_event_misses)
    # where every memo is dropped first, nothing is ever reused
    assert dag.no_memo.frame_event_hits == 0
    assert dag.no_memo.frame_event_misses == h.calls


def test_landing_on_a_peers_frame_gives_the_replayed_frame_hashes(dag):
    """The fast-sync case at this DAG's size: a hashgraph reset on a Frame
    that came over the wire (from_dict: no form anywhere), fed the events it
    lacks, commits the later blocks with the FrameHashes of the hashgraph
    that replayed from the start — its first frames' Roots are the landed
    Frame's events, which get their forms only then."""
    from babble_tpu.hashgraph import EventBody

    after = [rr for rr in dag.rounds if rr >= dag.change_round]
    landing = after[1]
    block = dag.blocks[landing]
    h2 = Hashgraph(InmemStore(100000))
    h2.reset(block, _round_trip(dag.plain.store.get_frame(landing)))
    known = h2.store.known_events()
    by_pub = dag.peers.by_pub_key
    for ev in dag.events:
        if ev.index() > known[by_pub[ev.creator()].id]:
            h2.insert_event_and_run_consensus(
                Event(EventBody.from_dict(ev.body.to_dict()), ev.signature),
                set_wire_info=True)
    later = range(block.index() + 1, h2.store.last_block_index() + 1)
    assert len(later) >= 3
    for b in later:
        ours = h2.store.get_block(b)
        assert ours.body.frame_hash == (
            dag.blocks[ours.round_received()].body.frame_hash)
        frame = h2.store.get_frame(ours.round_received())
        assert frame.canonical_bytes() == _plain_bytes(frame)
    assert h2.frame_event_hits > 0 and h2.frame_event_misses > 0


@pytest.mark.parametrize("counters,want", [
    ({"frame_event_hits": 12237.0, "frame_event_misses": 7713.0},
     100.0 * 12237 / 19950),
    ({"frame_event_hits": 0.0, "frame_event_misses": 40.0}, 0.0),
    ({"accel_sweeps": 3.0}, None),  # the parent commit: no such counter
])
def test_the_reuse_metric_reads_the_two_counters(counters, want):
    """benchmark/layer_metrics/frame_event_reuse_pct.catchup.json, the one
    entry this change adds to BENCHMARK.json: hits over hits + misses, and
    nothing (not 0, no error) where the program reports neither."""
    from benchmark.harness import layer, spec

    cell = spec.resolve_cell(spec.load_benchmark(), "catchup16.backlog8k")
    entry = next(m for m in cell.per_layer
                 if m["name"] == "frame_event_reuse_pct.catchup")
    assert entry["layer"] == "apply + commit"
    assert entry["moves"] == "catchup_events_per_s"
    got = layer.evaluate(
        cell.definitions[entry["name"]],
        {"counters": counters, "samples": {}, "trace": None})
    assert got == (want if want is None else pytest.approx(want))


def test_the_node_reports_its_own_hashgraphs_counters():
    """get_stats_snapshot and the registry carry the pair, read from the
    node's OWN hashgraph (the harness differences them over its window):
    two nodes in one process do not sum."""
    from tests.test_obs import _tiny_node

    a, b = _tiny_node(), _tiny_node()
    try:
        a.core.hg.frame_event_hits, a.core.hg.frame_event_misses = 5, 3
        stats = a.get_stats_snapshot()
        assert (stats["frame_event_hits"], stats["frame_event_misses"]) == (5, 3)
        reg = a.telemetry.registry.snapshot()
        assert reg["frame_event_hits_total"] == 5
        assert reg["frame_event_misses_total"] == 3
        other = b.get_stats_snapshot()
        assert (other["frame_event_hits"], other["frame_event_misses"]) == (0, 0)
    finally:
        a.shutdown()
        b.shutdown()
