"""Golden play-script DAG tests for the consensus core.

These replay the reference's hand-drawn DAG fixtures and assert identical
rounds / witnesses / fame / round-received / block contents
(reference test model: src/hashgraph/hashgraph_test.go — basic graph :153-166,
round graph :384-432, consensus graph :1049-1146, funky coin-round graph
:1998-2106, sparse graph :2327-2428). The play tables ARE the spec; the
expected values are the reference's.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import pytest

from babble_tpu.common.trilean import Trilean
from babble_tpu.common.utils import median_int
from babble_tpu.crypto import generate_key
from babble_tpu.crypto.keys import PrivateKey
from babble_tpu.hashgraph import (
    Block,
    BlockSignature,
    Event,
    EventBody,
    EventCoordinates,
    Frame,
    Hashgraph,
    InmemStore,
    InternalTransaction,
    SelfParentError,
    TransactionType,
    sort_frame_events,
    sort_topological,
)
from babble_tpu.peers import Peer, PeerSet

CACHE_SIZE = 100


@dataclass
class NodeFixture:
    key: PrivateKey
    pub_bytes: bytes = b""
    pub_hex: str = ""
    pub_id: int = 0
    events: List[Event] = field(default_factory=list)

    def __post_init__(self):
        self.pub_bytes = self.key.public_key.bytes()
        self.pub_hex = self.key.public_key.hex()
        self.pub_id = self.key.public_key.id()

    def sign_and_add(self, event: Event, name: str, index: Dict[str, str], ordered: List[Event]):
        event.sign(self.key)
        self.events.append(event)
        index[name] = event.hex()
        ordered.append(event)


# play: (node, index, self_parent, other_parent, name, tx_payload, sig_payload)
Play = Tuple[int, int, str, str, str, list, list]


def init_nodes(n: int):
    nodes = [NodeFixture(generate_key()) for _ in range(n)]
    peer_set = PeerSet(
        [Peer(net_addr="", pub_key_hex=nd.pub_hex, moniker="") for nd in nodes]
    )
    index: Dict[str, str] = {"": ""}
    ordered: List[Event] = []
    return nodes, index, ordered, peer_set


def play_events(plays: List[Play], nodes, index, ordered):
    for to, idx, sp, op, name, txs, sigs in plays:
        e = Event.new(
            [bytes(t) for t in txs or []],
            [],
            list(sigs or []),
            [index[sp], index[op]],
            nodes[to].pub_bytes,
            idx,
        )
        nodes[to].sign_and_add(e, name, index, ordered)


def create_hashgraph(ordered, peer_set) -> Hashgraph:
    h = Hashgraph(InmemStore(CACHE_SIZE))
    h.init(peer_set)
    for ev in ordered:
        h.insert_event(ev, set_wire_info=True)
    return h


def init_full(plays: List[Play], n: int):
    nodes, index, ordered, peer_set = init_nodes(n)
    play_events(plays, nodes, index, ordered)
    h = create_hashgraph(ordered, peer_set)
    return h, index, nodes, peer_set


def name_of(index: Dict[str, str], hash_: str) -> str:
    for name, h in index.items():
        if h == hash_:
            return name
    return hash_[:12]


# =============================================================================
# Basic graph (reference diagram hashgraph_test.go:153-166)
#
#   |  e12  |
#   |   | \ |
#   |  s10 e20
#   |   | / |
#   |   /   |
#   | / |   |
#  s00 |  s20
#   |   |   |
#  e01  |   |
#   | \ |   |
#  e0  e1  e2
# =============================================================================

BASIC_PLAYS: List[Play] = [
    (0, 0, "", "", "e0", None, None),
    (1, 0, "", "", "e1", None, None),
    (2, 0, "", "", "e2", None, None),
    (0, 1, "e0", "e1", "e01", None, None),
    (2, 1, "e2", "", "s20", None, None),
    (1, 1, "e1", "", "s10", None, None),
    (0, 2, "e01", "", "s00", None, None),
    (2, 2, "s20", "s00", "e20", None, None),
    (1, 2, "s10", "e20", "e12", None, None),
]


@pytest.fixture
def basic():
    h, index, _, _ = init_full(BASIC_PLAYS, 3)
    return h, index


def test_ancestor(basic):
    h, index = basic
    expected_true = [
        # first generation
        ("e01", "e0"), ("e01", "e1"), ("s00", "e01"), ("s20", "e2"),
        ("e20", "s00"), ("e20", "s20"), ("e12", "e20"), ("e12", "s10"),
        # second generation
        ("s00", "e0"), ("s00", "e1"), ("e20", "e01"), ("e20", "e2"),
        ("e12", "e1"), ("e12", "s20"),
        # third generation
        ("e20", "e0"), ("e20", "e1"), ("e20", "e2"), ("e12", "e01"),
        ("e12", "e0"), ("e12", "e1"), ("e12", "e2"),
    ]
    for d, a in expected_true:
        assert h.ancestor(index[d], index[a]), f"ancestor({d},{a})"
    for d, a in [("e01", "e2"), ("s00", "e2")]:
        assert not h.ancestor(index[d], index[a]), f"!ancestor({d},{a})"
    # Empty-hash lookups error in the reference; here they raise StoreError.
    from babble_tpu.common.errors import StoreError

    for d in ["e0", "s00", "e12"]:
        with pytest.raises(StoreError):
            h._ancestor(index[d], "")


def test_self_ancestor(basic):
    h, index = basic
    for d, a in [("e01", "e0"), ("s00", "e01"), ("e20", "e2"), ("e12", "e1")]:
        assert h.self_ancestor(index[d], index[a]), f"selfAncestor({d},{a})"
    for d, a in [
        ("e01", "e1"), ("e12", "e20"), ("s20", "e1"),
        ("e20", "e0"), ("e12", "e2"), ("e20", "e01"),
    ]:
        assert not h.self_ancestor(index[d], index[a]), f"!selfAncestor({d},{a})"


def test_see(basic):
    h, index = basic
    for d, a in [
        ("e01", "e0"), ("e01", "e1"), ("e20", "e0"), ("e20", "e01"),
        ("e12", "e01"), ("e12", "e0"), ("e12", "e1"), ("e12", "s20"),
    ]:
        assert h.see(index[d], index[a]), f"see({d},{a})"


def test_lamport_timestamp(basic):
    h, index = basic
    expected = {
        "e0": 0, "e1": 0, "e2": 0, "e01": 1, "s10": 1, "s20": 1,
        "s00": 2, "e20": 3, "e12": 4,
    }
    for e, ts in expected.items():
        assert h.lamport_timestamp(index[e]) == ts, e


def test_fork():
    """Forks (two events at the same creator height) must be rejected at
    insert (reference: hashgraph_test.go:332-382)."""
    nodes, index, ordered, peer_set = init_nodes(3)
    h = Hashgraph(InmemStore(CACHE_SIZE))
    h.init(peer_set)

    for i, nd in enumerate(nodes):
        e = Event.new([], [], [], ["", ""], nd.pub_bytes, 0)
        nd.sign_and_add(e, f"e{i}", index, ordered)
        h.insert_event(e, set_wire_info=True)

    # 'a' forks node2's index-0 slot (different payload => different hash).
    # The insert is refused like the reference — but as a typed ForkError
    # carrying both signed branches (the equivocation evidence the sentry
    # turns into a durable proof).
    from babble_tpu.hashgraph import ForkError

    event_a = Event.new([b"yo"], [], [], ["", ""], nodes[2].pub_bytes, 0)
    nodes[2].sign_and_add(event_a, "a", index, ordered)
    with pytest.raises(ForkError) as ei:
        h.insert_event(event_a, set_wire_info=True)
    assert ei.value.creator == event_a.creator()
    assert ei.value.index == 0
    assert ei.value.existing is not None
    assert ei.value.existing.hex() != event_a.hex()
    assert ei.value.incoming is event_a

    e01 = Event.new([], [], [], [index["e0"], index["a"]], nodes[0].pub_bytes, 1)
    nodes[0].sign_and_add(e01, "e01", index, ordered)
    with pytest.raises(Exception):
        h.insert_event(e01, set_wire_info=True)

    e20 = Event.new([], [], [], [index["e2"], index["e01"]], nodes[2].pub_bytes, 1)
    nodes[2].sign_and_add(e20, "e20", index, ordered)
    with pytest.raises(Exception):
        h.insert_event(e20, set_wire_info=True)


# =============================================================================
# Round graph (reference diagram hashgraph_test.go:384-401)
#
#   |  s11  |
#   |   |   |
#   |   f1  |
#   |  /|   |
#   | / s10 |
#   |/  |   |
#  e02  |   |
#   | \ |   |
#   |   \   |
#   |   | \ |
#  s00  |  e21
#   |   | / |
#   |  e10  s20
#   | / |   |
#  e0  e1  e2
# =============================================================================

ROUND_PLAYS: List[Play] = [
    (0, 0, "", "", "e0", None, None),
    (1, 0, "", "", "e1", None, None),
    (2, 0, "", "", "e2", None, None),
    (1, 1, "e1", "e0", "e10", None, None),
    (2, 1, "e2", "", "s20", None, None),
    (0, 1, "e0", "", "s00", None, None),
    (2, 2, "s20", "e10", "e21", None, None),
    (0, 2, "s00", "e21", "e02", None, None),
    (1, 2, "e10", "", "s10", None, None),
    (1, 3, "s10", "e02", "f1", None, None),
    (1, 4, "f1", "", "s11", [b"abc"], None),
]


@pytest.fixture
def round_graph():
    h, index, nodes, peer_set = init_full(ROUND_PLAYS, 3)
    # Seed rounds manually, as the reference does before DivideRounds
    # (hashgraph_test.go:420-429).
    from babble_tpu.hashgraph import RoundInfo

    r0 = RoundInfo()
    for w in ["e0", "e1", "e2"]:
        r0.add_created_event(index[w], True)
    h.store.set_round(0, r0)
    r1 = RoundInfo()
    r1.add_created_event(index["f1"], True)
    h.store.set_round(1, r1)
    return h, index, nodes, peer_set


def test_insert_event_coordinates(round_graph):
    """reference: hashgraph_test.go:434-573."""
    h, index, nodes, peer_set = round_graph
    p0, p1, p2 = (nodes[i].pub_hex for i in range(3))

    e0 = h.store.get_event(index["e0"])
    assert e0.body.self_parent_index == -1
    assert e0.body.other_parent_creator_id == 0
    assert e0.body.other_parent_index == -1
    assert e0.body.creator_id == nodes[0].pub_id
    assert h.first_descendants(index["e0"]) == {
        p0: EventCoordinates(index["e0"], 0),
        p1: EventCoordinates(index["e10"], 1),
        p2: EventCoordinates(index["e21"], 2),
    }
    assert h.last_ancestors(index["e0"]) == {p0: EventCoordinates(index["e0"], 0)}

    e21 = h.store.get_event(index["e21"])
    assert e21.body.self_parent_index == 1
    assert e21.body.other_parent_creator_id == nodes[1].pub_id
    assert e21.body.other_parent_index == 1
    assert e21.body.creator_id == nodes[2].pub_id
    assert h.first_descendants(index["e21"]) == {
        p0: EventCoordinates(index["e02"], 2),
        p1: EventCoordinates(index["f1"], 3),
        p2: EventCoordinates(index["e21"], 2),
    }
    assert h.last_ancestors(index["e21"]) == {
        p0: EventCoordinates(index["e0"], 0),
        p1: EventCoordinates(index["e10"], 1),
        p2: EventCoordinates(index["e21"], 2),
    }

    f1 = h.store.get_event(index["f1"])
    assert f1.body.self_parent_index == 2
    assert f1.body.other_parent_creator_id == nodes[0].pub_id
    assert f1.body.other_parent_index == 2
    assert f1.body.creator_id == nodes[1].pub_id
    assert h.first_descendants(index["f1"]) == {p1: EventCoordinates(index["f1"], 3)}
    assert h.last_ancestors(index["f1"]) == {
        p0: EventCoordinates(index["e02"], 2),
        p1: EventCoordinates(index["f1"], 3),
        p2: EventCoordinates(index["e21"], 2),
    }

    expected_undetermined = [
        index[n]
        for n in ["e0", "e1", "e2", "e10", "s20", "s00", "e21", "e02", "s10", "f1", "s11"]
    ]
    assert h.undetermined_events == expected_undetermined
    # 3 index-0 events + 1 event with transactions = 4 loaded
    assert h.pending_loaded_events == 4


def test_read_wire_info(round_graph):
    """Wire round-trip must reproduce the exact body and signature
    (reference: hashgraph_test.go:575-608)."""
    h, index, _, _ = round_graph
    for name, evh in index.items():
        if name == "":
            continue
        ev = h.store.get_event(evh)
        ev_from_wire = h.read_wire_info(ev.to_wire())
        assert ev.body == ev_from_wire.body, name
        assert ev.signature == ev_from_wire.signature, name
        assert ev_from_wire.verify(), name


def test_strongly_see(round_graph):
    """reference: hashgraph_test.go:610-647."""
    h, index, _, peer_set = round_graph
    ps = h.store.get_peer_set(0)
    for d, a in [
        ("e21", "e0"), ("e02", "e10"), ("e02", "e0"), ("e02", "e1"),
        ("f1", "e21"), ("f1", "e10"), ("f1", "e0"), ("f1", "e1"),
        ("f1", "e2"), ("s11", "e2"),
    ]:
        assert h.strongly_see(index[d], index[a], ps), f"stronglySee({d},{a})"
    for d, a in [
        ("e10", "e0"), ("e21", "e1"), ("e21", "e2"), ("e02", "e2"),
        ("s11", "e02"),
    ]:
        assert not h.strongly_see(index[d], index[a], ps), f"!stronglySee({d},{a})"


def test_witness(round_graph):
    """reference: hashgraph_test.go:649-671."""
    h, index, _, _ = round_graph
    for w in ["e0", "e1", "e2", "f1"]:
        assert h.witness(index[w]), w
    for w in ["e10", "e21", "e02"]:
        assert not h.witness(index[w]), w


def test_round(round_graph):
    """reference: hashgraph_test.go:673-699."""
    h, index, _, _ = round_graph
    expected = {
        "e0": 0, "e1": 0, "e2": 0, "s00": 0, "e10": 0, "s20": 0,
        "e21": 0, "e02": 0, "s10": 0, "f1": 1, "s11": 1,
    }
    for e, r in expected.items():
        assert h.round(index[e]) == r, e


def test_divide_rounds(round_graph):
    """reference: hashgraph_test.go:725-821."""
    h, index, _, _ = round_graph
    h.divide_rounds()

    assert h.store.last_round() == 1

    round0 = h.store.get_round(0)
    expected_r0 = {
        index["e0"]: True, index["e1"]: True, index["e2"]: True,
        index["e10"]: False, index["s20"]: False, index["e21"]: False,
        index["s00"]: False, index["e02"]: False, index["s10"]: False,
    }
    assert {
        x: e.witness for x, e in round0.created_events.items()
    } == expected_r0
    assert all(
        e.famous == Trilean.UNDEFINED for e in round0.created_events.values()
    )

    round1 = h.store.get_round(1)
    assert {x: e.witness for x, e in round1.created_events.items()} == {
        index["f1"]: True,
        index["s11"]: False,
    }

    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == [(0, False), (1, False)]

    expected_ts = {
        "e0": (0, 0), "e1": (0, 0), "e2": (0, 0), "s00": (1, 0),
        "e10": (1, 0), "s20": (1, 0), "e21": (2, 0), "e02": (3, 0),
        "s10": (2, 0), "f1": (4, 1), "s11": (5, 1),
    }
    for e, (ts, r) in expected_ts.items():
        ev = h.store.get_event(index[e])
        assert ev.round == r, e
        assert ev.lamport_timestamp == ts, e


def test_create_root(round_graph):
    """reference: hashgraph_test.go:823-858."""
    h, index, _, _ = round_graph
    h.divide_rounds()

    root_events_map = {
        "e0": ["e0"],
        "e02": ["e0", "s00", "e02"],
        "s10": ["e1", "e10", "s10"],
        "f1": ["e1", "e10", "s10", "f1"],
    }
    for evh_name, expected_names in root_events_map.items():
        ev = h.store.get_event(index[evh_name])
        root = h._create_root(ev.creator(), index[evh_name])
        got = [fe.core.hex() for fe in root.events]
        assert got == [index[n] for n in expected_names], evh_name


# =============================================================================
# Block / signature-pool graph (reference: hashgraph_test.go:869-1047)
# =============================================================================


def init_block_hashgraph():
    nodes, index, ordered, peer_set = init_nodes(3)
    for i, nd in enumerate(nodes):
        e = Event.new([], [], [], ["", ""], nd.pub_bytes, 0)
        nd.sign_and_add(e, f"e{i}", index, ordered)

    h = Hashgraph(InmemStore(CACHE_SIZE))
    h.init(peer_set)

    block = Block.new(
        0,
        1,
        b"framehash",
        peer_set,
        [b"block tx"],
        [
            InternalTransaction.join(Peer(net_addr="paris", pub_key_hex="0X0001", moniker="peer1")),
            InternalTransaction.leave(Peer(net_addr="london", pub_key_hex="0X0002", moniker="peer2")),
        ],
        0,
    )
    h.store.set_block(block)

    for ev in ordered:
        h.insert_event(ev, set_wire_info=True)
    return h, nodes, index


def test_insert_events_with_block_signatures():
    """reference: hashgraph_test.go:913-1047."""
    h, nodes, index = init_block_hashgraph()
    block = h.store.get_block(0)
    block_sigs = [block.sign(nd.key) for nd in nodes]

    # valid signatures ride in events and land on the block
    plays: List[Play] = [
        (1, 1, "e1", "e0", "e10", None, [block_sigs[1]]),
        (2, 1, "e2", "", "s20", None, [block_sigs[2]]),
        (0, 1, "e0", "", "s00", None, [block_sigs[0]]),
    ]
    for to, idx, sp, op, name, txs, sigs in plays:
        e = Event.new(
            [bytes(t) for t in txs or []], [], list(sigs or []),
            [index[sp], index[op]], nodes[to].pub_bytes, idx,
        )
        nodes[to].sign_and_add(e, name, index, [])
        h.insert_event(e, set_wire_info=True)

    assert len(h.pending_signatures) == 3
    h.process_sig_pool()
    assert len(h.store.get_block(0).signatures) == 3
    assert len(h.pending_signatures) == 0

    # signature of an unknown block: event inserted, signature ignored
    ps2 = h.store.get_peer_set(2)
    block1 = Block.new(1, 2, b"framehash", ps2, [], [], 0)
    sig = block1.sign(nodes[2].key)
    unknown_sig = BlockSignature(
        validator=nodes[2].pub_bytes, index=1, signature=sig.signature
    )
    e = Event.new(
        [], [], [unknown_sig], [index["s20"], index["e10"]], nodes[2].pub_bytes, 2
    )
    nodes[2].sign_and_add(e, "e21", index, [])
    h.insert_event(e, set_wire_info=True)
    h.store.get_event(index["e21"])  # must exist

    # signature from a non-creator validator: ignored, not appended
    bad_node = NodeFixture(generate_key())
    bad_sig = block.sign(bad_node.key)
    e = Event.new(
        [], [], [bad_sig], [index["s00"], index["e21"]], nodes[0].pub_bytes, 2
    )
    nodes[0].sign_and_add(e, "e02", index, [])
    h.insert_event(e, set_wire_info=True)
    h.process_sig_pool()
    assert len(h.store.get_block(0).signatures) == 3


# =============================================================================
# Consensus graph (reference diagram hashgraph_test.go:1049-1107)
# Rounds 0-4, blocks 0 (RR1, 7 evs) and 1 (RR2, 9 evs).
# =============================================================================

CONSENSUS_PLAYS: List[Play] = [
    (0, 0, "", "", "e0", None, None),
    (1, 0, "", "", "e1", None, None),
    (2, 0, "", "", "e2", None, None),
    (1, 1, "e1", "e0", "e10", None, None),
    (2, 1, "e2", "e10", "e21", [b"e21"], None),
    (2, 2, "e21", "", "e21b", None, None),
    (0, 1, "e0", "e21b", "e02", None, None),
    (1, 2, "e10", "e02", "f1", None, None),
    (1, 3, "f1", "", "f1b", [b"f1b"], None),
    (0, 2, "e02", "f1b", "f0", None, None),
    (2, 3, "e21b", "f1b", "f2", None, None),
    (1, 4, "f1b", "f0", "f10", None, None),
    (0, 3, "f0", "e21", "f0x", None, None),
    (2, 4, "f2", "f10", "f21", None, None),
    (0, 4, "f0x", "f21", "f02", None, None),
    (0, 5, "f02", "", "f02b", [b"f02b"], None),
    (1, 5, "f10", "f02b", "g1", None, None),
    (0, 6, "f02b", "g1", "g0", None, None),
    (2, 5, "f21", "g1", "g2", None, None),
    (1, 6, "g1", "g0", "g10", [b"g10"], None),
    (2, 6, "g2", "g10", "g21", None, None),
    (0, 7, "g0", "g21", "g02", [b"g02"], None),
    (1, 7, "g10", "g02", "h1", None, None),
    (0, 8, "g02", "h1", "h0", None, None),
    (2, 7, "g21", "h1", "h2", None, None),
    (1, 8, "h1", "h0", "h10", None, None),
    (2, 8, "h2", "h10", "h21", None, None),
    (0, 9, "h0", "h21", "h02", None, None),
    (1, 9, "h10", "h02", "i1", None, None),
    (0, 10, "h02", "i1", "i0", None, None),
    (2, 9, "h21", "i1", "i2", None, None),
]


@pytest.fixture(scope="module")
def consensus():
    """Shared read-only fixture for the heavier consensus-graph tests; each
    test that mutates state builds its own copy via init_full."""
    return init_full(CONSENSUS_PLAYS, 3)


def _witness_map(round_info):
    return {x: e.witness for x, e in round_info.created_events.items()}


def _fame_map(round_info):
    return {x: e.famous for x, e in round_info.created_events.items()}


EXPECTED_CREATED = {
    0: {"e0": True, "e1": True, "e2": True, "e10": False, "e21": False,
        "e21b": False, "e02": False},
    1: {"f1": True, "f1b": False, "f0": True, "f2": True, "f10": False,
        "f21": False, "f0x": False, "f02": False, "f02b": False},
    2: {"g1": True, "g0": True, "g2": True, "g10": False, "g21": False,
        "g02": False},
    3: {"h1": True, "h0": True, "h2": True, "h10": False, "h21": False,
        "h02": False},
    4: {"i1": True, "i0": True, "i2": True},
}

EXPECTED_TS = {
    "e0": (0, 0), "e1": (0, 0), "e2": (0, 0), "e10": (1, 0), "e21": (2, 0),
    "e21b": (3, 0), "e02": (4, 0), "f1": (5, 1), "f1b": (6, 1), "f0": (7, 1),
    "f2": (7, 1), "f10": (8, 1), "f0x": (8, 1), "f21": (9, 1), "f02": (10, 1),
    "f02b": (11, 1), "g1": (12, 2), "g0": (13, 2), "g2": (13, 2),
    "g10": (14, 2), "g21": (15, 2), "g02": (16, 2), "h1": (17, 3),
    "h0": (18, 3), "h2": (18, 3), "h10": (19, 3), "h21": (20, 3),
    "h02": (21, 3), "i1": (22, 4), "i0": (23, 4), "i2": (23, 4),
}


def test_divide_rounds_consensus_graph():
    """reference: hashgraph_test.go:1148-1260."""
    h, index, _, _ = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()

    for i in range(5):
        round_ = h.store.get_round(i)
        assert _witness_map(round_) == {
            index[n]: w for n, w in EXPECTED_CREATED[i].items()
        }, f"round {i}"

    for e, (ts, r) in EXPECTED_TS.items():
        ev = h.store.get_event(index[e])
        assert ev.round == r, e
        assert ev.lamport_timestamp == ts, e


def test_decide_fame():
    """reference: hashgraph_test.go:1262-1355."""
    h, index, _, _ = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()
    h.decide_fame()

    expected_fame = {
        0: {"e0": Trilean.TRUE, "e1": Trilean.TRUE, "e2": Trilean.TRUE},
        1: {"f1": Trilean.TRUE, "f0": Trilean.TRUE, "f2": Trilean.TRUE},
        2: {"g1": Trilean.TRUE, "g0": Trilean.TRUE, "g2": Trilean.TRUE},
        3: {"h1": Trilean.UNDEFINED, "h0": Trilean.UNDEFINED, "h2": Trilean.UNDEFINED},
        4: {"i1": Trilean.UNDEFINED, "i0": Trilean.UNDEFINED, "i2": Trilean.UNDEFINED},
    }
    for i in range(5):
        round_ = h.store.get_round(i)
        fames = _fame_map(round_)
        for n, expected in expected_fame[i].items():
            assert fames[index[n]] == expected, f"round {i} {n}"
        # non-witnesses stay undefined
        for n, w in EXPECTED_CREATED[i].items():
            if not w:
                assert fames[index[n]] == Trilean.UNDEFINED, n

    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == [(0, True), (1, True), (2, True), (3, False), (4, False)]


def test_decide_round_received():
    """reference: hashgraph_test.go:1357-1422."""
    h, index, _, _ = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()

    expected_received = {
        0: [],
        1: ["e0", "e1", "e2", "e10", "e21", "e21b", "e02"],
        2: ["f1", "f1b", "f0", "f2", "f10", "f0x", "f21", "f02", "f02b"],
        3: [],
        4: [],
    }
    for i in range(5):
        round_ = h.store.get_round(i)
        assert round_.received_events == [
            index[n] for n in expected_received[i]
        ], f"round {i}"

    for name, hash_ in index.items():
        if name == "":
            continue
        e = h.store.get_event(hash_)
        if name[0] == "e":
            assert e.round_received == 1, name
        elif name[0] == "f":
            assert e.round_received == 2, name
        else:
            assert e.round_received is None, name

    expected_undetermined = [
        index[n]
        for n in ["g1", "g0", "g2", "g10", "g21", "g02", "h1", "h0", "h2",
                   "h10", "h21", "h02", "i1", "i0", "i2"]
    ]
    assert h.undetermined_events == expected_undetermined


def test_process_decided_rounds():
    """reference: hashgraph_test.go:1424-1524."""
    h, index, _, _ = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()

    assert len(h.store.consensus_events()) == 16
    assert h.pending_loaded_events == 2

    block0 = h.store.get_block(0)
    assert block0.index() == 0
    assert block0.round_received() == 1
    assert block0.transactions() == [b"e21"]
    frame1 = h.get_frame(block0.round_received())
    assert block0.frame_hash() == frame1.hash()

    block1 = h.store.get_block(1)
    assert block1.index() == 1
    assert block1.round_received() == 2
    assert len(block1.transactions()) == 2
    assert block1.transactions()[1] == b"f02b"
    frame2 = h.get_frame(block1.round_received())
    assert block1.frame_hash() == frame2.hash()

    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == [(3, False), (4, False)]

    assert h.anchor_block is None


def test_known():
    """reference: hashgraph_test.go:1540-1557."""
    h, _, nodes, _ = init_full(CONSENSUS_PLAYS, 3)
    known = h.store.known_events()
    assert known[nodes[0].pub_id] == 10
    assert known[nodes[1].pub_id] == 9
    assert known[nodes[2].pub_id] == 9


def test_get_frame():
    """reference: hashgraph_test.go:1559-1712."""
    h, index, nodes, peer_set = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()

    # Round 1: all roots empty
    frame = h.get_frame(1)
    for nd in nodes:
        assert frame.roots[nd.pub_hex].events == []
    expected_names = ["e0", "e1", "e2", "e10", "e21", "e21b", "e02"]
    expected = sort_frame_events([h._create_frame_event(index[n]) for n in expected_names])
    assert [fe.core.hex() for fe in frame.events] == [fe.core.hex() for fe in expected]
    assert [fe.round for fe in frame.events] == [fe.round for fe in expected]
    ts = [h.store.get_event(index[w]).timestamp() for w in ["f0", "f1", "f2"]]
    assert frame.timestamp == median_int(ts)
    assert h.store.get_block(0).frame_hash() == frame.hash()

    # Round 2: roots contain each participant's past
    pasts = {0: ["e0", "e02"], 1: ["e1", "e10"], 2: ["e2", "e21", "e21b"]}
    frame2 = h.get_frame(2)
    for i, names in pasts.items():
        root = frame2.roots[nodes[i].pub_hex]
        assert [fe.core.hex() for fe in root.events] == [index[n] for n in names], i
    expected_names2 = ["f1", "f1b", "f0", "f2", "f10", "f0x", "f21", "f02", "f02b"]
    expected2 = sort_frame_events(
        [h._create_frame_event(index[n]) for n in expected_names2]
    )
    assert [fe.core.hex() for fe in frame2.events] == [
        fe.core.hex() for fe in expected2
    ]
    ts2 = [h.store.get_event(index[w]).timestamp() for w in ["g0", "g1", "g2"]]
    assert frame2.timestamp == median_int(ts2)


def _round_trip_frame(frame: Frame) -> Frame:
    """Serialize + parse, clearing the events' local annotations the way the
    reference's Marshal/Unmarshal does (hashgraph_test.go:1734-1738)."""
    return Frame.from_dict(
        __import__("json").loads(
            __import__("json").dumps(frame.to_dict(), default=_js_bytes)
        )
    )


def _js_bytes(o):
    from babble_tpu.crypto.canonical import PreNormalized, b64

    if isinstance(o, PreNormalized):
        return o.value
    if isinstance(o, (bytes, bytearray)):
        return b64(bytes(o))
    raise TypeError(str(type(o)))


def test_reset_from_frame():
    """reference: hashgraph_test.go:1714-1937."""
    h, index, nodes, peer_set = init_full(CONSENSUS_PLAYS, 3)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()

    block = h.store.get_block(1)
    frame = _round_trip_frame(h.get_frame(block.round_received()))

    h2 = Hashgraph(InmemStore(CACHE_SIZE))
    h2.reset(block, frame)

    expected_known = {
        nodes[0].pub_id: 5,
        nodes[1].pub_id: 4,
        nodes[2].pub_id: 4,
    }
    assert h2.store.known_events() == expected_known

    for d, a in [
        ("e02", "e0"), ("e02", "e1"), ("e21", "e0"),
        ("f1", "e0"), ("f1", "e1"), ("f1", "e2"),
    ]:
        assert h2.strongly_see(index[d], index[a], peer_set), f"stronglySee({d},{a})"

    # rounds and lamport timestamps must match the original hashgraph
    for fe in frame.events:
        ev_hex = fe.core.hex()
        assert h2.round(ev_hex) == h.round(ev_hex), name_of(index, ev_hex)
        assert h2.lamport_timestamp(ev_hex) == h.lamport_timestamp(
            ev_hex
        ), name_of(index, ev_hex)

    assert sorted(h.store.get_round(1).witnesses()) == sorted(
        h2.store.get_round(1).witnesses()
    )

    assert h2.store.last_block_index() == block.index()
    assert h2.last_consensus_round == block.round_received()
    assert h2.anchor_block is None

    # continue after reset: insert rounds 2-4 events into h2
    for r in range(2, 5):
        round_ = h.store.get_round(r)
        events = sort_topological(
            [h.store.get_event(x) for x in round_.created_events]
        )
        for ev in events:
            fresh = Event(
                EventBody.from_dict(ev.body.to_dict()), signature=ev.signature
            )
            h2.insert_event_and_run_consensus(fresh, set_wire_info=True)

    for r in range(1, 5):
        assert sorted(h.store.get_round(r).witnesses()) == sorted(
            h2.store.get_round(r).witnesses()
        ), f"round {r} witnesses"


# =============================================================================
# Funky graph — exercises coin rounds (reference: hashgraph_test.go:1998-2106)
# =============================================================================


def init_funky(full: bool):
    nodes, index, ordered, peer_set = init_nodes(4)
    for i, nd in enumerate(nodes):
        name = f"w0{i}"
        e = Event.new([name.encode()], [], [], ["", ""], nd.pub_bytes, 0)
        nd.sign_and_add(e, name, index, ordered)

    plays: List[Play] = [
        (2, 1, "w02", "w03", "a23", [b"a23"], None),
        (1, 1, "w01", "a23", "a12", [b"a12"], None),
        (0, 1, "w00", "", "a00", [b"a00"], None),
        (1, 2, "a12", "a00", "a10", [b"a10"], None),
        (2, 2, "a23", "a12", "a21", [b"a21"], None),
        (3, 1, "w03", "a21", "w13", [b"w13"], None),
        (2, 3, "a21", "w13", "w12", [b"w12"], None),
        (1, 3, "a10", "w12", "w11", [b"w11"], None),
        (0, 2, "a00", "w11", "w10", [b"w10"], None),
        (2, 4, "w12", "w11", "b21", [b"b21"], None),
        (3, 2, "w13", "b21", "w23", [b"w23"], None),
        (1, 4, "w11", "w23", "w21", [b"w21"], None),
        (0, 3, "w10", "", "b00", [b"b00"], None),
        (1, 5, "w21", "b00", "c10", [b"c10"], None),
        (2, 5, "b21", "c10", "w22", [b"w22"], None),
        (0, 4, "b00", "w22", "w20", [b"w20"], None),
        (1, 6, "c10", "w20", "w31", [b"w31"], None),
        (2, 6, "w22", "w31", "w32", [b"w32"], None),
        (0, 5, "w20", "w32", "w30", [b"w30"], None),
        (3, 3, "w23", "w32", "w33", [b"w33"], None),
        (1, 7, "w31", "w33", "d13", [b"d13"], None),
        (0, 6, "w30", "d13", "w40", [b"w40"], None),
        (1, 8, "d13", "w40", "w41", [b"w41"], None),
        (2, 7, "w32", "w41", "w42", [b"w42"], None),
        (3, 4, "w33", "w42", "w43", [b"w43"], None),
    ]
    if full:
        plays += [
            (2, 8, "w42", "w43", "e23", [b"e23"], None),
            (1, 9, "w41", "e23", "w51", [b"w51"], None),
        ]
    play_events(plays, nodes, index, ordered)
    h = create_hashgraph(ordered, peer_set)
    return h, index, nodes, peer_set


def test_funky_hashgraph_fame():
    """Coin round prevents round 0 from deciding while rounds 1-2 decide
    (reference: hashgraph_test.go:2108-2180)."""
    h, index, _, _ = init_funky(False)
    h.divide_rounds()
    h.decide_fame()

    assert h.store.last_round() == 4

    expected_pending = [(0, False), (1, True), (2, True), (3, False), (4, False)]
    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == expected_pending

    h.decide_round_received()
    h.process_decided_rounds()

    # a decided round is never processed before all earlier rounds decide
    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == expected_pending


def test_funky_hashgraph_blocks():
    """reference: hashgraph_test.go:2182-2250."""
    h, index, _, _ = init_funky(True)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()

    assert h.store.last_round() == 5

    assert [
        (pr.index, pr.decided) for pr in h.pending_rounds.get_ordered_pending_rounds()
    ] == [(4, False), (5, False)]

    expected_tx_counts = {0: 6, 1: 7, 2: 7}
    for bi, expected in expected_tx_counts.items():
        b = h.store.get_block(bi)
        assert len(b.transactions()) == expected, f"block {bi}"


def _get_diff(h: Hashgraph, known: Dict[int, int], peer_set: PeerSet) -> List[Event]:
    """reference: hashgraph_test.go:2550-2570."""
    diff: List[Event] = []
    for id_, ct in known.items():
        pk = peer_set.by_id[id_].pub_key_hex
        for eh in h.store.participant_events(pk, ct):
            diff.append(h.store.get_event(eh))
    return sort_topological(diff)


def _reset_and_continue(h: Hashgraph, index, peer_set, max_round: int):
    """Shared body of the funky/sparse reset tests
    (reference: hashgraph_test.go:2252-2325, 2430-2510). Returns the
    hashgraphs that landed, each with the index of the block it landed on."""
    landed = []
    for bi in range(3):
        block = h.store.get_block(bi)
        frame = _round_trip_frame(h.get_frame(block.round_received()))

        h2 = Hashgraph(InmemStore(CACHE_SIZE))
        h2.reset(block, frame)

        diff = _get_diff(h, h2.store.known_events(), peer_set)
        wire_diff = [e.to_wire() for e in diff]

        for orig, wev in zip(diff, wire_diff):
            ev = h2.read_wire_info(wev)
            assert ev.body == orig.body, name_of(index, orig.hex())
            h2.insert_event(ev, set_wire_info=False)

        h2.divide_rounds()
        h2.decide_fame()
        h2.decide_round_received()
        h2.process_decided_rounds()

        for r in range(bi, max_round + 1):
            hw = sorted(
                name_of(index, w) for w in h.store.get_round(r).witnesses()
            )
            h2w = sorted(
                name_of(index, w) for w in h2.store.get_round(r).witnesses()
            )
            assert hw == h2w, f"block {bi}, round {r} witnesses"
        landed.append((bi, h2))
    return landed


@pytest.mark.parametrize("graph", ["funky", "sparse"])
def test_reset_then_commit_gives_the_replayed_frame_hashes(graph):
    """A hashgraph that lands on a Frame a peer built (through from_dict:
    its events carry no frame form) and then commits further blocks gives
    them the FrameHashes of the hashgraph that replayed from the start —
    and each is the sha256 of the plain canonical encoding."""
    from babble_tpu.crypto.canonical import canonical_dumps
    from babble_tpu.crypto.hashing import sha256

    h, index, _, peer_set = (
        init_funky(True) if graph == "funky" else init_sparse()
    )
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()
    compared = 0
    for bi, h2 in _reset_and_continue(h, index, peer_set, 5):
        for k in range(bi + 1, h2.store.last_block_index() + 1):
            ours, theirs = h2.store.get_block(k), h.store.get_block(k)
            assert ours.round_received() == theirs.round_received()
            assert ours.body.frame_hash == theirs.body.frame_hash, (bi, k)
            frame = h2.store.get_frame(ours.round_received())
            assert frame.hash() == sha256(canonical_dumps(frame.to_dict()))
            assert frame.hash() == ours.body.frame_hash
            compared += 1
        if h2.store.last_block_index() > bi:
            assert h2.frame_event_misses > 0
    assert compared >= 3


def test_funky_hashgraph_reset():
    h, index, _, peer_set = init_funky(True)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()
    _reset_and_continue(h, index, peer_set, 5)


# =============================================================================
# Sparse graph (reference: hashgraph_test.go:2327-2428)
# =============================================================================


def init_sparse():
    nodes, index, ordered, peer_set = init_nodes(4)
    for i, nd in enumerate(nodes):
        name = f"w0{i}"
        e = Event.new([name.encode()], [], [], ["", ""], nd.pub_bytes, 0)
        nd.sign_and_add(e, name, index, ordered)

    plays: List[Play] = [
        (1, 1, "w01", "w00", "e10", [b"e10"], None),
        (2, 1, "w02", "e10", "e21", [b"e21"], None),
        (3, 1, "w03", "e21", "e32", [b"e32"], None),
        (0, 1, "w00", "e32", "w10", [b"w10"], None),
        (1, 2, "e10", "w10", "w11", [b"w11"], None),
        (0, 2, "w10", "w11", "f01", [b"f01"], None),
        (2, 2, "e21", "f01", "w12", [b"w12"], None),
        (3, 2, "e32", "w12", "w13", [b"w13"], None),
        (1, 3, "w11", "w13", "w21", [b"w21"], None),
        (2, 3, "w12", "w21", "w22", [b"w22"], None),
        (3, 3, "w13", "w22", "w23", [b"w23"], None),
        (1, 4, "w21", "w23", "g13", [b"g13"], None),
        (2, 4, "w22", "g13", "w32", [b"w32"], None),
        (3, 4, "w23", "w32", "w33", [b"w33"], None),
        (1, 5, "g13", "w33", "w31", [b"w31"], None),
        (2, 5, "w32", "w31", "h21", [b"h21"], None),
        (3, 5, "w33", "h21", "w43", [b"w43"], None),
        (1, 6, "w31", "w43", "w41", [b"w41"], None),
        (2, 6, "h21", "w41", "w42", [b"w42"], None),
        (3, 6, "w43", "w42", "i32", [b"i32"], None),
        (1, 7, "w41", "i32", "w51", [b"w51"], None),
    ]
    play_events(plays, nodes, index, ordered)
    h = create_hashgraph(ordered, peer_set)
    return h, index, nodes, peer_set


def test_sparse_hashgraph_reset():
    """reference: hashgraph_test.go:2430-2510."""
    h, index, _, peer_set = init_sparse()
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()
    _reset_and_continue(h, index, peer_set, 5)


def test_round_diff(round_graph):
    """reference: hashgraph_test.go:701-724 TestRoundDiff."""
    h, index, nodes, peer_set = round_graph
    h.divide_rounds()
    assert h.round_diff(index["f1"], index["e02"]) == 1
    assert h.round_diff(index["e02"], index["f1"]) == -1
    assert h.round_diff(index["e02"], index["e21"]) == 0


def test_event_sort_orders():
    """Topological sort = local insertion order; consensus sort = Lamport
    with signature-R tiebreak, deterministic across shuffles (reference:
    event.go:477-511 — the tiebreak makes block ordering node-independent,
    SURVEY.md hard-part 4)."""
    import random

    from babble_tpu.crypto.keys import generate_key
    from babble_tpu.hashgraph.event import (
        FrameEvent,
        sort_frame_events,
        sort_topological,
    )

    keys = [generate_key() for _ in range(4)]
    events = []
    for i, k in enumerate(keys):
        e = Event.new([], [], [], ["", ""], k.public_key.bytes(), 0)
        e.sign(k)
        e.topological_index = i
        events.append(e)

    shuffled = events[:]
    random.Random(7).shuffle(shuffled)
    assert [e.topological_index for e in sort_topological(shuffled)] == [
        0, 1, 2, 3]

    # all four share lamport 3: order must come from signature R alone and
    # be identical no matter the input permutation
    fes = [FrameEvent(e, round=1, lamport_timestamp=3, witness=False)
           for e in events]
    ref_order = [fe.core.hex() for fe in sort_frame_events(fes)]
    for seed in range(5):
        perm = fes[:]
        random.Random(seed).shuffle(perm)
        assert [fe.core.hex() for fe in sort_frame_events(perm)] == ref_order

    # mixed lamports dominate the tiebreak
    fes2 = [FrameEvent(e, round=1, lamport_timestamp=10 - i, witness=False)
            for i, e in enumerate(events)]
    got = [fe.lamport_timestamp for fe in sort_frame_events(fes2)]
    assert got == sorted(got)


def test_check_block_signature_threshold():
    """check_block demands MORE than 1/3 valid signatures from the right
    peer-set; forged and foreign signatures don't count (reference:
    hashgraph.go:1599-1630 — the gate fast-sync trusts its anchor with)."""
    from babble_tpu.crypto.keys import generate_key as _gen

    h, nodes, index = init_block_hashgraph()
    block = h.store.get_block(0)
    ps = h.store.get_peer_set(block.round_received())

    # zero signatures: refused
    with pytest.raises(ValueError, match="not enough"):
        h.check_block(block, ps)

    # wrong peer-set: refused before signatures are even counted
    alien = PeerSet(
        [Peer("inmem://alien", _gen().public_key.hex(), "alien")]
    )
    with pytest.raises(ValueError, match="wrong peer-set"):
        h.check_block(block, alien)

    # 1 of 3 validators (= trust_count, not more): still refused
    block.set_signature(block.sign(nodes[0].key))
    assert ps.trust_count() == 1
    with pytest.raises(ValueError, match="not enough"):
        h.check_block(block, ps)

    # signatures from outside the peer-set don't help
    outsider = _gen()
    foreign = block.sign(outsider)
    block.set_signature(foreign)
    with pytest.raises(ValueError, match="not enough"):
        h.check_block(block, ps)

    # a second REAL validator crosses the >1/3 threshold
    block.set_signature(block.sign(nodes[1].key))
    h.check_block(block, ps)  # no raise

    # anchor tracking follows the same threshold (frame retrieval is
    # exercised end-to-end by the fast-sync suites)
    assert h.anchor_block is None
    h.set_anchor_block(block)
    assert h.anchor_block == block.index()
