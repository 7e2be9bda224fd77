"""Dynamic-membership golden DAG suites, ported from the reference's
hashgraph-level dynamic tests (/root/reference/src/hashgraph/
hashgraph_dyn_test.go:87-846): R2Dyn (peer added at round 2, removed at
round 5), Usurper (events from a creator not yet in the round's peer-set
must not become witnesses), and Monologue (a single-validator chain).

These replay hand-drawn DAGs across peer-set changes and assert exact
rounds, lamport timestamps, witnesses, fame, round-received, and block
projections — the only direct exercise of per-round peer-set math, which
the device voting kernels reimplement as psi/member-mask tensors. Each
fixture therefore also runs through TensorConsensus (sync and pipelined)
and must match the oracle bit for bit.
"""

from __future__ import annotations

from typing import Dict, List

import pytest

from babble_tpu.common.trilean import Trilean
from babble_tpu.crypto.keys import generate_key
from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
from babble_tpu.hashgraph.accel import TensorConsensus
from babble_tpu.peers.peer import Peer

from tests.test_hashgraph import (
    CACHE_SIZE,
    NodeFixture,
    Play,
    init_nodes,
    play_events,
)
from tests.test_accel import _consensus_state, drain_pipelined

# =============================================================================
# R2Dyn — add participant 3 at round 2, remove participant 0 at round 5
# (ASCII diagram: hashgraph_dyn_test.go:13-83)
# =============================================================================

R2DYN_PLAYS_1: List[Play] = [
    (1, 1, "w01", "w00", "e10", [b"e10"], None),
    (2, 1, "w02", "e10", "e21", [b"e21"], None),
    (0, 1, "w00", "e21", "e12", [b"e12"], None),
    (1, 2, "e10", "e12", "w11", [b"w11"], None),
    (2, 2, "e21", "w11", "w12", [b"w12"], None),
    (0, 2, "e12", "w12", "w10", [b"w10"], None),
    (1, 3, "w11", "w10", "f10", [b"f10"], None),
    (2, 3, "w12", "f10", "w22", [b"w22"], None),
    (0, 3, "w10", "w22", "w20", [b"w20"], None),
    (1, 4, "f10", "w20", "w21", [b"w21"], None),
    (2, 4, "w22", "w21", "g21", [b"g21"], None),
]

R2DYN_PLAYS_2: List[Play] = [
    (3, 0, "R3", "g21", "w33", [b"w33"], None),
    (0, 4, "w20", "w33", "w30", [b"w30"], None),
    (1, 5, "w21", "w30", "w31", [b"w31"], None),
    (2, 5, "g21", "w31", "w32", [b"w32"], None),
    (3, 1, "w33", "w32", "w43", [b"w43"], None),
    (0, 5, "w30", "w43", "w40", [b"w40"], None),
    (1, 6, "w31", "w40", "w41", [b"w41"], None),
    (2, 6, "w32", "w41", "w42", [b"w42"], None),
]

R2DYN_PLAYS_3: List[Play] = [
    (3, 2, "w43", "w42", "w53", [b"w53"], None),
    (2, 7, "w42", "w53", "w52", [b"w52"], None),
    (1, 7, "w41", "w52", "w51", [b"w51"], None),
    (3, 3, "w53", "w51", "j31", [b"j31"], None),
    (2, 8, "w52", "j31", "w62", [b"w62"], None),
    (1, 8, "w51", "w62", "w61", [b"w61"], None),
    (3, 4, "j31", "w61", "w63", [b"w63"], None),
    (2, 9, "w62", "w63", "h23", [b"h23"], None),
    (1, 9, "w61", "h23", "w71", [b"w71"], None),
]


def _root_events(nodes, index, ordered) -> None:
    for i, nd in enumerate(nodes):
        name = f"w0{i}"
        e = Event.new([name.encode()], [], [], ["", ""], nd.pub_bytes, 0)
        nd.sign_and_add(e, name, index, ordered)


def _r2dyn_script():
    """Returns (steps, index): steps is an ordered list of
    ("insert", event) / ("peerset", round, PeerSet) actions — one script
    replayed identically through the oracle and device drivers
    (hashgraph_dyn_test.go:87-199)."""
    nodes, index, ordered, peer_set = init_nodes(3)
    _root_events(nodes, index, ordered)
    play_events(R2DYN_PLAYS_1, nodes, index, ordered)
    steps = [("peerset", 0, peer_set)]
    steps += [("insert", ev) for ev in ordered]

    # add participant 3; new peer-set effective from round 2
    node3 = NodeFixture(generate_key())
    nodes.append(node3)
    index["R3"] = ""
    new_peer_set = peer_set.with_new_peer(
        Peer(net_addr="", pub_key_hex=node3.pub_hex, moniker="")
    )
    steps.append(("peerset", 2, new_peer_set))
    ordered2: List[Event] = []
    play_events(R2DYN_PLAYS_2, nodes, index, ordered2)
    steps += [("insert", ev) for ev in ordered2]

    # remove participant 0; new peer-set effective from round 5
    peer0 = next(
        p for p in new_peer_set.peers if p.pub_key_hex == nodes[0].pub_hex
    )
    new_peer_set2 = new_peer_set.with_removed_peer(peer0)
    steps.append(("peerset", 5, new_peer_set2))
    ordered3: List[Event] = []
    play_events(R2DYN_PLAYS_3, nodes, index, ordered3)
    steps += [("insert", ev) for ev in ordered3]
    return steps, index


def _build(steps, accel: TensorConsensus | None = None,
           run_consensus: bool = False) -> Hashgraph:
    """Replay a script into a fresh Hashgraph. run_consensus=False mirrors
    the reference fixtures (stages invoked explicitly by each test);
    True drives the live per-insert pipeline (differential tests)."""
    h = Hashgraph(InmemStore(CACHE_SIZE))
    first = True
    for step in steps:
        if step[0] == "peerset":
            _, rnd, ps = step
            if first:
                h.init(ps)
                first = False
            else:
                h.store.set_peer_set(rnd, ps)
            if accel is not None:
                h.accel = accel
        else:
            ev = Event(step[1].body, step[1].signature)
            if run_consensus:
                h.insert_event_and_run_consensus(ev, set_wire_info=True)
            else:
                h.insert_event(ev, set_wire_info=True)
    if run_consensus:
        h.flush_consensus()
    return h


R2DYN_TIMESTAMPS: Dict[str, tuple] = {
    # name -> (lamport, round)   (hashgraph_dyn_test.go:210-242)
    "w00": (0, 0), "w01": (0, 0), "w02": (0, 0),
    "e10": (1, 0), "e21": (2, 0), "e12": (3, 0),
    "w11": (4, 1), "w12": (5, 1), "w10": (6, 1), "f10": (7, 1),
    "w22": (8, 2), "w20": (9, 2), "w21": (10, 2), "g21": (11, 2),
    "w33": (12, 3), "w30": (13, 3), "w31": (14, 3), "w32": (15, 3),
    "w43": (16, 4), "w40": (17, 4), "w41": (18, 4), "w42": (19, 4),
    "w53": (20, 5), "w52": (21, 5), "w51": (22, 5), "j31": (23, 5),
    "w62": (24, 6), "w61": (25, 6), "w63": (26, 6), "h23": (27, 6),
    "w71": (28, 7),
}

R2DYN_WITNESSES = {
    0: ["w00", "w01", "w02"],
    1: ["w10", "w11", "w12"],
    2: ["w20", "w21", "w22"],
    3: ["w30", "w31", "w32", "w33"],
    4: ["w40", "w41", "w42", "w43"],
    5: ["w51", "w52", "w53"],
    6: ["w61", "w62", "w63"],
    7: ["w71"],
}


def test_r2dyn_divide_rounds():
    steps, index = _r2dyn_script()
    h = _build(steps)
    h.divide_rounds()
    for name, (lamport, rnd) in R2DYN_TIMESTAMPS.items():
        ev = h.store.get_event(index[name])
        assert ev.round == rnd, f"{name} round {ev.round} != {rnd}"
        assert ev.lamport_timestamp == lamport, (
            f"{name} lamport {ev.lamport_timestamp} != {lamport}"
        )
    for rnd, names in R2DYN_WITNESSES.items():
        ri = h.store.get_round(rnd)
        ws = ri.witnesses()
        assert len(ws) == len(names), f"round {rnd}: {len(ws)} witnesses"
        for name in names:
            assert index[name] in ws, f"round {rnd} missing witness {name}"


R2DYN_FAME = {
    # round -> {name: (witness, famous)}   (hashgraph_dyn_test.go:295-355)
    0: {"w00": (True, Trilean.TRUE), "w01": (True, Trilean.TRUE),
        "w02": (True, Trilean.TRUE), "e10": (False, Trilean.UNDEFINED),
        "e21": (False, Trilean.UNDEFINED), "e12": (False, Trilean.UNDEFINED)},
    1: {"w10": (True, Trilean.TRUE), "w11": (True, Trilean.TRUE),
        "w12": (True, Trilean.TRUE), "f10": (False, Trilean.UNDEFINED)},
    2: {"w20": (True, Trilean.TRUE), "w21": (True, Trilean.TRUE),
        "w22": (True, Trilean.TRUE), "g21": (False, Trilean.UNDEFINED)},
    3: {"w30": (True, Trilean.TRUE), "w31": (True, Trilean.TRUE),
        "w32": (True, Trilean.TRUE), "w33": (True, Trilean.TRUE)},
    4: {"w40": (True, Trilean.TRUE), "w41": (True, Trilean.TRUE),
        "w42": (True, Trilean.TRUE), "w43": (True, Trilean.TRUE)},
    5: {"w51": (True, Trilean.TRUE), "w52": (True, Trilean.TRUE),
        "w53": (True, Trilean.TRUE), "j31": (False, Trilean.UNDEFINED)},
    6: {"w61": (True, Trilean.UNDEFINED), "w62": (True, Trilean.UNDEFINED),
        "w63": (True, Trilean.UNDEFINED), "h23": (False, Trilean.UNDEFINED)},
    7: {"w71": (True, Trilean.UNDEFINED)},
}


def test_r2dyn_decide_fame():
    steps, index = _r2dyn_script()
    h = _build(steps)
    h.divide_rounds()
    h.decide_fame()
    for rnd, expected in R2DYN_FAME.items():
        ri = h.store.get_round(rnd)
        assert len(ri.created_events) == len(expected), (
            f"round {rnd}: {len(ri.created_events)} created events"
        )
        for name, (wit, famous) in expected.items():
            re_ = ri.created_events[index[name]]
            assert re_.witness == wit, f"{name} witness {re_.witness}"
            assert re_.famous == famous, f"{name} famous {re_.famous}"


def test_r2dyn_decide_round_received():
    steps, index = _r2dyn_script()
    h = _build(steps)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    expected = {
        # received in the oracle's scan order (hashgraph_dyn_test.go:383-394)
        0: [],
        1: [index[n] for n in ("w00", "w01", "w02", "e10", "e21", "e12")],
        2: [index[n] for n in ("w11", "w12", "w10", "f10")],
        3: [index[n] for n in ("w22", "w20", "w21", "g21")],
        4: [index[n] for n in ("w33", "w30", "w31", "w32")],
        5: [index[n] for n in ("w43", "w40", "w41", "w42")],
        6: [],
        7: [],
    }
    for rnd, received in expected.items():
        ri = h.store.get_round(rnd)
        assert ri.received_events == received, (
            f"round {rnd}: {ri.received_events} != {received}"
        )


def test_r2dyn_process_decided_rounds():
    steps, index = _r2dyn_script()
    h = _build(steps)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    h.process_decided_rounds()

    assert len(h.store.consensus_events()) == 22
    assert h.pending_loaded_events == 9

    for i in range(4):
        rr = i + 1
        frame = h.store.get_frame(rr)
        ps = h.store.get_peer_set(rr)
        block = h.store.get_block(i)
        assert block.round_received() == rr
        assert block.frame_hash() == frame.hash()
        assert block.peers_hash() == ps.hash()


# =============================================================================
# Usurper — events created ahead of membership are not witnesses
# (hashgraph_dyn_test.go:455-646)
# =============================================================================

USURPER_PLAYS_2: List[Play] = [
    (0, 4, "w20", "g21", "w30", [b"w30"], None),
    (1, 5, "w21", "w30", "w31", [b"w31"], None),
    (2, 5, "g21", "w31", "w32", [b"w32"], None),
    (3, 0, "R3", "w32", "x32", [b"x32"], None),
    (0, 5, "w30", "x32", "h03", [b"h03"], None),
    (1, 6, "w31", "h03", "w41", [b"w41"], None),
]


def _usurper_script():
    nodes, index, ordered, peer_set = init_nodes(3)
    _root_events(nodes, index, ordered)
    play_events(R2DYN_PLAYS_1, nodes, index, ordered)
    steps = [("peerset", 0, peer_set)]
    steps += [("insert", ev) for ev in ordered]

    # the usurper joins a peer-set effective only from round 10
    usurper = NodeFixture(generate_key())
    nodes.append(usurper)
    index["R3"] = ""
    new_peer_set = peer_set.with_new_peer(
        Peer(net_addr="", pub_key_hex=usurper.pub_hex, moniker="")
    )
    steps.append(("peerset", 10, new_peer_set))
    ordered2: List[Event] = []
    play_events(USURPER_PLAYS_2, nodes, index, ordered2)
    steps += [("insert", ev) for ev in ordered2]
    return steps, index


USURPER_TIMESTAMPS = {
    "w00": (0, 0), "w01": (0, 0), "w02": (0, 0),
    "e10": (1, 0), "e21": (2, 0), "e12": (3, 0),
    "w11": (4, 1), "w12": (5, 1), "w10": (6, 1), "f10": (7, 1),
    "w22": (8, 2), "w20": (9, 2), "w21": (10, 2), "g21": (11, 2),
    "w30": (12, 3), "w31": (13, 3), "w32": (14, 3),
    "x32": (15, 3),  # NOT a witness: creator not in round 3's peer-set
    "h03": (16, 3), "w41": (17, 4),
}

USURPER_WITNESSES = {
    0: ["w00", "w01", "w02"],
    1: ["w10", "w11", "w12"],
    2: ["w20", "w21", "w22"],
    3: ["w30", "w31", "w32"],
    4: ["w41"],
}


def test_usurper_divide_rounds():
    steps, index = _usurper_script()
    h = _build(steps)
    h.divide_rounds()
    for name, (lamport, rnd) in USURPER_TIMESTAMPS.items():
        ev = h.store.get_event(index[name])
        assert ev.round == rnd, f"{name} round {ev.round} != {rnd}"
        assert ev.lamport_timestamp == lamport
    for rnd, names in USURPER_WITNESSES.items():
        ri = h.store.get_round(rnd)
        ws = ri.witnesses()
        assert len(ws) == len(names), f"round {rnd}: {len(ws)} witnesses"
        for name in names:
            assert index[name] in ws
    # the usurper's event must not be a witness anywhere
    r3 = h.store.get_round(3)
    assert not r3.created_events[index["x32"]].witness


# =============================================================================
# Monologue — single validator (hashgraph_dyn_test.go:648-846)
# =============================================================================

MONOLOGUE_PLAYS: List[Play] = [
    (0, 1, "w00", "", "w10", [b"w10"], None),
    (0, 2, "w10", "", "w20", [b"w20"], None),
    (0, 3, "w20", "", "w30", [b"w30"], None),
    (0, 4, "w30", "", "w40", [b"w40"], None),
    # payload b"w40" (not w50) reproduces the reference fixture byte for
    # byte, including its own copy-paste quirk (hashgraph_dyn_test.go:769)
    (0, 5, "w40", "", "w50", [b"w40"], None),
    (0, 6, "w50", "", "w60", [b"w60"], None),
    (0, 7, "w60", "", "w70", [b"w70"], None),
    (0, 8, "w70", "", "w80", [b"w80"], None),
]


def _monologue_script():
    nodes, index, ordered, peer_set = init_nodes(1)
    _root_events(nodes, index, ordered)
    play_events(MONOLOGUE_PLAYS, nodes, index, ordered)
    steps = [("peerset", 0, peer_set)]
    steps += [("insert", ev) for ev in ordered]
    return steps, index


def test_monologue_divide_rounds():
    steps, index = _monologue_script()
    h = _build(steps)
    h.divide_rounds()
    for i in range(9):
        name = f"w{i}0"
        ev = h.store.get_event(index[name])
        assert ev.round == i
        assert ev.lamport_timestamp == i
        ri = h.store.get_round(i)
        assert ri.witnesses() == [index[name]]


def test_monologue_decide_fame():
    steps, index = _monologue_script()
    h = _build(steps)
    h.divide_rounds()
    h.decide_fame()
    expected_famous = {i: Trilean.TRUE for i in range(7)}
    expected_famous[7] = Trilean.UNDEFINED
    expected_famous[8] = Trilean.UNDEFINED
    for i in range(9):
        ri = h.store.get_round(i)
        assert len(ri.created_events) == 1
        re_ = ri.created_events[index[f"w{i}0"]]
        assert re_.witness
        assert re_.famous == expected_famous[i], f"round {i}"


def test_monologue_decide_round_received():
    steps, index = _monologue_script()
    h = _build(steps)
    h.divide_rounds()
    h.decide_fame()
    h.decide_round_received()
    for i in range(7):
        ri = h.store.get_round(i)
        expected = [] if i == 0 else [index[f"w{i - 1}0"]]
        assert ri.received_events == expected, f"round {i}"


# =============================================================================
# The same scripts through TensorConsensus — the only direct exercise of the
# device kernels' per-round psi/member masks (multiple peer-set slots).
# =============================================================================

SCRIPTS = {
    "r2dyn": _r2dyn_script,
    "usurper": _usurper_script,
    "monologue": _monologue_script,
}


def _preregister(steps):
    """Move every peer-set registration ahead of the inserts. The staged
    golden fixtures interleave set_peer_set with insert batches, which
    makes a frame's all-peer-sets snapshot depend on WHEN the frame is
    built — fine for the reference's end-of-script staged runs, but
    timing-sensitive between per-insert and sweep-batched live drivers.
    Live nodes never hit this: peer-set registration rides the consensus
    order itself (the +6 effective-round rule, core.go:566-569)."""
    peersets = [s for s in steps if s[0] == "peerset"]
    inserts = [s for s in steps if s[0] == "insert"]
    return peersets + inserts


@pytest.mark.parametrize("script", list(SCRIPTS))
@pytest.mark.parametrize("mode", ["sync", "pipelined", "chip"])
def test_dyn_accel_matches_oracle(script, mode):
    """``chip`` is the lane a chip resolves: multi-slot windows through
    the batcher's re-padding WHILE pipelined (the batched test below
    launches them synchronously)."""
    steps, index = SCRIPTS[script]()
    steps = _preregister(steps)
    oracle = _build(steps, run_consensus=True)
    accel = TensorConsensus(
        sweep_events=3,
        async_compile=False,
        min_window=0,
        pipeline=(mode != "sync"),
        batcher=(mode == "chip"),
    )
    dev = _build(steps, accel=accel, run_consensus=True)
    if mode != "sync":
        drain_pipelined(dev)
    assert accel.sweeps > 0
    assert accel.fallbacks == 0
    assert _consensus_state(dev) == _consensus_state(oracle)


@pytest.mark.parametrize("script", list(SCRIPTS))
def test_dyn_accel_batched_matches_oracle(script):
    """The golden dynamic-membership fixtures through the co-located
    SWEEP BATCHER: multi-slot windows (psi/member machinery) re-padded to
    the batcher's monotone bucket and dispatched vmapped must reproduce
    the oracle bit for bit across join/leave — pins repad_window's S/R
    padding under real peer-set churn."""
    steps, index = SCRIPTS[script]()
    steps = _preregister(steps)
    oracle = _build(steps, run_consensus=True)
    accel = TensorConsensus(
        sweep_events=3,
        async_compile=False,
        min_window=0,
        pipeline=False,
        batcher=True,
    )
    dev = _build(steps, accel=accel, run_consensus=True)
    assert accel.sweeps > 0
    assert accel.fallbacks == 0
    assert _consensus_state(dev) == _consensus_state(oracle)


@pytest.mark.parametrize("script", list(SCRIPTS))
def test_dyn_accel_mesh_sharded_matches_oracle(script):
    """The golden dynamic-membership fixtures through the MESH-SHARDED
    voting kernel: witness-axis shard_map sweeps with per-round peer-set
    masks must reproduce the oracle bit for bit across join/leave — the
    strongest exercise of voting_shard's psi/member machinery (the
    windows here span up to three peer-set slots)."""
    from babble_tpu.parallel.mesh import consensus_mesh

    steps, index = SCRIPTS[script]()
    steps = _preregister(steps)
    oracle = _build(steps, run_consensus=True)
    accel = TensorConsensus(
        sweep_events=3,
        async_compile=False,
        min_window=0,
        pipeline=False,
        mesh=consensus_mesh(8),
    )
    dev = _build(steps, accel=accel, run_consensus=True)
    assert accel.sweeps > 0
    assert accel.fallbacks == 0
    assert _consensus_state(dev) == _consensus_state(oracle)
