"""The seeded backlog: the same seed gives the same bytes, a tag changes
every hash and signature but not the DAG, and a plain host ``Core`` accepts
the wire events as they are (CPU only)."""

import pytest

from benchmark.harness import data, reference


@pytest.fixture(scope="module")
def ring():
    keys = data.seeded_keys(4, 3000000019)  # a seed past 32 signed bits
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(4)])
    return keys, peers


def _stream(ring, seed=5, tag=0, n=120):
    keys, peers = ring
    return data.backlog_wire_events(keys, peers, [1, 2, 3], n, seed, 100, tag)


def _shape(wires):
    return [(w.body.creator_id, w.body.index, w.body.self_parent_index,
             w.body.other_parent_creator_id, w.body.other_parent_index)
            for w in wires]


def test_same_seed_same_bytes(ring):
    a, b = _stream(ring), _stream(ring)
    assert [w.signature for w in a] == [w.signature for w in b]
    assert [w.body.transactions for w in a] == [w.body.transactions for w in b]
    assert _shape(_stream(ring, seed=6)) != _shape(a)


def test_a_tag_changes_every_signature_and_not_the_dag(ring):
    a, b = _stream(ring, tag=0), _stream(ring, tag=1)
    assert _shape(a) == _shape(b)
    # initial events carry no transaction, so they alone repeat
    same = [i for i, (x, y) in enumerate(zip(a, b))
            if x.signature == y.signature]
    assert same == [i for i, w in enumerate(a) if w.body.index == 0]
    assert all(len(t) == 100 for w in a for t in w.body.transactions)


def test_the_rejoining_validator_is_silent_in_the_backlog(ring):
    keys, peers = ring
    me = peers.by_pub_key[keys[0].public_key.hex()].id
    assert me not in {w.body.creator_id for w in _stream(ring)}


def test_a_host_core_accepts_the_wire_events_and_the_audit_agrees(ring):
    from babble_tpu.dummy.state import State
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.node.core import Core
    from babble_tpu.node.validator import Validator
    from babble_tpu.proxy.proxy import InmemProxy

    keys, peers = ring
    wires = _stream(ring, n=240)
    core = Core(Validator(keys[0], "v0"), peers, peers, InmemStore(10000),
                InmemProxy(State()).commit_block)
    from_id = peers.by_pub_key[keys[1].public_key.hex()].id
    for chunk in data.chunks(wires, 100):
        core.sync(from_id, chunk, core.prepare_sync(chunk))
    # every wire event landed, plus the self-events the core recorded
    assert core.hg.topological_index > len(wires)
    assert core.get_consensus_events_count() > 100
    ok, note, blocks, ordered = reference.audit_against_oracle(core.hg, peers)
    assert ok, note
    assert blocks == core.get_last_block_index() + 1 > 0
    assert ordered == core.get_consensus_events_count()


@pytest.mark.parametrize("counters,ok", [
    ({"accel_sweeps": 3.0}, True),
    ({"accel_sweeps": 0.0}, False),
    ({"accel_sweeps": 3.0, "accel_fallbacks": 1.0}, False),
    ({"accel_sweeps": 3.0, "accel_breaker_open": 1.0}, False),
])
def test_the_window_has_to_drive_the_device_and_never_fall_back(counters, ok):
    held, notes = reference.device_path_held(counters)
    assert held is ok and bool(notes) is not ok
