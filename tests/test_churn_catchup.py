"""A validator that catches up across membership changes (deployment
``churn16``) at a small size on the CPU: 4 genesis validators and 2
joiners, the flush gate at 16 events.

- the benchmark's generator (``benchmark/harness/churn.py``) makes a backlog
  a sequential validator accepts whole, the same bytes for the same seeds;
- the program — ``Core.prepare_sync`` / ``Core.sync`` / ``process_sig_pool``
  with deferred voting — against the benchmark's plain reference (a host
  ``Hashgraph`` with a +6 commit step of its own): blocks, the validator
  set of every round, the last set, with sweeps of two validator-set slots
  and no fallback;
- what per-round peer-sets forced in the program: ``Hashgraph`` drains
  voting before it divides an event into a round whose peer-set an
  undecided request can still change (``peer_set_wait``), ``Core.sync``
  drains and decodes again when an event's creator is not in the repertoire
  yet (``creator_stall``), compilation follows (P, S), and window rebuilds
  are counted by reason. A backlog with no request enters none of it.
"""

from __future__ import annotations

import pytest

from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph import accel as accel_mod
from babble_tpu.node.core import Core
from babble_tpu.node.validator import Validator
from babble_tpu.ops import voting
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.proxy.proxy import dummy_commit_response
from benchmark.harness import churn, data, reference

N_GENESIS, N_JOINERS, ME = 4, 2, 0
REQUESTS = ["+x0", "-v3", "+x1"]
SEED, DAG_SEED = 3000000019, 2147489957


def _backlog(seed=SEED, n_events=600, eager=False, tag=0):
    keys = data.seeded_keys(N_GENESIS + N_JOINERS, seed)
    peers = churn.all_peers(keys, N_GENESIS)
    genesis = PeerSet(peers[:N_GENESIS])
    requests = churn.parse_requests(REQUESTS, N_GENESIS)
    script, wires = churn.churn_script(
        keys, peers, genesis, [i for i in range(N_GENESIS) if i != ME],
        requests, n_events, DAG_SEED, 40, 180, 100, eager_joiners=eager)
    if tag:
        wires = churn.wire_events(keys, peers, requests, script, 100, tag)
    final = churn.schedule_final_set(genesis, peers, requests)
    return keys, peers, genesis, requests, script, wires, final


BATCHER = pytest.mark.parametrize("batcher", [False, True],
                                  ids=["direct", "batcher"])


def _core(keys, genesis, pipeline, batcher=False):
    """v0's core as ``Node`` builds it with ``--accelerator``, the flush
    gate scaled to a 4-validator window and compiles inline. Pipelined
    with ``batcher`` on is the lane a chip resolves and ``churn16`` runs."""
    core = Core(Validator(keys[ME], "v0"), genesis, genesis,
                InmemStore(10000), dummy_commit_response,
                accelerated_verify=True)
    tc = core.hg.accel
    tc.min_window, tc.async_compile = 16, False
    tc.pipeline, tc.batcher = pipeline, batcher
    return core


def _ingest(core, wires, from_id, sync_events):
    for chunk in data.chunks(wires, sync_events):
        prepared = core.prepare_sync(chunk)
        core.sync(from_id, chunk, prepared)
        core.process_sig_pool()
    core.hg.drain_consensus()


def _wire_dicts(wires):
    return [w.to_dict() for w in wires]


def test_the_backlog_inserts_whole_through_a_sequential_hashgraph():
    keys, peers, genesis, requests, script, wires, final = _backlog()
    assert len(wires) == 600 and len(script) == 600
    assert {s.request for s in script} == {-1, 0, 1, 2}
    hg, plus_six = churn.sequential_hashgraph(genesis, len(wires))
    for we in wires:  # sequential decode + insert + consensus
        hg.insert_event_and_run_consensus(hg.read_wire_info(we),
                                          set_wire_info=False)
    assert hg.topological_index == 600
    assert [(pk, add) for _r, pk, add in plus_six.changes] == [
        (peers[r.key].pub_key_hex, r.add) for r in requests]
    assert plus_six.validators.hash() == final.hash()
    # both joiners created events, and only after they were admitted
    for k in (N_GENESIS, N_GENESIS + 1):
        firsts = [i for i, s in enumerate(script) if s.creator == k]
        asked = next(i for i, s in enumerate(script)
                     if s.request >= 0 and requests[s.request].key == k)
        assert firsts and firsts[0] > asked
    # the leaver fell silent
    last_v3 = max(i for i, s in enumerate(script) if s.creator == 3)
    assert last_v3 < 599 - 50


def test_the_same_seeds_give_the_same_bytes():
    a, b = _backlog(), _backlog()
    assert a[4] == b[4] and _wire_dicts(a[5]) == _wire_dicts(b[5])
    other_keys = _backlog(seed=SEED + 2)
    assert other_keys[4] == a[4]  # the shape is dag_seed's alone
    assert _wire_dicts(other_keys[5]) != _wire_dicts(a[5])
    tagged = _backlog(tag=1)
    assert tagged[4] == a[4]
    # every event that carries a payload is another event to a verifier
    sigs = {w.signature for w in a[5] if w.body.index > 0}
    assert len(sigs) > 590
    assert not sigs & {w.signature for w in tagged[5]}


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
def test_the_validator_equals_the_reference_across_the_changes(pipeline):
    keys, peers, genesis, requests, _script, wires, final = _backlog()
    core = _core(keys, genesis, pipeline)
    _ingest(core, wires, peers[1].id, 300)
    tc = core.hg.accel
    assert tc.sweeps > 0 and tc.fallbacks == 0 and tc.mesh_fallbacks == 0
    multi, launches = churn.multi_set_launches(
        {"accel_bucket_launches." + k: float(v)
         for k, v in tc.bucket_launches.items()})
    assert multi >= 1 and launches >= multi  # sweeps with S >= 2
    assert reference.stored_from_others(
        core.hg.store, keys[ME].public_key.hex()) == len(wires)
    got = churn.audit(core.hg, genesis, final, len(requests))
    assert got.blocks.ok and got.blocks.blocks > 20, got.note
    assert got.peer_sets_differing == 0 and got.rounds_compared > 30
    assert got.changes_in_reference == 3 and got.changes_not_applied == 0
    assert core.membership_changes_applied == 3
    assert core.validators.hash() == final.hash()
    assert core.get_consensus_events_count() == got.blocks.ordered
    # rebuilds are counted by what forced them
    by_reason = tc.stats()["accel_rebuilds_by_reason"]
    assert sum(by_reason.values()) == tc.window_state.rebuilds > 0
    assert "repertoire-change" in by_reason


@BATCHER
def test_a_joiners_first_event_in_the_sync_that_admits_it_is_stored(batcher):
    """The first sync of 300 holds the request for x0 (event 40), the block
    that admits it and x0's first event. Sequential decode + insert +
    consensus accepts it whole; with voting deferred the peer-set has to be
    waited for, or x0's creator id is unknown and the rest is refused."""
    keys, peers, genesis, _requests, script, wires, _final = _backlog()
    first_x0 = next(i for i, s in enumerate(script) if s.creator == N_GENESIS)
    assert 40 < first_x0 < 300
    core = _core(keys, genesis, pipeline=True, batcher=batcher)
    chunk = wires[:300]
    prepared = core.prepare_sync(chunk)
    assert len(prepared.decoded) == first_x0  # the decode stall
    core.sync(peers[1].id, chunk, prepared)
    assert reference.stored_from_others(
        core.hg.store, keys[ME].public_key.hex()) == 300
    assert core.hg.peer_set_waits >= 1
    assert core.membership_changes_applied == 1


@BATCHER
def test_an_eager_joiner_stalls_the_sync_until_voting_is_drained(batcher):
    """A joiner that starts as soon as a sequential validator has committed
    its admission, rounds before it is a member: no round's peer-set is in
    doubt yet, so nothing has been waited for, and its creator id is
    unknown where voting lags. ``Core.sync`` drains and decodes again."""
    keys, peers, genesis, requests, _script, wires, final = _backlog(
        eager=True)
    core = _core(keys, genesis, pipeline=True, batcher=batcher)
    _ingest(core, wires, peers[1].id, 300)
    assert core.sync_creator_stalls >= 1
    snap = core.obs.registry.snapshot()
    assert snap["sync_stage_seconds"]["creator_stall"]["count"] >= 1
    got = churn.audit(core.hg, genesis, final, len(requests))
    assert got.blocks.ok and got.changes_not_applied == 0, got.note
    assert got.peer_sets_differing == 0
    assert core.hg.accel.fallbacks == 0
    assert core.hg.accel.stats()["accel_batcher"] is batcher


@BATCHER
def test_a_stall_that_survives_a_drained_pipeline_is_raised(batcher):
    from babble_tpu.hashgraph.errors import UnknownParticipantError

    keys, peers, genesis, _requests, script, wires, _final = _backlog()
    first_x0 = next(i for i, s in enumerate(script) if s.creator == N_GENESIS)
    core = _core(keys, genesis, pipeline=True, batcher=batcher)
    # x0's first event with its admission left out: nobody's to resolve
    chunk = wires[:40] + wires[first_x0:first_x0 + 1]
    with pytest.raises(UnknownParticipantError):
        core.sync(peers[1].id, chunk, core.prepare_sync(chunk))
    assert reference.stored_from_others(
        core.hg.store, keys[ME].public_key.hex()) == 40


@BATCHER
def test_a_backlog_with_no_request_never_waits(batcher):
    keys = data.seeded_keys(N_GENESIS, SEED)
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(N_GENESIS)])
    wires = data.backlog_wire_events(keys, peers, [1, 2, 3], 600, DAG_SEED,
                                     100)
    core = _core(keys, peers, pipeline=True, batcher=batcher)
    _ingest(core, wires, peers.by_pub_key[keys[1].public_key.hex()].id, 300)
    assert core.hg.accel.sweeps > 0
    assert core.hg.accel.stats()["accel_batcher"] is batcher
    assert core.sync_creator_stalls == 0 and core.hg.peer_set_waits == 0
    assert core.membership_changes_applied == 0
    stages = core.obs.registry.snapshot()["sync_stage_seconds"]
    assert not {"creator_stall", "peer_set_wait", "membership"} & set(stages)
    assert set(core.hg.accel.stats()["accel_rebuilds_by_reason"]) <= {
        "initial", "oracle-pass", "round-bucket-overflow",
        "event-bucket-overflow", "witness-bucket-overflow", "empty"}


@pytest.fixture()
def compiled(monkeypatch):
    """The compile policy on its own: ``voting.precompile`` only records."""
    made = []
    monkeypatch.setattr(voting, "_ready_buckets", set())
    monkeypatch.setattr(accel_mod, "_peer_axes_seen", set())
    monkeypatch.setattr(accel_mod, "_variants_queued", set())

    def precompile(*key):
        made.append(key)
        voting.mark_bucket_ready(key)

    monkeypatch.setattr(voting, "precompile", precompile)

    def settle():
        import time

        deadline = time.monotonic() + 5.0
        while (not accel_mod._variant_queue.empty()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        time.sleep(0.05)
        return sorted(made)

    return settle


def test_compilation_follows_the_repertoire_and_the_slot_count(compiled):
    shapes = [(64, 256, 16), (128, 512, 16)]
    for W, E, R in shapes:  # a deployment's shapes at the genesis set
        voting.precompile(W, E, 16, 1, R)
        accel_mod._follow_peer_axes((W, E, 16, 1, R), compiled=True)
    assert compiled() == [(64, 256, 16, 1, 16), (128, 512, 16, 1, 16)]
    # a join: the repertoire is 17, P goes to 24 under every shape in use
    accel_mod._follow_peer_axes((64, 256, 24, 1, 16))
    assert {k for k in compiled() if k[2:4] == (24, 1)} == {
        (64, 256, 24, 1, 16), (128, 512, 24, 1, 16)}
    # a window that straddles the change: S 2
    accel_mod._follow_peer_axes((128, 512, 24, 2, 16))
    # a new shape, compiled on demand at one pair, is made at the others
    voting.precompile(256, 1024, 24, 2, 32)
    accel_mod._follow_peer_axes((256, 1024, 24, 2, 32), compiled=True)
    made = compiled()
    pairs = {(16, 1), (24, 1), (24, 2)}
    assert set(made) == {(W, E) + p + (R,) for p in pairs
                         for W, E, R in shapes + [(256, 1024, 32)]}
    assert len(made) == len(set(made))  # nothing compiled twice


def test_one_validator_set_compiles_nothing_ahead(compiled):
    for key in [(64, 256, 16, 1, 16), (128, 512, 16, 1, 16)]:
        accel_mod._follow_peer_axes(key)
        voting.precompile(*key)
        accel_mod._follow_peer_axes(key, compiled=True)
    assert compiled() == [(64, 256, 16, 1, 16), (128, 512, 16, 1, 16)]
    assert accel_mod._variants_queued == set()
