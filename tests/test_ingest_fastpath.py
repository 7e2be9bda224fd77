"""Batched-ingest fast path (ISSUE 1): one native batch-verify call per
incoming sync, per-event fallback pinpointing on batch failure, lock-free
decode+verify staging, and the event serialization memo's invalidation
contract.
"""

from __future__ import annotations

import threading
import time

import pytest

from babble_tpu.common.timed_lock import TimedLock
from babble_tpu.crypto import batch as host_batch
from babble_tpu.crypto.keys import generate_key
from babble_tpu.hashgraph.errors import InvalidSignatureError
from babble_tpu.hashgraph.event import WIRE_CACHE, Event, WireEvent

from tests.test_core import init_cores

needs_native = pytest.mark.skipif(
    not host_batch.available(), reason="native batch verifier unavailable"
)


# -- one batch verify per sync -------------------------------------------


@needs_native
def test_happy_path_one_batch_verify_per_sync():
    cores, _, _ = init_cores(2)
    # a chain of three more self-events on core 0
    for _ in range(3):
        cores[0].add_self_event("")

    diff = cores[0].event_diff(cores[1].known_events())
    wires = cores[0].to_wire(diff)
    assert len(wires) >= 4  # initial + 3 chained

    before = cores[1].ingest_batch_verifies
    cores[1].sync(cores[0].validator.id(), wires)
    assert cores[1].ingest_batch_verifies == before + 1
    assert cores[1].ingest_batch_size_max >= len(wires)
    assert cores[1].ingest_fallback_singles == 0
    # everything landed
    assert (
        cores[1].known_events()[cores[0].validator.id()]
        == diff[-1].index()
    )


@needs_native
def test_mixed_valid_invalid_sync_pinpoints_bad_event():
    cores, _, _ = init_cores(2)
    for _ in range(2):
        cores[0].add_self_event("")

    diff = cores[0].event_diff(cores[1].known_events())
    wires = list(cores[0].to_wire(diff))
    assert len(wires) == 3
    # corrupt the MIDDLE event's signature with a decodable-but-wrong one
    # (copy the WireEvent: to_wire() memoizes, mutating in place would
    # poison core 0's cache)
    bad_index = 1
    wires[bad_index] = WireEvent(
        body=wires[bad_index].body, signature="1|1"
    )
    bad_hex = diff[bad_index].hex()

    fallbacks_before = cores[1].ingest_fallback_singles
    with pytest.raises(ValueError) as exc:
        cores[1].sync(cores[0].validator.id(), wires)
    # exactly the corrupted event is named
    assert bad_hex in str(exc.value)
    # the batch flagged it; the scalar fallback pass re-checked ONLY it
    assert cores[1].ingest_fallback_singles == fallbacks_before + 1
    # the valid prefix inserted, the suffix after the offender did not
    assert (
        cores[1].known_events()[cores[0].validator.id()]
        == diff[bad_index - 1].index()
    )


@needs_native
def test_batch_artifact_cannot_reject_valid_event():
    """The fallback pass re-verifies flagged events through the scalar
    path, so a spurious batch verdict never rejects a valid event."""
    cores, _, _ = init_cores(2)
    cores[0].add_self_event("")
    diff = cores[0].event_diff(cores[1].known_events())
    wires = cores[0].to_wire(diff)

    orig = host_batch.prevalidate_events_host

    def all_flagged(events):
        # simulate a batch-layer artifact: everything reported bad
        for ev in events:
            ev.prevalidate(False)
        return True

    host_batch.prevalidate_events_host = all_flagged
    try:
        cores[1].sync(cores[0].validator.id(), wires)
    finally:
        host_batch.prevalidate_events_host = orig
    # all events survived via the scalar fallback, one single per event
    assert cores[1].ingest_fallback_singles >= len(wires)
    assert (
        cores[1].known_events()[cores[0].validator.id()]
        == diff[-1].index()
    )


# -- the re-checks stop at the first event confirmed bad ------------------


def _chain_wires(cores, extra):
    """Core 0's initial event and ``extra`` chained self-events, as the
    wire events core 1 does not know yet."""
    for _ in range(extra):
        cores[0].add_self_event("")
    diff = cores[0].event_diff(cores[1].known_events())
    # copies: to_wire() memoizes, mutating in place would poison core 0
    return diff, [WireEvent(body=w.body, signature=w.signature)
                  for w in cores[0].to_wire(diff)]


def _forge(wires, positions):
    for i in positions:
        wires[i] = WireEvent(body=wires[i].body, signature="1|1")


def _sync_refused(core, from_id, wires):
    """Sync ``wires`` into ``core`` through a prepared stage the test can
    read; returns the decoded events and the refusal."""
    prepared = core.prepare_sync(wires)
    with pytest.raises(InvalidSignatureError) as refused:
        core.sync(from_id, wires, prepared)
    return prepared.decoded, refused.value


@needs_native
@pytest.mark.parametrize("forged", [1, 2, 6])
def test_a_forged_run_is_rechecked_once_and_the_rest_skipped(forged):
    cores, _, _ = init_cores(2)
    prefix = 2
    diff, wires = _chain_wires(cores, prefix + forged - 1)
    assert len(wires) == prefix + forged
    _forge(wires, range(prefix, len(wires)))

    singles = cores[1].ingest_fallback_singles
    skipped = cores[1].ingest_fallback_skipped
    decoded, refused = _sync_refused(cores[1], cores[0].validator.id(), wires)
    assert refused.event is decoded[prefix]
    assert cores[1].ingest_fallback_singles == singles + 1
    assert cores[1].ingest_fallback_skipped == skipped + forged - 1
    # the prefix kept the batch's verdict, the re-checked event its own,
    # the later flagged events none
    assert [ev.prevalidated() for ev in decoded] == (
        [True] * prefix + [False] + [None] * (forged - 1))
    # the valid prefix inserted, nothing after it
    assert (
        cores[1].known_events()[cores[0].validator.id()]
        == diff[prefix - 1].index()
    )


@needs_native
def test_a_batch_artifact_before_a_bad_event_still_rejects_no_valid_one():
    """A planted batch verdict flags every event; the third is really bad.
    The first two are re-checked and inserted, the third refused, the
    rest left to insert's own verify — and a valid one of them, handed to
    insert once its parent is in, lands."""
    cores, _, _ = init_cores(2)
    diff, wires = _chain_wires(cores, 4)
    assert len(wires) == 5
    genuine = wires[2]
    _forge(wires, [2])

    orig = host_batch.prevalidate_events_host

    def all_flagged(events):
        for ev in events:
            ev.prevalidate(False)
        return True

    singles = cores[1].ingest_fallback_singles
    skipped = cores[1].ingest_fallback_skipped
    host_batch.prevalidate_events_host = all_flagged
    try:
        decoded, refused = _sync_refused(
            cores[1], cores[0].validator.id(), wires)
    finally:
        host_batch.prevalidate_events_host = orig
    assert refused.event is decoded[2]
    assert cores[1].ingest_fallback_singles == singles + 3
    assert cores[1].ingest_fallback_skipped == skipped + 2
    assert [ev.prevalidated() for ev in decoded] == [
        True, True, False, None, None]
    assert (
        cores[1].known_events()[cores[0].validator.id()] == diff[1].index()
    )

    # the genuine third event, then the fourth as the sync decoded it: the
    # batch's False on it was dropped, so the scalar verifier admits it
    cores[1].insert_event_and_run_consensus(
        cores[1].hg.read_wire_info(genuine), set_wire_info=False)
    cores[1].insert_event_and_run_consensus(decoded[3], set_wire_info=False)
    assert (
        cores[1].known_events()[cores[0].validator.id()] == diff[3].index()
    )


@needs_native
def test_a_skipped_event_handed_to_insert_alone_is_refused_by_the_scalar_path(
        monkeypatch):
    from babble_tpu import native_crypto

    cores, _, _ = init_cores(2)
    _diff, wires = _chain_wires(cores, 3)
    _forge(wires, [1, 2, 3])
    decoded, _ = _sync_refused(cores[1], cores[0].validator.id(), wires)
    skipped = decoded[2]
    assert skipped.prevalidated() is None

    calls = []
    verify_one = native_crypto.verify_one

    def counted(*args):
        calls.append(args)
        return verify_one(*args)

    monkeypatch.setattr(native_crypto, "verify_one", counted)
    with pytest.raises(InvalidSignatureError) as refused:
        cores[1].hg.insert_event(skipped)
    assert refused.value.event is skipped
    assert len(calls) == 1  # one scalar verification, of its signature
    assert skipped.prevalidated() is None


# -- verification happens OUTSIDE the core lock ---------------------------


@needs_native
def test_signature_verification_outside_core_lock():
    """Contention contract: the eager-sync handler runs decode+batch
    verification before taking the core lock; only the insert sweep runs
    under it."""
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.net.rpc import EagerSyncRequest, RPC

    from tests.test_node import make_cluster, shutdown_all

    network = InmemNetwork()
    nodes, _, _ = make_cluster(2, network)
    try:
        a, b = nodes
        b.core.add_self_event("")
        diff = b.core.event_diff(a.core.known_events())
        wires = b.core.to_wire(diff)
        assert wires

        seen = {}
        orig_prev = host_batch.prevalidate_events_host

        def spy_prevalidate(events):
            seen["verify_locked"] = a.core_lock.locked()
            return orig_prev(events)

        orig_insert = a.core.insert_event_and_run_consensus

        def spy_insert(ev, set_wire_info=False):
            seen.setdefault("insert_locked", a.core_lock.locked())
            return orig_insert(ev, set_wire_info)

        host_batch.prevalidate_events_host = spy_prevalidate
        a.core.insert_event_and_run_consensus = spy_insert
        try:
            rpc = RPC(EagerSyncRequest(b.get_id(), wires))
            a._process_eager_sync_request(rpc, rpc.command)
            resp, err = rpc.wait(timeout=5.0)
        finally:
            host_batch.prevalidate_events_host = orig_prev
            a.core.insert_event_and_run_consensus = orig_insert

        assert err is None and resp.success
        assert seen["verify_locked"] is False, (
            "batch signature verification ran under the core lock"
        )
        assert seen["insert_locked"] is True, (
            "insert sweep must still be serialized by the core lock"
        )
    finally:
        shutdown_all(nodes)


def test_timed_lock_accounts_contention():
    lock = TimedLock()
    assert lock.acquire()
    assert lock.locked()
    waited = []

    def contender():
        t0 = time.perf_counter()
        with lock:
            waited.append(time.perf_counter() - t0)

    t = threading.Thread(target=contender)
    t.start()
    time.sleep(0.05)
    lock.release()
    t.join(timeout=5.0)
    assert waited and waited[0] >= 0.04
    assert lock.wait_s_total >= 0.04
    assert lock.acquisitions == 2
    assert not lock.locked()


# -- serialization memo invalidation --------------------------------------


def test_wire_cache_hits_and_invalidation_on_mutation():
    key = generate_key()
    ev = Event.new(
        [b"payload"], [], [], ["", ""], key.public_key.bytes(), 0,
        timestamp=7,
    )
    ev.sign(key)

    h0, m0 = WIRE_CACHE.hits, WIRE_CACHE.misses
    w1 = ev.to_wire()
    w2 = ev.to_wire()
    assert w2 is w1  # memo hit: same shared WireEvent per event
    assert WIRE_CACHE.misses == m0 + 1
    assert WIRE_CACHE.hits == h0 + 1

    # wire-info mutation invalidates
    ev.set_wire_info(3, 4, 5, 6)
    w3 = ev.to_wire()
    assert w3 is not w1
    assert w3.body.creator_id == 6

    # re-signing invalidates (wire form carries the signature)
    ev.sign(key)
    w4 = ev.to_wire()
    assert w4 is not w3


def test_hash_and_normalized_memo_invalidated_on_body_mutation():
    key = generate_key()
    ev = Event.new(
        [b"a"], [], [], ["", ""], key.public_key.bytes(), 0, timestamp=1
    )
    h1 = ev.hash()
    n1 = ev.body.normalized()
    assert ev.body.normalized() is n1  # memoized

    ev.body.transactions.append(b"b")
    ev.invalidate_hash()
    h2 = ev.hash()
    n2 = ev.body.normalized()
    assert h2 != h1
    assert n2 is not n1
    assert ev.hex() != ""


# -- commit-before-publish ordering ---------------------------------------


def test_commit_completes_before_block_is_published():
    """The commit callback mutates the block body (state_hash, receipts)
    and signs it; set_block is what makes the block observable (advances
    last_block_index). Publishing first let concurrent readers cache a
    half-committed body hash — which this node then SIGNED (the
    bootstrap-recycle reproducibility flake)."""
    from babble_tpu.crypto.canonical import canonical_dumps
    from babble_tpu.crypto.hashing import sha256

    from tests.test_core import CONSENSUS_PLAYBOOK, sync_and_run_consensus

    cores, _, _ = init_cores(3)
    core = cores[0]
    seen = []
    orig = core.hg.commit_callback

    def spy(block):
        # at commit time the block must NOT yet be visible in the store
        seen.append(core.hg.store.last_block_index() < block.index())
        return orig(block)

    core.hg.commit_callback = spy
    for from_i, to_i, payload in CONSENSUS_PLAYBOOK:
        sync_and_run_consensus(cores, from_i, to_i, [payload])

    assert seen, "playbook never reached a commit"
    assert all(seen), "a block was published before its commit completed"
    # and the published block's cached hash is coherent with its content
    blk = core.hg.store.get_block(core.hg.store.last_block_index())
    # the signed digest covers the HEADER form (transactions committed
    # via TxRoot/TxCount — docs/parity.md, ISSUE-12)
    assert blk.body.hash() == sha256(canonical_dumps(blk.body.header_dict()))


def test_block_body_hash_cache_survives_racing_invalidation():
    """Versioned-cache contract: a digest computed against a body that
    mutated mid-walk must not be resurrected as the current hash."""
    from babble_tpu.hashgraph.block import BlockBody

    body = BlockBody(index=1, round_received=2, transactions=[b"a"])
    h1 = body.hash()
    # simulate the lost-invalidation interleaving: a stale digest written
    # back AFTER a mutation bumped the version
    stale = (getattr(body, "_hash_version", 0), h1)
    body.state_hash = b"s" * 32
    object.__setattr__(body, "_hash_cache", stale)
    h2 = body.hash()
    assert h2 != h1  # recomputed, not resurrected
    from babble_tpu.crypto.canonical import canonical_dumps
    from babble_tpu.crypto.hashing import sha256

    # fresh recompute matches the signed HEADER form (docs/parity.md)
    assert h2 == sha256(canonical_dumps(body.header_dict()))
