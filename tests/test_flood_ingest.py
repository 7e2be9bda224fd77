"""A rejoining validator catches up while a flooder pushes syncs of forged
events at it (deployment ``badsig16``) at a small size on the CPU: 4
validators, v3 the flooder (f = 1), 600 events in syncs of 100, after each
sync two pushes of 100 forged events (``benchmark/harness/flood.py``).

A ``Node`` taken through ``init()`` and not started; honest syncs go through
``Core.prepare_sync`` / ``Core.sync`` as the pull leg calls them, the pushes
through ``Node._process_rpc``, the node's own handler — on the host path
with the pipeline ``Node`` builds (the insert tail on its inserter thread),
on the lane a chip resolves, and inline with the pipeline stopped.
"""

from __future__ import annotations

import threading

import pytest

from babble_tpu.config.config import Config
from babble_tpu.dummy.state import State as DummyState
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.net.inmem import InmemNetwork
from babble_tpu.net.rpc import RPC, EagerSyncRequest
from babble_tpu.node.node import Node
from babble_tpu.node.validator import Validator
from babble_tpu.proxy.proxy import InmemProxy
from benchmark.harness import data, flood, reference

N, ME, FLOODER, EVENTS, SYNC, PUSHED = 4, 0, 3, 600, 100, 100
SEED, DAG_SEED = 3000000019, 2147487920
MODES = ("host", "chip-lane", "inline")


def _node(keys, peers, mode):
    conf = Config(bind_addr="inmem://v0", moniker="v0", log_level="error",
                  no_service=True, accelerator=mode == "chip-lane")
    node = Node(conf, Validator(keys[ME], "v0"), peers, peers,
                InmemStore(conf.cache_size),
                InmemNetwork().new_transport("inmem://v0"),
                InmemProxy(DummyState()))
    tc = node.core.hg.accel
    if tc is not None:
        tc.min_window, tc.async_compile = 16, False
        tc.pipeline, tc.batcher = True, True
    node.init()
    if mode == "inline":
        node.pipeline.stop()
    return node


def _stage_count(node, stage):
    try:
        return node.telemetry.registry.get("sync_stage_seconds", stage=stage)
    except KeyError:  # BABBLE_OBS=0 registers no span histogram
        return 0


class _Flooded:
    """One validator's catch-up under the flood, and what the tests read."""

    def __init__(self, mode):
        keys = data.seeded_keys(N, SEED)
        peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(N)])
        ids = [peers.by_pub_key[k.public_key.hex()].id for k in keys]
        self.ids, self.flooder = ids, ids[FLOODER]
        creators = [i for i in range(N) if i != ME]
        wires = data.backlog_wire_events(keys, peers, creators, EVENTS,
                                         DAG_SEED, 100)
        pushes = flood.Flood(wires, [self.flooder], SYNC, 2, PUSHED, 100, SEED)
        node = _node(keys, peers, mode)
        core, sentry = node.core, node.core.sentry
        self.sync_stacks = []  # (thread, spans open) at each Core.sync
        sync, tracer = core.sync, core.stage_observer

        def watched(*args, **kwargs):
            stack = ([s.name for s in tracer._thread().stack]
                     if tracer is not None else [])
            self.sync_stacks.append((threading.current_thread().name, stack))
            return sync(*args, **kwargs)

        core.sync = watched
        self.pushes = []
        try:
            for k, chunk in enumerate(data.chunks(wires, SYNC)):
                prepared = core.prepare_sync(chunk)
                with node.core_lock:
                    core.sync(ids[creators[0]], chunk, prepared)
                    core.process_sig_pool()
                for fid, events in pushes.after[k]:
                    decodes = _stage_count(node, "decode")
                    verifies = core.ingest_batch_verifies
                    rpc = RPC(EagerSyncRequest(fid, events))
                    node._process_rpc(rpc)
                    _resp, err = rpc.wait(timeout=60)
                    self.pushes.append({
                        "quarantined": sentry.is_quarantined(fid),
                        "decodes": _stage_count(node, "decode") - decodes,
                        "verifies": core.ingest_batch_verifies - verifies,
                        "error": err or ""})
            with node.core_lock:
                core.hg.drain_consensus()
            self.honest = set(flood.honest_hashes(wires, peers).values())
            own = keys[ME].public_key.bytes()
            self.stored = {ev.hex() for ev in
                           reference.stored_events(core.hg.store)
                           if ev.body.creator != own}
            self.audit = reference.audit_against_oracle(core.hg, peers)
            self.ordered = core.get_consensus_events_count()
            self.scored = {int(p) for p in sentry.suspects()["peers"]}
            self.invalid_signature = sentry.rejects.get("invalid_signature", 0)
            self.deferrals = sentry.quarantine_deferrals
            self.refused = sentry.refused_rpcs
            self.singles = core.ingest_fallback_singles
            self.skipped = core.ingest_fallback_skipped
            self.spans = {s: _stage_count(node, s) for s in (
                "eager_sync_in", "verify_fallback", "batch_verify")}
            self.observed = tracer is not None
        finally:
            node.shutdown()


@pytest.fixture(scope="module", params=MODES)
def flooded(request):
    return _Flooded(request.param)


def test_no_forged_event_is_stored(flooded):
    assert flooded.stored == flooded.honest
    assert len(flooded.honest) == EVENTS


def test_the_flooder_is_quarantined_at_its_fifth_push(flooded):
    quarantined = [p["quarantined"] for p in flooded.pushes]
    assert quarantined == [False] * 4 + [True] * 8
    assert [p["verifies"] for p in flooded.pushes] == [1] * 5 + [0] * 7
    assert flooded.invalid_signature == 5 and flooded.deferrals == 0
    assert all("invalid event signature" in p["error"]
               for p in flooded.pushes[:5])


def test_its_sixth_push_is_refused_before_any_decode(flooded):
    for p in flooded.pushes[5:]:
        assert p["decodes"] == 0 and p["verifies"] == 0
        assert p["error"] == f"peer {flooded.flooder} is quarantined"
    assert flooded.refused == 7


def test_no_honest_peer_has_a_sentry_record(flooded):
    assert flooded.scored == {flooded.flooder}


def test_the_blocks_equal_the_host_oracles(flooded):
    audit = flooded.audit
    assert audit.ok and audit.blocks > 0, audit.note
    assert flooded.ordered == audit.ordered


def test_the_two_spans_open_once_a_landed_push(flooded):
    # each landed push: one batch whose every event the batch call flagged,
    # its first re-checked alone and confirmed bad, the rest left unchecked;
    # honest batches open no verify_fallback
    assert flooded.spans["eager_sync_in"] == 5
    assert flooded.spans["verify_fallback"] == 5
    assert flooded.spans["batch_verify"] == EVENTS // SYNC + 5
    assert flooded.singles == 5
    assert flooded.skipped == 5 * (PUSHED - 1)


def test_the_insert_tail_runs_where_the_docs_say(flooded, request):
    """Pipelined, ``eager_sync_in`` holds stage 1 and the forged sync's
    ``Core.sync`` is a root on the inserter thread; inline it is inside
    ``eager_sync_in`` on the handler's thread."""
    here = threading.current_thread().name
    where = [(thread == here, tuple(stack))
             for thread, stack in flooded.sync_stacks]
    honest = EVENTS // SYNC  # the pull leg's syncs, under no span
    if request.node.callspec.params["flooded"] == "inline":
        assert where.count((True, ())) == honest
        assert where.count((True, ("eager_sync_in",))) == 5
    else:
        assert where.count((True, ())) == honest
        assert where.count((False, ())) == 5
        assert {t for t, _s in flooded.sync_stacks if t != here} == {
            "sync-inserter"}
    assert len(where) == honest + 5


def test_under_the_kill_switch_neither_span_opens(monkeypatch):
    import babble_tpu.obs.metrics as metrics_mod

    monkeypatch.setattr(metrics_mod, "_ENABLED", False)
    off = _Flooded("host")
    assert not off.observed
    assert off.spans == {"eager_sync_in": 0, "verify_fallback": 0,
                         "batch_verify": 0}
    # the defence does not depend on the spans
    assert off.stored == off.honest and off.scored == {off.flooder}
    assert off.invalid_signature == 5 and off.refused == 7
