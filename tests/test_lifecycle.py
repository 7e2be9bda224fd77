"""Lifecycle tier (docs/lifecycle.md): checkpoint-prune compaction and
elastic validator membership.

The load-bearing property: pruning is an OPTIMIZATION, never a consensus
input. Every sim scenario here runs a pruned arm against an un-pruned
shadow oracle (a separate same-seed run, or an un-pruned node inside the
same cluster) and asserts byte-identical commit digests while the
retained store footprint plateaus on the pruned side and grows
monotonically on the oracle. On top of that: the rotation state machine,
the autoscale policy, equivocation evidence surviving compaction (the
PR-5 evidence-table contract), the /checkpoint behind_retention slug,
and the `make prunesmoke` live cluster — prune mid-traffic, rotate a
validator out, rejoin it through fast-sync from a pruned peer.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from babble_tpu.config.config import Config
from babble_tpu.crypto.keys import generate_key
from babble_tpu.hashgraph.event import Event
from babble_tpu.hashgraph.persistent_store import PersistentStore
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.lifecycle import (
    AutoscalePolicy,
    BehindRetentionError,
    CheckpointPruner,
    RotationController,
)
from babble_tpu.lifecycle.rotation import (
    JOINING,
    LEAVING,
    MEMBER,
    OUT,
    SYNCING,
)
from babble_tpu.node.sentry import EquivocationProof
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.sim.harness import SimCluster
from babble_tpu.sim.scheduler import SimScheduler

pytestmark = pytest.mark.lifecycle


# -- rotation state machine / autoscale policy (pure units) ------------------


def test_rotation_state_machine_legal_path_and_counters():
    t = {"now": 0.0}
    rc = RotationController("v0", clock=lambda: t["now"])
    assert rc.state == MEMBER and rc.rotations == 0
    for state in (LEAVING, OUT, JOINING, SYNCING, MEMBER):
        t["now"] += 1.0
        rc.to(state)
    assert rc.state == MEMBER
    assert rc.rotations == 1
    # every hop stamped off the injected clock
    assert [s for s, _ in rc.transitions] == [
        LEAVING, OUT, JOINING, SYNCING, MEMBER,
    ]
    assert [ts for _, ts in rc.transitions] == [1.0, 2.0, 3.0, 4.0, 5.0]
    # join/fast-sync failure falls back to OUT and may retry
    rc.to(LEAVING)
    rc.to(OUT)
    rc.to(JOINING)
    rc.to(OUT)
    rc.to(JOINING)
    rc.to(SYNCING)
    rc.to(OUT)  # lost the race before BABBLING: back out, not stuck


def test_rotation_state_machine_rejects_illegal_hops():
    rc = RotationController()
    with pytest.raises(ValueError):
        rc.to(JOINING)  # MEMBER cannot join
    with pytest.raises(ValueError):
        rc.to(SYNCING)
    rc.to(LEAVING)
    with pytest.raises(ValueError):
        rc.to(MEMBER)  # no un-leaving
    with pytest.raises(ValueError):
        RotationController(initial="limbo")
    # a fresh joiner starts OUT and can go straight to JOINING
    rc2 = RotationController(initial=OUT)
    rc2.to(JOINING)


def test_autoscale_policy_hysteresis_cooldown_and_rails():
    p = AutoscalePolicy(grow_above=0.75, shrink_below=0.10,
                        min_validators=3, max_validators=5, cooldown_s=30.0)
    # dead band between the thresholds: hold
    assert p.decide(50, 100, 4, now=0.0) == AutoscalePolicy.HOLD
    # pressure above the grow bar
    assert p.decide(80, 100, 4, now=1.0) == AutoscalePolicy.GROW
    # cooldown gates the next decision even at full pressure
    assert p.decide(100, 100, 4, now=10.0) == AutoscalePolicy.HOLD
    assert p.decide(100, 100, 4, now=32.0) == AutoscalePolicy.GROW
    # max rail
    assert p.decide(100, 100, 5, now=70.0) == AutoscalePolicy.HOLD
    # shrink below the low bar, min rail stops it
    assert p.decide(2, 100, 5, now=110.0) == AutoscalePolicy.SHRINK
    assert p.decide(0, 100, 3, now=150.0) == AutoscalePolicy.HOLD
    # degenerate capacity reads as zero pressure, not a crash
    assert p.decide(7, 0, 4, now=200.0) in (
        AutoscalePolicy.SHRINK, AutoscalePolicy.HOLD
    )
    assert p.grows == 2 and p.shrinks >= 1
    with pytest.raises(ValueError):
        AutoscalePolicy(grow_above=0.2, shrink_below=0.5)


# -- sim: pruned arm vs un-pruned shadow oracle ------------------------------


def _run_sim_arm(seed: int, horizon_s: float, prune: bool, n_honest: int = 4,
                 tx_every_s: float = 0.05, n_txs: int = 200):
    sch = SimScheduler(seed=seed)
    extra = (
        {"prune_every_rounds": 4, "prune_keep_rounds": 2} if prune else {}
    )
    cl = SimCluster(sch, n_honest=n_honest, conf_extra=extra)
    cl.start()
    rng = sch.rng("txgen")
    t = 0.0
    for _ in range(n_txs):
        t += tx_every_s
        sch.at(t, lambda: cl.submit_auto(rng), "tx")
    sch.run_until(horizon_s)
    return cl


def test_prune_digests_byte_identical_to_unpruned_oracle():
    """The consensus acceptance bar: a pruned cluster and a same-seed
    un-pruned control commit byte-identical block sequences, while the
    pruned arm's retained event set stays a small fraction of the
    control's."""
    pruned = _run_sim_arm(seed=42, horizon_s=30.0, prune=True)
    oracle = _run_sim_arm(seed=42, horizon_s=30.0, prune=False)
    try:
        dp, du = pruned.commit_digests(), oracle.commit_digests()
        assert len(set(dp.values())) == 1, f"pruned arm forked: {dp}"
        assert dp == du, "pruning changed consensus output"
        stats_p = [n.get_stats() for n in pruned.nodes]
        stats_u = [n.get_stats() for n in oracle.nodes]
        assert all(int(s["lifecycle_prunes"]) > 0 for s in stats_p), (
            "no prune ever fired in the pruned arm"
        )
        for sp, su in zip(stats_p, stats_u):
            retained = int(sp["lifecycle_events_retained"])
            control = int(su["lifecycle_events_retained"])
            assert int(su["lifecycle_prunes"]) == 0
            assert retained < control / 4, (
                f"retained {retained} !<< control {control}"
            )
            # floor advanced and stays behind consensus
            assert int(sp["lifecycle_prune_floor"]) > 0
            assert int(sp["lifecycle_prune_lag_rounds"]) >= 0
    finally:
        pruned.shutdown()
        oracle.shutdown()


def test_prune_sim_deterministic_same_seed():
    """Pruning must not break sim determinism: two same-seed pruned runs
    are byte-identical, including the prune counters themselves."""
    a = _run_sim_arm(seed=7, horizon_s=20.0, prune=True)
    b = _run_sim_arm(seed=7, horizon_s=20.0, prune=True)
    try:
        assert a.commit_digests() == b.commit_digests()
        for na, nb in zip(a.nodes, b.nodes):
            sa, sb = na.get_stats(), nb.get_stats()
            for k in ("lifecycle_prunes", "lifecycle_prune_floor",
                      "lifecycle_pruned_events",
                      "lifecycle_events_retained"):
                assert sa[k] == sb[k], (k, sa[k], sb[k])
    finally:
        a.shutdown()
        b.shutdown()


def test_rotation_rejoin_from_pruned_checkpoint_sim():
    """A validator crash-rotates out; the survivors keep pruning; it
    rejoins via RotationController fast-sync from a PRUNED peer's sealed
    checkpoint and commits new blocks that byte-match the cluster."""
    sch = SimScheduler(seed=11)
    cl = SimCluster(
        sch, n_honest=4,
        conf_extra={"prune_every_rounds": 4, "prune_keep_rounds": 2},
    )
    cl.start()
    rng = sch.rng("txgen")
    t = 0.0
    for _ in range(400):
        t += 0.05
        sch.at(t, lambda: cl.submit_auto(rng), "tx")
    try:
        sch.run_until(8.0)
        victim = 3
        cl.set_node_down(victim)
        rc = RotationController(
            "node3", clock=sch.clock.monotonic, initial=OUT
        )
        # survivors keep committing AND pruning while node3 is out
        sch.run_until(24.0)
        donor = cl.nodes[0]
        assert donor.pruner is not None and donor.pruner.prunes > 0
        floor = donor.core.hg.prune_floor
        assert floor is not None and floor > 0
        # the donor has already compacted; ?snapshot=1 shape so the
        # rejoiner can restore its app state too (without it the app
        # state-hash chain forks and peers refuse to countersign)
        cp = donor.get_checkpoint(with_snapshot=True)
        cp = json.loads(json.dumps(cp))  # HTTP round-trip shape
        assert "snapshot" in cp
        anchor_index = int(cp["block"]["Body"]["Index"])

        node3 = cl.nodes[victim]
        behind_by = (
            donor.get_last_block_index() - node3.get_last_block_index()
        )
        assert behind_by > 0, "victim never fell behind"
        rc.rejoin_from_checkpoint(node3.core, cp, proxy=cl.proxies[victim])
        assert rc.state == SYNCING
        assert node3.get_last_block_index() >= anchor_index
        cl.set_node_up(victim)
        mark = node3.get_last_block_index()
        sch.run_until(40.0)
        assert node3.get_last_block_index() > mark, (
            "rejoined validator never committed"
        )
        rc.on_babbling()
        assert rc.state == MEMBER and rc.rotations == 1
        # no fork: every block the rejoined node holds post-anchor is
        # byte-identical to the donor's
        for bi in range(anchor_index,
                        min(node3.get_last_block_index(),
                            donor.get_last_block_index()) + 1):
            assert (
                node3.get_block(bi).body.hash()
                == donor.get_block(bi).body.hash()
            ), f"fork at block {bi}"
    finally:
        cl.shutdown()


# -- long-horizon plateau (the acceptance sim) -------------------------------


@pytest.mark.slow
def test_long_horizon_plateau_10k_rounds(tmp_path):
    """≥10k rounds of virtual time in ONE cluster: two pruning
    validators (one on SQLite so byte accounting is real) against an
    un-pruned in-cluster shadow oracle. The pruned stores' retained
    event counts and the SQLite byte size plateau; the oracle grows
    monotonically; commit digests stay identical across all three."""
    sch = SimScheduler(seed=1337)

    def store_factory(i):
        if i == 0:
            return PersistentStore(
                cache_size=20000, path=str(tmp_path / "n0.db")
            )
        return InmemStore(20000)

    cl = SimCluster(
        sch, n_honest=3, heartbeat_s=0.05, store_factory=store_factory
    )
    # pruning on nodes 0 and 1 only — node 2 is the in-cluster oracle
    for i in (0, 1):
        cl.nodes[i].pruner = CheckpointPruner(
            every_rounds=20, keep_rounds=2
        )
    cl.start()
    rng = sch.rng("txgen")

    samples = []  # (virtual_t, round, retained0, bytes0, retained_oracle)

    def sample_and_reschedule():
        s0 = cl.nodes[0].get_stats()
        s2 = cl.nodes[2].get_stats()
        samples.append((
            sch.now,
            int(s0["last_consensus_round"]),
            int(s0["lifecycle_events_retained"]),
            int(s0["lifecycle_store_bytes"]),
            int(s2["lifecycle_events_retained"]),
        ))
        sch.after(25.0, sample_and_reschedule, "sample")

    def pump_and_reschedule():
        # sustained load: rounds only advance at full rate while gossip
        # carries payloads, so an idle cluster would crawl (~0.1
        # rounds/s) and never reach 10k inside the ceiling
        cl.submit_auto(rng)
        sch.after(0.2, pump_and_reschedule, "txpump")

    sch.after(25.0, sample_and_reschedule, "sample")
    sch.after(0.1, pump_and_reschedule, "txpump")
    try:
        # several rounds/virtual-second under sustained load: run until
        # the consensus round passes 10k (bounded by a virtual-time
        # ceiling so a regression fails instead of spinning forever)
        horizon = 0.0
        while True:
            horizon += 500.0
            assert horizon <= 4000.0, (
                f"virtual-time ceiling before 10k rounds: {samples[-3:]}"
            )
            sch.run_until(horizon)
            lcr = cl.nodes[0].core.get_last_consensus_round_index() or 0
            if lcr >= 10_000:
                break

        # digest equality over the COMMON PREFIX: under a sustained tx
        # pump the nodes' committed tips legitimately lag each other by
        # a block or two at any instant — tip lag is pipelining, a fork
        # is a body-hash mismatch at the same index
        tip = min(n.get_last_block_index() for n in cl.nodes)
        assert tip > 1000, f"common tip only {tip} after 10k rounds"
        for bi in range(tip + 1):
            hashes = {n.get_block(bi).body.hash() for n in cl.nodes}
            assert len(hashes) == 1, f"forked at block {bi}: {hashes}"
        assert cl.nodes[0].pruner.prunes > 10
        assert cl.nodes[2].pruner is None

        # plateau: the pruned node's retained set and byte size are a
        # bounded SAWTOOTH (fill for every_rounds committed rounds, then
        # compact) — flatness means the envelope stops growing, so the
        # second half's peak must not exceed 2x the first half's peak,
        # while the oracle's retained set grows monotonically and ends
        # far above the pruned ceiling.
        half = len(samples) // 2
        late = samples[half:]
        retained0 = [s[2] for s in late]
        bytes0 = [s[3] for s in late]
        oracle = [s[4] for s in samples]
        early_peak_ev = max(s[2] for s in samples[:half])
        early_peak_b = max(s[3] for s in samples[:half])
        assert max(retained0) <= 2 * max(1, early_peak_ev), (
            f"pruned retained envelope grew: first-half peak "
            f"{early_peak_ev}, second-half peak {max(retained0)}"
        )
        assert max(bytes0) <= 2 * max(1, early_peak_b), (
            f"pruned byte envelope grew: first-half peak "
            f"{early_peak_b}, second-half peak {max(bytes0)}"
        )
        assert all(b >= a for a, b in zip(oracle, oracle[1:])), (
            "oracle retained set must grow monotonically"
        )
        assert oracle[-1] > 10 * max(retained0), (
            f"oracle {oracle[-1]} !>> pruned {max(retained0)}"
        )
    finally:
        cl.shutdown()


# -- evidence survives compaction (PR-5 evidence-table contract) -------------


def test_sentry_evidence_and_quarantine_survive_prune():
    """Equivocation proofs and quarantine state must outlive compaction:
    pruning drops events/rounds/frames, NEVER the evidence table — a
    rotation or prune must not amnesty a forker."""
    sch = SimScheduler(seed=23)
    cl = SimCluster(
        sch, n_honest=4,
        conf_extra={"prune_every_rounds": 3, "prune_keep_rounds": 1},
    )
    cl.start()
    rng = sch.rng("txgen")
    t = 0.0
    for _ in range(150):
        t += 0.05
        sch.at(t, lambda: cl.submit_auto(rng), "tx")
    try:
        sch.run_until(5.0)
        node = cl.nodes[0]
        # plant a REAL verified proof + quarantine before any more prunes
        key = generate_key()
        a = Event.new([b"a"], [], [], ["", ""], key.public_key.bytes(), 0)
        b = Event.new([b"b"], [], [], ["", ""], key.public_key.bytes(), 0)
        a.sign(key)
        b.sign(key)
        proof = EquivocationProof.from_events(a, b, observed_at=sch.now)
        with node.core_lock:
            assert node.core.sentry.add_proof(proof)
        prunes_before = node.pruner.prunes
        sch.run_until(25.0)
        assert node.pruner.prunes > prunes_before, "no prune after proof"
        # the proof survived every compaction, in the sentry AND the store
        surviving = node.core.sentry.proofs()
        assert any(p.key() == proof.key() for p in surviving)
        assert all(p.verify() for p in surviving)
        stored = node.core.hg.store.all_evidence()
        assert any(
            EquivocationProof.from_dict(d).key() == proof.key()
            for d in stored.values()
        )
    finally:
        cl.shutdown()


# -- /checkpoint retention semantics -----------------------------------------


def test_behind_retention_error_and_http_slug():
    """A /checkpoint request below the prune floor gets the distinct
    behind_retention slug (HTTP 410), NOT a generic 404; requests at or
    above the floor serve the earliest sealed anchor; no-round requests
    serve the latest (pruned) anchor."""
    from babble_tpu.service.service import Service

    sch = SimScheduler(seed=5)
    cl = SimCluster(
        sch, n_honest=4,
        conf_extra={"prune_every_rounds": 3, "prune_keep_rounds": 1},
    )
    cl.start()
    rng = sch.rng("txgen")
    t = 0.0
    for _ in range(200):
        t += 0.05
        sch.at(t, lambda: cl.submit_auto(rng), "tx")
    srv = None
    try:
        sch.run_until(25.0)
        node = cl.nodes[0]
        floor = node.core.hg.prune_floor
        assert floor is not None and floor > 1
        # node level: typed error with the floor attached
        with pytest.raises(BehindRetentionError) as ei:
            node.get_checkpoint(at_round=floor - 1)
        assert ei.value.requested == floor - 1
        assert ei.value.floor == floor
        # at/above the floor still serves (the anchor frame survived)
        cp = node.get_checkpoint()
        assert int(cp["block"]["Body"]["RoundReceived"]) >= floor
        before = node.behind_retention_rejections

        # HTTP level: the regression surface clients actually see
        srv = Service("127.0.0.1:0", node, logger=None)
        srv.serve_async()
        base = f"http://{srv.bind_addr}"
        with urllib.request.urlopen(f"{base}/checkpoint", timeout=10) as r:
            assert r.status == 200
            assert json.loads(r.read())["block"] == json.loads(
                json.dumps(cp["block"])
            )
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(
                f"{base}/checkpoint?round={floor - 1}", timeout=10
            )
        assert he.value.code == 410
        body = json.loads(he.value.read())
        assert body["error"] == "behind_retention"
        assert body["floor"] == floor
        assert body["requested"] == floor - 1
        assert node.behind_retention_rejections == before + 1
        # a round past the tip is a plain 404 (no sealed block), not 410
        with pytest.raises(urllib.error.HTTPError) as he2:
            urllib.request.urlopen(
                f"{base}/checkpoint?round=999999", timeout=10
            )
        assert he2.value.code == 404
    finally:
        if srv is not None:
            srv.shutdown()
        cl.shutdown()


# -- persistent store compaction mechanics -----------------------------------


def test_persistent_store_prune_shrinks_and_vacuums(tmp_path):
    """SQLite-level contract: prune_below deletes rows, size_stats sees
    it, incremental vacuum hands freed pages back (auto_vacuum is set at
    schema time so freed pages are actually reclaimable)."""
    db = str(tmp_path / "prune.db")
    store = PersistentStore(cache_size=1000, path=db)
    key = generate_key()
    store.set_peer_set(
        0, PeerSet([Peer("inmem://solo", key.public_key.hex(), "solo")])
    )
    events = []
    prev = ""
    for i in range(40):
        e = Event.new(
            [f"tx {i}".encode() * 50], [], [], [prev, ""],
            key.public_key.bytes(), i,
        )
        e.sign(key)
        store.set_event(e)
        events.append(e)
        prev = e.hex()
    before = store.size_stats()
    assert before["events"] == 40 and before["store_bytes"] > 0

    drop = {e.hex() for e in events[:30]}
    creator = events[0].creator()
    store.prune_below(
        floor_round=10, drop_events=drop, drop_rounds=set(),
        participant_floors={creator: 30},
    )
    store.vacuum(incremental=True)
    after = store.size_stats()
    assert after["events"] == 10
    # retained events still load, annotated fields intact
    for e in events[30:]:
        loaded = store.get_event(e.hex())
        assert loaded.hex() == e.hex()
    # dropped events are gone from cache AND disk
    store2_probe = events[0].hex()
    with pytest.raises(Exception):
        store.get_event(store2_probe)
    store.close()

    # a reopened store agrees (the DELETEs were durable)
    store2 = PersistentStore(cache_size=1000, path=db)
    assert store2.size_stats()["events"] == 10
    store2.close()


def test_persistent_event_annotations_roundtrip(tmp_path):
    """Round/lamport/round-received annotations persist with the event
    and reload — EXCEPT through bootstrap replay, which must recompute
    consensus from zero (topological_events strips them)."""
    db = str(tmp_path / "ann.db")
    store = PersistentStore(cache_size=100, path=db)
    key = generate_key()
    store.set_peer_set(
        0, PeerSet([Peer("inmem://solo", key.public_key.hex(), "solo")])
    )
    e = Event.new([b"x"], [], [], ["", ""], key.public_key.bytes(), 0)
    e.sign(key)
    e.set_round(7)
    e.set_lamport_timestamp(3)
    e.set_round_received(9)
    store.set_event(e)
    # evict the cache by reopening
    store.close()
    store2 = PersistentStore(cache_size=100, path=db)
    loaded = store2.get_event(e.hex())
    assert loaded.round == 7
    assert loaded.lamport_timestamp == 3
    assert loaded.round_received == 9
    stripped = list(store2.topological_events(0, 10))
    assert stripped[0].hex() == e.hex()
    assert stripped[0].round is None  # bootstrap recomputes
    assert stripped[0].round_received is None
    store2.close()


# -- make prunesmoke: live cluster, prune mid-traffic, rotate + rejoin -------


class _Bombardier:
    """Continuous background load (test_node_dyn idiom, local copy so
    the lifecycle suite stays importable standalone)."""

    def __init__(self, proxies, interval: float = 0.005):
        self.proxies = proxies
        self.interval = interval
        self._stop = threading.Event()
        self._t = None
        self._i = 0

    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()
        return self

    def _run(self):
        while not self._stop.is_set():
            self.proxies[self._i % len(self.proxies)].submit_tx(
                f"lifecycle tx {self._i}".encode()
            )
            self._i += 1
            time.sleep(self.interval)

    def stop(self):
        self._stop.set()
        if self._t:
            self._t.join(timeout=2.0)


def _wait(pred, deadline_s=90.0, msg="condition"):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    pytest.fail(f"timeout waiting for {msg}")


def test_prunesmoke_live_cluster_prune_rotate_rejoin():
    """`make prunesmoke`: a live 4-validator cluster under continuous
    load. Every validator prunes mid-traffic; one rotates out (polite
    PEER_REMOVE through consensus), then rejoins as a fresh validator
    whose catch-up fast-syncs from peers that have ALL pruned; liveness
    and byte-identical blocks are asserted across the membership
    change."""
    from babble_tpu.dummy.state import State as DummyState
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.node import Node
    from babble_tpu.node.state import State
    from babble_tpu.node.validator import Validator
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet
    from babble_tpu.proxy.proxy import InmemProxy

    network = InmemNetwork()
    n = 4
    keys = [generate_key() for _ in range(n)]
    peers = PeerSet([
        Peer(f"inmem://v{i}", k.public_key.hex(), f"v{i}")
        for i, k in enumerate(keys)
    ])
    nodes, proxies = [], []
    for i, k in enumerate(keys):
        conf = Config(
            heartbeat_timeout=0.02, slow_heartbeat_timeout=0.2,
            moniker=f"v{i}", log_level="error",
            enable_fast_sync=True, join_timeout=30.0,
            prune_every_rounds=3, prune_keep_rounds=1,
        )
        st = DummyState()
        pr = InmemProxy(st)
        node = Node(conf, Validator(k, f"v{i}"), peers, peers,
                    InmemStore(conf.cache_size),
                    network.new_transport(f"inmem://v{i}"), pr)
        node.init()
        nodes.append(node)
        proxies.append(pr)

    bomb = _Bombardier(proxies[:3]).start()
    joiner = None
    try:
        for nd in nodes:
            nd.run_async()
        # prune fires on every validator WHILE traffic flows
        _wait(
            lambda: all(
                nd.pruner is not None and nd.pruner.prunes > 0
                for nd in nodes
            ),
            msg="every validator pruned mid-traffic",
        )
        assert all(
            nd.core.hg.prune_floor is not None for nd in nodes
        )

        # rotate validator 3 out: polite leave through consensus
        rc = RotationController("v3")
        rc.rotate_out(nodes[3])
        assert rc.state == OUT
        survivors = nodes[:3]
        _wait(
            lambda: all(
                len(nd.core.peers.peers) == n - 1 for nd in survivors
            ),
            msg="PEER_REMOVE committed on the survivors",
        )

        # rejoin as a fresh validator: new key, empty store — its join
        # leg must fast-sync from peers that have all pruned their
        # history below the floor
        jkey = generate_key()
        jconf = Config(
            heartbeat_timeout=0.02, slow_heartbeat_timeout=0.2,
            moniker="v3b", log_level="error",
            enable_fast_sync=True, join_timeout=60.0,
        )
        jst = DummyState()
        jpr = InmemProxy(jst)
        joiner = Node(
            jconf, Validator(jkey, "v3b"),
            PeerSet(list(survivors[0].core.peers.peers)),
            survivors[0].core.genesis_peers,
            InmemStore(jconf.cache_size),
            network.new_transport("inmem://v3b"), jpr,
        )
        joiner.init()
        rc.to(JOINING)
        joiner.run_async()
        _wait(
            lambda: joiner.get_state() == State.BABBLING,
            msg="rotated validator back to BABBLING via pruned peers",
        )
        rc.to(SYNCING)
        rc.on_babbling()
        assert rc.rotations == 1

        # liveness: the new membership keeps committing, joiner included
        mark = min(nd.get_last_block_index() for nd in survivors)
        _wait(
            lambda: min(nd.get_last_block_index() for nd in survivors)
            > mark + 2,
            msg="cluster liveness after rotation",
        )
        jmark = joiner.get_last_block_index()
        _wait(
            lambda: joiner.get_last_block_index() > max(jmark, 0),
            msg="joiner commits",
        )

        # no fork: every block the joiner holds is byte-identical to the
        # survivors' copy (its store starts at its fast-sync anchor)
        top = min(
            [joiner.get_last_block_index()]
            + [nd.get_last_block_index() for nd in survivors]
        )
        lo = None
        for bi in range(top + 1):
            try:
                jb = joiner.get_block(bi)
            except Exception:
                continue  # below the joiner's anchor
            lo = bi if lo is None else lo
            for nd in survivors:
                assert (
                    jb.body.hash() == nd.get_block(bi).body.hash()
                ), f"fork at block {bi}"
        assert lo is not None, "joiner holds no comparable blocks"
    finally:
        bomb.stop()
        if joiner is not None:
            joiner.shutdown()
        for nd in nodes:
            nd.shutdown()
