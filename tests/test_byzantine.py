"""Honest-vs-Byzantine soaks: a live cluster with an active adversary
(babble_tpu.adversary) that *lies* — forks its chain, floods forged
signatures, ignores the negotiated sync_limit — while the honest side's
defenses (typed rejection classification → sentry scoring → time-boxed
quarantine + durable equivocation proofs, docs/robustness.md §Byzantine
fault model) must keep the cluster safe and live.

The short soaks carry the ``byz`` marker and run in tier-1 /
``make byzsmoke``; the f=⌊(N−1)/3⌋ storm (two simultaneous adversaries
under chaos) stays ``-m slow``. Seeded via BABBLE_CHAOS_SEED like the
chaos suite.
"""

from __future__ import annotations

import time
from typing import List, Optional

import pytest

from babble_tpu.adversary import ByzantineNode
from babble_tpu.config.config import Config
from babble_tpu.crypto.keys import generate_key
from babble_tpu.dummy.state import State as DummyState
from babble_tpu.hashgraph.persistent_store import PersistentStore
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.net.chaos import ChaosController, ChaosTransport, LinkFaults, seed_from_env
from babble_tpu.net.inmem import InmemNetwork
from babble_tpu.node.node import Node
from babble_tpu.node.sentry import EquivocationProof
from babble_tpu.node.validator import Validator
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.proxy.proxy import InmemProxy


def make_mixed_cluster(
    n_honest: int,
    attack: str,
    n_byz: int = 1,
    tmp_path=None,
    chaos_drop: float = 0.0,
    sync_limit: Optional[int] = None,
    heartbeat: float = 0.02,
    byz_kwargs: Optional[dict] = None,
    attacks: Optional[List[str]] = None,
):
    """n_honest honest Nodes + n_byz ByzantineNodes sharing one peer set
    over an in-mem network. Honest node 0 rides a PersistentStore when
    ``tmp_path`` is given (for restart assertions); adversary transports
    are wrapped in a seeded ChaosTransport when ``chaos_drop`` > 0."""
    network = InmemNetwork()
    n = n_honest + n_byz
    keys = [generate_key() for _ in range(n)]
    peers = PeerSet(
        [
            Peer(f"inmem://node{i}", k.public_key.hex(), f"node{i}")
            for i, k in enumerate(keys)
        ]
    )
    addr_of = {p.pub_key_hex: p.net_addr for p in peers.peers}

    def conf(i: int, **kw) -> Config:
        c = Config(
            heartbeat_timeout=heartbeat,
            slow_heartbeat_timeout=0.2,
            moniker=f"node{i}",
            log_level="warning",
            # long enough that soak assertions never race the expiry
            sentry_quarantine_s=120.0,
            **kw,
        )
        if sync_limit is not None:
            c.sync_limit = sync_limit
        return c

    nodes: List[Node] = []
    proxies: List[InmemProxy] = []
    for i in range(n_honest):
        store = (
            PersistentStore(10000, str(tmp_path / "node0.db"))
            if (i == 0 and tmp_path is not None)
            else InmemStore(10000)
        )
        proxy = InmemProxy(DummyState())
        node = Node(
            conf(i),
            Validator(keys[i], f"node{i}"),
            peers,
            peers,
            store,
            network.new_transport(addr_of[keys[i].public_key.hex()]),
            proxy,
        )
        node.init()
        nodes.append(node)
        proxies.append(proxy)

    ctl = None
    if chaos_drop > 0.0:
        ctl = ChaosController(
            seed=seed_from_env(),
            default_faults=LinkFaults(drop=chaos_drop),
            drop_hold_s=0.02,
        )
    byzs: List[ByzantineNode] = []
    for j in range(n_byz):
        i = n_honest + j
        trans = network.new_transport(addr_of[keys[i].public_key.hex()])
        if ctl is not None:
            trans = ChaosTransport(trans, ctl)
        byzs.append(
            ByzantineNode(
                conf(i),
                Validator(keys[i], f"node{i}"),
                peers,
                peers,
                InmemStore(10000),
                trans,
                attack=attacks[j] if attacks else attack,
                seed=seed_from_env() + j,
                **(byz_kwargs or {}),
            )
        )
    return network, peers, keys, nodes, proxies, byzs


def _drive(nodes, proxies, seconds: float, predicate=None, tag="byz tx"):
    """Submit traffic for up to ``seconds``; returns early (True) once
    ``predicate()`` holds."""
    deadline = time.monotonic() + seconds
    i = 0
    while time.monotonic() < deadline:
        proxies[i % len(proxies)].submit_tx(f"{tag} {i}".encode())
        i += 1
        if predicate is not None and predicate():
            return True
        time.sleep(0.01)
    return predicate() if predicate is not None else True


def _bombard_until(nodes, proxies, target_block: int, timeout: float):
    ok = _drive(
        nodes,
        proxies,
        timeout,
        predicate=lambda: all(
            n.get_last_block_index() >= target_block for n in nodes
        ),
    )
    if not ok:
        indexes = [n.get_last_block_index() for n in nodes]
        pytest.fail(f"liveness timeout: block indexes {indexes} < {target_block}")


def _check_no_fork(nodes):
    """Every block ALL honest nodes hold must be byte-identical."""
    common = min(n.get_last_block_index() for n in nodes)
    for bi in range(common + 1):
        ref = nodes[0].get_block(bi).body.hash()
        for n in nodes[1:]:
            assert n.get_block(bi).body.hash() == ref, (
                f"FORK: block {bi} differs on node {n.get_id()}"
            )
    return common


def _shutdown(nodes, byzs):
    for b in byzs:
        b.stop()
    for n in nodes:
        n.shutdown()


# -- the capstone soak ----------------------------------------------------


def _equivocation_soak_attempt(tmp_path):
    """One full equivocation-soak attempt (see the test below for the
    acceptance contract): 4 honest + 1 equivocating node under 10%
    chaos drop on the adversary's links. Honest nodes commit identical
    chains past the attack window; the adversary lands in quarantine with
    a verifiable equivocation proof on honest nodes; the proof survives a
    restart of the persistent node with --store --bootstrap; queues stay
    bounded."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    network, peers, keys, nodes, proxies, byzs = make_mixed_cluster(
        4, "equivocate", tmp_path=tmp_path, chaos_drop=0.10,
        byz_kwargs={"fork_height": 1, "interval": 0.03},
    )
    byz = byzs[0]
    byz_id = byz.core.validator.id()
    try:
        for n in nodes:
            n.run_async()
        byz.run_async()

        def attacked_and_caught():
            # the persistent node AND at least one other honest node must
            # hold the proof and have the adversary quarantined
            caught = [
                n
                for n in nodes
                if n.core.sentry.is_quarantined(byz_id)
                and len(n.core.sentry.proofs()) > 0
            ]
            return nodes[0] in caught and len(caught) >= 2

        assert _drive(nodes, proxies, 60.0, predicate=attacked_and_caught), (
            f"adversary never caught: forks_minted={byz.forks_minted} "
            f"stats={[n.core.sentry.stats() for n in nodes]}"
        )
        assert byz.forks_minted >= 1
        byz.stop()

        # liveness past the attack window: NEW blocks commit without the
        # (quarantined) adversary, and chains stay identical
        base = max(n.get_last_block_index() for n in nodes)
        _bombard_until(nodes, proxies, base + 2, timeout=90.0)
        common = _check_no_fork(nodes)
        assert common >= base + 2

        # /suspects payload: adversary quarantined, proof verifiable
        body = nodes[0].get_suspects()
        entry = body["peers"][str(byz_id)]
        assert entry["quarantined"] is True
        assert entry["causes"].get("fork", 0) >= 1
        assert entry["moniker"] == "node4"
        assert len(body["proofs"]) >= 1
        assert EquivocationProof.from_dict(body["proofs"][0]).verify()

        # the selector of a catching node skips the adversary
        assert any(
            n.core.peer_selector.stats()["selector_quarantine_skips"] > 0
            for n in nodes
        )

        # telemetry saw the attack (ISSUE-6: soaks assert on telemetry,
        # not only end state): DURING the quarantine window the
        # registry's sentry gauges/counters on a catching node show the
        # quarantine and the fork evidence, and the Prometheus rendering
        # of the same registry carries the fork-cause reject counter —
        # the same facts through /metrics that get_stats reports.
        caught = [n for n in nodes if n.core.sentry.is_quarantined(byz_id)]
        assert caught
        for n in caught:
            t = n.telemetry
            assert t.value("sentry_quarantined_peers") >= 1
            assert t.value("sentry_quarantines_total") >= 1
            assert t.value("sentry_proofs") >= 1
            assert t.value("sentry_rejects_total", cause="fork") >= 1
            rendered = t.render_metrics()
            assert "sentry_quarantined_peers 1" in rendered
            assert 'sentry_rejects_total{cause="fork"}' in rendered
            # registry and get_stats agree on the quarantine count
            assert n.get_stats()["sentry_quarantines_total"] == str(
                t.value("sentry_quarantines_total")
            )
        # bounded queues: the attack must not leave RPC backlogs
        for n in nodes:
            assert n.trans.consumer().qsize() < 256

        # restart the persistent node with --store --bootstrap: the proof
        # must still be there
        proof_keys = {p.key() for p in nodes[0].core.sentry.proofs()}
        nodes[0].shutdown()
        node0b = Node(
            Config(
                heartbeat_timeout=0.02,
                slow_heartbeat_timeout=0.2,
                moniker="node0",
                log_level="warning",
                bootstrap=True,  # implies store; replays the DB
            ),
            Validator(keys[0], "node0"),
            peers,
            peers,
            PersistentStore(10000, str(tmp_path / "node0.db")),
            network.new_transport("inmem://node0"),
            InmemProxy(DummyState()),
        )
        nodes[0] = node0b  # _shutdown in finally covers the new incarnation
        node0b.init()
        reloaded = {p.key() for p in node0b.core.sentry.proofs()}
        assert proof_keys and proof_keys <= reloaded, (
            "equivocation proofs must survive --store --bootstrap restart"
        )
        body2 = node0b.get_suspects()
        assert len(body2["proofs"]) >= 1
        assert EquivocationProof.from_dict(body2["proofs"][0]).verify()
    finally:
        _shutdown(nodes, byzs)


@pytest.mark.byz
def test_equivocation_soak_quarantine_proofs_and_restart(tmp_path):
    """Acceptance (ISSUE-5) — with the ISSUE-15 retry-once corroboration:
    this soak is the known under-load tier-1 flake (it passes standalone;
    a loaded host can starve the 4-node cluster past the drive window).
    A first-attempt assertion failure triggers ONE full fresh-cluster
    re-run, and only a failure of BOTH runs fails the test —
    corroboration, not masking: a real
    regression fails twice, a host-load artifact doesn't repeat."""
    try:
        _equivocation_soak_attempt(tmp_path / "run1")
    except AssertionError as first:
        print(
            "byz soak: first attempt failed under load "
            f"({str(first)[:200]}); corroborating with one re-run"
        )
        _equivocation_soak_attempt(tmp_path / "run2")


# -- receiving-side caps under a real oversize attacker -------------------


@pytest.mark.byz
def test_oversize_pushes_capped_scored_and_quarantined():
    """An adversary shoving batches far beyond sync_limit gets truncated
    at every honest receiver (sync_limit_truncations moves), scored, and
    quarantined — while the cluster keeps committing."""
    network, peers, keys, nodes, proxies, byzs = make_mixed_cluster(
        3, "oversize", sync_limit=16,
        byz_kwargs={"interval": 0.03, "oversize_factor": 3},
    )
    byz = byzs[0]
    byz_id = byz.core.validator.id()
    try:
        for n in nodes:
            n.run_async()
        byz.run_async()

        def capped():
            return any(
                n.sync_limit_truncations > 0
                and n.core.sentry.is_quarantined(byz_id)
                for n in nodes
            )

        assert _drive(nodes, proxies, 45.0, predicate=capped), (
            f"oversize never caught: byz={byz.stats()} "
            f"trunc={[n.sync_limit_truncations for n in nodes]}"
        )
        hit = next(n for n in nodes if n.sync_limit_truncations > 0)
        stats = hit.get_stats()
        assert int(stats["sync_limit_truncations"]) > 0
        assert int(stats["sentry_rejects_oversized_sync"]) > 0
        # honest progress under the flood
        _bombard_until(nodes, proxies, 1, timeout=90.0)
        _check_no_fork(nodes)
    finally:
        _shutdown(nodes, byzs)


@pytest.mark.byz
def test_garbage_and_lying_known_do_not_stall_the_cluster():
    """Garbage wire payloads and pathological known-maps score the sender
    but never stall honest consensus or blame honest peers."""
    network, peers, keys, nodes, proxies, byzs = make_mixed_cluster(
        3, "garbage", byz_kwargs={"interval": 0.03},
    )
    byz = byzs[0]
    try:
        for n in nodes:
            n.run_async()
        byz.run_async()
        _bombard_until(nodes, proxies, 2, timeout=90.0)
        _check_no_fork(nodes)
        # the attack registered somewhere
        assert any(
            sum(n.core.sentry.rejects.values()) > 0 for n in nodes
        )
        # no honest node quarantines another honest node
        honest_ids = {n.get_id() for n in nodes}
        for n in nodes:
            for hid in honest_ids:
                assert not n.core.sentry.is_quarantined(hid)
    finally:
        _shutdown(nodes, byzs)


# -- the storm: f = ⌊(N−1)/3⌋ simultaneous adversaries --------------------


@pytest.mark.byz
@pytest.mark.slow
def test_byzantine_storm_f_adversaries_under_chaos():
    """N=7, f=2: a split-brain equivocator AND a wrong-key flooder attack
    simultaneously through lossy links. Safety must hold (no two honest
    nodes ever commit different blocks) and both adversaries end up
    quarantined with the equivocator's proof recorded somewhere."""
    network, peers, keys, nodes, proxies, byzs = make_mixed_cluster(
        5, "equivocate", n_byz=2, chaos_drop=0.10,
        attacks=["equivocate", "wrong_key"],
        byz_kwargs={"interval": 0.03},
    )
    byzs[0].split = True  # the nastier split-brain variant
    byz_ids = [b.core.validator.id() for b in byzs]
    try:
        for n in nodes:
            n.run_async()
        # let the honest cluster commit before the storm begins
        _bombard_until(nodes, proxies, 1, timeout=120.0)
        for b in byzs:
            b.run_async()

        def both_caught():
            return all(
                any(n.core.sentry.is_quarantined(bid) for n in nodes)
                for bid in byz_ids
            ) and any(len(n.core.sentry.proofs()) > 0 for n in nodes)

        assert _drive(nodes, proxies, 90.0, predicate=both_caught), (
            f"storm uncaught: {[n.core.sentry.stats() for n in nodes]}"
        )
        for b in byzs:
            b.stop()
        # SAFETY above liveness under split-brain: whatever committed is
        # byte-identical everywhere (the split fork may legitimately slow
        # or wedge cross-partition gossip — docs/robustness.md records
        # this as the known equivocation wedge)
        _check_no_fork(nodes)
        for n in nodes:
            assert n.trans.consumer().qsize() < 512
    finally:
        _shutdown(nodes, byzs)
