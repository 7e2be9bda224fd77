"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding
(pjit/shard_map over a Mesh) is exercised without TPU hardware: XLA_FLAGS
is read lazily at first backend initialization, and the platform is pinned
with jax.config.update before any computation runs — an explicit cpu pin,
which ops/device.py honours as the caller's choice.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the secp256k1 Shamir-ladder kernel takes
# ~15 s to compile per batch-size bucket; caching makes repeat test runs
# fast. Placed by the same helper live nodes use: JAX_COMPILATION_CACHE_DIR
# when set, else <checkout>/.jax_cache (gitignored).
from babble_tpu.ops.device import setup_compile_cache  # noqa: E402

setup_compile_cache(jax)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def keys3():
    """Three deterministic private keys for small fixtures."""
    from babble_tpu.crypto.keys import PrivateKey

    return [PrivateKey(d) for d in (0xA11CE, 0xB0B, 0xCA401)]


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process end-to-end scenarios"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection soaks (deterministic under "
        "BABBLE_CHAOS_SEED; short ones run in tier-1 / make chaossmoke, "
        "the long nemesis storm is also marked slow)",
    )
    config.addinivalue_line(
        "markers",
        "byz: honest-vs-Byzantine soaks (seeded; short ones run in "
        "tier-1 / make byzsmoke, the f=⌊(N−1)/3⌋ storm is also marked "
        "slow)",
    )
    config.addinivalue_line(
        "markers",
        "sim: deterministic virtual-time simulation scenarios "
        "(babble_tpu.sim, docs/simulation.md; the seeded sweep runs in "
        "make simsmoke / simsweep)",
    )
    config.addinivalue_line(
        "markers",
        "trace: cross-node causal-tracing smokes (live cluster + "
        "/trace endpoints + traceview merge; make tracesmoke)",
    )
    config.addinivalue_line(
        "markers",
        "healthview: cluster-healthview smokes (live multi-node merge "
        "over HTTP + SLO scoring; make healthsmoke)",
    )
    config.addinivalue_line(
        "markers",
        "client: light-client gateway smokes (streaming subscriptions, "
        "inclusion proofs, checkpointed replicas, sharded gateway; "
        "make clientsmoke — docs/clients.md)",
    )
    config.addinivalue_line(
        "markers",
        "lifecycle: checkpoint-prune compaction + elastic membership "
        "(pruned-vs-oracle digest equality, retention plateau, "
        "rotation/rejoin from pruned checkpoints; make prunesmoke — "
        "docs/lifecycle.md)",
    )


def setup_testnet_datadirs(tmp_path, n: int, base_port: int,
                           moniker_prefix: str = "n"):
    """keygen + peers.json/peers.genesis.json for an n-node localhost
    testnet — the one datadir scaffolding shared by the engine, example,
    and crash-recovery suites."""
    from babble_tpu.crypto.keyfile import SimpleKeyfile
    from babble_tpu.crypto.keys import generate_key
    from babble_tpu.peers.json_peer_set import JSONPeerSet
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet

    keys = [generate_key() for _ in range(n)]
    peers = PeerSet(
        [
            Peer(f"127.0.0.1:{base_port + i}", k.public_key.hex(),
                 f"{moniker_prefix}{i}")
            for i, k in enumerate(keys)
        ]
    )
    datadirs = []
    for i, k in enumerate(keys):
        d = tmp_path / f"{moniker_prefix}{i}"
        d.mkdir()
        SimpleKeyfile(str(d / "priv_key")).write_key(k)
        JSONPeerSet(str(d)).write(peers)
        datadirs.append(d)
    return keys, peers, datadirs
