"""A rejoining validator of a 64-validator ring (deployment ``catchup64``)
at a reduced backlog on the CPU: 63 creators, 3,000 events, the flush gate
low enough that sweeps run at P 64.

- the program — ``Core.prepare_sync`` / ``Core.sync`` / ``process_sig_pool``
  with deferred voting on the device path — against the benchmark's plain
  reference (a host ``Hashgraph`` replaying what the validator stored):
  the same blocks byte for byte, the same count of ordered events;
- the programs ``Node.init`` prewarms for a ring of 64 cover every bucket
  that pass launched, and the list for 16 validators is the one the
  16-validator cells were tuned on.
"""

from __future__ import annotations

import pytest

from babble_tpu.hashgraph import InmemStore
from babble_tpu.hashgraph.accel import prewarm_keys
from babble_tpu.node.core import Core
from babble_tpu.node.validator import Validator
from babble_tpu.proxy.proxy import dummy_commit_response
from benchmark.harness import data, reference

N, ME, EVENTS, SYNC_EVENTS = 64, 0, 3000, 1000
SEED, DAG_SEED = 3000000019, 2147487920  # DAG_SEED: backlog8k.json's


@pytest.fixture(scope="module")
def caught_up():
    """v0's core as ``Node`` builds it with ``--accelerator``, fed the
    backlog in syncs of SyncLimit; compiles inline, the flush gate at 16."""
    keys = data.seeded_keys(N, SEED)
    peers = data.peer_set(keys, [f"inmem://v{i}" for i in range(N)])
    creators = [i for i in range(N) if i != ME]
    wires = data.backlog_wire_events(keys, peers, creators, EVENTS,
                                     DAG_SEED, 100)
    core = Core(Validator(keys[ME], "v0"), peers, peers, InmemStore(10000),
                dummy_commit_response, accelerated_verify=True)
    tc = core.hg.accel
    tc.min_window, tc.async_compile = 16, False
    tc.pipeline, tc.batcher = False, False
    from_id = peers.by_pub_key[keys[creators[0]].public_key.hex()].id
    for chunk in data.chunks(wires, SYNC_EVENTS):
        prepared = core.prepare_sync(chunk)
        core.sync(from_id, chunk, prepared)
        core.process_sig_pool()
    core.hg.drain_consensus()
    return core, peers, keys[ME].public_key.hex(), tc.stats()


def test_the_validator_equals_the_reference_at_64(caught_up):
    core, peers, own, stats = caught_up
    assert stats["accel_sweeps"] >= 1 and stats["accel_fallbacks"] == 0
    assert {label.split("x")[3] for label in stats["accel_bucket_launches"]
            } == {"64"}
    assert reference.stored_from_others(core.hg.store, own) == EVENTS
    audit = reference.audit_against_oracle(core.hg, peers)
    assert audit.missing_events == 0
    assert audit.blocks >= 1 and audit.differing_blocks == 0, audit.note
    assert core.get_consensus_events_count() == audit.ordered > 0
    # the first-descendant walk found every ancestor's witness flag
    assert core.hg.fd_walk_steps > 0 and core.hg.fd_walk_flag_misses == 0


def test_the_prewarm_list_covers_a_64_ring_and_keeps_the_16_list(caught_up):
    stats = caught_up[-1]
    launched = {tuple(int(d) for d in label.split("x")[1:])
                for label in stats["accel_bucket_launches"]}
    assert launched and launched <= set(prewarm_keys(N))
    # the list the 16-validator cells were tuned on, unchanged
    assert prewarm_keys(16) == [
        (16, 32, 16, 1, 8), (16, 64, 16, 1, 8), (32, 128, 16, 1, 8),
        (64, 256, 16, 1, 8), (64, 256, 16, 1, 16), (64, 512, 16, 1, 16),
        (128, 512, 16, 1, 16), (128, 1024, 16, 1, 16),
        (128, 1024, 16, 1, 32), (256, 1024, 16, 1, 32),
    ]
    assert prewarm_keys(4) == [
        (16, 32, 8, 1, 8), (16, 64, 8, 1, 8), (32, 128, 8, 1, 8),
        (64, 256, 8, 1, 8), (64, 256, 8, 1, 16), (64, 512, 8, 1, 16),
        (128, 512, 8, 1, 16), (128, 1024, 8, 1, 16),
    ]
