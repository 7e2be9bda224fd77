"""The plain reference and the comparison that decides ``correct``.

The reference is the host ``Hashgraph`` with no accelerator attached: the
same events in the same order through the oracle pipeline (insert,
DivideRounds, DecideFame, DecideRoundReceived, ProcessDecidedRounds) must
give the same blocks, byte for byte under ``ORACLE_BLOCK_KEYS``. Copied from
``chip_smoke.py`` (``ordered_events``, ``replay``, ``block_bytes``,
``_ORACLE_BLOCK_KEYS``).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

# what consensus decides about a block; StateHash, receipts and signatures
# come from the application and the validators' keys, not from voting
ORACLE_BLOCK_KEYS = (
    "Index", "RoundReceived", "Timestamp", "FrameHash", "PeersHash",
    "TxRoot", "Transactions", "InternalTransactions",
)


def block_bytes(block, keys: Optional[Sequence[str]] = None) -> bytes:
    from babble_tpu.crypto.canonical import canonical_dumps

    d = block.body.to_dict()
    if keys is not None:
        d = {k: d[k] for k in keys}
    return canonical_dumps(d)


def stored_events(store) -> List:
    """Every event a store holds, in the order it was inserted."""
    events, seen = [], set()
    for pk in store.repertoire_by_pub_key():
        for eh in store.participant_events(pk, -1):
            if eh not in seen:
                seen.add(eh)
                events.append(store.get_event(eh))
    events.sort(key=lambda e: e.topological_index)
    return events


def oracle_replay(events: Sequence, peers):
    """``events`` through a fresh host Hashgraph, one at a time."""
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore

    h = Hashgraph(InmemStore(max(100000, 2 * len(events))))
    h.init(peers)
    for ev in events:
        h.insert_event_and_run_consensus(
            Event(ev.body, ev.signature), set_wire_info=True
        )
    return h


def audit_against_oracle(hg, peers) -> Tuple[bool, str, int, int]:
    """Replay one validator's stored events through the oracle and compare
    every block it committed. Returns (ok, note, blocks compared, events
    the oracle ordered into blocks)."""
    events = stored_events(hg.store)
    if len(events) != hg.topological_index:
        return (False, f"audited history evicted: store holds {len(events)} "
                f"of {hg.topological_index} events", 0, 0)
    oracle = oracle_replay(events, peers)
    n_blocks = hg.store.last_block_index() + 1
    if oracle.store.last_block_index() + 1 < n_blocks:
        return (False, f"oracle made {oracle.store.last_block_index() + 1} "
                f"blocks, the validator {n_blocks}", 0, 0)
    for b in range(n_blocks):
        if block_bytes(oracle.store.get_block(b), ORACLE_BLOCK_KEYS) != (
            block_bytes(hg.store.get_block(b), ORACLE_BLOCK_KEYS)
        ):
            return False, f"host oracle disagrees on block {b}", b, 0
    return (True, f"{len(events)} events, {n_blocks} blocks equal to the "
            f"host oracle's", n_blocks, oracle.store.consensus_events_count())


def blocks_identical(nodes) -> Tuple[bool, str, int]:
    """Blocks 0..common byte-identical across all validators."""
    last = [nd.get_last_block_index() for nd in nodes]
    common = min(last)
    if common < 0:
        return False, f"a validator committed no block: {last}", 0
    for b in range(common + 1):
        if len({block_bytes(nd.get_block(b)) for nd in nodes}) != 1:
            return False, f"block {b} differs across validators", b
    return (True, f"blocks 0..{common} byte-identical across {len(nodes)} "
            f"validators (last index {min(last)}..{max(last)})", common + 1)


def device_path_held(counters: dict) -> Tuple[bool, List[str]]:
    """The window drove the device and never left it for a fault: at least
    one sweep, and no fallback, mesh fallback or breaker open inside it."""
    notes = []
    if counters.get("accel_sweeps", 0.0) < 1:
        notes.append("no device sweep ran inside the window")
    for k in ("accel_fallbacks", "accel_mesh_fallbacks", "accel_breaker_open"):
        if counters.get(k, 0.0) > 0:
            notes.append(f"{k} rose by {counters[k]:.0f} inside the window")
    return not notes, notes
