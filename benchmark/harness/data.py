"""Seeded inputs: validator keys and the backlog a rejoining validator ingests.

Copied from ``chip_smoke.py`` (``seeded_keys``, ``seeded_stream``), with two
changes the benchmark needs: signatures are RFC 6979 (the native signer), so
the same seed gives the same bytes — ``PrivateKey.sign`` prefers OpenSSL's
random nonce; and the wire fields are filled in here from the generator's
own bookkeeping, which is what a source ``Hashgraph.set_wire_info`` would
look up.
"""

from __future__ import annotations

import random
from typing import List, Sequence


def seeded_keys(n: int, seed: int):
    from babble_tpu.crypto.keys import PrivateKey

    rng = random.Random(seed)
    return [PrivateKey(rng.getrandbits(250) + 1) for _ in range(n)]


def peer_set(keys, addrs: Sequence[str]):
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet

    return PeerSet([
        Peer(addr, k.public_key.hex(), f"v{i}")
        for i, (k, addr) in enumerate(zip(keys, addrs))
    ])


def sign_event(event, key) -> None:
    """Deterministic (RFC 6979) signature through the native signer."""
    from babble_tpu import native_crypto
    from babble_tpu.crypto.keys import encode_signature

    rs = native_crypto.sign(key.bytes(), event.hash())
    if rs is None:
        raise RuntimeError("native signer unavailable (g++ missing?)")
    event.signature = encode_signature(*rs)


def backlog_wire_events(
    keys: Sequence, peers, creators: Sequence[int], n_events: int,
    seed: int, tx_bytes: int, tag: int = 0,
) -> List:
    """``n_events`` wire events of a random-gossip DAG among ``creators``
    (indexes into ``keys``): each event's self-parent is its creator's
    head, its other-parent a random other creator's head — the shape live
    gossip makes. One ``tx_bytes`` transaction per non-initial event.

    The DAG's shape depends on ``seed`` alone; ``tag`` goes into every
    payload, so streams of one seed with different tags are the same DAG
    with different hashes and signatures (distinct events to a verifier,
    the same rounds and fame to consensus)."""
    from babble_tpu.hashgraph import Event

    rng = random.Random(seed)
    ids = [peers.by_pub_key[keys[c].public_key.hex()].id for c in creators]
    m = len(creators)
    heads = [""] * m
    seqs = [-1] * m
    wires = []
    order = list(range(m))
    while len(wires) < n_events:
        rng.shuffle(order)
        for i in order:
            if len(wires) >= n_events:
                break
            op, j = "", -1
            if wires:
                j = rng.randrange(m - 1)
                j = j if j < i else j + 1
                op = heads[j]
                if op == "":
                    continue
            idx = seqs[i] + 1
            tx = (b"backlog %d tx %d " % (tag, len(wires))).ljust(tx_bytes, b"x")
            key = keys[creators[i]]
            e = Event.new(
                [tx] if idx else [], [], [], [heads[i], op],
                key.public_key.bytes(), idx, timestamp=len(wires),
            )
            sign_event(e, key)
            e.set_wire_info(
                seqs[i], ids[j] if op else 0, seqs[j] if op else -1, ids[i]
            )
            heads[i] = e.hex()
            seqs[i] = idx
            wires.append(e.to_wire())
    return wires


def chunks(items: Sequence, size: int) -> List[Sequence]:
    return [items[pos:pos + size] for pos in range(0, len(items), size)]
