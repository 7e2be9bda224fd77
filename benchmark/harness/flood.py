"""``badsig16``'s flood and its plain reference.

The generator. A flooder is a validator of the ring that also pushes
``EagerSyncRequest``s of forged events: each claims the flooder's own
identity at the heights above the flooder's head in what the rejoining
validator holds at that moment (its first forged event's self-parent is that
head, the others chain on), takes as other-parent an event the validator
holds, so that every one decodes and reaches verification, and carries a
random well-formed (r, s) that nobody signed — a flooder wants the
verifier's cost, not a signing cost. ``Flood`` makes the pushes of a pass
once, in set-up, from the honest stream's shape (every stream of a run
shares ``dag_seed``, hence its heads); ``refresh()`` gives every forged
event a fresh signature before each pass, so no signature that reaches
decode has been seen before in the process and the verdict cache never
answers for one.

The reference, independent of the ingest path: a stream's event hashes
decoded here from the wire form alone (``honest_hashes``), the store's
events against them as sets, and forged and honest events checked by the
pure-Python curve (``crypto/secp256k1.py``) beside the native verifier
(``sample_items`` + ``verdicts_differing``).
"""

from __future__ import annotations

import random
from typing import Dict, List, Sequence, Tuple

# r and s as base-36 strings of this many digits: under 36**49 < 2**254,
# so both lie in [1, N) and s in the low half a verifier accepts — a
# forged signature costs a verifier the whole check
SIG_DIGITS = 49
_B36 = b"0123456789abcdefghijklmnopqrstuvwxyz"
_TO_B36 = bytes(_B36[b % 36] for b in range(256))


def _heads_after(wires: Sequence, sync_events: int) -> List[Dict[int, int]]:
    """Each creator's highest index among the first k syncs' events, for
    every k: what the validator holds of it once sync k - 1 is in."""
    heads: Dict[int, int] = {}
    out = []
    for pos, we in enumerate(wires, 1):
        b = we.body
        heads[b.creator_id] = max(heads.get(b.creator_id, -1), b.index)
        if pos % sync_events == 0 or pos == len(wires):
            out.append(dict(heads))
    return out


class Flood:
    """The forged pushes of one pass: ``after[k]`` lists the
    ``(flooder id, wire events)`` pushed once honest sync ``k`` is in, each
    flooder ``pushes`` times, ``events`` forged events a push."""

    def __init__(self, wires: Sequence, flooder_ids: Sequence[int],
                 sync_events: int, pushes: int, events: int, tx_bytes: int,
                 seed: int):
        from babble_tpu.hashgraph.event import WireBody, WireEvent

        self._rng = random.Random(f"badsig flood {seed}")
        rng = self._rng
        self.forged: List = []
        self.after: List[List[Tuple[int, List]]] = []
        for k, heads in enumerate(_heads_after(wires, sync_events)):
            holders = sorted(heads)
            pushed = []
            for fid in flooder_ids:
                others = [c for c in holders if c != fid]
                for j in range(pushes):
                    tx = (b"flood %d %d %d " % (fid, k, j)).ljust(tx_bytes, b"x")
                    batch = []
                    for i in range(events):
                        idx = heads.get(fid, -1) + 1 + i
                        op = others[rng.randrange(len(others))]
                        batch.append(WireEvent(body=WireBody(
                            transactions=[tx], creator_id=fid, index=idx,
                            self_parent_index=idx - 1,
                            other_parent_creator_id=op,
                            other_parent_index=heads[op],
                            timestamp=(k + 1) * sync_events + i)))
                    self.forged.extend(batch)
                    pushed.append((fid, batch))
            self.after.append(pushed)
        self.refresh()

    def refresh(self) -> None:
        """A fresh random (r, s) for every forged event."""
        width = 2 * SIG_DIGITS
        digits = self._rng.randbytes(width * len(self.forged)).translate(
            _TO_B36).decode("ascii")
        for n, we in enumerate(self.forged):
            at = n * width
            we.signature = (digits[at:at + SIG_DIGITS] + "|"
                            + digits[at + SIG_DIGITS:at + width])


def _body(we, parents: List[str], peers):
    from babble_tpu.hashgraph.event import EventBody

    b = we.body
    return EventBody(
        transactions=list(b.transactions), internal_transactions=[],
        block_signatures=[], parents=parents,
        creator=peers.by_id[b.creator_id].pub_key_bytes(), index=b.index,
        timestamp=b.timestamp, self_parent_index=b.self_parent_index,
        other_parent_creator_id=b.other_parent_creator_id,
        other_parent_index=b.other_parent_index, creator_id=b.creator_id)


def _decode(wires: Sequence, peers, known: Dict[Tuple[int, int], str]):
    """``(wire event, Event)`` for each of ``wires`` in order, parents
    resolved through ``known`` ((creator id, index) -> hash), which grows by
    each event decoded."""
    from babble_tpu.hashgraph.event import Event

    out = []
    for we in wires:
        b = we.body
        parents = [known[(b.creator_id, b.self_parent_index)]
                   if b.self_parent_index >= 0 else "",
                   known[(b.other_parent_creator_id, b.other_parent_index)]
                   if b.other_parent_index >= 0 else ""]
        ev = Event(_body(we, parents, peers), signature=we.signature)
        known[(b.creator_id, b.index)] = ev.hex()
        out.append((we, ev))
    return out


def honest_hashes(wires: Sequence, peers) -> Dict[Tuple[int, int], str]:
    """(creator id, index) -> hash of every event of an honest stream."""
    known: Dict[Tuple[int, int], str] = {}
    _decode(wires, peers, known)
    return known


def sample_items(wires: Sequence, flood: Flood, peers, n: int) -> List:
    """``n`` honest events of the stream and ``n`` forged ones of the
    flood's first pushes, as (public key, message hash, signature, whether
    it is honest)."""
    known: Dict[Tuple[int, int], str] = {}
    honest = _decode(wires, peers, known)
    step = max(1, len(honest) // n)
    items = [(ev.body.creator, ev.hash(), ev.signature, True)
             for _we, ev in honest[::step][:n]]
    pushes = flood.after[0]
    per_push = -(-n // len(pushes))
    for _fid, batch in pushes:
        for _we, ev in _decode(batch[:per_push], peers, dict(known)):
            if len(items) < 2 * n:
                items.append((ev.body.creator, ev.hash(), ev.signature, False))
    return items


def verdicts_differing(items: List) -> int:
    """Items whose verdict by the curve's own arithmetic, or by the native
    verifier, is not what the item is: honest valid, forged invalid."""
    from babble_tpu import native_crypto
    from babble_tpu.crypto import secp256k1 as curve
    from babble_tpu.crypto.keys import decode_signature

    differing = 0
    for creator, msg, sig, honest in items:
        pub = curve.unmarshal_pubkey(creator)
        r, s = decode_signature(sig)
        pure = curve.verify(pub, msg, r, s)
        native = native_crypto.verify_one(
            pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big"), msg, r, s)
        differing += pure != honest or native != honest
    return differing
