"""Seeded inputs and the plain reference for a deployment whose validator
does not replay: it lands on an anchor block's Frame (``fastsync16``).

A real ring's events carry block signatures — every validator signs every
block it commits and sends the signature in its next event — and only a
block that more than a third of the validators signed can be an anchor. So
the generator runs consensus while it emits, as ``churn.py``'s does: the
events go one at a time into a DONOR, a host-path ``Core`` (no accelerator)
with a dummy application behind it, and when the donor commits a block each
creator signs it with its own key (RFC 6979, the native signer) and the
signature rides in that creator's next event. The donor is an observer — its
own key is in no validator set, so ``Core.commit`` signs nothing itself and
every byte of a stream follows from ``--seed`` and ``dag_seed``. The DAG's
shape is ``data.backlog_wire_events``' rule (self-parent the creator's head,
other-parent a random other creator's head, one transaction per non-initial
event, a ``tag`` in every payload), from ``dag_seed`` alone.

What a stream hands the driver is made BY the program, as another machine
of the ring would make it: the response is ``get_anchor_block_with_frame()``
and ``proxy.get_snapshot(block.index())`` (``Node.
_process_fast_forward_request``), marshalled as the socket transports
marshal it (``net/codec.py``), so every landing decodes objects of its own;
the tail is the donor's ``event_diff`` for the ``known_events()`` a
hashgraph has right after ``reset`` on that Frame, marshalled as a
``SyncResponse`` carries events. A stream is bytes and a few numbers: the
dozens a run holds put nothing on the collector's lists.

The reference (``replay``) shares nothing with any of it: a sequential host
``Hashgraph`` with no accelerator and NO reset, fed the stream's whole
history from genesis, a dummy application of its own behind a commit step
of its own. Fast-sync's guarantee is that from the anchor on the two hold
the same chain.
"""

from __future__ import annotations

import random
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import data, reference


def encode_response(resp) -> bytes:
    from babble_tpu.net import codec
    from babble_tpu.net.rpc import FAST_FORWARD

    return codec.encode_response(FAST_FORWARD, resp)


def decode_response(payload: bytes):
    """A ``FastForwardResponse`` of fresh objects, as a socket transport
    hands a validator: nothing in it is shared with the donor or with an
    earlier landing."""
    from babble_tpu.net import codec
    from babble_tpu.net.rpc import FAST_FORWARD

    return codec.decode_response(FAST_FORWARD, payload)


def encode_events(wires: Sequence) -> bytes:
    """Wire events as a peer's ``SyncResponse`` carries them."""
    from babble_tpu.net import codec
    from babble_tpu.net.rpc import SYNC, SyncResponse

    return codec.encode_response(SYNC, SyncResponse(0, list(wires), {}))


def decode_events(payload: bytes) -> List:
    from babble_tpu.net import codec
    from babble_tpu.net.rpc import SYNC

    return codec.decode_response(SYNC, payload).events


def sign_block(block, key):
    """A creator's deterministic (RFC 6979) signature over a block's body,
    what ``Block.sign`` makes with OpenSSL's random nonce."""
    from babble_tpu import native_crypto
    from babble_tpu.crypto.keys import encode_signature
    from babble_tpu.hashgraph.event import BlockSignature

    rs = native_crypto.sign(key.bytes(), block.body.hash())
    if rs is None:
        raise RuntimeError("native signer unavailable (g++ missing?)")
    return BlockSignature(validator=key.public_key.bytes(),
                          index=block.index(),
                          signature=encode_signature(*rs))


class Stream(NamedTuple):
    """One ring's history as a late validator meets it."""

    history: List  # every wire event from genesis, in emission order
    response: bytes  # the donor's FastForwardResponse, marshalled
    tail: bytes  # the donor's diff against the landing's known, marshalled
    tail_events: int
    anchor_index: int
    anchor_round: int
    anchor_signatures: int
    frame_events: int  # roots' and the round's, what a landing inserts
    frame_bytes: int  # the Frame's canonical JSON
    tail_block_signatures: int
    ordered_after: int  # events the donor received in rounds above the anchor's
    blocks_after: int  # blocks the donor committed above the anchor's index


class Donor:
    """The ring as one host-path ``Core`` sees it: every event of the
    creators, consensus after each, the creators' block signatures gathered
    from the events that carry them."""

    def __init__(self, keys, peers, creators: Sequence[int], observer_key,
                 room: int):
        from babble_tpu.dummy.state import State as DummyState
        from babble_tpu.hashgraph import InmemStore
        from babble_tpu.node.core import Core
        from babble_tpu.node.validator import Validator
        from babble_tpu.proxy.proxy import InmemProxy

        self.keys, self.peers, self.creators = keys, peers, list(creators)
        self.proxy = InmemProxy(DummyState())
        self.core = Core(Validator(observer_key, "donor"), peers, peers,
                         InmemStore(max(100000, 2 * room)),
                         self.proxy.commit_block)
        # creator -> signatures it has made and not sent yet
        self.unsent: Dict[int, List] = {c: [] for c in self.creators}
        self.core.commit_listeners.append(self._sign)

    def _sign(self, block) -> None:
        for c in self.creators:
            self.unsent[c].append(sign_block(block, self.keys[c]))

    def take_signatures(self, creator: int) -> List:
        sigs, self.unsent[creator] = self.unsent[creator], []
        return sigs

    def insert(self, event) -> None:
        self.core.insert_event_and_run_consensus(event, set_wire_info=False)
        self.core.process_sig_pool()

    def answer(self) -> bytes:
        """What ``Node._process_fast_forward_request`` answers, marshalled:
        a block the ring commits later cannot reach it."""
        from babble_tpu.net.rpc import FastForwardResponse

        anchor = self.core.hg.anchor_block
        if anchor is None or anchor < 1:
            raise ValueError(
                f"the ring's {self.core.hg.topological_index} events gave no "
                f"anchor block at index 1 or above (anchor {anchor}, last "
                f"block {self.core.get_last_block_index()}): the history "
                "is too short")
        block, frame = self.core.get_anchor_block_with_frame()
        return encode_response(FastForwardResponse(
            from_id=self.peers.by_pub_key[
                self.keys[self.creators[0]].public_key.hex()].id,
            block=block, frame=frame,
            snapshot=self.proxy.get_snapshot(block.index())))

    def ordered_after(self, round_received: int) -> int:
        store = self.core.hg.store
        last = self.core.hg.last_consensus_round
        return sum(len(store.get_round(r).received_events)
                   for r in range(round_received + 1, (last or 0) + 1))


def landing_known(response: bytes, peers) -> Dict[int, int]:
    """The ``known_events()`` of a hashgraph right after ``reset`` on the
    response's Frame: what a landed validator tells its peers it holds."""
    from babble_tpu.hashgraph import Hashgraph, InmemStore

    resp = decode_response(response)
    hg = Hashgraph(InmemStore(10000))
    hg.init(peers)
    hg.reset(resp.block, resp.frame)
    return hg.store.known_events()


def ring_stream(keys, peers, creators: Sequence[int], observer_key,
                n_events: int, dag_seed: int, tx_bytes: int,
                tag: int = 0, poll_at: Optional[int] = None,
                ) -> Tuple[Stream, Donor]:
    """``n_events`` of a random-gossip ring among ``creators`` with block
    signatures in them, and what a late validator is handed: the donor's
    response and the tail above it. The donor answers the poll when it
    holds ``poll_at`` events (None: the whole history) and the ring moves
    on; the tail is its diff at the END. Fails loudly when the ring has no
    anchor at block index 1 or above by then (upstream's poll takes only
    an index above 0). Returns the donor too, for what only set-up asks
    of it."""
    from babble_tpu.hashgraph import Event

    rng = random.Random(dag_seed)
    donor = Donor(keys, peers, creators, observer_key, n_events)
    ids = [peers.by_pub_key[keys[c].public_key.hex()].id for c in creators]
    m = len(creators)
    heads = [""] * m
    seqs = [-1] * m
    history: List = []
    payload = None
    order = list(range(m))
    while len(history) < n_events:
        rng.shuffle(order)
        for i in order:
            if len(history) >= n_events:
                break
            op, j = "", -1
            if history:
                j = rng.randrange(m - 1)
                j = j if j < i else j + 1
                op = heads[j]
                if op == "":
                    continue
            idx = seqs[i] + 1
            tx = (b"backlog %d tx %d " % (tag, len(history))).ljust(
                tx_bytes, b"x")
            key = keys[creators[i]]
            e = Event.new(
                [tx] if idx else [], [], donor.take_signatures(creators[i]),
                [heads[i], op], key.public_key.bytes(), idx,
                timestamp=len(history),
            )
            data.sign_event(e, key)
            e.set_wire_info(
                seqs[i], ids[j] if op else 0, seqs[j] if op else -1, ids[i]
            )
            heads[i] = e.hex()
            seqs[i] = idx
            history.append(e.to_wire())
            donor.insert(e)
            if len(history) == poll_at:
                payload = donor.answer()
    if payload is None:
        payload = donor.answer()
    resp = decode_response(payload)
    tail = donor.core.to_wire(
        donor.core.event_diff(landing_known(payload, peers)))
    rr = resp.block.round_received()
    return Stream(
        history=history,
        response=payload,
        tail=encode_events(tail),
        tail_events=len(tail),
        anchor_index=resp.block.index(),
        anchor_round=rr,
        anchor_signatures=len(resp.block.signatures),
        frame_events=len(resp.frame.sorted_frame_events()),
        frame_bytes=len(resp.frame.canonical_bytes()),
        tail_block_signatures=sum(len(w.body.block_signatures) for w in tail),
        ordered_after=donor.ordered_after(rr),
        blocks_after=donor.core.get_last_block_index() - resp.block.index(),
    ), donor


class Ring(NamedTuple):
    """Who is in a ring, from ``--seed``: the validators' keys, the
    donor's (an observer's, in no validator set), the validator set and
    the creators (everyone but the late validator)."""

    keys: List
    observer: object
    peers: object
    creators: List[int]


def ring_of(validators: int, late: int, seed: int) -> Ring:
    keys = data.seeded_keys(validators + 1, seed)
    peers = data.peer_set(keys[:validators],
                          [f"inmem://v{i}" for i in range(validators)])
    return Ring(keys[:validators], keys[validators], peers,
                [i for i in range(validators) if i != late])


class Job(NamedTuple):
    """One stream to make, in plain numbers: a worker process builds the
    ring from them again."""

    validators: int
    late: int
    seed: int
    history_events: int
    dag_seed: int
    tx_bytes: int
    poll_at: Optional[int]
    tag: int
    forged: bool = False  # the two forged offers beside it
    history: bool = False  # keep the whole history (the reference's input)


def make_stream(job: Job) -> Tuple[Stream, List[Tuple[str, bytes]]]:
    """(the stream, its forged offers if asked for). The history is
    dropped unless asked for: the driver holds dozens of streams, and
    only the reference reads one."""
    ring = ring_of(job.validators, job.late, job.seed)
    stream, donor = ring_stream(
        ring.keys, ring.peers, ring.creators, ring.observer,
        job.history_events, job.dag_seed, job.tx_bytes, tag=job.tag,
        poll_at=job.poll_at)
    forged = forged_offers(stream, donor) if job.forged else []
    return (stream if job.history else stream._replace(history=[])), forged


def _worker_init() -> None:
    # a worker runs host code alone; should anything in it ever reach
    # for jax, it must not reach for the chip its parent holds
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"


def make_streams(jobs: Sequence[Job], workers: int) -> List[Tuple]:
    """``make_stream`` over ``jobs``, in order. Each stream is its own
    ring's consensus from genesis (≈ 1 ms an event), so with ``workers``
    above 1 they are made side by side in spawned processes — spawned,
    not forked: the parent holds a chip and its runtime's threads."""
    if workers <= 1:
        return [make_stream(j) for j in jobs]
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_worker_init) as pool:
        return list(pool.map(make_stream, jobs))


def forged_offers(stream: Stream, donor: Donor) -> List[Tuple[str, bytes]]:
    """Two responses a validator has to refuse: the stream's own with its
    block's signatures cut to ``trust_count`` (a third, not MORE than a
    third), and the stream's block beside the Frame of another round."""
    resp = decode_response(stream.response)
    trust = resp.frame.peers.trust_count()
    kept = sorted(resp.block.signatures)[:trust]
    resp.block.signatures = {v: resp.block.signatures[v] for v in kept}
    few = encode_response(resp)

    resp = decode_response(stream.response)
    hg = donor.core.hg
    earlier = hg.store.get_block(stream.anchor_index - 1).round_received()
    resp.frame = hg.get_frame(earlier)
    return [("signatures cut to a third", few),
            ("the frame of another round", encode_response(resp))]


class Reference(NamedTuple):
    """What the validator that replayed everything holds."""

    blocks: Dict[int, bytes]  # index -> bytes under ORACLE_BLOCK_KEYS
    state_hashes: Dict[int, bytes]
    received_in: Dict[int, int]  # round -> events received in it
    events: int

    def ordered_after(self, round_received: int) -> int:
        return sum(n for r, n in self.received_in.items()
                   if r > round_received)


def replay(history: Sequence, peers) -> Reference:
    """The stream's whole history from genesis through a sequential host
    ``Hashgraph`` — no accelerator, no reset — with a dummy application of
    its own: its commit step takes the application's state hash and
    receipts into the block, and nothing else of a validator."""
    from babble_tpu.dummy.state import State as DummyState
    from babble_tpu.hashgraph import Hashgraph, InmemStore

    app = DummyState()

    def commit(block) -> None:
        answer = app.commit_handler(block)
        block.body.state_hash = answer.state_hash
        block.body.internal_transaction_receipts = answer.receipts

    hg = Hashgraph(InmemStore(max(100000, 2 * len(history))), commit)
    hg.init(peers)
    for we in history:
        hg.insert_event_and_run_consensus(hg.read_wire_info(we),
                                          set_wire_info=False)
    store = hg.store
    blocks, hashes = {}, {}
    for b in range(store.last_block_index() + 1):
        block = store.get_block(b)
        blocks[b] = reference.block_bytes(block, reference.ORACLE_BLOCK_KEYS)
        hashes[b] = bytes(block.state_hash())
    last = hg.last_consensus_round
    received = {r: len(store.get_round(r).received_events)
                for r in range(0, (last if last is not None else -1) + 1)}
    return Reference(blocks, hashes, received, len(history))


class Landed(NamedTuple):
    """A validator's chain from its landing block on, in the reference's
    terms."""

    blocks: Dict[int, bytes]
    state_hashes: Dict[int, bytes]


def chain_of(hg, first: int) -> Landed:
    """Blocks ``first``.. of a hashgraph's store under ORACLE_BLOCK_KEYS,
    and their state hashes."""
    blocks, hashes = {}, {}
    for b in range(first, hg.store.last_block_index() + 1):
        block = hg.store.get_block(b)
        blocks[b] = reference.block_bytes(block, reference.ORACLE_BLOCK_KEYS)
        hashes[b] = bytes(block.state_hash())
    return Landed(blocks, hashes)


def differing(got: Dict[int, bytes], want: Dict[int, bytes],
              indexes) -> List[int]:
    """The indexes at which the validator's value is not the reference's
    (one the reference never made differs)."""
    return [b for b in indexes if got.get(b) != want.get(b) or b not in want]
