"""Seeded inputs and the plain reference for a deployment whose validator
set CHANGES inside the backlog (``churn16``): signed join and leave
requests ride in the events, and each takes effect at the round its block
was received plus six (upstream ``core.go:562-650``).

The backlog has to stay causally valid for a sequential validator — a
joiner's first event may only follow the block that admitted it, a leaver
falls silent after its removal — so the generator runs a host ``Hashgraph``
of its own (no accelerator) over what it emits, with the +6 rule applied on
each committed block (``PlusSix``), and asks it when a joiner may start and
a leaver has to stop. That is done once, for the first stream: what comes
out is a ``Script`` — who created each event, on whose head, carrying which
request — and the re-tagged streams are the same script with another tag
in every payload, so they share rounds as ``data.backlog_wire_events``'s
streams do. The script's shape comes from ``dag_seed`` alone (rounds do not
depend on hashes); keys, ids and signatures come from ``--seed``.

The reference (``audit``) is ``reference.oracle_replay``'s pipeline with
``PlusSix`` as its commit step: it shares nothing with ``node/core.py``.
"""

from __future__ import annotations

import random
from typing import Dict, List, NamedTuple, Sequence, Tuple

from . import data, reference

EFFECTIVE_DELAY = 6  # upstream core.go:566-569: round received + 6


class Step(NamedTuple):
    """One event of the script: indexes into ``keys``; ``other`` is -1 for
    the very first event, ``request`` -1 for an event that carries none."""

    creator: int
    other: int
    request: int


class Request(NamedTuple):
    add: bool
    key: int  # index into ``keys`` of the peer it names


def parse_requests(names: Sequence[str], n_genesis: int) -> List[Request]:
    """``+x1`` asks for joiner 1 (``keys[n_genesis + 1]``) to be added,
    ``-v15`` for genesis validator 15 to be removed."""
    out = []
    for name in names:
        sign, kind, num = name[0], name[1], int(name[2:])
        if sign not in "+-" or kind not in "vx":
            raise ValueError(f"request {name!r} is not +x<n>, -v<n>, ...")
        out.append(Request(sign == "+", num if kind == "v" else n_genesis + num))
    return out


def all_peers(keys, n_genesis: int) -> List:
    """A ``Peer`` for every key: v0.. the genesis validators, x0.. the
    keys that join."""
    from babble_tpu.peers.peer import Peer

    out = []
    for i, k in enumerate(keys):
        name = f"v{i}" if i < n_genesis else f"x{i - n_genesis}"
        out.append(Peer(f"inmem://{name}", k.public_key.hex(), name))
    return out


def signed_request(req: Request, peers: Sequence, keys, nonce: int):
    """The ``InternalTransaction`` a peer signs for itself, with the
    deterministic signature ``data.sign_event`` uses (``join()`` /
    ``leave()`` draw a random nonce and OpenSSL a random k)."""
    from babble_tpu import native_crypto
    from babble_tpu.crypto.keys import encode_signature
    from babble_tpu.hashgraph.internal_transaction import (
        InternalTransaction,
        InternalTransactionBody,
        TransactionType,
    )

    kind = TransactionType.PEER_ADD if req.add else TransactionType.PEER_REMOVE
    itx = InternalTransaction(
        InternalTransactionBody(kind, peers[req.key], nonce=nonce))
    rs = native_crypto.sign(keys[req.key].bytes(), itx.body.hash())
    if rs is None:
        raise RuntimeError("native signer unavailable (g++ missing?)")
    itx.signature = encode_signature(*rs)
    return itx


class PlusSix:
    """The commit step of a sequential validator, and nothing else of one:
    every request of a committed block is accepted and the new validator
    set is stored for round received + 6. ``changes`` lists what it did,
    ``(effective round, request's peer pub key, added?)``."""

    def __init__(self, genesis_peers):
        self.hg = None  # set once the Hashgraph that calls back exists
        self.validators = genesis_peers
        self.changes: List[Tuple[int, str, bool]] = []

    def __call__(self, block) -> None:
        from babble_tpu.hashgraph.internal_transaction import TransactionType

        itxs = block.internal_transactions()
        if not itxs:
            return
        effective = block.round_received() + EFFECTIVE_DELAY
        for itx in itxs:
            add = itx.body.type == TransactionType.PEER_ADD
            self.validators = (
                self.validators.with_new_peer(itx.body.peer) if add
                else self.validators.with_removed_peer(itx.body.peer))
            self.changes.append((effective, itx.body.peer.pub_key_hex, add))
        self.hg.store.set_peer_set(effective, self.validators)


def sequential_hashgraph(genesis_peers, room: int):
    """A host ``Hashgraph`` with no accelerator and ``PlusSix`` as its
    commit callback. Returns (hashgraph, its PlusSix)."""
    from babble_tpu.hashgraph import Hashgraph, InmemStore

    step = PlusSix(genesis_peers)
    h = Hashgraph(InmemStore(max(100000, 2 * room)), step)
    step.hg = h
    h.init(genesis_peers)
    return h, step


class _Emitter:
    """Builds, signs and wires the events of one stream from script steps;
    the bookkeeping a source ``Hashgraph.set_wire_info`` would look up."""

    def __init__(self, keys, peers, requests, tx_bytes: int, tag: int):
        self.keys, self.peers, self.requests = keys, peers, requests
        self.tx_bytes, self.tag = tx_bytes, tag
        self.ids = [p.id for p in peers]
        self.heads = [""] * len(keys)
        self.seqs = [-1] * len(keys)
        self.events: List = []

    def emit(self, step: Step):
        from babble_tpu.hashgraph import Event

        i, j, n = step.creator, step.other, len(self.events)
        op = self.heads[j] if j >= 0 else ""
        idx = self.seqs[i] + 1
        tx = (b"backlog %d tx %d " % (self.tag, n)).ljust(self.tx_bytes, b"x")
        itxs = []
        if step.request >= 0:
            itxs.append(signed_request(self.requests[step.request],
                                       self.peers, self.keys,
                                       nonce=step.request + 1))
        e = Event.new(
            [tx] if idx else [], itxs, [], [self.heads[i], op],
            self.keys[i].public_key.bytes(), idx, timestamp=n,
        )
        data.sign_event(e, self.keys[i])
        e.set_wire_info(self.seqs[i], self.ids[j] if op else 0,
                        self.seqs[j] if op else -1, self.ids[i])
        self.heads[i] = e.hex()
        self.seqs[i] = idx
        self.events.append(e)
        return e


def churn_script(keys, peers, genesis_peers, creators: Sequence[int],
                 requests: Sequence[Request], n_events: int, dag_seed: int,
                 first_request_event: int, request_every: int,
                 tx_bytes: int, eager_joiners: bool = False,
                 ) -> Tuple[List[Step], List]:
    """The script of an ``n_events`` random-gossip backlog among
    ``creators`` in which request k rides in event ``first_request_event +
    k * request_every``, and the wire events of its first stream (tag 0).

    Each event's creator takes its own head and a random other creator's,
    as in ``data.backlog_wire_events``. A joiner enters the draw once the
    sequential hashgraph has committed its admission and reached the
    effective round (upstream ``core.go:293-296``: no self-event before
    the accepted round); a leaver leaves it once that hashgraph's last
    consensus round has reached its removed round (``core.go:458-478``).
    ``eager_joiners`` (no cell's; the tests') lets a joiner start as soon as
    that hashgraph has committed its admission, rounds before it is a
    member: the earliest first event a sequential validator still accepts."""
    rng = random.Random(dag_seed)
    hg, plus_six = sequential_hashgraph(genesis_peers, n_events)
    out = _Emitter(keys, peers, requests, tx_bytes, tag=0)
    pub = {p.pub_key_hex: i for i, p in enumerate(peers)}
    active = list(creators)
    waiting: Dict[int, Tuple[int, bool]] = {}  # key -> (effective, added?)
    seen_changes = 0
    at = {first_request_event + k * request_every: k
          for k in range(len(requests))}
    script: List[Step] = []
    while len(script) < n_events:
        order = list(active)
        rng.shuffle(order)
        for i in order:
            if len(script) >= n_events:
                break
            if i not in active:
                continue  # left during this turn of the table
            j = -1
            if script:
                others = [c for c in active if c != i]
                j = others[rng.randrange(len(others))]
                if out.heads[j] == "":
                    continue
            step = Step(i, j, at.get(len(script), -1))
            script.append(step)
            e = out.emit(step)
            hg.insert_event_and_run_consensus(e, set_wire_info=False)
            for effective, pk, add in plus_six.changes[seen_changes:]:
                waiting[pub[pk]] = (effective, add)
            seen_changes = len(plus_six.changes)
            for k, (effective, add) in list(waiting.items()):
                if add and (eager_joiners
                            or hg.store.last_round() >= effective):
                    active.append(k)
                    del waiting[k]
                elif (not add and hg.last_consensus_round is not None
                      and hg.last_consensus_round >= effective):
                    active.remove(k)
                    del waiting[k]
    if seen_changes < len(requests):
        raise ValueError(
            f"only {seen_changes} of {len(requests)} requests were "
            f"committed inside {n_events} events: the schedule does not fit")
    return script, [e.to_wire() for e in out.events]


def wire_events(keys, peers, requests, script: Sequence[Step], tx_bytes: int,
                tag: int) -> List:
    """The script's stream under another ``tag``: the same DAG, other
    hashes and signatures."""
    out = _Emitter(keys, peers, requests, tx_bytes, tag)
    for step in script:
        out.emit(step)
    return [e.to_wire() for e in out.events]


def schedule_final_set(genesis_peers, peers, requests: Sequence[Request]):
    """The validator set the schedule leaves, every request accepted."""
    ps = genesis_peers
    for req in requests:
        ps = (ps.with_new_peer(peers[req.key]) if req.add
              else ps.with_removed_peer(peers[req.key]))
    return ps


class ChurnAudit(NamedTuple):
    """One validator's blocks and validator sets against the reference's."""

    blocks: reference.Audit
    rounds_compared: int
    peer_sets_differing: int
    changes_in_reference: int
    changes_not_applied: int
    note: str


def audit(hg, genesis_peers, final_set, n_requests: int) -> ChurnAudit:
    """Replay one validator's stored events through the sequential
    hashgraph with ``PlusSix`` and compare: every block under
    ``ORACLE_BLOCK_KEYS``, the validator set of every round both hold,
    and the changes — as many as were asked for, each at the reference's
    round, the last set the schedule's."""
    events = reference.stored_events(hg.store)
    missing = hg.topological_index - len(events)
    if missing:
        note = (f"audited history evicted: store holds {len(events)} of "
                f"{hg.topological_index} events")
        return ChurnAudit(
            reference.Audit(note, len(events), 0, 0, missing, 0),
            0, 0, 0, n_requests, note)
    from babble_tpu.hashgraph import Event

    oracle, plus_six = sequential_hashgraph(genesis_peers, len(events))
    for ev in events:
        oracle.insert_event_and_run_consensus(
            Event(ev.body, ev.signature), set_wire_info=True)
    n_blocks = hg.store.last_block_index() + 1
    n_oracle = oracle.store.last_block_index() + 1
    keys = reference.ORACLE_BLOCK_KEYS
    differing = [
        b for b in range(n_blocks)
        if b >= n_oracle
        or reference.block_bytes(oracle.store.get_block(b), keys)
        != reference.block_bytes(hg.store.get_block(b), keys)
    ]
    if differing:
        note = (f"sequential reference disagrees on {len(differing)} of "
                f"{n_blocks} blocks, first {differing[0]} (it made {n_oracle})")
    else:
        note = (f"{len(events)} events, {n_blocks} blocks equal to the "
                "sequential reference's")
    blocks = reference.Audit(note, len(events), n_blocks,
                             oracle.store.consensus_events_count(), 0,
                             len(differing))
    last = min(hg.store.last_round(), oracle.store.last_round())
    # a change reaches up to six rounds past the last block's round
    rounds = range(0, last + EFFECTIVE_DELAY + 2)
    sets_differing = sum(
        1 for r in rounds
        if hg.store.get_peer_set(r).hash() != oracle.store.get_peer_set(r).hash())
    theirs = oracle.store.get_all_peer_sets()
    ours = hg.store.get_all_peer_sets()
    # every change the reference made that the validator did not make at
    # the same round to the same set, every request the reference itself
    # never saw committed, and a last set that is not the schedule's
    not_applied = sum(
        1 for r, ps in theirs.items()
        if r > 0 and [p.pub_key_hex for p in ours.get(r, [])]
        != [p.pub_key_hex for p in ps])
    not_applied += max(0, n_requests - len(plus_six.changes))
    newest = hg.store.get_peer_set(max(ours))
    if newest.hash() != final_set.hash():
        not_applied += 1
    note += (f"; validator sets of rounds 0..{rounds[-1]}: "
             f"{sets_differing} differ; the reference applied "
             f"{len(plus_six.changes)} of {n_requests} requests, "
             f"{not_applied} not applied alike by the validator")
    return ChurnAudit(blocks, len(rounds), sets_differing,
                      len(plus_six.changes), not_applied, note)


def multi_set_launches(counters: Dict[str, float]) -> Tuple[float, float]:
    """(launches whose bucket holds two or more validator-set slots, all
    launches) from the ``*_bucket_launches.<BxWxExPxSxR>`` counters."""
    multi = total = 0.0
    for name, n in counters.items():
        if n <= 0 or not name.startswith(
                ("batch_bucket_launches.", "accel_bucket_launches.")):
            continue
        dims = name.split(".", 1)[1].split("x")
        total += n
        if int(dims[4]) >= 2:
            multi += n
    return multi, total
