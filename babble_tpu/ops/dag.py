"""Tensorized hashgraph pipeline — the DAG consensus math as XLA programs.

This is the TPU-first re-expression of the consensus hot loops (SURVEY.md §7
step 4b-d). Instead of the oracle's per-event recursion with LRU caches
(reference: src/hashgraph/hashgraph.go:172-206 stronglySee, 208-282 round,
875-998 DecideFame, 1002-1095 DecideRoundReceived), the whole undetermined
window is packed into dense struct-of-arrays tensors and processed with
masked comparisons, matmuls, and fixpoint sweeps:

- events are rows; peers are columns (``PeerSet.peer_index`` fixes the
  coordinate of each peer).
- ``last_ancestors``/``first_descendants`` become ``[E, P] int32`` tensors:
  each event's two integer rows (``Hashgraph.window_coordinates``), their
  columns permuted from the hashgraph's column space to the peer columns.
- ``stronglySee`` becomes a broadcast compare + super-majority reduction —
  an ``[E, E, P]`` masked tensor summed over P.
- round assignment becomes a bounded fixpoint sweep (``lax.while_loop``):
  each pass propagates parent rounds one DAG level further.
- virtual voting becomes per-round vote matrices ``[E, E]`` updated by
  masked matmuls (yay counts = SS @ votes), with coin-round hash bits.
- round-received becomes famous-witness see-mask reductions.

Everything is jittable with static shapes (pad E to a bucket size for
compile-cache friendliness). Differential-tested against the CPU oracle on
the golden DAGs in tests/test_ops_dag.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from babble_tpu.ops.intdot import vote_matmul

INT32_MAX = np.int32(2**31 - 1)


@dataclass
class DagSnapshot:
    """Dense struct-of-arrays view of a DAG window.

    E = number of events (topological order), P = number of peers.
    Missing coordinates: last_ancestors = -1, first_descendants = INT32_MAX
    (the host rows' ``_LA_MISSING`` / ``_FD_MISSING``, clamped to int32).
    """

    creator: np.ndarray  # [E] int32, peer index of each event's creator
    index: np.ndarray  # [E] int32, per-creator sequence number
    self_parent: np.ndarray  # [E] int32, event row of self-parent, -1 if none
    other_parent: np.ndarray  # [E] int32, event row of other-parent, -1 if none
    last_ancestors: np.ndarray  # [E, P] int32
    first_descendants: np.ndarray  # [E, P] int32
    middle_bit: np.ndarray  # [E] bool, coin-round bit of each event's hash
    n_peers: int
    hashes: List[str]  # row -> event hex (host-side bookkeeping only)

    @property
    def n_events(self) -> int:
        return int(self.creator.shape[0])

    # super-majority threshold of the window's peer-set; filled by
    # snapshot_from_hashgraph from PeerSet.super_majority() so the tensor
    # pipeline can never drift from the oracle's rule.
    super_majority: int = 0


def snapshot_from_hashgraph(h, event_hashes: Optional[List[str]] = None) -> DagSnapshot:
    """Extract a DagSnapshot from a Hashgraph (oracle) store.

    ``event_hashes`` defaults to all events in topological order. The peer
    coordinate is the sorted-PeerSet index (PeerSet.peer_index).
    """
    from babble_tpu.hashgraph.hashgraph import middle_bit

    store = h.store
    peer_set = store.get_peer_set(0)
    pub_keys = peer_set.pub_keys()
    peer_col = {pk: i for i, pk in enumerate(pub_keys)}
    n_peers = len(pub_keys)

    if event_hashes is None:
        from babble_tpu.common.errors import StoreError

        events = []
        for pk in pub_keys:
            try:
                hashes = store.participant_events(pk, -1)
            except StoreError:
                continue  # participant has no events yet
            events.extend(store.get_event(eh) for eh in hashes)
        events.sort(key=lambda e: e.topological_index)
        event_hashes = [e.hex() for e in events]

    row = {eh: i for i, eh in enumerate(event_hashes)}
    E = len(event_hashes)

    creator = np.full(E, -1, np.int32)
    index = np.full(E, -1, np.int32)
    self_parent = np.full(E, -1, np.int32)
    other_parent = np.full(E, -1, np.int32)
    la = np.full((E, n_peers), -1, np.int32)
    fd = np.full((E, n_peers), INT32_MAX, np.int32)
    mid = np.zeros(E, bool)

    src = h.coord_columns(pub_keys)
    for i, eh in enumerate(event_hashes):
        ev = store.get_event(eh)
        creator[i] = peer_col[ev.creator()]
        index[i] = ev.index()
        self_parent[i] = row.get(ev.self_parent(), -1)
        other_parent[i] = row.get(ev.other_parent(), -1)
        la[i], fd[i] = h.window_coordinates(ev, src)
        mid[i] = middle_bit(eh)

    return DagSnapshot(
        creator=creator,
        index=index,
        self_parent=self_parent,
        other_parent=other_parent,
        last_ancestors=la,
        first_descendants=fd,
        middle_bit=mid,
        n_peers=n_peers,
        hashes=list(event_hashes),
        super_majority=peer_set.super_majority(),
    )


# =============================================================================
# Predicates as tensor ops
# =============================================================================


def see_matrix(creator: jnp.ndarray, index: jnp.ndarray, la: jnp.ndarray) -> jnp.ndarray:
    """SEE[x, y] = x sees y = la[x, creator(y)] >= index(y)
    (oracle: Hashgraph._ancestor via lastAncestors, hashgraph.go:108-128)."""
    # gather la[x, creator[y]] -> [E, E]
    la_xc = la[:, creator]  # [E(x), E(y)]
    return la_xc >= index[None, :]


def strongly_see_matrix(
    la: jnp.ndarray, fd: jnp.ndarray, super_majority: int
) -> jnp.ndarray:
    """SS[x, y] = #{p : la[x,p] >= fd[y,p]} >= super_majority, with missing
    coordinates excluded by the -1 / INT32_MAX sentinels
    (oracle: hashgraph.go:184-206).

    Memory note: materializes [E, E, P]; BABBLE_PALLAS=1 (a TPU is then
    required) routes this through the Pallas tiled kernel
    (ops/pallas_kernels.strongly_see_pallas), which streams the peer axis
    through VMEM instead — O(TILE_X * E) peak, no [E, E, P] intermediate.
    """
    from babble_tpu.ops.device import pallas_requested

    if pallas_requested():
        from babble_tpu.ops.pallas_kernels import strongly_see_pallas

        return strongly_see_pallas(la, fd, super_majority)
    ge = la[:, None, :] >= fd[None, :, :]  # [E, E, P]
    counts = jnp.sum(ge, axis=-1, dtype=jnp.int32)
    return counts >= super_majority


# =============================================================================
# Round assignment — fixpoint frontier sweep
# =============================================================================


def compute_rounds(
    creator: jnp.ndarray,
    self_parent: jnp.ndarray,
    other_parent: jnp.ndarray,
    ss: jnp.ndarray,
    super_majority: int,
    max_iters: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Iteratively compute (rounds, witness flags) for every event.

    Replaces the oracle's recursive ``round``/``witness`` (hashgraph.go:
    208-327): each sweep recomputes every event's round from its parents'
    current rounds and the strongly-seen witnesses of the parent round;
    sweeping to fixpoint propagates one DAG level per pass. All ops are
    static-shape tensor ops, so XLA fuses the whole sweep into one program.
    """
    E = creator.shape[0]
    if max_iters is None:
        max_iters = E + 2

    has_sp = self_parent >= 0
    has_op = other_parent >= 0
    sp = jnp.where(has_sp, self_parent, 0)
    op = jnp.where(has_op, other_parent, 0)

    def witness_of(rounds: jnp.ndarray) -> jnp.ndarray:
        # witness = first event of its round on its creator's chain:
        # no self-parent, or round > self-parent's round (hashgraph.go:297-327).
        sp_round = jnp.where(has_sp, rounds[sp], -1)
        return rounds > sp_round

    def sweep(rounds: jnp.ndarray) -> jnp.ndarray:
        sp_round = jnp.where(has_sp, rounds[sp], -1)
        op_round = jnp.where(has_op, rounds[op], -1)
        parent_round = jnp.maximum(sp_round, op_round)  # [E]

        wit = witness_of(rounds)
        # count witnesses w of round parent_round[x] strongly seen by x
        same_round = rounds[None, :] == parent_round[:, None]  # [E(x), E(w)]
        seen = ss & same_round & wit[None, :]
        counts = jnp.sum(seen, axis=1)
        inc = counts >= super_majority
        return jnp.where(parent_round < 0, 0, parent_round + inc)

    def cond(state):
        i, rounds, changed = state
        return jnp.logical_and(i < max_iters, changed)

    def body(state):
        i, rounds, _ = state
        new_rounds = sweep(rounds)
        return i + 1, new_rounds, jnp.any(new_rounds != rounds)

    rounds0 = jnp.zeros(E, jnp.int32)
    _, rounds, _ = lax.while_loop(cond, body, (0, rounds0, jnp.array(True)))
    return rounds, witness_of(rounds)


def compute_lamport(
    self_parent: jnp.ndarray, other_parent: jnp.ndarray, max_iters: Optional[int] = None
) -> jnp.ndarray:
    """Lamport timestamps via the same fixpoint pattern
    (oracle: hashgraph.go:355-387)."""
    E = self_parent.shape[0]
    if max_iters is None:
        max_iters = E + 2
    has_sp = self_parent >= 0
    has_op = other_parent >= 0
    sp = jnp.where(has_sp, self_parent, 0)
    op = jnp.where(has_op, other_parent, 0)

    def body(state):
        i, lt, _ = state
        plt = jnp.maximum(
            jnp.where(has_sp, lt[sp], -1), jnp.where(has_op, lt[op], -1)
        )
        new_lt = plt + 1
        return i + 1, new_lt, jnp.any(new_lt != lt)

    def cond(state):
        i, _, changed = state
        return jnp.logical_and(i < max_iters, changed)

    _, lt, _ = lax.while_loop(
        cond, body, (0, jnp.zeros(E, jnp.int32), jnp.array(True))
    )
    return lt


# =============================================================================
# Virtual voting — fame as masked matmuls
# =============================================================================


def decide_fame(
    rounds: jnp.ndarray,
    witness: jnp.ndarray,
    see: jnp.ndarray,
    ss: jnp.ndarray,
    middle_bit: jnp.ndarray,
    super_majority: int,
    last_round: int,
    coin_round_freq: int = 4,
) -> jnp.ndarray:
    """Fame of every witness: +1 famous, 0 undecided, -1 not famous.

    Vectorization of the oracle's VOTE_LOOP (hashgraph.go:875-998): for each
    voting round j, every remaining witness-pair (y in round j, x any earlier
    witness) updates in parallel:

    - diff == 1: votes[y, x] = SEE[y, x]
    - else: yays[y, x] = Σ_w SS_j-1[y, w] · votes[w, x] over witnesses w of
      round j-1 — one boolean matmul for ALL (y, x) pairs at once; majority
      and super-majority thresholds decide or carry the vote; coin rounds
      (diff % freq == 0) fall back to y's hash bit when not settled.

    Decisions freeze (first decision wins), exactly like the sticky
    roundEvent.Famous in the oracle.
    """
    E = rounds.shape[0]

    def per_round(j, state):
        votes, fame = state
        # voters: witnesses of round j
        voter = witness & (rounds == j)  # [E]
        diff = j - rounds  # [E(x)] per candidate

        # --- direct vote at diff 1
        direct = see  # [E(y), E(x)]

        # --- derived vote: majority among strongly-seen witnesses of j-1
        prev_wit = witness & (rounds == (j - 1))  # [E(w)]
        ss_prev = ss & prev_wit[None, :]  # [E(y), E(w)]
        n_ss = jnp.sum(ss_prev, axis=1)  # [E(y)]
        # the pipeline's FLOPs center, as an exact int8->int32 MXU tally
        yays = vote_matmul(ss_prev, votes)  # [E(y), E(x)]
        nays = n_ss[:, None] - yays
        v = yays >= nays
        t = jnp.maximum(yays, nays)
        settled = t >= super_majority

        is_coin = (diff % coin_round_freq) == 0  # [E(x)]
        # normal round: vote = v; decided when settled
        # coin round: vote = v if settled else middle_bit(y)
        derived_vote = jnp.where(
            is_coin[None, :] & ~settled, middle_bit[:, None], v
        )
        new_vote = jnp.where((diff == 1)[None, :], direct, derived_vote)

        # A (y, x) pair only participates when y is a voter and x is an
        # earlier witness (diff >= 1).
        active = voter[:, None] & witness[None, :] & (diff >= 1)[None, :]
        votes = jnp.where(active, new_vote, votes)

        # Decisions: normal rounds only, settled pairs, undecided candidates.
        decide_pair = (
            active & ~is_coin[None, :] & (diff > 1)[None, :] & settled
        )  # [E(y), E(x)]
        decided_now = jnp.any(decide_pair, axis=0)  # [E(x)]
        # value decided: v from any deciding voter (all deciding voters of the
        # same x agree by construction — they share the settled super-majority)
        decided_val = jnp.any(decide_pair & v, axis=0)
        newly = decided_now & (fame == 0)
        fame = jnp.where(newly, jnp.where(decided_val, 1, -1), fame)
        return votes, fame

    votes0 = jnp.zeros((E, E), bool)
    fame0 = jnp.zeros(E, jnp.int32)
    votes, fame = lax.fori_loop(1, last_round + 1, per_round, (votes0, fame0))
    return fame


def decide_round_received(
    rounds: jnp.ndarray,
    witness: jnp.ndarray,
    fame: jnp.ndarray,
    see: jnp.ndarray,
    super_majority: int,
    last_round: int,
) -> jnp.ndarray:
    """round_received[x], or -1 if undetermined (oracle: hashgraph.go:1002-1095).

    For each decided round i (all witnesses decided), an event x is received
    at the FIRST i > round(x) where every famous witness of i sees x and the
    famous count reaches the super-majority — a per-round boolean reduction
    over the SEE mask.
    """
    E = rounds.shape[0]

    # decided round: has witnesses, none undecided, famous count... The oracle
    # requires a super-majority of decided witnesses and zero undecided.
    def round_decided(i):
        wits = witness & (rounds == i)
        undecided = wits & (fame == 0)
        n_decided = jnp.sum(wits & (fame != 0))
        return (~jnp.any(undecided)) & (n_decided >= super_majority)

    def per_round(i, state):
        rr, blocked = state
        decided = round_decided(i)
        fw = witness & (rounds == i) & (fame == 1)  # famous witnesses of i
        n_fw = jnp.sum(fw)
        # x received at i: every famous witness sees x, count >= sm
        sees_x = see | (~fw)[:, None]  # ignore non-famous rows
        all_see = jnp.all(sees_x, axis=0) & (n_fw >= super_majority)
        relevant = rounds < i  # the oracle's i loop starts at round(x)+1
        eligible = decided & ~blocked & relevant & (rr < 0) & all_see
        rr = jnp.where(eligible, i, rr)
        # An event stops scanning at its first undecided round AFTER its own
        # round (the oracle breaks out of the per-event i loop) — per-event,
        # because the scan starts at round(x)+1.
        blocked = blocked | (relevant & ~decided)
        return rr, blocked

    rr0 = jnp.full(E, -1, jnp.int32)
    blocked0 = jnp.zeros(E, bool)
    rr, _ = lax.fori_loop(1, last_round + 1, per_round, (rr0, blocked0))
    return rr


# =============================================================================
# Full pipeline entry
# =============================================================================


# Counts traces of _run_jit, so tests can pin the compile-cache property.
_trace_count = 0


def pipeline_core(creator, index, sp, op, la, fd, mid, sm, round_bound):
    """The whole consensus sweep as one traceable function. ``sm`` and
    ``round_bound`` must be Python ints (static under jit).

    Returns (see, ss, packed) where packed is [5, E] int32 stacking
    (rounds, witness, lamport, fame, round_received) — one tensor, so the
    host pays a single device→host transfer for all per-event results.
    """
    global _trace_count
    _trace_count += 1
    see = see_matrix(creator, index, la)
    ss = strongly_see_matrix(la, fd, sm)
    rounds, wit = compute_rounds(creator, sp, op, ss, sm)
    lamport = compute_lamport(sp, op)
    fame = decide_fame(rounds, wit, see, ss, mid, sm, round_bound)
    rr = decide_round_received(rounds, wit, fame, see, sm, round_bound)
    packed = jnp.stack(
        [
            rounds.astype(jnp.int32),
            wit.astype(jnp.int32),
            lamport.astype(jnp.int32),
            fame.astype(jnp.int32),
            rr.astype(jnp.int32),
        ]
    )
    return see, ss, packed


_run_jit = partial(jax.jit, static_argnums=(7, 8))(pipeline_core)


def run_pipeline(
    snapshot: DagSnapshot, return_matrices: bool = False
) -> Dict[str, np.ndarray]:
    """Run the tensorized pipeline on a snapshot; returns host arrays.

    This is the all-at-once (batch) formulation: given the DAG window, it
    computes rounds, witnesses, lamport timestamps, fame, and round-received
    in one jit-compiled program, cached per (shape, super-majority, bound).

    Only the [E] per-event outputs are fetched to the host; the [E, E]
    see/strongly-see matrices are device intermediates and are only
    transferred when ``return_matrices`` is set (host<->device bandwidth is
    the bottleneck, not FLOPs — fetching them costs ~7x the compute).
    """
    sm = snapshot.super_majority

    # Loop bound for the voting/receiving sweeps. Rounds are data-dependent,
    # but advancing past round r requires strongly seeing a super-majority
    # of round-r witnesses, so every passed round contains >= sm distinct
    # witness events: last_round <= E // sm + 1. The bound is derived from
    # (shape, sm) only — both already static — so the jit cache stays warm
    # across windows. Iterations past the real last round see empty voter
    # masks and are no-ops.
    round_bound = snapshot.n_events // max(1, sm) + 2

    see, ss, packed = _run_jit(
        jnp.asarray(snapshot.creator),
        jnp.asarray(snapshot.index),
        jnp.asarray(snapshot.self_parent),
        jnp.asarray(snapshot.other_parent),
        jnp.asarray(snapshot.last_ancestors),
        jnp.asarray(snapshot.first_descendants),
        jnp.asarray(snapshot.middle_bit),
        sm,
        round_bound,
    )
    host = np.asarray(packed)  # one transfer for all per-event outputs
    out = {
        "rounds": host[0],
        "witness": host[1].astype(bool),
        "lamport": host[2],
        "fame": host[3],
        "round_received": host[4],
    }
    if return_matrices:
        out["see"] = np.asarray(see)
        out["strongly_see"] = np.asarray(ss)
    return out


# =============================================================================
# Synthetic DAG windows (benchmarks, multi-chip dry runs)
# =============================================================================


def synthetic_snapshot(n_peers: int, n_events: int, seed: int = 7) -> DagSnapshot:
    """Build a deterministic gossip-shaped DagSnapshot without any crypto.

    Simulates round-robin-with-jitter gossip: after one root per peer, each
    new event's creator self-parents on its head and other-parents on
    another peer's head. Coordinates (last_ancestors/first_descendants) are
    derived from the exact ancestry closure, so the window is a valid DAG
    in the same dense form snapshot_from_hashgraph produces.
    """
    assert n_events >= n_peers
    rng = np.random.RandomState(seed)

    creator = np.full(n_events, -1, np.int32)
    index = np.full(n_events, -1, np.int32)
    sp = np.full(n_events, -1, np.int32)
    op = np.full(n_events, -1, np.int32)

    heads = [-1] * n_peers
    per_creator_seq = [0] * n_peers
    # ancestry[i, j] = event j is an ancestor of event i (incl. self)
    anc = np.zeros((n_events, n_events), bool)

    for i in range(n_events):
        if i < n_peers:
            c = i  # roots, one per peer
        else:
            c = int(rng.randint(n_peers))
        creator[i] = c
        index[i] = per_creator_seq[c]
        per_creator_seq[c] += 1
        anc[i, i] = True
        if heads[c] >= 0:
            sp[i] = heads[c]
            anc[i] |= anc[heads[c]]
        if i >= n_peers:
            others = [p for p in range(n_peers) if p != c and heads[p] >= 0]
            if others:
                o = int(rng.choice(others))
                op[i] = heads[o]
                anc[i] |= anc[heads[o]]
        heads[c] = i

    la = np.full((n_events, n_peers), -1, np.int32)
    fd = np.full((n_events, n_peers), INT32_MAX, np.int32)
    for i in range(n_events):
        for p in range(n_peers):
            rows = np.where(anc[i] & (creator == p))[0]
            if rows.size:
                la[i, p] = index[rows].max()
        # first descendant of i per peer: min index among events that have
        # i as an ancestor
        desc = np.where(anc[:, i])[0]
        for p in range(n_peers):
            rows = desc[creator[desc] == p]
            if rows.size:
                fd[i, p] = index[rows].min()

    # deterministic pseudo-random coin bits
    mid = ((np.arange(n_events, dtype=np.uint64) * 2654435761) >> 16) & 1 == 1

    sm_threshold = 2 * n_peers // 3 + 1
    return DagSnapshot(
        creator=creator,
        index=index,
        self_parent=sp,
        other_parent=op,
        last_ancestors=la,
        first_descendants=fd,
        middle_bit=mid,
        n_peers=n_peers,
        hashes=[f"synthetic-{i}" for i in range(n_events)],
        super_majority=sm_threshold,
    )
