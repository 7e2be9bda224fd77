"""Live consensus offload: fame + round-received as device tensor programs.

This is the kernel behind ``--accelerator``'s consensus path. The division of
labour with the host is deliberate and reference-exact:

- The host keeps the *incremental* bookkeeping the reference does per insert —
  signature checks, fork prevention, coordinate maintenance, and round/witness
  assignment (reference: src/hashgraph/hashgraph.go:672-750, 807-872). These
  walks gate insert-time semantics (the first-descendant walk stops at
  witnesses, hashgraph.go:503-512) so they must observe exactly the state the
  reference would; they are O(depth) per event and cheap.
- The device takes the *batch* work that dominates the pipeline — virtual
  voting (DecideFame, hashgraph.go:875-998) and round-received
  (DecideRoundReceived, hashgraph.go:1002-1095) — as masked matmuls and
  boolean reductions over a dense window snapshot.

Only witnesses vote and are voted on, so the vote state lives on a compact
witness axis W instead of the full event axis E: fame is O(R·W²) and the
see-visibility mask is [W, E], which keeps warm sweeps at
milliseconds even when a large undecided window (E in the hundreds) has
accumulated. (A dense [E, E] formulation measurably death-spirals: slow
sweeps grow the window, which slows sweeps further.)

Unlike :mod:`babble_tpu.ops.dag` (the all-at-once pipeline used by
``__graft_entry__`` and the multi-chip dryrun), these kernels support
**dynamic membership**:
peer-sets vary per round, so the peer axis is padded to the full repertoire
and each round carries a peer-set slot (``psi``) selecting a membership mask
and super-majority threshold (reference: per-round peer-sets in DecideFame,
hashgraph.go:875-998, interval lookup caches.go:126-222).

The whole sweep — fame voting, per-round decidedness, and round-received —
is ONE fused device call returning ONE concatenated int32 vector
``[fame | round_received]``. A design with a host step in the middle (the
round-3 two-call split) pays the device→host readback twice; the fused
kernel pays it once — and the async pipeline in
:mod:`babble_tpu.hashgraph.accel` hides even that behind gossip. (The
readback's cost is not measured on a local chip.)

The oracle's *sticky* round-decided flag (roundInfo.go:73-96; a round once
decided stays decided even if a laggard later inserts an undecided witness)
is preserved by passing the host's pre-sweep sticky flags in and computing
post-sweep decidedness on device: fame decisions are monotone (the kernel
only fills UNDEFINED slots), so device decidedness from (sticky | recompute
over post-sweep fame) equals the oracle's post-apply ``witnesses_decided``.

Shapes are padded to buckets (W, E, R and S to powers of two, P to a
multiple of 8) so XLA compiles once per bucket and the jit cache stays warm
across sweeps; compiled buckets are tracked module-wide so every node in a
process shares warm-up work.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from babble_tpu.common.errors import StoreError
from babble_tpu.ops.intdot import vote_matmul
from babble_tpu.common.trilean import Trilean

INT32_MAX = np.int32(2**31 - 1)

# Frequency of coin rounds (reference: hashgraph.go:24-25). Kept in sync with
# babble_tpu.hashgraph.hashgraph.COIN_ROUND_FREQ.
COIN_ROUND_FREQ = 4


def _bucket_pow2(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _bucket_mult(n: int, m: int) -> int:
    return max(m, ((n + m - 1) // m) * m)


@dataclass
class VotingWindow:
    """Dense window over the undecided suffix of the hashgraph.

    Two row spaces:
    - E rows: undetermined events + all witnesses of rounds >= the window
      floor (``hashes``/``row``). Carries creator/index/rounds/undet.
    - W rows: the witness subset (``wit_hashes``/``wit_row``), indexing into
      E rows via ``wit_idx``. Carries coordinates, fame state, coin bits.

    Rounds are rebased by ``base`` so in-kernel round indexes stay small
    regardless of absolute round numbers.
    """

    # E-space
    creator: np.ndarray  # [E] int32 peer column of creator (0 for padding)
    index: np.ndarray  # [E] int32 per-creator sequence (-1 padding)
    rounds: np.ndarray  # [E] int32 rebased round (-10 padding)
    undet: np.ndarray  # [E] bool — rows eligible for round-received
    # W-space (witnesses)
    wit_idx: np.ndarray  # [W] int32 row in E-space (0 for padding)
    la_w: np.ndarray  # [W, P] int32, -1 missing
    fd_w: np.ndarray  # [W, P] int32, INT32_MAX missing
    rounds_w: np.ndarray  # [W] int32 rebased (-10 padding)
    valid_w: np.ndarray  # [W] bool
    fame0_w: np.ndarray  # [W] int32 {-1, 0, 1} initial fame from round infos
    mid_w: np.ndarray  # [W] bool coin bits
    # peer-sets per round
    member: np.ndarray  # [S, P] bool membership masks
    sm_s: np.ndarray  # [S] int32 super-majority per slot
    psi: np.ndarray  # [R] int32 rebased-round -> peer-set slot
    sm_r: np.ndarray  # [R] int32 rebased-round -> super-majority
    # round-scan state for the fused decided/hard-block computation
    exists_r: np.ndarray  # [R] bool — round info readable from the store
    prior_dec_r: np.ndarray  # [R] bool — pre-sweep sticky decided flags
    lb_gate_r: np.ndarray  # [R] bool — round above the fast-sync lower bound
    base: int  # absolute round of rebased round 0
    hashes: List[str] = field(default_factory=list)  # real E rows
    row: Dict[str, int] = field(default_factory=dict)
    wit_hashes: List[str] = field(default_factory=list)  # real W rows
    wit_row: Dict[str, int] = field(default_factory=dict)
    # Resident-window provenance (ops/window_state.py): windows snapshotted
    # from a persistent WindowState carry the state's generation at
    # snapshot time plus a back-reference, so downstream consumers (the
    # sweep batcher, TensorConsensus._apply) can detect that the state
    # mutated underneath them and discard stale results instead of
    # applying them through moved row maps.
    generation: int = 0
    state: Optional[object] = None

    @property
    def n_events(self) -> int:
        return int(self.creator.shape[0])

    @property
    def n_witnesses(self) -> int:
        return int(self.wit_idx.shape[0])


# =============================================================================
# Kernels
# =============================================================================


def pallas_mode() -> Optional[str]:
    """How the live sweep's membership strongly-see should run:
    ``"tpu"`` (BABBLE_PALLAS=1 — the compiled Pallas tiled kernel; raises
    off a TPU), ``"interpret"`` (BABBLE_PALLAS_INTERPRET=1 — the same
    kernel in interpreter mode, for differential tests on CPU), or None
    (the XLA einsum). Evaluated at TRACE time, so it must be set before
    the first sweep of a shape bucket compiles."""
    import os

    from babble_tpu.ops.device import pallas_requested

    if os.environ.get("BABBLE_PALLAS_INTERPRET") == "1":
        return "interpret"
    return "tpu" if pallas_requested() else None


def _strongly_see_counts(la_w, fd_w, member):
    """counts[s, w, w'] = peers of slot s through which w strongly sees w'
    (oracle: hashgraph.go:172-206 with the per-round peer-set argument).
    The [W, W, P] compare stays small because W is the witness count, not
    the event count."""
    mode = pallas_mode()
    if mode is not None:
        # Pallas tiled kernel: streams the peer axis through VMEM, no
        # [W, W, P] intermediate (ops/pallas_kernels.py). Bit-identical
        # counts; differential-tested in interpreter mode.
        from babble_tpu.ops.pallas_kernels import member_ss_counts_pallas

        counts = member_ss_counts_pallas(
            la_w, fd_w, member, interpret=(mode == "interpret")
        )
    else:
        # XLA einsum: operands are 0/1, so int8 inputs with an int32
        # accumulator are EXACT while letting the TPU tile the contraction
        # onto the MXU (int8 matmul units) instead of the VPU; counts are
        # bounded by P (peer axis) which fits int32 trivially.
        ge = (la_w[:, None, :] >= fd_w[None, :, :]).astype(jnp.int8)
        counts = jnp.einsum(
            "vwp,sp->svw",
            ge,
            member.astype(jnp.int8),
            preferred_element_type=jnp.int32,
        )
    return counts


def _fame_core(creator, index, la_w, fd_w, rounds_w, valid_w, fame0_w, mid_w,
               wit_idx, member, sm_s, psi, sm_r):
    """Virtual voting on the witness axis (oracle: hashgraph.go:875-998)
    with per-round peer-sets. Returns (see_we, fame_w); ``see_we`` ([W, E],
    witness w sees event x) stays on device for the round-received kernel."""
    R = psi.shape[0]

    # SEE[w, x] = w sees x via lastAncestors (oracle: hashgraph.go:96-128).
    see_we = (la_w[:, creator] >= index[None, :]) & valid_w[:, None]
    see_ww = see_we[:, wit_idx]  # witness-to-witness visibility

    with jax.named_scope("strongly_see_counts"):
        counts = _strongly_see_counts(la_w, fd_w, member)
    ss_all = counts >= sm_s[:, None, None]  # [S, W, W]

    def per_round(j, state):
        votes, fame = state
        voter = valid_w & (rounds_w == j)  # [W(y)]
        diff = j - rounds_w  # [W(x)] per candidate

        # Derived vote: majority among strongly-seen witnesses of j-1,
        # evaluated against round j-1's peer-set (hashgraph.go:928-948).
        prev_w = valid_w & (rounds_w == (j - 1))
        slot_prev = psi[jnp.clip(j - 1, 0, R - 1)]
        ss_prev = ss_all[slot_prev] & prev_w[None, :]  # [W(y), W(w)]
        n_ss = jnp.sum(ss_prev, axis=1, dtype=jnp.int32)
        yays = vote_matmul(ss_prev, votes)  # exact int8->int32 MXU tally
        nays = n_ss[:, None] - yays
        v = yays >= nays
        t = jnp.maximum(yays, nays)
        sm_j = sm_r[jnp.clip(j, 0, R - 1)]  # round j's super-majority
        settled = t >= sm_j

        is_coin = (diff % COIN_ROUND_FREQ) == 0
        derived = jnp.where(is_coin[None, :] & ~settled, mid_w[:, None], v)
        new_vote = jnp.where((diff == 1)[None, :], see_ww, derived)

        active = voter[:, None] & valid_w[None, :] & (diff >= 1)[None, :]
        votes = jnp.where(active, new_vote, votes)

        decide_pair = active & ~is_coin[None, :] & (diff > 1)[None, :] & settled
        decided_now = jnp.any(decide_pair, axis=0)
        decided_val = jnp.any(decide_pair & v, axis=0)
        newly = decided_now & (fame == 0)
        fame = jnp.where(newly, jnp.where(decided_val, 1, -1), fame)
        return votes, fame

    W = rounds_w.shape[0]
    votes0 = jnp.zeros((W, W), bool)
    votes, fame = lax.fori_loop(1, R, per_round, (votes0, fame0_w))
    return see_we, fame


def _rr_core(see_we, rounds_w, valid_w, fame_w, rounds_e, undet_e,
             decided_r, hard_block_r, sm_r):
    """Round-received (oracle: hashgraph.go:1002-1095). ``decided_r`` and
    ``hard_block_r`` are host-computed per-round masks carrying the oracle's
    exact scan semantics: an event's ascending round scan stops at the
    first hard-blocking round after its own (a missing round info, or an
    undecided round above the fast-sync lower bound — hashgraph.go:1019-1046)
    and receives only at decided rounds."""
    E = rounds_e.shape[0]
    R = decided_r.shape[0]

    def per_round(i, state):
        rr, blocked = state
        fw = valid_w & (rounds_w == i) & (fame_w == 1)  # famous witnesses of i
        n_fw = jnp.sum(fw, dtype=jnp.int32)
        sees_x = see_we | (~fw)[:, None]
        all_see = jnp.all(sees_x, axis=0) & (n_fw >= sm_r[jnp.clip(i, 0, R - 1)])
        relevant = rounds_e < i
        eligible = (
            decided_r[i] & ~blocked & relevant & (rr < 0) & all_see & undet_e
        )
        rr = jnp.where(eligible, i, rr)
        blocked = blocked | (relevant & hard_block_r[i])
        return rr, blocked

    rr0 = jnp.full(E, -1, jnp.int32)
    blocked0 = jnp.zeros(E, bool)
    rr, _ = lax.fori_loop(1, R, per_round, (rr0, blocked0))
    return rr


def _sweep_core(creator, index, la_w, fd_w, rounds_w, valid_w, fame0_w, mid_w,
                wit_idx, member, sm_s, psi, sm_r,
                rounds_e, undet_e, exists_r, prior_dec_r, lb_gate_r):
    """The fused sweep: fame voting → per-round decidedness → round-received
    in one compiled program, one output buffer, one readback.

    Decidedness replicates ``RoundInfo.witnesses_decided``
    (roundInfo.go:78-96) on device: a round is decided when no witness is
    UNDEFINED and the decided count reaches the round's super-majority —
    OR the host's sticky pre-sweep flag was already set. Hard-blocking
    replicates the oracle's receive-scan stops (hashgraph.go:1019-1046):
    an unreadable round blocks unconditionally; an undecided round blocks
    only above the fast-sync lower bound.
    """
    with jax.named_scope("fame"):
        see_we, fame = _fame_core(
            creator, index, la_w, fd_w, rounds_w, valid_w, fame0_w, mid_w,
            wit_idx, member, sm_s, psi, sm_r,
        )
    R = psi.shape[0]
    r_ax = jnp.arange(R)
    m_rw = valid_w[None, :] & (rounds_w[None, :] == r_ax[:, None])  # [R, W]
    undecided_w = fame == 0
    has_undec = jnp.any(m_rw & undecided_w[None, :], axis=1)
    cnt = jnp.sum(m_rw & (~undecided_w)[None, :], axis=1, dtype=jnp.int32)
    decided_r = prior_dec_r | (exists_r & ~has_undec & (cnt >= sm_r))
    hard_block_r = (~exists_r) | ((~decided_r) & lb_gate_r)
    with jax.named_scope("round_received"):
        rr = _rr_core(see_we, rounds_w, valid_w, fame, rounds_e, undet_e,
                      decided_r, hard_block_r, sm_r)
    return jnp.concatenate([fame, rr])


# The two compiled programs. A jitted function's __name__ is the program's
# name in a profiler trace (``jit_<name>``), and the benchmark finds the
# sweep's device time by it: both contain ``counting_sweep``, and the two
# are told apart by what follows.


def counting_sweep_single(*args):
    return _sweep_core(*args)


def counting_sweep_batched(*args):
    """The SAME fused program vmapped over a leading batch axis, so
    co-located nodes' windows ride ONE device dispatch and ONE readback
    (hashgraph/sweep_batcher.py). Exact per-window semantics: vmap adds a
    batch dimension, it never mixes rows."""
    return jax.vmap(_sweep_core)(*args)


_sweep_jit = jax.jit(counting_sweep_single)
_batched_sweep_jit = jax.jit(counting_sweep_batched)


# =============================================================================
# Host side: window construction and result application
# =============================================================================


def _fame_init(trilean: Trilean) -> int:
    if trilean == Trilean.TRUE:
        return 1
    if trilean == Trilean.FALSE:
        return -1
    return 0


def build_voting_window(hg) -> Optional[VotingWindow]:
    """Snapshot the undecided suffix of a Hashgraph into dense tensors.

    Returns None when there is nothing to decide. Raises StoreError when a
    needed event/round has been evicted — the caller falls back to the
    oracle sweep in that case.

    Window floor = min(first pending round, min round over undetermined
    events): pending rounds can trail the undetermined set when all their
    events were received before fame was decided, and vice versa, so both
    bound the rows the vote and receive scans touch.
    """
    store = hg.store
    undetermined = list(hg.undetermined_events)
    pending = [pr.index for pr in hg.pending_rounds.get_ordered_pending_rounds()]
    if not undetermined and not pending:
        return None

    floors = list(pending)
    undet_rounds: Dict[str, int] = {}
    # Events fetched for the floor computation are reused by the row-fill
    # loop below — the undetermined set dominates E, so fetching each row
    # twice doubled the store traffic of every rebuild.
    ev_cache: Dict[str, object] = {}
    for h in undetermined:
        ev = store.get_event(h)
        if ev.round is None:
            return None  # divide_rounds has not run yet
        ev_cache[h] = ev
        undet_rounds[h] = ev.round
        floors.append(ev.round)
    base = min(floors)
    last_round = store.last_round()

    # Peer columns span the full repertoire so any peer-set's mask and any
    # event's coordinates map onto the same axis.
    rep = store.repertoire_by_pub_key()
    pub_keys = sorted(rep.keys())
    peer_col = {pk: i for i, pk in enumerate(pub_keys)}
    n_peers = len(pub_keys)

    # E rows: all undetermined events first (their list order is the
    # oracle's scan order), then every witness of rounds >= base from the
    # round infos. W rows: the witness subset.
    hashes: List[str] = list(undetermined)
    rows = {h: i for i, h in enumerate(hashes)}
    witness_info: Dict[str, tuple] = {}  # hash -> (round, famous)
    for r in range(base, last_round + 1):
        try:
            ri = store.get_round(r)
        except StoreError:
            continue
        for x, re_ in ri.created_events.items():
            if re_.witness:
                witness_info[x] = (r, re_.famous)
                if x not in rows:
                    rows[x] = len(hashes)
                    hashes.append(x)
    wit_hashes = list(witness_info.keys())
    wit_rows = {h: i for i, h in enumerate(wit_hashes)}

    E_real = len(hashes)
    W_real = len(wit_hashes)
    E = _bucket_pow2(E_real, 32)
    W = _bucket_pow2(W_real, 16)
    P = _bucket_mult(n_peers, 8)
    R_real = last_round - base + 2
    R = _bucket_pow2(R_real, 8)

    creator = np.zeros(E, np.int32)
    index = np.full(E, -1, np.int32)
    rounds = np.full(E, -10, np.int32)
    undet_mask = np.zeros(E, bool)
    wit_idx = np.zeros(W, np.int32)
    la_w = np.full((W, P), -1, np.int32)
    fd_w = np.full((W, P), INT32_MAX, np.int32)
    rounds_w = np.full(W, -10, np.int32)
    valid_w = np.zeros(W, bool)
    fame0_w = np.zeros(W, np.int32)
    mid_w = np.zeros(W, bool)

    from babble_tpu.hashgraph.hashgraph import middle_bit

    src = hg.coord_columns(pub_keys)
    for h, i in rows.items():
        ev = ev_cache.get(h)
        if ev is None:
            ev = store.get_event(h)
        creator[i] = peer_col[ev.creator()]
        index[i] = ev.index()
        if h in undet_rounds:
            r_abs = undet_rounds[h]
        else:
            r_abs = witness_info[h][0]
        rounds[i] = r_abs - base
        undet_mask[i] = h in undet_rounds
        w = wit_rows.get(h)
        if w is not None:
            wit_idx[w] = i
            rounds_w[w] = r_abs - base
            valid_w[w] = True
            fame0_w[w] = _fame_init(witness_info[h][1])
            mid_w[w] = middle_bit(h)
            la_w[w, :n_peers], fd_w[w, :n_peers] = hg.window_coordinates(
                ev, src)

    # Per-round peer-sets: one slot per distinct set effective in the window
    # (interval semantics of PeerSetCache.get, caches.go:169-193). Rounds
    # past the last recorded change reuse the final set, which is exactly
    # what the interval lookup returns.
    slot_of: Dict[bytes, int] = {}
    members: List[np.ndarray] = []
    sms: List[int] = []
    psi = np.zeros(R, np.int32)
    sm_r = np.full(R, 2**30, np.int32)
    exists_r = np.zeros(R, bool)
    prior_dec_r = np.zeros(R, bool)
    lb_gate_r = np.zeros(R, bool)
    lb = hg.round_lower_bound
    for r in range(R):
        a = base + r
        lb_gate_r[r] = lb is None or lb < a
        try:
            ri = store.get_round(a)
        except StoreError:
            pass  # exists_r stays False -> hard-blocks the receive scan
        else:
            exists_r[r] = True
            prior_dec_r[r] = ri.decided
        ps = store.get_peer_set(a)
        key = ps.hash()
        s = slot_of.get(key)
        if s is None:
            s = len(members)
            slot_of[key] = s
            m = np.zeros(P, bool)
            for pk in ps.pub_keys():
                c = peer_col.get(pk)
                if c is not None:
                    m[c] = True
            members.append(m)
            sms.append(ps.super_majority())
        psi[r] = s
        sm_r[r] = sms[s]

    S = _bucket_pow2(len(members), 1)
    member = np.zeros((S, P), bool)
    sm_s = np.full(S, 2**30, np.int32)
    for s, m in enumerate(members):
        member[s] = m
        sm_s[s] = sms[s]

    return VotingWindow(
        creator=creator,
        index=index,
        rounds=rounds,
        undet=undet_mask,
        wit_idx=wit_idx,
        la_w=la_w,
        fd_w=fd_w,
        rounds_w=rounds_w,
        valid_w=valid_w,
        fame0_w=fame0_w,
        mid_w=mid_w,
        member=member,
        sm_s=sm_s,
        psi=psi,
        sm_r=sm_r,
        exists_r=exists_r,
        prior_dec_r=prior_dec_r,
        lb_gate_r=lb_gate_r,
        base=base,
        hashes=hashes,
        row=rows,
        wit_hashes=wit_hashes,
        wit_row=wit_rows,
    )


def bucket_key(win: VotingWindow) -> tuple:
    return (
        win.n_witnesses,
        win.n_events,
        win.member.shape[1],
        win.member.shape[0],
        win.psi.shape[0],
    )


def bucket_label(key: tuple, batch: int = 1) -> str:
    """``BxWxExPxSxR``: the name a launch of the program at bucket ``key``
    (``batch`` windows to a vmapped execution) is counted under in the
    ``*_bucket_launches`` stats. It holds no ``.``."""
    return "x".join(str(int(d)) for d in (batch,) + tuple(key))


def count_launch(launches: Dict[str, int], key: tuple, batch: int = 1) -> None:
    """Count one launch of the program at bucket ``key`` in ``launches``,
    the ``bucket_launches`` tally of whoever launched it."""
    label = bucket_label(key, batch)
    launches[label] = launches.get(label, 0) + 1


def repad_window(win: VotingWindow, key: tuple) -> VotingWindow:
    """Grow a window to a LARGER shape bucket with the same neutral fills
    build_voting_window pads with — co-located nodes at slightly different
    DAG progress land in different buckets, and the batcher re-pads a
    whole wave to their elementwise-max bucket so it rides one dispatch.

    Safe by the same argument as the builder's own padding: invalid W rows
    (valid_w False) never vote and never count; sentinel E rows (index -1,
    undet False) are seen by nobody and can't receive; extra R rows have no
    voters (no witness carries their round) and, being past every real
    round, their hard-block can't cut an earlier receive scan; extra S
    slots are unreferenced (psi points only at real slots). Row indexes of
    real entries are preserved, so the result maps back through the
    ORIGINAL window's row/wit_row tables."""
    W, E, P, S, R = key
    W0, E0 = win.n_witnesses, win.n_events
    P0, S0, R0 = win.member.shape[1], win.member.shape[0], win.psi.shape[0]
    if (W0, E0, P0, S0, R0) == key:
        return win

    def pad(a, n, fill):
        if n == 0:
            return a
        widths = [(0, n)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths, constant_values=fill)

    la_w = pad(win.la_w, W - W0, -1)
    fd_w = pad(win.fd_w, W - W0, INT32_MAX)
    if P > P0:
        la_w = np.pad(la_w, ((0, 0), (0, P - P0)), constant_values=-1)
        fd_w = np.pad(fd_w, ((0, 0), (0, P - P0)),
                      constant_values=INT32_MAX)
    member = pad(win.member, S - S0, False)
    if P > P0:
        member = np.pad(member, ((0, 0), (0, P - P0)),
                        constant_values=False)
    return VotingWindow(
        creator=pad(win.creator, E - E0, 0),
        index=pad(win.index, E - E0, -1),
        rounds=pad(win.rounds, E - E0, -10),
        undet=pad(win.undet, E - E0, False),
        wit_idx=pad(win.wit_idx, W - W0, 0),
        la_w=la_w,
        fd_w=fd_w,
        rounds_w=pad(win.rounds_w, W - W0, -10),
        valid_w=pad(win.valid_w, W - W0, False),
        fame0_w=pad(win.fame0_w, W - W0, 0),
        mid_w=pad(win.mid_w, W - W0, False),
        member=member,
        sm_s=pad(win.sm_s, S - S0, 2**30),
        psi=pad(win.psi, R - R0, 0),
        sm_r=pad(win.sm_r, R - R0, 2**30),
        exists_r=pad(win.exists_r, R - R0, False),
        prior_dec_r=pad(win.prior_dec_r, R - R0, False),
        lb_gate_r=pad(win.lb_gate_r, R - R0, False),
        base=win.base,
        hashes=win.hashes,
        row=win.row,
        wit_hashes=win.wit_hashes,
        wit_row=win.wit_row,
        generation=win.generation,
        state=win.state,
    )


# Compiled-bucket bookkeeping shared by every TensorConsensus in the process
# (the underlying jit cache is global, so warm-up work must be too).
_ready_buckets: set = set()
_ready_lock = None  # created lazily to keep import cheap


def _bucket_lock():
    global _ready_lock
    if _ready_lock is None:
        import threading

        _ready_lock = threading.Lock()
    return _ready_lock


def bucket_ready(key: tuple) -> bool:
    with _bucket_lock():
        return key in _ready_buckets


def mark_bucket_ready(key: tuple) -> None:
    with _bucket_lock():
        _ready_buckets.add(key)


def ready_buckets() -> set:
    """The shape buckets whose single-window program is compiled: the
    shapes this process has had in use."""
    with _bucket_lock():
        return set(_ready_buckets)


# The vmapped program is a different executable per (batch, bucket); its
# readiness is tracked separately so the batcher can route unwarmed batch
# shapes through warm single-window dispatches meanwhile.
_ready_batched: set = set()


def batched_ready(key: tuple, batch: int) -> bool:
    with _bucket_lock():
        return (batch, key) in _ready_batched


def precompile_batched(batch: int, W: int, E: int, P: int, S: int,
                       R: int) -> None:
    """Compile (or load from the persistent cache) the batched sweep for a
    (batch, bucket) pair on all-invalid dummy windows."""
    key = (W, E, P, S, R)
    wins = [dummy_window(*key) for _ in range(batch)]
    read_batched(launch_batched(wins, batch), wins)
    with _bucket_lock():
        _ready_batched.add((batch, key))


def dummy_window(W: int, E: int, P: int, S: int, R: int) -> VotingWindow:
    """An all-invalid window of a given shape bucket, for precompilation."""
    return VotingWindow(
        creator=np.zeros(E, np.int32),
        index=np.full(E, -1, np.int32),
        rounds=np.full(E, -10, np.int32),
        undet=np.zeros(E, bool),
        wit_idx=np.zeros(W, np.int32),
        la_w=np.full((W, P), -1, np.int32),
        fd_w=np.full((W, P), INT32_MAX, np.int32),
        rounds_w=np.full(W, -10, np.int32),
        valid_w=np.zeros(W, bool),
        fame0_w=np.zeros(W, np.int32),
        mid_w=np.zeros(W, bool),
        member=np.zeros((S, P), bool),
        sm_s=np.full(S, 2**30, np.int32),
        psi=np.zeros(R, np.int32),
        sm_r=np.full(R, 2**30, np.int32),
        exists_r=np.zeros(R, bool),
        prior_dec_r=np.zeros(R, bool),
        lb_gate_r=np.zeros(R, bool),
        base=0,
    )


def precompile(W: int, E: int, P: int, S: int, R: int) -> None:
    """Compile (or load from the persistent cache) the fused sweep kernel
    for a shape bucket by running it on an all-invalid dummy window. Called
    from a background thread (TensorConsensus / node prewarm) so live
    sweeps never stall on XLA compilation."""
    run_sweep(dummy_window(W, E, P, S, R))
    mark_bucket_ready((W, E, P, S, R))


# VotingWindow attribute names in _sweep_core's positional order (rounds /
# undet are the E-space rounds_e / undet_e arguments).
_WIN_FIELDS = (
    "creator", "index", "la_w", "fd_w", "rounds_w", "valid_w", "fame0_w",
    "mid_w", "wit_idx", "member", "sm_s", "psi", "sm_r", "rounds", "undet",
    "exists_r", "prior_dec_r", "lb_gate_r",
)


def launch_sweep(win: VotingWindow):
    """Dispatch the fused sweep. Returns the device output buffer WITHOUT
    reading it back — the readback is paid by read_sweep (on a
    background thread in the node's pipelined mode)."""
    return _sweep_jit(*(jnp.asarray(getattr(win, f)) for f in _WIN_FIELDS))


_dummy_cache: Dict[tuple, VotingWindow] = {}


def _cached_dummy(key: tuple) -> VotingWindow:
    """Batch-padding dummies are deterministic per bucket; caching one per
    key keeps the ~20-array allocation off the hot flush path (the same
    object is stacked repeatedly — stacking copies the data anyway)."""
    win = _dummy_cache.get(key)
    if win is None:
        win = _dummy_cache[key] = dummy_window(*key)
    return win


def launch_batched(wins: List[VotingWindow], batch: int):
    """Dispatch ONE batched sweep over same-bucket windows, padded with
    all-invalid dummies to ``batch`` rows (one program per (B, bucket)).
    Returns the [B, W+E] device buffer unread."""
    key = bucket_key(wins[0])
    ws = list(wins) + [_cached_dummy(key)] * (batch - len(wins))
    stacked = (
        jnp.asarray(np.stack([np.asarray(getattr(w, f)) for w in ws]))
        for f in _WIN_FIELDS
    )
    return _batched_sweep_jit(*stacked)


def read_batched(out, wins: List[VotingWindow]):
    """ONE readback of the [B, W+E] batched output, split into per-window
    (fame, rr) pairs (padding rows discarded)."""
    host = np.asarray(out)
    res = []
    for i, w in enumerate(wins):
        W = w.n_witnesses
        res.append((host[i, :W], host[i, W:W + w.n_events]))
    return res


def read_sweep(out, win: VotingWindow):
    """One readback of the concatenated [fame | round_received] vector,
    split into (fame[W], rr[E]) numpy arrays."""
    host = np.asarray(out)
    W = win.n_witnesses
    return host[:W], host[W:W + win.n_events]


def run_sweep(win: VotingWindow):
    """Synchronous fused sweep: dispatch + single readback."""
    return read_sweep(launch_sweep(win), win)


def apply_fame(hg, win: VotingWindow, fame: np.ndarray) -> tuple:
    """Write fame into the pending rounds' infos and mark decided rounds
    with the oracle's own sticky rule (mirrors the tail of
    Hashgraph.decide_fame, hashgraph.go:985-996). Returns
    (decided_rounds, applied): ``applied`` is the exact [(hash, ±1)] list
    of set_fame writes, which the incremental WindowState replays into its
    fame mirror at the next snapshot."""
    store = hg.store
    decided_rounds: List[int] = []
    applied: List[tuple] = []
    for pr in hg.pending_rounds.get_ordered_pending_rounds():
        try:
            ri = store.get_round(pr.index)
        except StoreError:
            continue
        ps = store.get_peer_set(pr.index)
        for x, re_ in ri.created_events.items():
            if not re_.witness or re_.famous != Trilean.UNDEFINED:
                continue
            i = win.wit_row.get(x)
            if i is None:
                continue
            f = int(fame[i])
            if f != 0:
                ri.set_fame(x, f == 1)
                applied.append((x, f))
        if ri.witnesses_decided(ps):
            decided_rounds.append(pr.index)
        store.set_round(pr.index, ri)
    hg.pending_rounds.update(decided_rounds)
    return decided_rounds, applied


def apply_round_received(hg, win: VotingWindow, rr: np.ndarray) -> List[str]:
    """Stamp received events and retire them from the undetermined list, in
    the oracle's scan order (mirrors Hashgraph.decide_round_received,
    hashgraph.go:1047-1091). Returns the received hashes — the exact row
    releases the incremental WindowState applies at the next snapshot."""
    store = hg.store
    # Two-phase: gather every fallible store read first so a StoreError can
    # abort BEFORE any mutation — a partially-applied receive pass followed
    # by the oracle fallback would double-receive events (add_received_event
    # has no dedup) and fork the node's blocks from its peers'. Each round's
    # info is fetched ONCE and shared by all its received events: a store
    # that deserializes fresh copies per get (the persistent store) would
    # otherwise keep only the last event of a round.
    new_undetermined: List[str] = []
    updates = []  # (event, round_received_abs)
    round_infos = {}  # round -> RoundInfo, fetched once
    for h in hg.undetermined_events:
        i = win.row.get(h)
        r = int(rr[i]) if i is not None else -1
        if r >= 0:
            a = r + win.base
            if a not in round_infos:
                round_infos[a] = store.get_round(a)
            updates.append((store.get_event(h), a))
        else:
            new_undetermined.append(h)
    for ev, a in updates:
        ev.set_round_received(a)
        store.set_event(ev)
        round_infos[a].add_received_event(ev.hex())
    for a, tr in round_infos.items():
        store.set_round(a, tr)
    hg.undetermined_events = new_undetermined
    return [ev.hex() for ev, _ in updates]
