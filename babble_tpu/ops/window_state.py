"""Incremental, device-resident voting windows (ISSUE 2).

``ops.voting.build_voting_window`` rebuilds the dense window snapshot from
scratch on every flush — one store fetch per row, fresh numpy allocation,
and a full host→device upload — even though consecutive sweeps share
almost all rows. :class:`WindowState` replaces that with the persistent-
device-state discipline a training/inference stack applies to KV caches:

- **Host mirrors** of the per-row window arrays live across sweeps, with a
  row-recycling free-list. Each snapshot is updated in O(ΔE): new
  undetermined events and newly-minted witnesses append rows (fed by the
  hashgraph's delta channels — see ``Hashgraph.drain_accel_delta``),
  events received by a sweep release their rows, and witness rows are
  repacked only when their ``first_descendants`` actually changed (the one
  per-row field the insert path mutates after the fact) or their fame was
  applied.
- **Device residency**: the 11 per-row arrays stay on the device between
  sweeps. The compiled resident program takes the previous buffers plus a
  compact, bucket-padded delta (row indexes + replacement rows; padding
  indexes point past the array so the scatter drops them) and applies it
  in place via ``jax.jit(donate_argnums=...)`` — host→device traffic
  scales with the delta, not the padded window.
- **Rebuild fallback**: any situation the delta protocol cannot express
  falls back to a from-scratch ``build_voting_window`` rebuild (with
  headroom added to the shape buckets so steady-state growth doesn't
  immediately rebuild again). Triggers: repertoire change, R/S/E/W bucket
  overflow, a round evicted from the store, a laggard event assigned a
  round below the frozen window floor, or any oracle pass having mutated
  consensus state behind the window's back (``mark_dirty``). The rebuild
  IS the correctness oracle: tests/test_incremental_window.py asserts the
  incremental mirrors equal a fresh rebuild after every mutation step.

Ownership rules for the donated buffers (see docs/tpu.md "Resident window
state"): ``WindowState.device`` holds the ONLY live reference to the
resident buffers. ``dispatch`` consumes them (donation invalidates the
inputs) and immediately replaces them with the program's outputs; any
failure drops residency and marks the state dirty, so a stale handle can
never be redispatched. Results are applied only while
``Snapshot.generation == WindowState.generation`` — a readback that lands
after a later mutation is discarded, never applied through moved row maps.

The window floor (``base``) is FROZEN between rebuilds: rows of rounds
that decide under a frozen floor stay in the window as settled voters —
harmless by exactly the repad argument (settled fame is never refilled,
determined events have ``undet`` False) — until the R bucket overflows and
a rebuild re-bases. This keeps per-row rounds immutable, which is what
makes the delta protocol O(ΔE).
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from babble_tpu.common.errors import StoreError
from babble_tpu.ops import voting
from babble_tpu.ops.voting import (
    INT32_MAX,
    VotingWindow,
    _bucket_mult,
    _bucket_pow2,
    _fame_init,
)

# CPU XLA ignores buffer donation (it still runs correctly, copy-on-write);
# the per-compile warning would otherwise spam every node under a cpu pin.
# On a TPU donation is real (chip_smoke.py checks the inputs are deleted).
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


class StaleWindowError(RuntimeError):
    """A window snapshot's WindowState mutated before its results could be
    used; the owner must discard them (and ride the oracle fallback)."""


# The per-row ("resident") window fields, in VotingWindow attribute order.
RESIDENT_FIELDS = (
    "creator", "index", "rounds", "undet", "wit_idx",
    "la_w", "fd_w", "rounds_w", "valid_w", "fame0_w", "mid_w",
)
# The per-sweep ("fresh") fields — tiny [R]/[S,P] arrays recomputed from
# the store every snapshot and uploaded whole (peer-set membership masks
# are cached by peer-set hash, so mask construction only happens when
# membership actually changes).
FRESH_FIELDS = (
    "member", "sm_s", "psi", "sm_r", "exists_r", "prior_dec_r", "lb_gate_r",
)


def delta_shape(key: tuple) -> Tuple[int, int]:
    """(DE, DW) delta-row buckets for a window bucket — fixed per bucket so
    each bucket compiles exactly ONE resident program. Sized for a gossip
    round's worth of churn; bigger deltas take the full-refresh path."""
    W, E, _P, _S, _R = key
    return max(32, E // 8), max(8, W // 8)


def _resident_core(creator, index, rounds, undet, wit_idx, la_w, fd_w,
                   rounds_w, valid_w, fame0_w, mid_w,
                   e_idx, e_creator, e_index, e_rounds, e_undet,
                   w_idx, w_wit_idx, w_la, w_fd, w_rounds, w_valid,
                   w_fame0, w_mid,
                   member, sm_s, psi, sm_r, exists_r, prior_dec_r, lb_gate_r):
    """Scatter the delta rows into the resident buffers, then run the same
    fused sweep as ops.voting._sweep_core. Padding delta rows carry an
    out-of-bounds index (E / W), which mode="drop" discards — so one
    compiled program serves every delta size up to the bucket. Returns
    (new resident buffers, [fame | rr])."""
    creator = creator.at[e_idx].set(e_creator, mode="drop")
    index = index.at[e_idx].set(e_index, mode="drop")
    rounds = rounds.at[e_idx].set(e_rounds, mode="drop")
    undet = undet.at[e_idx].set(e_undet, mode="drop")
    wit_idx = wit_idx.at[w_idx].set(w_wit_idx, mode="drop")
    la_w = la_w.at[w_idx].set(w_la, mode="drop")
    fd_w = fd_w.at[w_idx].set(w_fd, mode="drop")
    rounds_w = rounds_w.at[w_idx].set(w_rounds, mode="drop")
    valid_w = valid_w.at[w_idx].set(w_valid, mode="drop")
    fame0_w = fame0_w.at[w_idx].set(w_fame0, mode="drop")
    mid_w = mid_w.at[w_idx].set(w_mid, mode="drop")
    out = voting._sweep_core(
        creator, index, la_w, fd_w, rounds_w, valid_w, fame0_w, mid_w,
        wit_idx, member, sm_s, psi, sm_r, rounds, undet,
        exists_r, prior_dec_r, lb_gate_r,
    )
    return (
        (creator, index, rounds, undet, wit_idx, la_w, fd_w, rounds_w,
         valid_w, fame0_w, mid_w),
        out,
    )


# Donating the 11 resident buffers lets XLA update them in place: the
# host→device transfer per sweep is the delta pack plus the tiny [R]/[S,P]
# fresh arrays, never the padded window.
_resident_jit = jax.jit(_resident_core, donate_argnums=tuple(range(11)))

# Compiled-bucket registry for the resident program, mirroring ops.voting's
# (separate executables, so separate readiness).
_ready_resident: set = set()


def resident_ready(key: tuple) -> bool:
    with voting._bucket_lock():
        return key in _ready_resident


def mark_resident_ready(key: tuple) -> None:
    with voting._bucket_lock():
        _ready_resident.add(key)


def _empty_delta(key: tuple) -> tuple:
    """An all-padding delta pack (every index out of bounds → dropped)."""
    W, E, P, _S, _R = key
    DE, DW = delta_shape(key)
    return (
        np.full(DE, E, np.int32),          # e_idx (OOB → dropped)
        np.zeros(DE, np.int32),            # e_creator
        np.full(DE, -1, np.int32),         # e_index
        np.full(DE, -10, np.int32),        # e_rounds
        np.zeros(DE, bool),                # e_undet
        np.full(DW, W, np.int32),          # w_idx (OOB → dropped)
        np.zeros(DW, np.int32),            # w_wit_idx
        np.full((DW, P), -1, np.int32),    # w_la
        np.full((DW, P), INT32_MAX, np.int32),  # w_fd
        np.full(DW, -10, np.int32),        # w_rounds
        np.zeros(DW, bool),                # w_valid
        np.zeros(DW, np.int32),            # w_fame0
        np.zeros(DW, bool),                # w_mid
    )


def precompile_resident(W: int, E: int, P: int, S: int, R: int) -> None:
    """Compile (or load from the persistent cache) the resident delta
    program for a bucket on an all-invalid dummy window + empty delta."""
    key = (W, E, P, S, R)
    win = voting.dummy_window(*key)
    bufs = tuple(jnp.asarray(getattr(win, f)) for f in RESIDENT_FIELDS)
    fresh = tuple(jnp.asarray(getattr(win, f)) for f in FRESH_FIELDS)
    new_bufs, out = _resident_jit(*bufs, *_empty_delta(key), *fresh)
    np.asarray(out)  # block until the executable is really ready
    mark_resident_ready(key)


@dataclass
class Snapshot:
    """One sweep's immutable view of the WindowState: the mirror-backed
    VotingWindow, the state generation it was taken at, and the packed
    delta (None ⇒ the dispatch must do a full upload / residency reseed)."""

    win: VotingWindow
    generation: int
    delta: Optional[tuple]
    rebuilt: bool
    rows_delta: int
    rows_reused: int


class _Rebuild(Exception):
    """Internal: the delta protocol cannot express this mutation."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _NotReady(Exception):
    """Internal: an undetermined event has no round yet (divide_rounds has
    not run) — same condition build_voting_window returns None for."""


class WindowState:
    """Persistent incremental window for ONE hashgraph (owned by its
    TensorConsensus). All methods run on the consensus thread."""

    def __init__(self, mesh=None) -> None:
        # Optional jax.sharding.Mesh: residency lives as per-shard device
        # buffers (parallel/voting_shard.py shardings) and dispatch runs
        # the sharded resident program; None keeps the single-device
        # program. The W bucket is aligned to the mesh size at rebuild so
        # the witness axis always divides the shard count.
        self.mesh = mesh
        self.generation = 0  # bumped on every mirror mutation or rebuild
        self.dirty = True  # force a rebuild on the next snapshot
        self.dirty_reason = "initial"
        self.rebuilds = 0
        # the same count by what forced each rebuild ("initial",
        # "oracle-pass", "repertoire-change", "peer-set-slot-overflow",
        # "round-bucket-overflow", ...)
        self.rebuilds_by_reason: Dict[str, int] = {}
        self.mirror: Optional[Dict[str, np.ndarray]] = None
        self.row: Dict[str, int] = {}
        self.wit_row: Dict[str, int] = {}
        self.undet_set: Set[str] = set()
        self.free_e: List[int] = []
        self.free_w: List[int] = []
        self.base = 0
        self.key: Optional[tuple] = None  # (W, E, P, S, R)
        self.pub_keys: tuple = ()
        self.peer_col: Dict[str, int] = {}
        # per peer column, the hashgraph's coordinate column of that peer
        self.coord_src: Optional[np.ndarray] = None
        self.exists_prev: Optional[np.ndarray] = None
        # The ONLY live reference to the resident device buffers (donation
        # ownership rule: dispatch consumes and replaces it atomically).
        self.device: Optional[tuple] = None
        # membership-mask cache keyed by peer-set hash: masks are rebuilt
        # only when membership actually changes
        self._mask_cache: Dict[bytes, Tuple[np.ndarray, int]] = {}
        # feedback from the owning TensorConsensus's apply step
        self._pending_fame: List[Tuple[str, int]] = []
        self._pending_received: List[str] = []

    # -- lifecycle -----------------------------------------------------------

    def mark_dirty(self, reason: str = "oracle") -> None:
        """Anything mutated consensus state behind the window's back (an
        oracle pass, a reset, a failed sweep): drop residency and rebuild
        at the next snapshot. Bumping the generation here is what makes
        in-flight sweeps from the old state detectably stale."""
        self.dirty = True
        self.dirty_reason = reason
        self.device = None
        self.generation += 1
        self._pending_fame = []
        self._pending_received = []

    def drop_residency(self) -> None:
        """A snapshot's delta was committed to the mirrors but no dispatch
        carried it to the device (compile wait, admission loss, batcher
        backlog): the resident buffers now trail the mirrors. Keep the
        mirrors — the delta protocol is still exact — but force the next
        dispatched sweep to reseed residency with a full upload."""
        self.device = None

    def note_applied(self, fame_pairs: List[Tuple[str, int]],
                     received: List[str]) -> None:
        """Record what apply_fame/apply_round_received just wrote to the
        store, so the next delta scan updates the mirrors to match."""
        self._pending_fame.extend(fame_pairs)
        self._pending_received.extend(received)

    # -- snapshot ------------------------------------------------------------

    def snapshot(self, hg, timers: Dict[str, float],
                 copy_rows: bool = False) -> Optional[Snapshot]:
        """Bring the mirrors up to date with the hashgraph (O(ΔE) delta, or
        a from-scratch rebuild when a trigger fires) and return this
        sweep's Snapshot. None ⇒ nothing to decide. Raises StoreError on
        eviction mid-scan (the caller falls back to the oracle; the state
        is marked dirty so the next snapshot rebuilds)."""
        try:
            if self.dirty or self.mirror is None:
                return self._rebuild(hg, timers, copy_rows,
                                     self.dirty_reason)
            try:
                return self._delta_snapshot(hg, timers, copy_rows)
            except _Rebuild as why:
                return self._rebuild(hg, timers, copy_rows, why.reason)
            except _NotReady:
                # an undetermined event has no round yet (divide_rounds
                # mid-retry) — no sweep this flush. The scan may already
                # have consumed channels/feedback and touched bookkeeping,
                # so resync via a rebuild next time.
                self.mark_dirty("round-pending")
                return None
        except (_Rebuild, _NotReady):
            raise AssertionError("unreachable")  # pragma: no cover
        except BaseException:
            # A half-applied delta scan (store eviction mid-fetch) leaves
            # the mirrors inconsistent: discard them.
            self.mark_dirty("snapshot-error")
            raise

    def _rebuild(self, hg, timers, copy_rows: bool,
                 reason: str) -> Optional[Snapshot]:
        t0 = time.perf_counter()
        # stale channels/feedback describe the pre-rebuild world
        hg.drain_accel_delta()
        self._pending_fame = []
        self._pending_received = []
        win = voting.build_voting_window(hg)
        if win is None:
            # nothing to decide; stay dirty so the next snapshot rebuilds
            self.mark_dirty("empty")
            timers["build"] = timers.get("build", 0.0) + (
                time.perf_counter() - t0
            )
            return None
        # Headroom: grow an axis past the builder's bucket ONLY when the
        # real count is already within ``slack`` of the boundary (a
        # rebuild would otherwise fire again within a sweep or two).
        # Everywhere else the state keeps the builder's exact buckets —
        # that keeps rebuilt keys on the shapes prewarm_buckets compiled,
        # so a freshly (re)built state meets warm programs instead of
        # kicking compiles, and it keeps the kernel small (every bucket
        # step inflates W quadratically; a premature rebuild only costs
        # one more host build). R's slack covers the frozen floor: the
        # round span grows by one per new round until a rebuild re-bases.
        W0, E0, P0, S0, R0 = voting.bucket_key(win)

        def head(n_real: int, bucket: int, minimum: int, slack: int) -> int:
            if n_real + slack <= bucket:
                return bucket
            return _bucket_pow2(n_real + slack, minimum)

        E_real = len(win.hashes)
        W_real = len(win.wit_hashes)
        R_real = hg.store.last_round() - win.base + 2
        key = (
            head(W_real, W0, 16, max(2, W_real // 16)),
            head(E_real, E0, 32, max(8, E_real // 16)),
            P0,
            S0,
            head(R_real, R0, 8, 2),
        )
        if self.mesh is not None:
            # the sharded sweep splits the witness axis over every device:
            # align the W bucket so it always divides the mesh size (both
            # are powers of two in practice; a mesh with an odd factor can
            # never divide a doubled power-of-two bucket, so cap the climb
            # at one doubling past W*n and leave the bucket unaligned —
            # the dispatch layer falls back to the single program)
            n = int(self.mesh.devices.size)
            W_m = key[0]
            while W_m % n and W_m <= key[0] * n:
                W_m *= 2
            if W_m % n == 0:
                key = (W_m,) + key[1:]
        win = voting.repad_window(win, key)
        self.mirror = {f: np.asarray(getattr(win, f)) for f in RESIDENT_FIELDS}
        self.row = dict(win.row)
        self.wit_row = dict(win.wit_row)
        self.undet_set = set(hg.undetermined_events)
        W, E = key[0], key[1]
        self.free_e = list(range(E - 1, E_real - 1, -1))
        self.free_w = list(range(W - 1, W_real - 1, -1))
        self.base = win.base
        self.key = key
        rep = hg.store.repertoire_by_pub_key()
        self.pub_keys = tuple(sorted(rep.keys()))
        self.peer_col = {pk: i for i, pk in enumerate(self.pub_keys)}
        self.coord_src = hg.coord_columns(self.pub_keys)
        self.exists_prev = np.asarray(win.exists_r)
        self.device = None  # reseeded by the next full dispatch
        self._mask_cache.clear()
        self.generation += 1
        self.rebuilds += 1
        self.rebuilds_by_reason[reason] = (
            self.rebuilds_by_reason.get(reason, 0) + 1)
        self.dirty = False
        timers["build"] = timers.get("build", 0.0) + (time.perf_counter() - t0)
        rows = len(self.row) + len(self.wit_row)
        fresh = {f: np.asarray(getattr(win, f)) for f in FRESH_FIELDS}
        return Snapshot(
            win=self._window(fresh, copy_rows),
            generation=self.generation,
            delta=None,
            rebuilt=True,
            rows_delta=rows,
            rows_reused=0,
        )

    def _delta_snapshot(self, hg, timers, copy_rows: bool) -> Optional[Snapshot]:
        t0 = time.perf_counter()
        store = hg.store
        m = self.mirror
        W, E, P, S, R = self.key

        rep = store.repertoire_by_pub_key()
        if len(rep) != len(self.pub_keys) or tuple(sorted(rep)) != self.pub_keys:
            raise _Rebuild("repertoire-change")
        last_round = store.last_round()
        if last_round - self.base + 2 > R:
            raise _Rebuild("round-bucket-overflow")

        new_wits, fd_dirty = hg.drain_accel_delta()
        fame_pairs, self._pending_fame = self._pending_fame, []
        received, self._pending_received = self._pending_received, []

        # New undetermined events are a strict suffix of the list: inserts
        # append, and the only removals since the last snapshot were our
        # own apply (recorded in ``received``) — any other mutation path
        # marks the state dirty and never reaches this scan.
        undet = hg.undetermined_events
        new_undet: List[str] = []
        for h in reversed(undet):
            if h in self.undet_set:
                break
            new_undet.append(h)
        new_undet.reverse()

        e_upd: Dict[int, tuple] = {}  # row -> (creator, index, round, undet)
        w_upd: Dict[int, dict] = {}  # w-row -> field dict

        # 1. events our apply received: witnesses keep their row with the
        #    undet flag cleared; plain events release their row.
        for h in received:
            i = self.row.get(h)
            if i is None:
                continue
            self.undet_set.discard(h)
            if h in self.wit_row:
                e_upd[i] = (
                    int(m["creator"][i]), int(m["index"][i]),
                    int(m["rounds"][i]), False,
                )
            else:
                e_upd[i] = (0, -1, -10, False)
                del self.row[h]
                self.free_e.append(i)

        # 2. fresh undetermined events append rows.
        ev_cache: Dict[str, object] = {}
        for h in new_undet:
            ev = store.get_event(h)
            ev_cache[h] = ev
            if ev.round is None:
                raise _NotReady()
            if ev.round < self.base:
                raise _Rebuild("round-below-floor")
            i = self.row.get(h)
            if i is None:
                if not self.free_e:
                    raise _Rebuild("event-bucket-overflow")
                i = self.free_e.pop()
                self.row[h] = i
            self.undet_set.add(h)
            e_upd[i] = (
                self.peer_col[ev.creator()], ev.index(),
                ev.round - self.base, True,
            )

        # 3. newly-minted witnesses gain W rows (packed from the store).
        for r, h in new_wits:
            if h in self.wit_row:
                continue
            if r < self.base:
                raise _Rebuild("witness-below-floor")
            ev = ev_cache.get(h)
            if ev is None:
                ev = store.get_event(h)
                ev_cache[h] = ev
            i = self.row.get(h)
            if i is None:
                if not self.free_e:
                    raise _Rebuild("event-bucket-overflow")
                i = self.free_e.pop()
                self.row[h] = i
                e_upd[i] = (
                    self.peer_col[ev.creator()], ev.index(),
                    r - self.base, h in self.undet_set,
                )
            if not self.free_w:
                raise _Rebuild("witness-bucket-overflow")
            w = self.free_w.pop()
            self.wit_row[h] = w
            w_upd[w] = self._pack_witness(hg, ev, i, r - self.base, fame0=0)

        # 4. witnesses whose first_descendants mutated since the last
        #    snapshot (the one post-insert per-row mutation) repack.
        for h in fd_dirty:
            w = self.wit_row.get(h)
            if w is None or w in w_upd:
                continue
            ev = ev_cache.get(h)
            if ev is None:
                ev = store.get_event(h)
            w_upd[w] = self._pack_witness(
                hg, ev, int(m["wit_idx"][w]), int(m["rounds_w"][w]),
                fame0=int(m["fame0_w"][w]),
            )

        # 5. fame our apply wrote settles witness rows in place.
        for h, f in fame_pairs:
            w = self.wit_row.get(h)
            if w is None:
                continue
            if w in w_upd:
                w_upd[w]["fame0_w"] = f
            else:
                w_upd[w] = {
                    "wit_idx": int(m["wit_idx"][w]),
                    "la_w": np.array(m["la_w"][w]),
                    "fd_w": np.array(m["fd_w"][w]),
                    "rounds_w": int(m["rounds_w"][w]),
                    "valid_w": bool(m["valid_w"][w]),
                    "fame0_w": f,
                    "mid_w": bool(m["mid_w"][w]),
                }

        if len(self.undet_set) != len(undet):
            raise _Rebuild("undetermined-bookkeeping-divergence")

        # apply to the mirrors
        for i, (c, idx, rr_, ud) in e_upd.items():
            m["creator"][i] = c
            m["index"][i] = idx
            m["rounds"][i] = rr_
            m["undet"][i] = ud
        for w, row in w_upd.items():
            m["wit_idx"][w] = row["wit_idx"]
            m["la_w"][w] = row["la_w"]
            m["fd_w"][w] = row["fd_w"]
            m["rounds_w"][w] = row["rounds_w"]
            m["valid_w"][w] = row["valid_w"]
            m["fame0_w"][w] = row["fame0_w"]
            m["mid_w"][w] = row["mid_w"]
        if e_upd or w_upd:
            self.generation += 1
        timers["delta_scan"] = timers.get("delta_scan", 0.0) + (
            time.perf_counter() - t0
        )

        if not self.undet_set and not (
            hg.pending_rounds.get_ordered_pending_rounds()
        ):
            # Nothing left to decide, so no dispatch will carry this delta
            # to the device: the resident buffers now trail the mirrors.
            # Drop residency — the next dispatched sweep full-uploads.
            if e_upd or w_upd:
                self.device = None
            return None

        t1 = time.perf_counter()
        fresh = self._round_block(hg)  # may raise _Rebuild (eviction, S)
        DE, DW = delta_shape(self.key)
        delta = None
        if (
            not copy_rows  # batcher snapshots never dispatch a delta
            and len(e_upd) <= DE
            and len(w_upd) <= DW
        ):
            delta = self._pack_delta(e_upd, w_upd, DE, DW)
        win = self._window(fresh, copy_rows)
        timers["pack"] = timers.get("pack", 0.0) + (time.perf_counter() - t1)
        rows_delta = len(e_upd) + len(w_upd)
        return Snapshot(
            win=win,
            generation=self.generation,
            delta=delta,
            rebuilt=False,
            rows_delta=rows_delta,
            rows_reused=max(
                0, len(self.row) + len(self.wit_row) - rows_delta
            ),
        )

    def _pack_witness(self, hg, ev, e_row: int, round_rebased: int,
                      fame0: int) -> dict:
        from babble_tpu.hashgraph.hashgraph import middle_bit

        P = self.key[2]
        n = len(self.coord_src)
        la = np.full(P, -1, np.int32)
        fd = np.full(P, INT32_MAX, np.int32)
        la[:n], fd[:n] = hg.window_coordinates(ev, self.coord_src)
        return {
            "wit_idx": e_row,
            "la_w": la,
            "fd_w": fd,
            "rounds_w": round_rebased,
            "valid_w": True,
            "fame0_w": fame0,
            "mid_w": middle_bit(ev.hex()),
        }

    def _pack_delta(self, e_upd: Dict[int, tuple], w_upd: Dict[int, dict],
                    DE: int, DW: int) -> tuple:
        W, E, P, _S, _R = self.key
        e_idx = np.full(DE, E, np.int32)
        e_creator = np.zeros(DE, np.int32)
        e_index = np.full(DE, -1, np.int32)
        e_rounds = np.full(DE, -10, np.int32)
        e_undet = np.zeros(DE, bool)
        for k, (i, (c, idx, rr_, ud)) in enumerate(e_upd.items()):
            e_idx[k] = i
            e_creator[k] = c
            e_index[k] = idx
            e_rounds[k] = rr_
            e_undet[k] = ud
        w_idx = np.full(DW, W, np.int32)
        w_wit_idx = np.zeros(DW, np.int32)
        w_la = np.full((DW, P), -1, np.int32)
        w_fd = np.full((DW, P), INT32_MAX, np.int32)
        w_rounds = np.full(DW, -10, np.int32)
        w_valid = np.zeros(DW, bool)
        w_fame0 = np.zeros(DW, np.int32)
        w_mid = np.zeros(DW, bool)
        for k, (w, row) in enumerate(w_upd.items()):
            w_idx[k] = w
            w_wit_idx[k] = row["wit_idx"]
            w_la[k] = row["la_w"]
            w_fd[k] = row["fd_w"]
            w_rounds[k] = row["rounds_w"]
            w_valid[k] = row["valid_w"]
            w_fame0[k] = row["fame0_w"]
            w_mid[k] = row["mid_w"]
        return (e_idx, e_creator, e_index, e_rounds, e_undet,
                w_idx, w_wit_idx, w_la, w_fd, w_rounds, w_valid,
                w_fame0, w_mid)

    # -- per-sweep round/peer-set block --------------------------------------

    def _round_block(self, hg) -> dict:
        """The [R]/[S,P] fresh arrays, recomputed from the store each sweep
        (they're tiny and prior_dec_r/exists_r genuinely change per sweep).
        Raises _Rebuild when a previously-readable round was evicted or the
        distinct peer-set count outgrows the S bucket."""
        store = hg.store
        W, E, P, S, R = self.key
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
            a = self.base + r
            lb_gate_r[r] = lb is None or lb < a
            try:
                ri = store.get_round(a)
            except StoreError:
                if self.exists_prev is not None and self.exists_prev[r]:
                    raise _Rebuild("round-evicted")
            else:
                exists_r[r] = True
                prior_dec_r[r] = ri.decided
            ps = store.get_peer_set(a)
            key = ps.hash()
            s = slot_of.get(key)
            if s is None:
                s = len(members)
                if s >= S:
                    raise _Rebuild("peer-set-slot-overflow")
                slot_of[key] = s
                cached = self._mask_cache.get(key)
                if cached is None:
                    mask = np.zeros(P, bool)
                    for pk in ps.pub_keys():
                        c = self.peer_col.get(pk)
                        if c is not None:
                            mask[c] = True
                    cached = (mask, ps.super_majority())
                    self._mask_cache[key] = cached
                members.append(cached[0])
                sms.append(cached[1])
            psi[r] = s
            sm_r[r] = sms[s]
        member = np.zeros((S, P), bool)
        sm_s = np.full(S, 2**30, np.int32)
        for s, mk in enumerate(members):
            member[s] = mk
            sm_s[s] = sms[s]
        self.exists_prev = exists_r
        return {
            "member": member, "sm_s": sm_s, "psi": psi, "sm_r": sm_r,
            "exists_r": exists_r, "prior_dec_r": prior_dec_r,
            "lb_gate_r": lb_gate_r,
        }

    def _window(self, fresh: dict, copy_rows: bool) -> VotingWindow:
        """A VotingWindow over the mirrors plus this sweep's fresh [R]/[S,P]
        arrays. ``copy_rows`` copies the per-row arrays (batcher
        submissions outlive the snapshot and must not see later in-place
        delta mutations); otherwise the arrays are shared and consumers
        rely on the generation check."""
        m = self.mirror
        rows = {
            f: (np.array(m[f]) if copy_rows else m[f])
            for f in RESIDENT_FIELDS
        }
        return VotingWindow(
            **rows,
            **fresh,
            base=self.base,
            hashes=list(self.row),
            row=self.row if not copy_rows else dict(self.row),
            wit_hashes=list(self.wit_row),
            wit_row=self.wit_row if not copy_rows else dict(self.wit_row),
            generation=self.generation,
            state=self,
        )

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, snap: Snapshot, allow_inline_compile: bool = True):
        """Launch the sweep for a snapshot, keeping the window device-
        resident. Delta path: donate the previous buffers + scatter the
        delta (transfer scales with ΔE). Full path (no delta / no
        residency / resident program not warm): upload the mirrors once
        through the plain fused program and keep the uploaded buffers as
        the new residency seed. Returns the unread [fame | rr] device
        buffer. Returns (out, used_delta)."""
        if self.mesh is not None:
            return self._dispatch_mesh(snap, allow_inline_compile)
        key = self.key
        win = snap.win
        if (
            snap.delta is not None
            and self.device is not None
            and (allow_inline_compile or resident_ready(key))
        ):
            bufs, self.device = self.device, None  # consume: donation
            fresh = tuple(jnp.asarray(getattr(win, f)) for f in FRESH_FIELDS)
            try:
                new_bufs, out = _resident_jit(*bufs, *snap.delta, *fresh)
            except BaseException:
                self.mark_dirty("dispatch-error")
                raise
            mark_resident_ready(key)
            self.device = tuple(new_bufs)
            return out, True
        # full upload; the uploaded buffers seed residency for next sweep
        bufs = tuple(jnp.asarray(getattr(win, f)) for f in RESIDENT_FIELDS)
        named = dict(zip(RESIDENT_FIELDS, bufs))
        args = [
            named[f] if f in named else jnp.asarray(getattr(win, f))
            for f in voting._WIN_FIELDS
        ]
        try:
            out = voting._sweep_jit(*args)
        except BaseException:
            self.mark_dirty("dispatch-error")
            raise
        self.device = bufs
        return out, False

    # index of each RESIDENT_FIELD inside voting._WIN_FIELDS order — the
    # mesh full-upload path keeps those placed operands as the residency
    # seed (creator, index, rounds, undet, wit_idx, la_w, fd_w, rounds_w,
    # valid_w, fame0_w, mid_w)
    _PLACED_RESIDENT_IDX = (0, 1, 13, 14, 8, 2, 3, 4, 5, 6, 7)

    def _dispatch_mesh(self, snap: Snapshot, allow_inline_compile: bool):
        """Mesh variant of dispatch: residency is a tuple of per-shard
        device buffers (voting_shard.resident_shardings), the delta path
        donates them to the sharded resident program, and the full path
        seeds them by placing the mirrors with the sweep's shardings.
        Same ownership rules as the single-device path."""
        from babble_tpu.parallel import voting_shard as vshard

        mesh = self.mesh
        key = self.key
        win = snap.win
        if (
            snap.delta is not None
            and self.device is not None
            and (allow_inline_compile
                 or vshard.resident_bucket_ready(mesh, key))
        ):
            bufs, self.device = self.device, None  # consume: donation
            fresh = tuple(np.asarray(getattr(win, f)) for f in FRESH_FIELDS)
            try:
                new_bufs, out = vshard.resident_jitted(mesh)(
                    *bufs, *snap.delta, *fresh
                )
            except BaseException:
                self.mark_dirty("dispatch-error")
                raise
            vshard.mark_resident_bucket_ready(mesh, key)
            self.device = tuple(new_bufs)
            return out, True
        # full upload through the plain sharded sweep; the placed per-row
        # operands seed residency for the next delta sweep
        placed = vshard.place_window(mesh, win)
        try:
            out = vshard._jitted(mesh)(*placed)
        except BaseException:
            self.mark_dirty("dispatch-error")
            raise
        self.device = tuple(placed[i] for i in self._PLACED_RESIDENT_IDX)
        return out, False
