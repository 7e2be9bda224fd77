"""SweepBatcher — ONE device dispatch for all co-located nodes' sweeps.

Multi-validator hosts (the 16-node threaded topology, tests, any
in-process cluster) run many consensus engines against ONE device. The
per-node admission control in :mod:`babble_tpu.hashgraph.accel` keeps
their sweeps from convoying, but it is still one dispatch+readback PER
NODE — n nodes pay n device readbacks per flush cycle, and the losers
ride the host oracle.

The batcher replaces that with data parallelism over the node axis: flush
requests arriving within a short coalesce window are grouped by window
shape bucket, stacked along a leading batch axis, and dispatched as ONE
vmapped program (``ops.voting._batched_sweep_jit``) with ONE readback for
the whole host. This is the architecture BASELINE.md's config 3 calls
for — one chip batching consensus for many co-located validators — and
it is the tpu-native answer to the reference's per-process nodes (each Go
node owns its consensus loop, node.go; here the device amortizes them).

Semantics: vmap adds a batch dimension and never mixes rows, so each
window's [fame | round_received] vector is bit-identical to its
single-dispatch result (pinned by tests/test_sweep_batcher.py). Failures
set the ticket error and the owning node falls back to its oracle —
exactly the degradation contract of TensorConsensus.

Enabled per-node via ``BABBLE_ACCEL_BATCH=1`` (TensorConsensus resolves
it at first flush). The batcher is in-process by design: cross-process
coalescing would need shared device buffers; separate processes keep the
flock admission slots instead.
"""

from __future__ import annotations

import logging
import threading
import time

from ..common.timed_lock import named_lock
from ..obs.metrics import enabled as obs_enabled
from ..obs.trace import NULL_STAGE, annotation
from typing import Dict, List, Optional

logger = logging.getLogger("babble_tpu.hashgraph.sweep_batcher")


class Ticket:
    """One node's submitted window; the batcher delivers (fame, rr) or an
    error. ``done`` is set exactly once.

    A window's life is stamped where it happens (perf_counter seconds):
    ``t_submit`` by the owner's thread; ``t_taken`` (``_loop`` took the
    wave), ``t_launched`` (the last program of its group was launched)
    and ``t_read`` (its result, or its error, is on the host — ``done``
    is set right after) by the batcher thread, each beside the batcher
    thread's own CPU clock (``c_*``, 0 under BABBLE_OBS=0)."""

    __slots__ = ("win", "result", "error", "done", "batch_size", "mesh",
                 "owner", "t_submit", "t_taken", "t_launched", "t_read",
                 "c_taken", "c_launched", "c_read")

    def __init__(self, win, mesh=None, owner: Optional[str] = None):
        self.t_submit = time.perf_counter()
        self.t_taken = self.t_launched = self.t_read = 0.0
        self.c_taken = self.c_launched = self.c_read = 0.0
        self.win = win
        self.result = None  # (fame, rr) numpy arrays
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.batch_size = 0  # how many windows shared the dispatch
        # Coprocessor lane: a configured jax Mesh routes this window to
        # the sharded program shared by every co-located validator on the
        # same mesh; owner is the submitting validator's identity (for
        # the copro_validators multiplexing stat).
        self.mesh = mesh
        self.owner = owner


class SweepBatcher:
    """Process-wide coalescing dispatcher (one daemon thread)."""

    _instance: Optional["SweepBatcher"] = None
    _instance_lock = threading.Lock()

    #: how long the dispatcher waits after the first submission for
    #: co-located nodes' flushes to land. Gossip heartbeats are >= 10 ms,
    #: so a few ms captures one whole flush wave without adding visible
    #: decision latency (the pipelined mode hides it behind gossip anyway).
    COALESCE_S = 0.004
    MAX_BATCH = 16
    #: consecutive waves strictly below the target bucket before it decays
    #: back toward the observed per-wave max — one oversized window (a
    #: rejoin backlog, a churn spike) must not permanently inflate the
    #: padded shapes every later batch pays to compute.
    DECAY_WAVES = 24

    def __init__(self) -> None:
        # Named for the BABBLE_LOCKCHECK order recorder (lockcheck.py).
        self._lock = named_lock("batcher")
        self._pending: List[Ticket] = []
        self._work = threading.Event()
        self._compiling: set = set()
        # Shape-space discipline: every batched dispatch pads to B =
        # MAX_BATCH and to a MONOTONE target bucket (elementwise max of
        # everything seen, seeded by the prewarmed ``floor_key``) — without
        # this, drifting per-wave buckets spray one-off (B, shape) compiles
        # and batches never meet a warm program (measured: 9 distinct
        # compile kicks in one 20 s run, zero warm batches).
        self.floor_key: Optional[tuple] = None
        self._target: Optional[tuple] = None
        # decay bookkeeping (see _update_target)
        self._below_waves = 0
        self._decay_max: Optional[tuple] = None
        # stats
        self.batches = 0  # batched dispatches (>= 2 windows)
        self.singles = 0  # lone or unwarmed windows dispatched singly
        self.windows = 0  # total windows served
        self.max_batch_seen = 0
        self.compile_kicks = 0
        self.refused = 0  # submissions bounced by backpressure
        self.target_decays = 0  # times the monotone bucket shrank back
        # Coprocessor (mesh) lane: per-mesh monotone target buckets (the
        # wave pads every validator's window to ONE aligned shape so the
        # whole cluster shares each mesh's compile cache) and the distinct
        # validators multiplexed so far.
        self._mesh_targets: Dict[tuple, tuple] = {}
        self.copro_waves = 0  # mesh waves dispatched
        self.copro_windows = 0  # windows served through a mesh wave
        self._owners: set = set()  # validators seen on any mesh lane
        # A served window's life, summed (seconds): queue = submit →
        # taken, launch = taken → launched, read = launched → on the
        # host; stage_cpu_s is the batcher thread's own CPU time inside
        # launch and read (wall minus CPU: it waited, for the GIL or the
        # device). Written by the batcher thread only.
        self.stage_s = {"queue": 0.0, "launch": 0.0, "read": 0.0}
        self.stage_cpu_s = {"launch": 0.0, "read": 0.0}
        self.stage_windows = 0  # served windows the sums above cover
        # launches by the shape bucket that RAN (voting.bucket_label)
        self.bucket_launches: Dict[str, int] = {}
        self._obs = obs_enabled()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="sweep-batcher"
        )
        self._thread.start()

    @classmethod
    def instance(cls) -> "SweepBatcher":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    #: refuse submissions past this backlog: the caller's oracle is cheaper
    #: than queueing behind a convoy (the admission-slot economics, kept).
    MAX_QUEUE = 32

    def submit(self, win, mesh=None,
               owner: Optional[str] = None) -> Optional[Ticket]:
        """Queue a window for the next coalesced dispatch, or return None
        when the batcher is backlogged — the caller must run its oracle,
        exactly like losing an admission slot. With ``mesh`` the window
        rides the coprocessor lane: one wave of overlapped SHARDED
        dispatches padded to a shared per-mesh bucket."""
        with self._lock:
            if len(self._pending) >= self.MAX_QUEUE:
                self.refused += 1
                return None
            t = Ticket(win, mesh=mesh, owner=owner)
            self._pending.append(t)
        self._work.set()
        return t

    def stats(self) -> dict:
        return {
            "batch_batches": self.batches,
            "batch_singles": self.singles,
            "batch_windows": self.windows,
            "batch_max": self.max_batch_seen,
            "batch_compile_kicks": self.compile_kicks,
            "batch_refused": self.refused,
            "batch_target_decays": self.target_decays,
            "batch_stage_windows": self.stage_windows,
            "batch_stage_ms": {
                k: round(1000.0 * v, 3) for k, v in self.stage_s.items()
            },
            "batch_stage_cpu_ms": {
                k: round(1000.0 * v, 3) for k, v in self.stage_cpu_s.items()
            },
            "batch_bucket_launches": dict(self.bucket_launches),
            # coprocessor lane: mesh waves, windows multiplexed through
            # them, and distinct validators sharing the mesh(es)
            "copro_waves": self.copro_waves,
            "copro_windows": self.copro_windows,
            "copro_validators": len(self._owners),
        }

    # -- dispatcher ----------------------------------------------------------

    def _loop(self) -> None:
        while True:
            self._work.wait()
            # Let the rest of the flush wave land before grouping: nodes
            # flush on the same gossip cadence, so the first submission
            # predicts more within a few ms.
            time.sleep(self.COALESCE_S)
            with self._lock:
                batch, self._pending = self._pending, []
                self._work.clear()
            now, cpu = self._now()
            for t in batch:
                t.t_taken, t.c_taken = now, cpu
            if batch:
                try:
                    self._dispatch(batch)
                except BaseException as err:  # never kill the daemon
                    for t in batch:
                        if not t.done.is_set():
                            self._finish(t, error=err)
                    logger.warning("sweep batch dispatch failed",
                                   exc_info=True)

    # -- a window's life -----------------------------------------------------

    def _now(self) -> tuple:
        """(wall, this thread's CPU) — the CPU clock only with telemetry
        on, like every other clock read the kill switch removes."""
        return (time.perf_counter(),
                time.thread_time() if self._obs else 0.0)

    def _wave_span(self, stage: str, group: List[Ticket]):
        """The profiler annotation of one wave stage on the batcher
        thread, owned by the validators whose windows ride the wave."""
        if not self._obs:
            return NULL_STAGE
        owners = ",".join(sorted({t.owner for t in group if t.owner}))
        return annotation(stage, owners) or NULL_STAGE

    def _launched(self, group: List[Ticket]) -> None:
        """The last program of ``group`` has been launched."""
        now, cpu = self._now()
        for t in group:
            t.t_launched, t.c_launched = now, cpu

    def _finish(self, t: Ticket, result=None, error=None,
                batch_size: int = 0) -> None:
        """Hand a ticket back to its owner. A served window's stamps are
        folded into the stage sums; a failed one only gets its stamp."""
        t.t_read, t.c_read = self._now()
        if error is not None:
            t.error = error
        else:
            t.result, t.batch_size = result, batch_size
            self.stage_windows += 1
            s, c = self.stage_s, self.stage_cpu_s
            s["queue"] += t.t_taken - t.t_submit
            s["launch"] += t.t_launched - t.t_taken
            s["read"] += t.t_read - t.t_launched
            c["launch"] += t.c_launched - t.c_taken
            c["read"] += t.c_read - t.c_launched
        t.done.set()

    def _dispatch(self, tickets: List[Ticket]) -> None:
        # Partition the wave into lanes: one per configured mesh (the
        # coprocessor path — every co-located validator on the same mesh
        # shares its compile cache and padded bucket) plus the
        # single-device lane. Lanes dispatch independently; a wave can
        # carry both without cross-contamination.
        lanes: Dict[Optional[tuple], List[Ticket]] = {}
        meshes: Dict[tuple, object] = {}
        for t in tickets:
            if t.mesh is not None:
                from babble_tpu.parallel import voting_shard

                mk = voting_shard._mesh_key(t.mesh)
                meshes[mk] = t.mesh
                lanes.setdefault(mk, []).append(t)
            else:
                lanes.setdefault(None, []).append(t)
        for mk, lane in lanes.items():
            group = lane
            while len(group) > self.MAX_BATCH:
                head, group = group[: self.MAX_BATCH], group[self.MAX_BATCH:]
                self._dispatch_lane(meshes.get(mk), head)
            self._dispatch_lane(meshes.get(mk), group)

    def _dispatch_lane(self, mesh, group: List[Ticket]) -> None:
        if mesh is not None:
            self._dispatch_mesh_group(mesh, group)
        else:
            self._dispatch_group(group)

    def _gate_stale(self, group: List[Ticket]) -> List[Ticket]:
        # Resident-state generation gate: windows snapshotted from a
        # persistent WindowState carry (state, generation). If the state
        # mutated between submit and dispatch (a rebuild, an invalidate),
        # the window's row maps are stale — computing it would hand the
        # owner results it must discard anyway, so fail the ticket now and
        # let that node's oracle carry the flush. This is what keys a
        # batched wave to the resident-state generation — and what keeps
        # one validator's reset from ever corrupting a co-multiplexed
        # neighbour: stale generations never ride a dispatch.
        fresh: List[Ticket] = []
        for t in group:
            state = getattr(t.win, "state", None)
            if state is not None and state.generation != t.win.generation:
                from babble_tpu.ops.window_state import StaleWindowError

                self._finish(t, error=StaleWindowError(
                    f"window generation {t.win.generation} != state "
                    f"generation {state.generation}"
                ))
                continue
            fresh.append(t)
        return fresh

    def _dispatch_mesh_group(self, mesh, group: List[Ticket]) -> None:
        """Coprocessor wave: every validator's window re-pads to ONE
        mesh-aligned monotone bucket and launches through the shared
        per-mesh sharded program — launch all, read all, so the device
        overlaps the windows' work and the wave pays ~one readback. The
        padding rule is the batcher's (elementwise-max bucket, neutral
        fills) with the witness axis grown until the mesh size divides
        it; the compile cache is voting_shard's per-mesh jit, shared by
        every validator on this mesh."""
        from babble_tpu.ops import voting
        from babble_tpu.parallel import voting_shard

        group = self._gate_stale(group)
        if not group:
            return
        for t in group:
            if t.owner is not None:
                self._owners.add(t.owner)
        keys = [voting.bucket_key(t.win) for t in group]
        wave = tuple(max(k[d] for k in keys) for d in range(5))
        n = int(mesh.devices.size)
        W_m = wave[0]
        while W_m % n and W_m <= wave[0] * n:
            # doubling a power-of-two W can never reach a multiple of a
            # mesh with an odd factor; cap the climb and launch unaligned
            # (the per-ticket try/except below converts the shard error
            # into a ticket failure -> the owner's oracle path)
            W_m *= 2
        if W_m % n == 0:
            wave = (W_m,) + wave[1:]
        mk = voting_shard._mesh_key(mesh)
        prev = self._mesh_targets.get(mk)
        if prev is not None:
            wave = tuple(max(a, b) for a, b in zip(wave, prev))
        self._mesh_targets[mk] = wave
        launched = []
        with self._wave_span("launch", group):
            for t in group:
                try:
                    padded = voting.repad_window(t.win, wave)
                    launched.append((
                        t, padded,
                        voting_shard._jitted(mesh)(
                            *voting_shard.place_window(mesh, padded)
                        ),
                    ))
                    voting.count_launch(self.bucket_launches, wave)
                except BaseException as err:
                    self._finish(t, error=err)
            self._launched([t for t, _p, _o in launched])
        import numpy as np

        served = 0
        with self._wave_span("read", group):
            for t, padded, out in launched:
                try:
                    host = np.asarray(out)
                except BaseException as err:
                    self._finish(t, error=err)
                    continue
                # real rows keep their indexes under repad: slice back to
                # the ORIGINAL window's row spaces
                self._finish(t, result=(
                    host[: t.win.n_witnesses],
                    host[padded.n_witnesses:
                         padded.n_witnesses + t.win.n_events],
                ), batch_size=len(launched))
                served += 1
        if served:
            self.copro_waves += 1
            self.copro_windows += served
            self.windows += served
            self.max_batch_seen = max(self.max_batch_seen, served)

    def _dispatch_group(self, group: List[Ticket]) -> None:
        from babble_tpu.ops import voting

        group = self._gate_stale(group)
        if not group:
            return

        # Co-located nodes at slightly different DAG progress land in
        # DIFFERENT shape buckets; grouping by exact bucket would leave
        # every wave as singles. Instead the whole wave re-pads to the
        # monotone target bucket (repad_window: same neutral fills as the
        # builder, bit-identical decisions) and rides one dispatch.
        keys = [voting.bucket_key(t.win) for t in group]
        if self.floor_key is not None:
            keys.append(self.floor_key)
        wave = tuple(max(k[d] for k in keys) for d in range(5))
        target = self._update_target(wave)
        B = self.MAX_BATCH
        if len(group) > 1 and voting.batched_ready(target, B):
            try:
                with self._wave_span("launch", group):
                    padded = [voting.repad_window(t.win, target)
                              for t in group]
                    out = voting.launch_batched(padded, B)
                    voting.count_launch(self.bucket_launches, target, B)
                    self._launched(group)
                with self._wave_span("read", group):
                    results = voting.read_batched(out, padded)
            except BaseException as err:
                for t in group:
                    self._finish(t, error=err)
                return
            self.batches += 1
            self.windows += len(group)
            self.max_batch_seen = max(self.max_batch_seen, len(group))
            for t, (fame, rr) in zip(group, results):
                # slice the padded vectors back to the ORIGINAL window's
                # row spaces (real rows keep their indexes under repad)
                self._finish(
                    t,
                    result=(fame[: t.win.n_witnesses], rr[: t.win.n_events]),
                    batch_size=len(group),
                )
            return
        if len(group) > 1:
            self._kick_compile(target, B)
        # Unwarmed batch shape (or a lone window): serve through the warm
        # single-window program so decisions keep flowing. Launch ALL
        # buffers first, read back after — launch_sweep returns unread
        # device buffers, so the device overlaps the windows' work and the
        # wave pays ~one readback latency instead of a serial convoy.
        launched = []
        with self._wave_span("launch", group):
            for t in group:
                try:
                    launched.append((t, voting.launch_sweep(t.win)))
                    voting.count_launch(self.bucket_launches,
                                        voting.bucket_key(t.win))
                except BaseException as err:
                    self.singles += 1
                    self.windows += 1
                    self._finish(t, error=err)
            self._launched([t for t, _o in launched])
        with self._wave_span("read", group):
            for t, out in launched:
                self.singles += 1
                self.windows += 1
                try:
                    result = voting.read_sweep(out, t.win)
                except BaseException as err:
                    self._finish(t, error=err)
                    continue
                self._finish(t, result=result, batch_size=1)

    def _update_target(self, wave: tuple) -> tuple:
        """Monotone-with-decay shape bucket. The target grows to cover
        every wave (keeping dispatches on one warm program), but after
        DECAY_WAVES consecutive waves strictly below it, it shrinks back
        to the elementwise max actually observed in that window — so one
        oversized window stops permanently inflating padded shapes. The
        floor_key rides inside ``wave`` (the caller folds it in), so
        decay never drops below the prewarmed floor."""
        t = self._target
        if t is None:
            self._target = wave
            return wave
        grown = tuple(max(w, d) for w, d in zip(wave, t))
        if grown != t or wave == t:
            # at or above the target in some dimension: (re)grow and
            # restart the decay observation window
            self._target = grown
            self._below_waves = 0
            self._decay_max = None
            return grown
        # strictly below the target in >= 1 dim, nowhere above
        dm = self._decay_max
        self._decay_max = (
            wave if dm is None else tuple(max(a, b) for a, b in zip(dm, wave))
        )
        self._below_waves += 1
        if self._below_waves >= self.DECAY_WAVES:
            self._target = self._decay_max
            self.target_decays += 1
            self._below_waves = 0
            self._decay_max = None
        return self._target

    def _kick_compile(self, key: tuple, batch: int) -> None:
        gate = (batch, key)
        with self._lock:
            if gate in self._compiling:
                return
            self._compiling.add(gate)
        self.compile_kicks += 1

        def work() -> None:
            from babble_tpu.ops import voting

            try:
                t0 = time.perf_counter()
                voting.precompile_batched(batch, *key)
                logger.info(
                    "batched sweep ready for B=%d bucket %s in %.1fs",
                    batch, key, time.perf_counter() - t0,
                )
            except Exception:
                logger.warning(
                    "batched precompile failed for B=%d %s", batch, key,
                    exc_info=True,
                )
            finally:
                with self._lock:
                    self._compiling.discard(gate)

        threading.Thread(target=work, daemon=True,
                         name="sweep-batch-compile").start()
