"""TensorConsensus — drives the device voting kernels for a live Hashgraph.

Attached to a Hashgraph by the node's core when ``--accelerator`` is on.
``Hashgraph.insert_event_and_run_consensus`` then defers DecideFame /
DecideRoundReceived to batched device sweeps (the reference runs them per
insert, hashgraph.go:644-668; here a sweep covers a whole sync batch so
device dispatch amortizes across the gossip round — SURVEY.md hard-part 6).

Two modes, chosen by where the kernels run (ops/device.on_accelerator):

- **Synchronous** (host XLA — an explicit cpu pin, tests): one fused
  device call per flush — snapshot the undecided window, run fame +
  decidedness + round-received in one compiled program, read back one
  buffer, apply.

- **Pipelined** (real accelerator): the flush path never waits for a
  device→host readback (its cost is not measured on a local chip).
  Each flush first applies the PREVIOUS sweep's results (read back by a
  background thread while gossip continued — the readback releases the
  GIL), then snapshots and launches the next sweep (sub-millisecond
  dispatch). Applying a snapshot's decisions after later inserts is exactly
  the hashgraph's incremental == batch property — the same property the
  reference's per-insert pipeline relies on — so consensus output is
  bit-identical; only decision latency shifts by one flush interval.

Any store eviction or snapshot failure falls back to the oracle sweep for
that round — consensus output is identical either way, and the node keeps
running; the ``fallbacks`` counter surfaces it in /stats.
"""

from __future__ import annotations

import atexit
import logging
import queue
import threading
import time
from typing import Optional

from babble_tpu.common.breaker import CircuitBreaker
from babble_tpu.common.errors import StoreError
from babble_tpu.obs.trace import NULL_STAGE, Tracer, annotation

logger = logging.getLogger("babble_tpu.hashgraph.accel")


def _breaker_from_env(clock=None) -> CircuitBreaker:
    """Device-path circuit breaker with env-tunable parameters: open after
    BABBLE_ACCEL_BREAKER_N failures within BABBLE_ACCEL_BREAKER_WINDOW_S
    seconds, refuse the device for BABBLE_ACCEL_BREAKER_COOLDOWN_S, then
    probe one sweep to half-open/re-close. ``clock`` (a common.clock.Clock
    or bare monotonic callable) makes the trip window and cooldown run on
    the node's time source — virtual under the sim engine."""
    import os

    return CircuitBreaker(
        threshold=max(1, int(os.environ.get("BABBLE_ACCEL_BREAKER_N", "5"))),
        window_s=float(os.environ.get("BABBLE_ACCEL_BREAKER_WINDOW_S", "30")),
        cooldown_s=float(
            os.environ.get("BABBLE_ACCEL_BREAKER_COOLDOWN_S", "15")
        ),
        **({"clock": clock} if clock is not None else {}),
    )


class _Inflight:
    """A launched sweep whose output buffer a background thread is reading
    back while gossip continues."""

    __slots__ = ("win", "result", "error", "done", "generation", "t_launch",
                 "t_done", "topo", "snap", "readback_s", "wake_s", "_slots",
                 "_slot_lock", "_slot_held")

    def __init__(self, win, generation: int, topo: int, slots=None,
                 snap=None):
        self.win = win
        self.result = None  # (fame, rr) numpy arrays once read back
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.generation = generation
        self.t_launch = time.perf_counter()
        self.t_done = 0.0  # set by the reader when the readback lands
        self.topo = topo  # hashgraph topological index at snapshot time
        # Resident-window provenance: the WindowState snapshot this sweep
        # was launched from (None on the legacy full-build path). Its
        # generation gates apply — see TensorConsensus._apply.
        self.snap = snap
        self.readback_s = 0.0  # device→host wait measured by the reader
        # batcher path: ticket done → the reader thread runs again
        self.wake_s: Optional[float] = None
        # Admission-control slot ownership: released exactly once, by the
        # reader when the readback lands OR by the abandonment path when a
        # wedged readback times out — whichever gets there first.
        self._slots = slots
        self._slot_lock = threading.Lock()
        self._slot_held = slots is not None

    def release_slot(self) -> None:
        with self._slot_lock:
            held, self._slot_held = self._slot_held, False
        if held:
            self._slots.release()


# Sweep admission control. Co-located nodes (multi-validator hosts, the
# 16-node bench, tests) share ONE device; without a cap their redundant
# sweeps convoy on the readback path and per-sweep latency balloons (not
# measured on a local chip). Capping in-flight sweeps keeps device
# latency flat; flushes that lose the race ride the oracle, which is
# exactly the small-window economics already encoded in min_window.
#
# Two scopes:
# - in-process (default): a plain semaphore covers threads in one
#   interpreter (threaded clusters, tests);
# - cross-process (BABBLE_ACCEL_SLOT_DIR): flock-guarded slot files, so
#   independent node PROCESSES on one host coordinate too. A chip belongs
#   to one process at a time, so this only ever coordinates host-XLA
#   processes under an explicit cpu pin; no launcher sets it.


class _FlockSlots:
    """Semaphore-shaped admission slots shared ACROSS processes via
    non-blocking flock on a fixed set of slot files. Locks die with the
    process, so a crashed node can never leak a slot."""

    def __init__(self, dir_path: str, n: int):
        import os

        os.makedirs(dir_path, exist_ok=True)
        self._paths = [
            os.path.join(dir_path, f"sweep-slot-{i}.lock") for i in range(n)
        ]
        self._lock = threading.Lock()
        self._held: list = []  # (path, fd) LIFO

    def acquire(self, blocking: bool = False) -> bool:
        import fcntl
        import os

        assert not blocking, "admission slots are try-acquire only"
        with self._lock:
            held_paths = {p for p, _ in self._held}
            for p in self._paths:
                if p in held_paths:
                    continue
                fd = os.open(p, os.O_CREAT | os.O_RDWR, 0o644)
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                except OSError:
                    os.close(fd)
                    continue
                self._held.append((p, fd))
                return True
            return False

    def release(self) -> None:
        import fcntl
        import os

        with self._lock:
            if not self._held:
                return
            _, fd = self._held.pop()
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)


def _parts_recorded(stage: str, seconds: float) -> None:
    """Sink of a span whose parts are recorded one by one."""


def _is_stale_window(err: BaseException) -> bool:
    """True for the batcher's stale-generation rejection — the window
    snapshot aged out before dispatch, which says nothing about device
    health (the breaker must not count it as a failure)."""
    try:
        from babble_tpu.ops.window_state import StaleWindowError
    except Exception:
        return False
    return isinstance(err, StaleWindowError)


# Compilation follows the validator set. A window's bucket is (W, E, P, S,
# R): P the repertoire padded to a multiple of 8, S the peer-set slots of
# the rounds it spans. A membership change moves P or S under every shape
# (W, E, R) in use at once, and each moved bucket would meet a compile
# wait of its own, one flush at a time, with the oracle carrying each.
# So the (P, S) pairs this process has seen and the shapes it has compiled
# are kept as a cross product: when a window shows a new pair, every
# compiled shape is compiled at it; when a shape is first compiled, it is
# compiled at every other pair seen. One worker thread, one program at a
# time: the compile a flush is waiting for has a thread of its own
# (_compile_bucket) and is never queued behind these. With one pair — a
# validator set that never changes — nothing is queued and no thread
# starts. Single-device programs only: a mesh compiles on demand.
_peer_axes_seen: set = set()
_variants_queued: set = set()
_variant_queue: "queue.SimpleQueue[tuple]" = queue.SimpleQueue()
_variant_lock = threading.Lock()
_variant_worker: Optional[threading.Thread] = None
_variants_stopping = threading.Event()
variant_compiles = 0  # programs the worker compiled, process-wide
_variants_done = 0  # ... and those it is through with, compiled or failed


def variant_backlog() -> int:
    """Programs queued by the policy that the worker is not through with:
    while it is over 0 a compile is running, or about to."""
    return len(_variants_queued) - _variants_done


def _follow_peer_axes(key: tuple, compiled: bool = False) -> None:
    """``key`` is the bucket of a window at the flush gate, or
    (``compiled``) one whose on-demand compile just finished. Queues the
    variants that keep shapes x pairs whole."""
    global _variant_worker
    from babble_tpu.ops import voting

    pair = key[2:4]
    with _variant_lock:
        new_pair = pair not in _peer_axes_seen
        _peer_axes_seen.add(pair)
        if len(_peer_axes_seen) == 1 or not (new_pair or compiled):
            return
        wanted = []
        if new_pair:
            wanted += [(W, E) + pair + (R,)
                       for (W, E, _P, _S, R) in voting.ready_buckets()]
        if compiled:
            wanted += [key[:2] + q + key[4:]
                       for q in _peer_axes_seen if q != pair]
        for k in wanted:
            if k not in _variants_queued and not voting.bucket_ready(k):
                _variants_queued.add(k)
                _variant_queue.put(k)
        if _variant_worker is None and not _variant_queue.empty():
            _variant_worker = threading.Thread(
                target=_compile_variants, daemon=True,
                name="voting-variant-compile")
            _variant_worker.start()
            atexit.register(_stop_variants)


def _stop_variants() -> None:
    """At interpreter exit: let the worker finish the program it is
    compiling and go. A daemon thread killed inside XLA's compiler takes
    the process down with it (SIGABRT, "exception not rethrown")."""
    _variants_stopping.set()
    _variant_queue.put(None)  # wakes a worker that waits for work
    _variant_worker.join(timeout=120.0)


def _compile_variants() -> None:
    global variant_compiles, _variants_done
    from babble_tpu.ops import voting

    while True:
        key = _variant_queue.get()
        if _variants_stopping.is_set():
            return
        try:
            if not voting.bucket_ready(key):
                t0 = time.perf_counter()
                voting.precompile(*key)
                variant_compiles += 1
                logger.info("voting kernels ready for variant %s in %.1fs",
                            key, time.perf_counter() - t0)
        except Exception:
            logger.warning("variant %s precompile failed", key,
                           exc_info=True)
        _variants_done += 1


_INFLIGHT_SLOTS = None
_slots_lock = threading.Lock()


def _inflight_slots():
    global _INFLIGHT_SLOTS
    if _INFLIGHT_SLOTS is None:
        with _slots_lock:
            if _INFLIGHT_SLOTS is None:
                import os

                n = max(1, int(os.environ.get("BABBLE_ACCEL_MAX_INFLIGHT", "2")))
                slot_dir = os.environ.get("BABBLE_ACCEL_SLOT_DIR")
                if slot_dir:
                    _INFLIGHT_SLOTS = _FlockSlots(slot_dir, n)
                else:
                    _INFLIGHT_SLOTS = threading.Semaphore(n)
    return _INFLIGHT_SLOTS


class TensorConsensus:
    def __init__(self, sweep_events: int = 256, async_compile: bool = True,
                 min_window: int | None = None,
                 pipeline: bool | None = None,
                 mesh=None,
                 batcher: bool | None = None,
                 resident: bool | None = None,
                 breaker: CircuitBreaker | None = None,
                 clock=None,
                 owner: str | None = None):
        # Force a sweep mid-batch once this many inserts accumulate, so the
        # window tensors stay inside one shape bucket even under huge syncs.
        # Normal cadence is one sweep per gossip round (core.sync flush).
        self.sweep_events = sweep_events
        # Crossover threshold: below this many undetermined events the
        # incremental oracle beats the sweep's fixed dispatch+readback cost,
        # so small windows stay on the host and the device takes over
        # exactly when the oracle's O(witnesses² · rounds) voting would
        # start to crawl. None = resolve on first use. 0 forces the device
        # path (tests).
        self.min_window = min_window
        # Pipelined (non-blocking) sweeps: None = resolve on first flush —
        # on a real accelerator the readback latency is hidden behind
        # gossip; on host XLA readback is free and synchronous sweeps keep
        # decision latency minimal.
        self.pipeline = pipeline
        # Compile window-shape buckets off the consensus thread: the first
        # sweep of a new bucket would otherwise stall gossip for the XLA
        # compile (seconds on CPU, tens of seconds cold on TPU) while
        # holding the core lock. Until a bucket's kernels are ready the
        # oracle carries consensus — output is identical either way.
        self.async_compile = async_compile
        # Optional jax.sharding.Mesh: sweeps run witness-axis sharded over
        # the device mesh (parallel/voting_shard.py) instead of on one
        # device. Output is bit-identical; only placement differs.
        self.mesh = mesh
        # Validator identity for the coprocessor stats (the SweepBatcher
        # counts distinct owners multiplexed onto one mesh); falls back to
        # a per-engine token when the node doesn't name itself.
        self.owner = owner
        # Co-located batching: route sweeps through the process-wide
        # SweepBatcher so all nodes on this host share ONE device dispatch
        # per flush wave (BASELINE config-3 architecture). None = resolve
        # from BABBLE_ACCEL_BATCH at first flush. With a mesh the batcher
        # runs as a consensus coprocessor: co-located validators' windows
        # are padded to one aligned bucket and multiplexed onto the SAME
        # sharded program (shared per-mesh compile cache, one wave of
        # overlapped dispatches).
        self.batcher = batcher
        # Incremental device-resident windows (ops/window_state.py): the
        # snapshot is a persistent WindowState updated in O(ΔE) per sweep,
        # and the window tensors stay on the device between sweeps (the
        # resident program donates the previous buffers and applies a
        # compact delta). None = resolve from BABBLE_ACCEL_RESIDENT at
        # first flush (default ON). Under a mesh, residency is per-shard:
        # the delta scatters into the sharded buffers through the mesh
        # resident program (voting_shard.resident_jitted) and the
        # single-device rebuild stays the correctness oracle. With the
        # batcher, the host side stays incremental but windows are
        # submitted as copies (the batch wave cannot donate per-node
        # buffers).
        self.resident = resident
        self.window_state = None
        # Device-path circuit breaker: transient failures fall back to the
        # oracle per-flush as before, but a FLAPPING device (N failures in
        # a window) opens the breaker and the node stops paying for device
        # dispatch attempts for a cooldown; a probe sweep then re-enables
        # the path once the device answers again. This replaces any notion
        # of a sticky "disable forever" kill-switch: degradation is always
        # recoverable.
        self.breaker = (breaker if breaker is not None
                        else _breaker_from_env(clock))
        self.sweeps = 0
        self.fallbacks = 0
        self.compile_waits = 0
        self.small_windows = 0  # flushes routed to the oracle by min_window
        self.deferred = 0  # flushes that rode behind an in-flight readback
        self.contended = 0  # launches skipped: device at max in-flight sweeps
        self.stale_drops = 0  # readbacks discarded by the generation check
        self.rows_delta_total = 0  # delta rows uploaded across sweeps
        self.rows_reused_total = 0  # resident rows reused across sweeps
        # Mesh padding visibility (satellite: no more silent single-device
        # fallback when W doesn't divide the mesh): rows added to align
        # the witness axis, and windows that still dropped to the
        # single-device program because padding itself failed.
        self.mesh_pad_rows = 0
        self.mesh_fallbacks = 0
        self.generation = 0  # bumped by Hashgraph.reset/bootstrap
        # A sweep whose readback exceeds this is abandoned (hung device):
        # the oracle takes over so a dead device can stall only one sweep's
        # worth of decisions, never the node.
        self.readback_timeout_s = 30.0
        self._last_snapshot_topo = -1
        # topological index of the hashgraph at the snapshot of the sweep
        # applied last: what Hashgraph.voting_deferred compares
        self.applied_topo = -1
        self.last_window_events = 0
        # Per-stage rolling sums (seconds) for /debug and bench breakdowns.
        # snapshot cost = build (full rebuilds) + delta_scan + pack
        # (incremental); dispatch and readback are the device leg as this
        # validator's threads see it. Two waits BETWEEN threads are
        # recorded apart: wake (the batcher has the result → this
        # engine's reader thread runs again; part of readback) and
        # result_idle (the reader is done → the next flush applies it).
        self.stage_s = {
            "build": 0.0, "delta_scan": 0.0, "pack": 0.0,
            "dispatch": 0.0, "readback": 0.0, "wake": 0.0,
            "result_idle": 0.0, "apply": 0.0,
        }
        # Optional per-sample stage observer (obs.telemetry wires the
        # accel_stage_seconds{stage=...} histogram here); stage_s keeps
        # the legacy rolling totals either way.
        self.stage_observer = None
        # The span tree the stages that run on the flushing thread hang
        # in. obs.telemetry swaps in the node's tracer, which makes them
        # children of its `flush` span and adds their CPU seconds and
        # profiler annotations; this bare one only times them.
        self.spans = Tracer()
        # launches of this engine's own programs by shape bucket
        # (voting.bucket_label); the batcher counts the ones it makes
        self.bucket_launches: dict = {}
        self._inflight: Optional[_Inflight] = None
        self._compiling = set()
        # bucket compiles running because a flush of this engine met a
        # compile wait (the (key, use_mesh) gates of _compiling)
        self._awaited_compiles = 0
        self._lock = threading.Lock()

    def _stage(self, stage: str, seconds: float) -> None:
        """One stage sample: legacy rolling total + histogram observer."""
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + seconds
        obs = self.stage_observer
        if obs is not None:
            obs(stage, seconds)

    def _span(self, stage: str):
        """A stage that runs on the flushing thread, as a span that
        records into ``_stage`` when it closes."""
        return self.spans.span(stage, sink=self._stage)

    def _thread_span(self, stage: str):
        """The profiler annotation of a stage on one of this engine's
        helper threads (their time is recorded by ``_apply``, on the
        flushing thread); nothing unless the node's tracer is wired."""
        owner = self.spans.owner
        if owner is None:
            return NULL_STAGE
        return annotation(stage, owner) or NULL_STAGE

    def _thread_name(self, role: str) -> str:
        return f"{self._copro_owner()}:{role}"

    def _count_launch(self, win) -> None:
        """One program of this engine's own was launched at ``win``'s
        shape bucket (single, resident or mesh: one window each)."""
        from babble_tpu.ops import voting

        voting.count_launch(self.bucket_launches, voting.bucket_key(win))

    # -- gates --------------------------------------------------------------

    def should_sweep(self, pending_inserts: int) -> bool:
        return pending_inserts >= self.sweep_events

    def use_device(self, undetermined: int) -> bool:
        """Window-size gate: route small windows to the oracle."""
        if self.min_window is None:
            import os

            from babble_tpu.ops.device import on_accelerator

            env = os.environ.get("BABBLE_ACCEL_MIN_WINDOW")
            if env is not None:
                self.min_window = int(env)
            else:
                self.min_window = 192 if on_accelerator() else 256
        if undetermined >= self.min_window:
            return True
        self.small_windows += 1
        return False

    def busy(self) -> bool:
        """True while decisions are pending on an in-flight sweep — keeps
        the node's fast heartbeat ticking so the next flush applies them —
        and while a bucket compile that a compile wait of this engine
        kicked is still running: a flush that applied a result and whose
        relaunch then met that wait has reported "handled" with the
        newest events still undecided, and nothing else tells a drain (or
        a quiet node) to flush again. It is False again once every such
        compile is over, or has failed."""
        return self._inflight is not None or self._awaited_compiles > 0

    def wait_inflight(self) -> bool:
        """Block until the sweep in flight has been read back (or has
        outlived ``readback_timeout_s``, which the next flush then
        handles). False when none was in flight."""
        inf = self._inflight
        if inf is None:
            return False
        inf.done.wait(self.readback_timeout_s)
        return True

    def handed_to_oracle(self, hg) -> None:
        """The oracle stages are about to run on ``hg``: they mutate fame
        and round-received state the resident mirrors can't track in
        O(ΔE), so the next engaged snapshot rebuilds from scratch. The
        hashgraph's delta channels go too: that rebuild reads the store
        directly, and on a node whose windows never clear the min_window
        gate NO snapshot ever drains them — without this they'd grow one
        entry per witness/fd-update forever."""
        if self.window_state is not None:
            self.window_state.mark_dirty("oracle-pass")
            hg.drain_accel_delta()

    def invalidate(self) -> None:
        """Drop any in-flight sweep (hashgraph reset / fast-sync landing):
        its snapshot no longer describes this store. Reclaim its admission
        slot — dropping the reference would lose the timeout-reclaim path,
        and a wedged readback would then leak the slot forever. If the
        readback is merely slow, the device is briefly over-admitted by
        one sweep; the reader's own eventual release is a no-op."""
        self.generation += 1
        inf = self._inflight
        if inf is not None:
            inf.release_slot()
            # the dropped sweep never reports an outcome; if it was the
            # half-open probe, release the probe slot so the breaker can
            # admit another
            self.breaker.cancel()
        self._inflight = None
        self._last_snapshot_topo = -1
        self.applied_topo = -1
        if self.window_state is not None:
            # drop residency + force a rebuild: the mirrors describe a
            # store that no longer exists
            self.window_state.mark_dirty("invalidate")

    # -- compile management -------------------------------------------------

    def _use_mesh(self, win) -> bool:
        """True when _dispatch will take the sharded path for this window.
        With a mesh configured this is the normal case: windows whose
        witness axis the mesh size doesn't divide are PADDED to it by
        _mesh_align before they get here — the old silent single-device
        fallback is gone. A window that still arrives unaligned (padding
        failed; counted in mesh_fallbacks) rides the single program."""
        return (
            self.mesh is not None
            and win.n_witnesses % self.mesh.devices.size == 0
        )

    def _mesh_align(self, win):
        """Pad the witness axis so the mesh size divides it (repad_window:
        neutral fills, real rows keep their indexes, decisions identical).
        Counts the padding in mesh_pad_rows; a padding failure counts a
        mesh_fallback and returns the window unchanged (single-device)."""
        n = int(self.mesh.devices.size)
        if n <= 0 or win.n_witnesses % n == 0:
            return win
        from babble_tpu.ops import voting

        key = voting.bucket_key(win)
        W_m = key[0]
        while W_m % n:
            if W_m > key[0] * n:
                # doubling a power-of-two W can never reach a multiple of
                # a mesh with an odd factor — give up, ride single-device
                self.mesh_fallbacks += 1
                return win
            W_m *= 2
        try:
            padded = voting.repad_window(win, (W_m,) + key[1:])
        except Exception:
            logger.warning(
                "mesh witness-axis padding failed for bucket %s", key,
                exc_info=True,
            )
            self.mesh_fallbacks += 1
            return win
        self.mesh_pad_rows += W_m - key[0]
        return padded

    def _copro_owner(self) -> str:
        """Stable validator identity for coprocessor multiplexing stats."""
        return self.owner if self.owner else f"tc-{id(self):x}"

    def _bucket_ready(self, win) -> bool:
        """True when the window's shape bucket is compiled FOR THE PATH
        _dispatch will take (single-device and per-mesh jit caches are
        separate programs). Otherwise kicks a background compile (once)
        and returns False."""
        from babble_tpu.ops import voting

        if not self.async_compile:
            return True  # compile inline (tests, explicit opt-out)
        key = voting.bucket_key(win)
        use_mesh = self._use_mesh(win)
        if use_mesh:
            from babble_tpu.parallel import voting_shard

            ready = voting_shard.bucket_ready(self.mesh, key)
        else:
            _follow_peer_axes(key)
            ready = voting.bucket_ready(key)
        if ready:
            return True
        gate = (key, use_mesh)
        with self._lock:
            kick = gate not in self._compiling
            if kick:
                self._compiling.add(gate)
                self._awaited_compiles += 1
        if kick:
            threading.Thread(
                target=self._compile_bucket, args=(key, use_mesh),
                daemon=True, name=self._thread_name("bucket-compile"),
            ).start()
        self.compile_waits += 1
        return False

    def _compile_bucket(self, key: tuple, use_mesh: bool = False) -> None:
        from babble_tpu.ops import voting

        try:
            t0 = time.perf_counter()
            if use_mesh:
                from babble_tpu.parallel import voting_shard

                voting_shard.precompile(self.mesh, *key)
            else:
                voting.precompile(*key)
                _follow_peer_axes(key, compiled=True)
            logger.info(
                "voting kernels ready for bucket %s (mesh=%s) in %.1fs",
                key,
                use_mesh,
                time.perf_counter() - t0,
            )
        except Exception:
            # Leave the bucket un-ready so a later sweep retries the
            # background compile instead of stalling inline on it.
            logger.warning("bucket %s precompile failed", key, exc_info=True)
        finally:
            with self._lock:
                self._compiling.discard((key, use_mesh))
                self._awaited_compiles -= 1

    # -- flush entry point ---------------------------------------------------

    def flush(self, hg) -> bool:
        """Handle one consensus flush. Returns False when the caller must
        run the oracle voting stages instead — and marks the resident
        window state dirty in that case, because the oracle pass that
        follows mutates fame/round-received state the mirrors can't track
        in O(ΔE); the next engaged snapshot rebuilds from scratch."""
        handled = self._flush(hg)
        if not handled:
            self.handed_to_oracle(hg)
        return handled

    def _flush(self, hg) -> bool:
        if self.pipeline is None:
            import os

            env = os.environ.get("BABBLE_ACCEL_PIPELINE")
            if env is not None:
                # test/bench override: exercise the pipelined (or sync)
                # path regardless of the resolved backend
                self.pipeline = env == "1"
            else:
                from babble_tpu.ops.device import on_accelerator

                self.pipeline = on_accelerator()
        if self.batcher is None:
            import os

            # Default: batch only on a REAL accelerator, where dispatch is
            # async and a vmapped batch costs ~one window's latency. On
            # host XLA a central dispatcher convoys sweeps that already
            # run at full host throughput (measured: 16-node threaded
            # accel dropped ~2.7x with the batcher forced on), so CPU
            # tests that force pipeline=True must not pick it up.
            # BABBLE_ACCEL_BATCH=1/0 overrides either way. With a mesh
            # the batcher multiplexes co-located validators onto the
            # sharded program (the coprocessor mode) instead of stacking
            # single-device ones.
            env = os.environ.get("BABBLE_ACCEL_BATCH")
            if env is not None:
                self.batcher = env == "1"
            else:
                from babble_tpu.ops.device import on_accelerator

                self.batcher = on_accelerator()
        if self.resident is None:
            self.resident = resident_default_on()
        if self.resident and self.window_state is None:
            from babble_tpu.ops.window_state import WindowState

            self.window_state = WindowState(mesh=self.mesh)
        # turn on the hashgraph's delta channels (new witnesses, fd
        # mutations) exactly when a WindowState consumes them
        hg._accel_track_delta = bool(self.resident)
        if not self.pipeline:
            if not self.use_device(len(hg.undetermined_events)):
                return False
            if not self.breaker.allow():
                # breaker open: the device is known-bad; don't pay for a
                # dispatch attempt, let the oracle carry the flush
                return False
            return self.sweep(hg)

        handled = False
        inf = self._inflight
        if inf is not None:
            if inf.generation != self.generation:
                inf.release_slot()  # same reclaim rationale as invalidate()
                self._inflight = None
            elif not inf.done.is_set():
                if (
                    time.perf_counter() - inf.t_launch
                    > self.readback_timeout_s
                ):
                    # Hung readback: abandon the sweep and let the oracle
                    # take over so the node keeps deciding. Reclaim the
                    # admission slot here — the parked reader thread may
                    # never finish, and a leaked slot would silently
                    # disable the accelerator process-wide (its own
                    # eventual release is a no-op after this).
                    inf.release_slot()
                    self._inflight = None
                    self._note_fallback(
                        TimeoutError(
                            f"sweep readback exceeded "
                            f"{self.readback_timeout_s:.0f}s"
                        )
                    )
                    return False
                # Results still in flight; decisions arrive next
                # flush. Skipping the oracle here is what hides the
                # readback latency.
                self.deferred += 1
                return True
            else:
                self._inflight = None
                if not self._apply(hg, inf):
                    return False  # oracle carries this flush
                handled = True
        # Relaunch only when the DAG grew since the last snapshot: a sweep
        # over an identical window returns identical decisions, so spinning
        # launch/apply on a quiescent backlog would burn a device sweep per
        # heartbeat for nothing and pin busy() high forever.
        if hg.topological_index != self._last_snapshot_topo and self.use_device(
            len(hg.undetermined_events)
        ):
            if not self.breaker.allow():
                return handled  # breaker open: oracle unless already applied
            launched = self._launch(hg)
            return handled or launched
        return handled

    # -- pipelined internals -------------------------------------------------

    def _dispatch(self, win):
        """Launch the fused sweep — single-device, or witness-axis sharded
        over the configured mesh (bit-identical output, different
        placement). Windows reach here already mesh-aligned (_mesh_align);
        one that didn't (padding failed) is counted and rides the
        single-device program."""
        from babble_tpu.ops import voting

        if self._use_mesh(win):
            from babble_tpu.parallel import voting_shard

            return voting_shard._jitted(self.mesh)(
                *voting_shard.place_window(self.mesh, win)
            )
        if self.mesh is not None:
            self.mesh_fallbacks += 1
        return voting.launch_sweep(win)

    def _snapshot(self, hg, for_batcher: bool = False):
        """This sweep's window: the legacy from-scratch build, or — in
        resident mode — an O(ΔE) WindowState snapshot (delta over the
        persistent mirrors, rebuilding only when a trigger fires).
        Returns (win, snap); win None ⇒ nothing to decide; snap None on
        the legacy path. ``for_batcher`` snapshots copied row arrays so
        the batcher's asynchronous dispatch never reads mirrors a later
        delta mutated in place."""
        from babble_tpu.ops import voting

        if not self.resident:
            with self._span("build"):
                win = voting.build_voting_window(hg)
            return win, None
        timers: dict = {}
        try:
            # WindowState times its own parts (build | delta_scan + pack);
            # the span around them only places them in the tree
            with self.spans.span("snapshot", sink=_parts_recorded):
                snap = self.window_state.snapshot(
                    hg, timers, copy_rows=for_batcher
                )
        finally:
            for k, v in timers.items():
                self._stage(k, v)
        if snap is None:
            return None, None
        self.rows_delta_total += snap.rows_delta
        self.rows_reused_total += snap.rows_reused
        return snap.win, snap

    def _dispatch_snap(self, win, snap):
        """Dispatch one sweep. With a WindowState snapshot, the window
        stays device-resident: the delta program (once warm) donates the
        previous buffers and uploads only the delta; until it is warm the
        full-upload path reseeds residency through the plain program while
        a background thread compiles the delta program. Under a mesh the
        same discipline runs sharded: the delta scatters into per-shard
        resident buffers via voting_shard.resident_jitted."""
        if snap is None or self.batcher:
            return self._dispatch(win)
        from babble_tpu.ops import window_state as ws

        state = self.window_state
        if state.mesh is not None:
            from babble_tpu.parallel import voting_shard

            ready = voting_shard.resident_bucket_ready(state.mesh, state.key)
        else:
            ready = ws.resident_ready(state.key)
        if (
            snap.delta is not None
            and state.device is not None
            and self.async_compile
            and not ready
        ):
            self._kick_resident(state.key)
        out, _used_delta = state.dispatch(
            snap, allow_inline_compile=not self.async_compile
        )
        return out

    def _kick_resident(self, key: tuple) -> None:
        from babble_tpu.ops import window_state as ws

        mesh = self.window_state.mesh if self.window_state else None
        gate = (key, "resident", mesh is not None)
        with self._lock:
            if gate in self._compiling:
                return
            self._compiling.add(gate)

        def work() -> None:
            try:
                t0 = time.perf_counter()
                if mesh is not None:
                    from babble_tpu.parallel import voting_shard

                    voting_shard.precompile_resident(mesh, *key)
                else:
                    ws.precompile_resident(*key)
                logger.info(
                    "resident delta program ready for bucket %s (mesh=%s)"
                    " in %.1fs",
                    key, mesh is not None, time.perf_counter() - t0,
                )
            except Exception:
                logger.warning(
                    "resident precompile failed for %s", key, exc_info=True
                )
            finally:
                with self._lock:
                    self._compiling.discard(gate)

        threading.Thread(target=work, daemon=True,
                         name=self._thread_name("resident-compile")).start()

    def _launch(self, hg) -> bool:
        from babble_tpu.ops import voting

        try:
            win, snap = self._snapshot(hg, for_batcher=bool(self.batcher))
            if win is None:
                self.breaker.cancel()  # no device attempt to judge
                self.applied_topo = hg.topological_index
                return True  # nothing undecided
            if self.mesh is not None:
                # resident snapshots are already mesh-aligned (WindowState
                # aligns W at rebuild); this pads the legacy/batcher path
                win = self._mesh_align(win)
            if not self._bucket_ready(win):
                if snap is not None:
                    # the snapshot's delta is committed to the mirrors but
                    # never reached the device — reseed residency later
                    self.window_state.drop_residency()
                self.breaker.cancel()
                return False
        except Exception as err:
            self._note_fallback(err)
            return False

        if self.batcher:
            # Co-located batching: the process-wide batcher coalesces this
            # window with other nodes' into ONE device dispatch + readback;
            # its own backpressure replaces the admission slots.
            from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

            ticket = SweepBatcher.instance().submit(
                win, mesh=self.mesh, owner=self._copro_owner()
            )
            if ticket is None:
                # backlogged: the oracle carries this flush (same
                # economics as losing an admission slot)
                self.contended += 1
                if snap is not None:
                    self.window_state.drop_residency()
                self.breaker.cancel()
                return False
            inf = _Inflight(win, self.generation, hg.topological_index,
                            None, snap)

            def batch_reader() -> None:
                try:
                    t_r = time.perf_counter()
                    with self._thread_span("readback"):
                        ticket.done.wait()
                    # coalesce wait + dispatch + readback, from this
                    # node's perspective; its last part is this thread
                    # getting to run again once the batcher was done
                    t_w = time.perf_counter()
                    inf.readback_s = t_w - t_r
                    inf.wake_s = t_w - ticket.t_read
                    if ticket.error is not None:
                        inf.error = ticket.error
                    else:
                        inf.result = ticket.result
                finally:
                    inf.t_done = time.perf_counter()
                    inf.done.set()

            threading.Thread(
                target=batch_reader, daemon=True,
                name=self._thread_name("sweep-reader"),
            ).start()
            self._inflight = inf
            self._last_snapshot_topo = hg.topological_index
            return True

        # Admission control covers only actual device occupancy — the
        # host-side window build above runs slot-free so co-located nodes
        # aren't starved during work that never touches the device.
        try:
            slots = _inflight_slots()
            acquired = slots.acquire(blocking=False)
        except OSError as err:
            # _FlockSlots.acquire opens slot files; a vanished slot dir or
            # fd exhaustion must degrade to the oracle like every other
            # failure in this module, never kill the gossip path.
            self._note_fallback(err)
            return False
        if not acquired:
            # Device already at max in-flight sweeps (co-located nodes
            # share it): let the oracle carry this flush instead of
            # joining a readback convoy.
            self.contended += 1
            if snap is not None:
                self.window_state.drop_residency()
            self.breaker.cancel()
            return False
        inf = _Inflight(win, self.generation, hg.topological_index, slots,
                        snap)
        try:
            with self._span("dispatch"):
                out = self._dispatch_snap(win, snap)
            self._count_launch(win)

            def reader() -> None:
                try:
                    t_r = time.perf_counter()
                    with self._thread_span("readback"):
                        inf.result = voting.read_sweep(out, inf.win)
                    inf.readback_s = time.perf_counter() - t_r
                except BaseException as e:  # device failure
                    inf.error = e
                finally:
                    inf.release_slot()
                    inf.t_done = time.perf_counter()
                    inf.done.set()

            threading.Thread(
                target=reader, daemon=True,
                name=self._thread_name("sweep-reader"),
            ).start()
        except BaseException as err:
            inf.release_slot()
            if not isinstance(err, Exception):
                raise  # KeyboardInterrupt & friends propagate
            self._note_fallback(err)
            return False
        self._inflight = inf
        self._last_snapshot_topo = hg.topological_index
        return True

    def _apply(self, hg, inf: _Inflight) -> bool:
        from babble_tpu.ops import voting

        t0 = time.perf_counter()
        if inf.error is not None:
            if _is_stale_window(inf.error):
                # batcher rejected an aged-out window: neutral outcome,
                # same handling as the snap-generation check below
                self.stale_drops += 1
                self.breaker.cancel()
                return False
            self._note_fallback(inf.error)
            return False
        state = self.window_state
        if inf.snap is not None and (
            state is None or inf.snap.generation != state.generation
        ):
            # Donation/generation safety: the resident state mutated after
            # this sweep launched (rebuild, invalidate, a newer snapshot),
            # so its row maps no longer describe these results. Discard
            # them — the oracle carries this flush and the dirty state
            # rebuilds at the next snapshot.
            self.stale_drops += 1
            self.breaker.cancel()  # not the device's fault: no verdict
            return False
        with self._span("apply"):
            try:
                fame, rr = inf.result
                _decided, fame_applied = voting.apply_fame(hg, inf.win, fame)
                received = voting.apply_round_received(hg, inf.win, rr)
            except Exception as err:
                self._note_fallback(err)
                return False
            if inf.snap is not None and state is not None:
                state.note_applied(fame_applied, received)
        self._stage("readback", inf.readback_s)
        if inf.wake_s is not None:
            self._stage("wake", inf.wake_s)
        # the result lay ready until this flush came
        self._stage("result_idle", max(0.0, t0 - inf.t_done))
        self.breaker.record_success()
        self.sweeps += 1
        self.applied_topo = inf.topo
        self.last_window_events = len(inf.win.hashes)
        return True

    # -- synchronous sweep ---------------------------------------------------

    def sweep(self, hg) -> bool:
        """One blocking fused sweep. Returns False when the caller must
        fall back to the oracle pipeline."""
        from babble_tpu.ops import voting

        try:
            win, snap = self._snapshot(hg, for_batcher=bool(self.batcher))
            if win is None:
                self.breaker.cancel()  # no device attempt to judge
                self.applied_topo = hg.topological_index
                return True  # nothing undecided
            if self.mesh is not None:
                win = self._mesh_align(win)
            if not self._bucket_ready(win):
                self.breaker.cancel()
                return False
            t1 = time.perf_counter()
            if self.batcher:
                # Synchronous mode still coalesces with concurrent nodes:
                # submit and wait — co-located threads flushing in the
                # same wave share the dispatch.
                from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

                ticket = SweepBatcher.instance().submit(
                    win, mesh=self.mesh, owner=self._copro_owner()
                )
                if ticket is None:
                    self.contended += 1
                    self.breaker.cancel()
                    return False
                self._stage("dispatch", time.perf_counter() - t1)
                with self._span("readback"):
                    if not ticket.done.wait(self.readback_timeout_s):
                        raise TimeoutError(
                            "batched sweep exceeded "
                            f"{self.readback_timeout_s:.0f}s"
                        )
                    self._stage("wake", time.perf_counter() - ticket.t_read)
                if ticket.error is not None:
                    raise ticket.error
                fame, rr = ticket.result
            else:
                with self._span("dispatch"):
                    out = self._dispatch_snap(win, snap)
                self._count_launch(win)
                with self._span("readback"):
                    fame, rr = voting.read_sweep(out, win)
            with self._span("apply"):
                _decided, fame_applied = voting.apply_fame(hg, win, fame)
                received = voting.apply_round_received(hg, win, rr)
                if snap is not None and self.window_state is not None:
                    self.window_state.note_applied(fame_applied, received)
        except Exception as err:
            if _is_stale_window(err):
                self.stale_drops += 1
                self.breaker.cancel()
                return False
            self._note_fallback(err)
            return False
        self.breaker.record_success()
        self.sweeps += 1
        self.applied_topo = hg.topological_index
        self.last_window_events = len(win.hashes)
        return True

    def _note_fallback(self, err: BaseException) -> None:
        # Any failure — store eviction, a device error mid-run, a device
        # OOM — must degrade to the oracle, not kill the sync. Writebacks
        # are ordered so no partial mutation precedes a fallible read (see
        # apply_round_received), making the oracle re-run safe.
        self.fallbacks += 1
        # feed the circuit breaker: N of these within its window open it,
        # and the node stops paying for device attempts until a cooldown
        # probe succeeds (state machine in common/breaker.py)
        self.breaker.record_failure()
        if self.window_state is not None:
            # the oracle pass that follows mutates state the mirrors can't
            # track; the next snapshot must rebuild
            self.window_state.mark_dirty("fallback")
        if isinstance(err, StoreError):
            logger.warning("accelerated sweep fell back to oracle: %s", err)
        else:
            logger.warning(
                "accelerated sweep fell back to oracle",
                exc_info=(type(err), err, err.__traceback__),
            )

    def stats(self) -> dict:
        from babble_tpu.ops import voting as _voting

        out = {
            "consensus_engine": "device",
            # which strongly-see path the sweep kernels trace: "tpu" =
            # Pallas on hardware, "interpret" = Pallas interpreter
            # (tests), None = XLA einsum
            "accel_pallas": _voting.pallas_mode(),
            "accel_batcher": bool(self.batcher),
            "accel_sweeps": self.sweeps,
            "accel_fallbacks": self.fallbacks,
            "accel_compile_waits": self.compile_waits,
            "accel_small_windows": self.small_windows,
            "accel_deferred": self.deferred,
            "accel_contended": self.contended,
            "accel_min_window": self.min_window,
            "accel_pipeline": self.pipeline,
            "accel_mesh": (
                "x".join(str(d) for d in self.mesh.devices.shape)
                if self.mesh is not None
                else None
            ),
            "accel_last_window_events": self.last_window_events,
            # Per-stage breakdown (ms totals): snapshot cost is build (full
            # rebuilds) + delta_scan + pack (incremental); dispatch and
            # readback split the device leg; wake (inside readback) and
            # result_idle are the waits between this engine's threads.
            "accel_stage_ms": {
                k: round(1000.0 * v, 1) for k, v in self.stage_s.items()
            },
            "accel_bucket_launches": dict(self.bucket_launches),
            # Resident-window counters: delta rows uploaded vs rows served
            # from the device-resident buffers, and how often the
            # incremental state fell back to a from-scratch rebuild.
            "accel_resident": bool(self.resident),
            "accel_rows_delta": self.rows_delta_total,
            "accel_rows_reused": self.rows_reused_total,
            "accel_rebuilds": (
                self.window_state.rebuilds
                if self.window_state is not None
                else 0
            ),
            # ... and what forced them (window_state.py: "oracle-pass",
            # "repertoire-change", "peer-set-slot-overflow", ...)
            "accel_rebuilds_by_reason": (
                dict(self.window_state.rebuilds_by_reason)
                if self.window_state is not None
                else {}
            ),
            # programs compiled ahead of need because the validator set
            # moved P or S (process-wide, see _follow_peer_axes)
            "accel_variant_compiles": variant_compiles,
            "accel_variant_backlog": variant_backlog(),
            # programs prewarm_buckets compiled or loaded, and the seconds
            # its passes took (process-wide, summed over prewarm threads)
            "accel_prewarm_programs": prewarm_programs,
            "accel_prewarm_seconds": round(prewarm_seconds, 6),
            "accel_stale_drops": self.stale_drops,
            # Mesh padding visibility: witness rows added to align W to
            # the mesh, and windows that dropped to single-device anyway
            "accel_mesh_pad_rows": self.mesh_pad_rows,
            "accel_mesh_fallbacks": self.mesh_fallbacks,
        }
        # circuit-breaker surface: accel_breaker_state/open/probes/skips/
        # failures (open = count of closed→open transitions)
        out.update(self.breaker.stats(prefix="accel_breaker_"))
        if self.batcher or (self.batcher is None and batcher_default_on()):
            # also before the first flush has resolved ``batcher``: the
            # tallies are process-wide, and a reader that differences two
            # snapshots of a fresh engine would otherwise take the whole
            # process's history for this engine's window
            from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

            out.update(SweepBatcher.instance().stats())
        return out


def resident_default_on() -> bool:
    """Whether TensorConsensus will resolve resident=True with default
    settings (BABBLE_ACCEL_RESIDENT unset or not \"0\"). Used by prewarm
    to decide whether the resident delta programs are worth compiling."""
    import os

    return os.environ.get("BABBLE_ACCEL_RESIDENT") != "0"


def batcher_default_on() -> bool:
    """Whether TensorConsensus will resolve batcher=True with default
    settings: forced by BABBLE_ACCEL_BATCH, else pipelined (accelerator)
    mode. Used by prewarm to decide whether the batched floor bucket is
    worth compiling."""
    import os

    env = os.environ.get("BABBLE_ACCEL_BATCH")
    if env is not None:
        return env == "1"
    from babble_tpu.ops.device import on_accelerator

    return on_accelerator()


# Process-wide tallies of prewarm_buckets' work: programs compiled (or
# loaded from the persistent cache) and the seconds its passes took,
# summed over every prewarm thread (co-located nodes run one each, so
# the seconds can exceed the wall time); updated under _prewarm_lock.
prewarm_programs = 0
prewarm_seconds = 0.0
_prewarm_lock = threading.Lock()

#: Validators a ring of the legacy list was sized for: at or below it
#: prewarm_keys returns the list the 16-validator cells were tuned on, and
#: prewarm_buckets seeds the batcher's floor.
_LEGACY_PEERS = 16


def prewarm_keys(n_peers: int) -> list:
    """The single-window buckets (W, E, P, S, R) prewarm_buckets compiles
    for a ring of ``n_peers`` validators at one peer-set slot.

    Up to 16 validators it is the list the 16-validator cells were tuned
    on. Above, it scales with the ring: a round holds one witness per
    validator and, in random gossip, about 11 events per validator (700 at
    63 creators). A window of k rounds of witnesses takes W = k·n rounded
    up to a power of two, for k = 2, 4, 8, 16; it holds between a third and
    all of those rounds' events undetermined, E = 4W or 8W; and spans R 8
    up to 4 rounds, R 8 or 16 at 8, R 16 or 32 at 16 (a rebuild's slack of
    two rounds included)."""
    from babble_tpu.ops import voting

    P = voting._bucket_mult(n_peers, 8)
    S = 1
    if n_peers <= _LEGACY_PEERS:
        keys = [
            (16, 32, P, S, 8),
            (16, 64, P, S, 8),
            (32, 128, P, S, 8),
            (64, 256, P, S, 8),
            (64, 256, P, S, 16),
            (64, 512, P, S, 16),
            (128, 512, P, S, 16),
            (128, 1024, P, S, 16),
        ]
        if n_peers >= 12:
            # sustained backlogs at 16+ validators accumulate rounds past
            # the R=16 bucket before decisions drain; compiling R=32 up
            # front keeps mid-run compiles (and their single-core steal)
            # off the hot path. Small clusters never hit these shapes —
            # skipping them keeps their prewarm cheap.
            keys += [
                (128, 1024, P, S, 32),
                (256, 1024, P, S, 32),
            ]
        return keys
    keys = []
    for k, rs in ((2, (8,)), (4, (8,)), (8, (8, 16)), (16, (16, 32))):
        W = voting._bucket_pow2(k * n_peers, 16)
        keys += [(W, E, P, S, R) for R in rs for E in (4 * W, 8 * W)]
    return keys


def prewarm_buckets(n_peers: int, background: bool = True, mesh=None,
                    spans: Optional[Tracer] = None):
    """Compile (or load from the persistent XLA cache) the window-shape
    buckets a freshly started node is most likely to hit (prewarm_keys),
    so the first real backlog meets warm kernels instead of a compile
    wait. Called from Node.init when --accelerator is on; runs in a daemon
    thread by default (compiles happen in XLA's C++ with the GIL
    released). With a mesh, the SHARDED kernels are warmed too (separate
    jit cache). The work is the span ``prewarm`` of ``spans`` (the node's
    tracer) on the thread that does it; ``accel_prewarm_programs`` /
    ``accel_prewarm_seconds`` in stats() count it process-wide."""
    from babble_tpu.ops import voting

    P = voting._bucket_mult(n_peers, 8)
    buckets = prewarm_keys(n_peers)
    tracer = spans if spans is not None else Tracer()

    def warm() -> None:
        global prewarm_programs, prewarm_seconds
        t0 = time.perf_counter()
        done = 0
        try:
            with tracer.span("prewarm"):
                done = work()
        finally:
            with _prewarm_lock:
                prewarm_programs += done
                prewarm_seconds += time.perf_counter() - t0

    def work() -> int:
        """Returns the programs it compiled or loaded."""
        done = 0
        if (mesh is None and batcher_default_on()
                and n_peers <= _LEGACY_PEERS):
            # Seed the co-located batcher: compile the B=MAX_BATCH floor
            # bucket and pin it as the batcher's target floor, so the
            # FIRST flush wave meets a warm batched program instead of a
            # compile kick (the monotone target then stays inside this
            # shape until windows genuinely outgrow it). No floor was
            # measured for co-located nodes of a larger ring: there the
            # first wave sets the batcher's target.
            from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

            floor = (
                (128, 1024, P, 1, 32) if n_peers >= 12 else (64, 512, P, 1, 16)
            )

            svc = SweepBatcher.instance()
            if svc.floor_key is None or tuple(
                max(a, b) for a, b in zip(svc.floor_key, floor)
            ) != svc.floor_key:
                try:
                    voting.precompile_batched(SweepBatcher.MAX_BATCH, *floor)
                    svc.floor_key = floor
                    done += 1
                except Exception:
                    logger.warning(
                        "batched floor prewarm failed for %s", floor,
                        exc_info=True,
                    )
        for key in buckets:
            if mesh is not None:
                # the sharded kernel is the only one _dispatch will ever
                # run for this bucket — don't burn compile time (and
                # device contention) on the unused single-device program.
                # Buckets whose W the mesh doesn't divide are warmed at
                # the shape _mesh_align pads them to.
                from babble_tpu.parallel import voting_shard

                n = int(mesh.devices.size)
                W_m = key[0]
                while W_m % n:
                    W_m *= 2
                key = (W_m,) + key[1:]
                if not voting_shard.bucket_ready(mesh, key):
                    try:
                        voting_shard.precompile(mesh, *key)
                        done += 1
                    except Exception:
                        logger.warning(
                            "mesh prewarm failed for %s", key, exc_info=True
                        )
                if resident_default_on() and not batcher_default_on():
                    # the mesh resident delta program is a separate
                    # executable, same rationale as the single-device one
                    if not voting_shard.resident_bucket_ready(mesh, key):
                        try:
                            voting_shard.precompile_resident(mesh, *key)
                            done += 1
                        except Exception:
                            logger.warning(
                                "mesh resident prewarm failed for %s", key,
                                exc_info=True,
                            )
            elif not voting.bucket_ready(key):
                try:
                    voting.precompile(*key)
                    done += 1
                except Exception:
                    logger.warning(
                        "prewarm failed for %s", key, exc_info=True
                    )
            if mesh is None and resident_default_on() and not batcher_default_on():
                # resident delta program for the same bucket (a separate
                # executable): first delta sweeps then meet a warm
                # program instead of riding full uploads while a
                # background compile catches up. With the batcher on,
                # sweeps ride the vmapped program and the resident
                # executable would never run — don't burn compiles on it.
                from babble_tpu.ops import window_state as ws

                if not ws.resident_ready(key):
                    try:
                        ws.precompile_resident(*key)
                        done += 1
                    except Exception:
                        logger.warning(
                            "resident prewarm failed for %s", key,
                            exc_info=True,
                        )
        return done

    if background:
        t = threading.Thread(target=warm, daemon=True, name="voting-prewarm")
        t.start()
        return t
    warm()
    return None
