"""SweepBatcher: co-located nodes' sweeps coalesced into ONE vmapped
device dispatch (babble_tpu/hashgraph/sweep_batcher.py).

Pinned properties:
- the batched (vmapped) sweep is bit-identical per window to the
  single-window program, including batch padding rows;
- concurrent same-bucket submissions actually share a dispatch
  (ticket.batch_size > 1) once the batched bucket is warm;
- unwarmed batch shapes degrade to warm single dispatches (liveness);
- a live accelerated replay with the batcher enabled produces the
  oracle's exact consensus.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from babble_tpu.ops import voting


def _two_windows():
    """Two same-bucket voting windows from different replayed DAGs."""
    from tests.test_accel import BUILDERS, _ordered_events
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore

    wins = []
    for name in ("consensus", "consensus"):
        h0, index, nodes, peer_set = BUILDERS[name]()
        ordered = _ordered_events(h0)
        h = Hashgraph(InmemStore(1000))
        h.init(peer_set)
        # second replay drops the tail event so the windows differ
        drop = 1 if wins else 0
        for ev in ordered[: len(ordered) - drop]:
            e = Event(ev.body, ev.signature)
            e.prevalidate(True)
            h.insert_event(e, set_wire_info=True)
            h.divide_rounds()
        wins.append(voting.build_voting_window(h))
    assert wins[0] is not None and wins[1] is not None
    return wins


def test_batched_sweep_matches_single():
    wins = _two_windows()
    key0, key1 = voting.bucket_key(wins[0]), voting.bucket_key(wins[1])
    assert key0 == key1, "builder DAGs should share a shape bucket"
    singles = [voting.run_sweep(w) for w in wins]
    for B in (2, 4):
        batched = voting.read_batched(voting.launch_batched(wins, B), wins)
        for (f1, r1), (f2, r2) in zip(singles, batched):
            np.testing.assert_array_equal(f1, f2)
            np.testing.assert_array_equal(r1, r2)


def test_repad_window_preserves_decisions():
    """A window grown to a larger bucket (every axis) sweeps to the exact
    decisions of the original — the invariant the batcher's wave re-padding
    rests on."""
    wins = _two_windows()
    for win in wins:
        W, E, P, S, R = voting.bucket_key(win)
        grown = voting.repad_window(win, (W * 2, E * 2, P + 8, S * 2, R * 2))
        f1, r1 = voting.run_sweep(win)
        f2, r2 = voting.run_sweep(grown)
        np.testing.assert_array_equal(f1, f2[: len(f1)])
        np.testing.assert_array_equal(r1, r2[: len(r1)])


def test_batcher_coalesces_mixed_buckets():
    """Windows from DIFFERENT shape buckets still share one dispatch: the
    wave re-pads to its elementwise-max bucket."""
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    wins = _two_windows()
    key = voting.bucket_key(wins[0])
    # grow one window's bucket so the two differ
    big = voting.repad_window(wins[1], (key[0] * 2, key[1], key[2],
                                        key[3], key[4]))
    target = (key[0] * 2,) + key[1:]
    voting.precompile_batched(SweepBatcher.MAX_BATCH, *target)

    svc = SweepBatcher()
    singles = [voting.run_sweep(wins[0]), voting.run_sweep(big)]
    t1, t2 = svc.submit(wins[0]), svc.submit(big)
    assert t1.done.wait(60) and t2.done.wait(60)
    assert t1.error is None and t2.error is None, (t1.error, t2.error)
    assert t1.batch_size == 2 and t2.batch_size == 2
    for t, (f_want, r_want) in zip((t1, t2), singles):
        f_got, r_got = t.result
        np.testing.assert_array_equal(f_got, f_want[: len(f_got)])
        np.testing.assert_array_equal(r_got, r_want[: len(r_got)])


def test_batcher_backpressure_refuses_past_cap():
    from babble_tpu.hashgraph import sweep_batcher as sb

    win = _two_windows()[0]
    svc = sb.SweepBatcher.__new__(sb.SweepBatcher)  # no dispatcher thread
    svc._lock = __import__("threading").Lock()
    svc._pending = []
    svc._work = __import__("threading").Event()
    svc.refused = 0
    tickets = [svc.submit(win) for _ in range(sb.SweepBatcher.MAX_QUEUE + 3)]
    assert sum(1 for t in tickets if t is None) == 3
    assert svc.refused == 3


def test_batcher_coalesces_concurrent_submissions():
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    wins = _two_windows()
    key = voting.bucket_key(wins[0])
    voting.precompile_batched(SweepBatcher.MAX_BATCH, *key)
    assert voting.batched_ready(key, SweepBatcher.MAX_BATCH)

    # fresh instance: the singleton's monotone target may have been grown
    # past this bucket by other tests
    svc = SweepBatcher()
    singles = [voting.run_sweep(w) for w in wins]
    tickets = []
    lock = threading.Lock()

    def submit(w):
        t = svc.submit(w)
        with lock:
            tickets.append(t)
        t.done.wait(60)

    threads = [threading.Thread(target=submit, args=(w,)) for w in wins]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(tickets) == 2
    for t in tickets:
        assert t.done.is_set()
        assert t.error is None, t.error
    # both rode one dispatch
    assert all(t.batch_size == 2 for t in tickets), [
        t.batch_size for t in tickets
    ]
    got = {id(t.win): t.result for t in tickets}
    for w, (f_want, r_want) in zip(wins, singles):
        f_got, r_got = got[id(w)]
        np.testing.assert_array_equal(f_got, f_want)
        np.testing.assert_array_equal(r_got, r_want)


def test_batcher_unwarmed_degrades_to_singles():
    from babble_tpu.hashgraph import sweep_batcher as sb

    wins = _two_windows()
    key = voting.bucket_key(wins[0])

    # a fresh service instance (not the singleton) with an un-warmed
    # batched bucket for the standard batch size: group must ride singles
    svc = sb.SweepBatcher()
    with voting._bucket_lock():
        voting._ready_batched.discard((sb.SweepBatcher.MAX_BATCH, key))
    t1, t2 = svc.submit(wins[0]), svc.submit(wins[1])
    assert t1.done.wait(60) and t2.done.wait(60)
    assert t1.error is None and t2.error is None
    assert t1.batch_size == 1 and t2.batch_size == 1
    assert svc.singles >= 2
    # and the compile kick was recorded so a later wave can batch
    assert svc.compile_kicks >= 1


def test_batcher_dispatch_failure_fails_tickets_not_daemon(monkeypatch):
    """A device failure mid-batch must error every ticket in the wave
    (the owning nodes fall back to their oracles) and leave the
    dispatcher thread alive for the next wave."""
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher
    from babble_tpu.ops import voting

    wins = _two_windows()
    key = voting.bucket_key(wins[0])
    voting.precompile_batched(SweepBatcher.MAX_BATCH, *key)

    svc = SweepBatcher()

    def boom(*a, **k):
        raise RuntimeError("device fell off the bus")

    monkeypatch.setattr(voting, "launch_batched", boom)
    monkeypatch.setattr(voting, "launch_sweep", boom)
    t1, t2 = svc.submit(wins[0]), svc.submit(wins[1])
    assert t1.done.wait(30) and t2.done.wait(30)
    assert isinstance(t1.error, RuntimeError)
    assert isinstance(t2.error, RuntimeError)

    # the daemon survives: with the fault cleared, the next wave serves
    monkeypatch.undo()
    t3 = svc.submit(wins[0])
    assert t3.done.wait(30)
    assert t3.error is None
    f_want, r_want = voting.run_sweep(wins[0])
    np.testing.assert_array_equal(t3.result[0], f_want)
    np.testing.assert_array_equal(t3.result[1], r_want)


@pytest.mark.parametrize("graph", ["consensus", "funky_full"])
def test_accel_with_batcher_matches_oracle(graph):
    from tests.test_accel import (
        BUILDERS,
        _consensus_state,
        _ordered_events,
        _replay,
    )
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
    from babble_tpu.hashgraph.accel import TensorConsensus

    h0, index, nodes, peer_set = BUILDERS[graph]()
    ordered = _ordered_events(h0)
    oracle = _replay(ordered, peer_set)

    h = Hashgraph(InmemStore(1000))
    h.init(peer_set)
    h.accel = TensorConsensus(sweep_events=8, async_compile=False,
                              min_window=0, batcher=True)
    for ev in ordered:
        e = Event(ev.body, ev.signature)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    h.flush_consensus()
    assert h.accel.fallbacks == 0
    assert _consensus_state(h) == _consensus_state(oracle)


def test_target_bucket_decays_after_sustained_small_waves():
    """One oversized window must not permanently inflate the padded
    shapes: after DECAY_WAVES consecutive waves strictly below the
    target, the bucket shrinks back to the observed per-wave max."""
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    b = SweepBatcher.__new__(SweepBatcher)  # no dispatcher thread
    b._target = None
    b._below_waves = 0
    b._decay_max = None
    b.target_decays = 0

    small = (8, 4, 2, 2, 2)
    spike = (64, 32, 8, 8, 8)

    assert b._update_target(small) == small
    # one oversized wave inflates the target (monotone growth preserved)
    assert b._update_target(spike) == spike
    # small waves keep padding to the spike shape for DECAY_WAVES...
    for _ in range(SweepBatcher.DECAY_WAVES - 1):
        assert b._update_target(small) == spike
    # ...then the bucket decays to the observed max of the window
    assert b._update_target(small) == small
    assert b.target_decays == 1
    # regrowth still works after a decay
    assert b._update_target(spike) == spike


def test_target_bucket_decay_resets_on_regrowth():
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    b = SweepBatcher.__new__(SweepBatcher)
    b._target = None
    b._below_waves = 0
    b._decay_max = None
    b.target_decays = 0

    small = (8, 4, 2, 2, 2)
    mid = (16, 8, 4, 4, 4)
    spike = (64, 32, 8, 8, 8)

    b._update_target(spike)
    for _ in range(SweepBatcher.DECAY_WAVES - 1):
        b._update_target(small)
    # a wave AT the target resets the observation window: no decay yet
    assert b._update_target(spike) == spike
    for _ in range(SweepBatcher.DECAY_WAVES - 1):
        assert b._update_target(mid) == spike
    assert b.target_decays == 0
    # the decayed bucket is the window's observed max, not the smallest
    b._update_target(mid)
    assert b._target == mid
    assert b.target_decays == 1


# -- a window's life: stamps, stage sums, the bucket that ran ----------------


def test_ticket_stamps_are_monotone_and_sum_inside_the_owners_wait():
    """submit <= taken <= launched <= read, taken a coalesce interval after
    submit, and queue + launch + read (what SweepBatcher.stats() sums) is
    no more than the owner waited for its ticket."""
    import time

    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    win = _two_windows()[0]
    voting.precompile(*voting.bucket_key(win))
    svc = SweepBatcher()
    t0 = time.perf_counter()
    t = svc.submit(win)
    assert t.done.wait(60) and t.error is None, t.error
    waited = time.perf_counter() - t0
    assert t0 <= t.t_submit <= t.t_taken <= t.t_launched <= t.t_read
    assert t.t_taken - t.t_submit >= SweepBatcher.COALESCE_S
    assert t.c_taken <= t.c_launched <= t.c_read  # the batcher's CPU clock
    stats = svc.stats()
    life = sum(stats["batch_stage_ms"].values())
    assert set(stats["batch_stage_ms"]) == {"queue", "launch", "read"}
    assert life == pytest.approx(1e3 * (t.t_read - t.t_submit), abs=0.01)
    assert 0 < life <= 1e3 * waited
    cpu = stats["batch_stage_cpu_ms"]
    assert set(cpu) == {"launch", "read"}
    assert 0 <= cpu["launch"] <= stats["batch_stage_ms"]["launch"] + 1.0
    # one window served: the sums are per window, like batch_windows
    assert stats["batch_windows"] == 1


def test_failed_ticket_is_stamped_but_not_summed(monkeypatch):
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    win = _two_windows()[0]
    svc = SweepBatcher()

    def boom(*a, **k):
        raise RuntimeError("device fell off the bus")

    monkeypatch.setattr(voting, "launch_sweep", boom)
    t = svc.submit(win)
    assert t.done.wait(30) and isinstance(t.error, RuntimeError)
    assert t.t_read >= t.t_taken >= t.t_submit
    stats = svc.stats()
    assert stats["batch_stage_ms"] == {"queue": 0.0, "launch": 0.0,
                                       "read": 0.0}
    assert stats["batch_bucket_launches"] == {}


def test_bucket_launches_count_the_bucket_that_ran():
    """A lone window counts 1x<its own bucket>; a batched wave counts ONE
    launch of 16x<the target bucket> — not the windows' own buckets."""
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    wins = _two_windows()
    key = voting.bucket_key(wins[0])
    B = SweepBatcher.MAX_BATCH
    big = voting.repad_window(wins[1], (key[0] * 2,) + key[1:])
    target = (key[0] * 2,) + key[1:]
    voting.precompile_batched(B, *target)

    svc = SweepBatcher()
    t0 = svc.submit(wins[0])
    assert t0.done.wait(60) and t0.error is None, t0.error
    single = voting.bucket_label(key)
    assert single == "1x" + "x".join(str(d) for d in key)
    assert "." not in single  # the harness flattens dotted names
    assert svc.stats()["batch_bucket_launches"] == {single: 1}

    t1, t2 = svc.submit(wins[0]), svc.submit(big)
    assert t1.done.wait(60) and t2.done.wait(60)
    assert t1.batch_size == 2 and t2.batch_size == 2
    assert svc.stats()["batch_bucket_launches"] == {
        single: 1, voting.bucket_label(target, B): 1}
    assert voting.bucket_label(target, B).startswith(f"{B}x{key[0] * 2}x")
    # three windows served, two launches
    assert svc.stats()["batch_windows"] == 3
    # both windows of the wave were launched by the one program
    assert t1.t_launched == t2.t_launched


def test_engine_counts_its_own_launches_and_names_its_threads():
    """Without the batcher a TensorConsensus launches its own programs and
    counts them under accel_bucket_launches; with it, the reader thread
    carries the owner's name and the owner records wake + result_idle."""
    from tests.test_accel import BUILDERS, _ordered_events
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
    from babble_tpu.hashgraph.accel import TensorConsensus

    h0, index, nodes, peer_set = BUILDERS["consensus"]()
    ordered = _ordered_events(h0)

    def replay(**kw):
        h = Hashgraph(InmemStore(1000))
        h.init(peer_set)
        h.accel = TensorConsensus(sweep_events=8, async_compile=False,
                                  min_window=0, owner="v3", **kw)
        names = set()
        for ev in ordered:
            h.insert_event_and_run_consensus(
                Event(ev.body, ev.signature), set_wire_info=True)
            names |= {t.name for t in threading.enumerate()}
        for _ in range(200):
            h.flush_consensus()
            if not h.accel.busy():
                break
            h.accel._inflight.done.wait(10)
        return h.accel, names

    own, _ = replay(batcher=False, pipeline=False)
    s = own.stats()
    assert s["accel_sweeps"] > 0
    assert sum(s["accel_bucket_launches"].values()) == s["accel_sweeps"]
    assert all(k.startswith("1x") for k in s["accel_bucket_launches"])
    assert "kernel" not in s["accel_stage_ms"]
    assert s["accel_stage_ms"]["wake"] == 0  # no batcher: nobody to wake

    piped, names = replay(batcher=True, pipeline=True)
    s = piped.stats()
    assert s["accel_sweeps"] > 0 and s["accel_fallbacks"] == 0
    assert s["accel_bucket_launches"] == {}  # the batcher launched them
    assert "v3:sweep-reader" in names
    st = piped.stage_s
    assert st["wake"] > 0 and st["result_idle"] > 0
    assert st["wake"] <= st["readback"]


def test_the_two_sweep_programs_have_stable_distinct_names():
    """A profiler trace finds a program by its jit name: both contain
    ``counting_sweep`` (the benchmark's ``sweep_device_us`` matches it) and
    they differ, so single and vmapped executions can be told apart."""
    win = _two_windows()[0]
    args = [np.asarray(getattr(win, f)) for f in voting._WIN_FIELDS]
    single = voting._sweep_jit.lower(*args).as_text()
    batched = voting._batched_sweep_jit.lower(
        *(np.stack([a, a]) for a in args)).as_text()
    assert "module @jit_counting_sweep_single" in single
    assert "module @jit_counting_sweep_batched" in batched
    # the fused program's stages are named scopes inside it
    debug = voting._sweep_jit.lower(*args).as_text(debug_info=True)
    for scope in ("fame", "strongly_see_counts", "round_received"):
        assert scope in debug, scope
