#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that babble_tpu still starts on the chip.

Drives the main path once, in ONE process (a chip belongs to one process at
a time; nothing here starts a child): sixteen validators with
``Config(accelerator=True)`` and upstream defaults, 100-byte transactions,
through ``Node``/``Core``/``Hashgraph`` + ``TensorConsensus`` +
``SweepBatcher``. Phases, each timed and printed as smoke output (none of it
is a benchmark):

- programs: every compiled program of the live path at the real
  16-validator buckets, each compared bit-for-bit with the host result;
- replay:   a seeded 16-validator DAG replayed through the host oracle and
  through ``TensorConsensus`` at its defaults — identical consensus;
- live:     the 16-validator in-process TCP cluster under closed-loop load,
  one validator started a few seconds late so its catch-up backlog crosses
  the device gate on the normal sync path.

No ``BABBLE_*`` variable is set here: the pipelined / batched / resident /
min-window choices are the ones the code makes on seeing the chip.

    python chip_smoke.py                  one chip, all three phases
    python chip_smoke.py --mesh4          four chips: ONLY the witness-sharded
                                          path and what it is compared with
    python chip_smoke.py --cpu-rehearsal [--mesh4]
                                          control-flow rehearsal on host XLA at
                                          tiny shapes; prints the cpu platform

The LAST stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Any failed phase: non-zero exit and ``"ok": false``. Without a TPU (and
without --cpu-rehearsal) it stops at its first act, ``jax.devices()``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
import traceback

REAL = dict(validators=16, replay_events=4000, program_events=700,
            resident_events=1500, live_s=20.0, late_after_events=600)
TINY = dict(validators=4, replay_events=300, program_events=120,
            resident_events=200, live_s=5.0, late_after_events=60)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- seeded data --------------------------------------------------------------


def seeded_keys(n: int, seed: int):
    from babble_tpu.crypto.keys import PrivateKey

    rng = random.Random(seed)
    return [PrivateKey(rng.getrandbits(250) + 1) for _ in range(n)]


def seeded_stream(n_peers: int, n_events: int, seed: int):
    """A deterministic random-gossip event stream with 100-byte
    transactions: each event's self-parent is its creator's head, its
    other-parent a random peer's head — the DAG shape live gossip makes."""
    from babble_tpu.hashgraph import Event
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet

    keys = seeded_keys(n_peers, seed)
    rng = random.Random(seed + 1)
    peers = PeerSet([
        Peer(f"inmem://p{i}", k.public_key.hex(), f"p{i}")
        for i, k in enumerate(keys)
    ])
    heads = [""] * n_peers
    seqs = [-1] * n_peers
    events = []
    order = list(range(n_peers))
    while len(events) < n_events:
        rng.shuffle(order)
        for i in order:
            if len(events) >= n_events:
                break
            op = ""
            if events:
                j = rng.randrange(n_peers - 1)
                j = j if j < i else j + 1
                op = heads[j]
                if op == "":
                    continue
            idx = seqs[i] + 1
            tx = (b"smoke tx %d " % len(events)).ljust(100, b"x")
            e = Event.new(
                [tx] if idx else [], [], [], [heads[i], op],
                keys[i].public_key.bytes(), idx, timestamp=len(events),
            )
            e.sign(keys[i])
            heads[i] = e.hex()
            seqs[i] = idx
            events.append(e)
    return events, peers


def fresh_hashgraph(peers, accel=None):
    from babble_tpu.hashgraph import Hashgraph, InmemStore

    h = Hashgraph(InmemStore(100000))
    h.init(peers)
    h.accel = accel
    return h


def copy_event(ev):
    from babble_tpu.hashgraph import Event

    return Event(ev.body, ev.signature)


def insert_only(h, events) -> None:
    """Insert + divide_rounds, voting deferred: grows an undecided window."""
    for ev in events:
        e = copy_event(ev)
        e.prevalidate(True)
        h.insert_event(e, set_wire_info=True)
        h.divide_rounds()


def replay(events, peers, accel=None):
    """The live driver (tests/test_accel.py pattern): per insert the oracle
    pipeline, or with ``accel`` the deferred device sweeps + final flush."""
    h = fresh_hashgraph(peers, accel)
    for ev in events:
        h.insert_event_and_run_consensus(copy_event(ev), set_wire_info=True)
    h.flush_consensus()
    if accel is not None:
        drain_pipelined(h)
    return h


def drain_pipelined(h, max_iters: int = 200) -> None:
    """Flush until nothing is in flight and consensus stopped changing:
    each flush applies one in-flight sweep's results and may launch
    another."""
    prev = None
    for _ in range(max_iters):
        inf = h.accel._inflight
        if inf is not None:
            inf.done.wait(30.0)
        h._accel_pending = max(h._accel_pending, 1)
        h.flush_consensus()
        if h.accel.busy():
            continue
        cur = consensus_state(h)
        if cur == prev:
            return
        prev = cur
    raise AssertionError("pipelined replay never quiesced")


_ORACLE_BLOCK_KEYS = (
    "Index", "RoundReceived", "Timestamp", "FrameHash", "PeersHash",
    "TxRoot", "Transactions", "InternalTransactions",
)


def block_bytes(block, keys=None) -> bytes:
    from babble_tpu.crypto.canonical import canonical_dumps

    d = block.body.to_dict()
    if keys is not None:
        d = {k: d[k] for k in keys}
    return canonical_dumps(d)


def ordered_events(store):
    events, seen = [], set()
    for pk in store.repertoire_by_pub_key():
        for eh in store.participant_events(pk, -1):
            if eh not in seen:
                seen.add(eh)
                events.append(store.get_event(eh))
    events.sort(key=lambda e: e.topological_index)
    return events


def consensus_state(h):
    """Everything consensus decides, keyed by event hash / round / block."""
    store = h.store
    events = {
        ev.hex(): (ev.round, ev.lamport_timestamp, ev.round_received)
        for ev in ordered_events(store)
    }
    rounds = {}
    for r in range(store.last_round() + 1):
        ri = store.get_round(r)
        rounds[r] = (
            {x: (e.witness, int(e.famous))
             for x, e in ri.created_events.items()},
            sorted(ri.received_events),
        )
    blocks = {
        b: block_bytes(store.get_block(b))
        for b in range(store.last_block_index() + 1)
    }
    return events, rounds, blocks, sorted(h.undetermined_events)


def oracle_expectation(events, peers, win):
    """What the host oracle decides for ``win``'s rows: one DecideFame +
    DecideRoundReceived pass over the same inserts, read back from the
    store — independent of the device code."""
    import numpy as np

    from babble_tpu.common.trilean import Trilean

    h = fresh_hashgraph(peers)
    insert_only(h, events)
    h.decide_fame()
    h.decide_round_received()
    fame = np.zeros(win.n_witnesses, np.int32)
    for x, w in win.wit_row.items():
        ev = h.store.get_event(x)
        famous = h.store.get_round(ev.round).created_events[x].famous
        fame[w] = {Trilean.TRUE: 1, Trilean.FALSE: -1}.get(famous, 0)
    rr = np.full(win.n_events, -1, np.int32)
    undet = set(h.undetermined_events)
    for x, i in win.row.items():
        if win.undet[i] and x not in undet:
            rr[i] = h.store.get_event(x).round_received - win.base
    return fame, rr


# -- compile-cache visibility -------------------------------------------------

# jax's own counters: compile requests that consulted the persistent cache,
# how many it answered, and how many new entries it wrote (jax names the
# write "cache_misses")
_cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0,
                 "cache_misses": 0}


def _on_jax_event(event: str, **_kw) -> None:
    name = event.rsplit("/", 1)[-1]
    if name in _cache_events:
        _cache_events[name] += 1


def cache_report() -> str:
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or (
        jax.config.jax_compilation_cache_dir
    )
    c = _cache_events
    return (f"compile cache at {where}: {c['compile_requests_use_cache']} "
            f"requests, {c['cache_hits']} hits, {c['cache_misses']} written "
            f"so far")


# -- phase: programs ----------------------------------------------------------


def resident_case(events, peers, sizes, mesh, on_tpu):
    """Drive the resident (donated) delta program through TensorConsensus
    and prove (a) consecutive delta sweeps ran, the second consuming the
    first's outputs, (b) donation took effect on the chip, (c) the
    in-place scatter left the resident buffers equal to the host mirrors,
    (d) consensus equals the oracle's. Returns the WindowState."""
    import numpy as np

    from babble_tpu.hashgraph.accel import TensorConsensus
    from babble_tpu.ops.window_state import RESIDENT_FIELDS, WindowState

    prefix = events[: sizes["resident_events"]]
    # a sweep every 16 inserts keeps each delta inside the program's fixed
    # delta-row buckets (window_state.delta_shape), as a gossip round's
    # churn does; bigger steps take the full-upload path
    tc = TensorConsensus(
        sweep_events=16, async_compile=False, min_window=0, pipeline=False,
        batcher=False, resident=True, mesh=mesh,
    )
    state = tc.window_state = WindowState(mesh=mesh)
    used, donated = [], []
    inner = state.dispatch

    def recording_dispatch(snap, **kw):
        before = state.device
        out, used_delta = inner(snap, **kw)
        used.append(used_delta)
        if used_delta:
            donated.append(all(b.is_deleted() for b in before))
        return out, used_delta

    state.dispatch = recording_dispatch
    h = replay(prefix, peers, tc)
    runs = "".join("D" if u else "F" for u in used)
    log(f"  resident: dispatches {runs} (D=delta via the donated program, "
        f"F=full upload), rebuilds={state.rebuilds}, "
        f"rows_reused={tc.rows_reused_total}, fallbacks={tc.fallbacks}")
    assert "DD" in runs, "no two consecutive delta sweeps ran"
    assert tc.fallbacks == 0 and tc.sweeps > 0
    if on_tpu:
        assert donated and all(donated), (
            "donation did not take effect: a delta sweep left its input "
            "buffers alive"
        )
    if state.device is not None:
        for f, buf in zip(RESIDENT_FIELDS, state.device):
            np.testing.assert_array_equal(
                np.asarray(buf), state.mirror[f],
                err_msg=f"resident buffer {f} != host mirror",
            )
    want = consensus_state(replay(prefix, peers))
    assert consensus_state(h) == want, "resident replay diverged from oracle"
    return state


def phase_programs(sizes, rehearsal, stream):
    import numpy as np

    import jax

    from babble_tpu.hashgraph.accel import prewarm_buckets
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher
    from babble_tpu.ops import voting
    from babble_tpu.ops.pallas_kernels import member_ss_counts_pallas

    events, peers = stream
    n = sizes["validators"]
    on_tpu = jax.devices()[0].platform == "tpu"

    # 1. the node's own prewarm, inline: every real-bucket program of
    #    _sweep_jit plus the B=MAX_BATCH floor of _batched_sweep_jit
    t0 = time.perf_counter()
    if not rehearsal:
        prewarm_buckets(n, background=False)
    log(f"  prewarm_buckets({n}) inline: {time.perf_counter() - t0:.2f}s; "
        f"{cache_report()}")

    # 2. _sweep_jit on a seeded undecided window vs the host oracle
    prefix = events[: sizes["program_events"]]
    h = fresh_hashgraph(peers)
    B = SweepBatcher.MAX_BATCH
    cuts = [len(prefix) * (k + 1) // B for k in range(B)]
    wins, done = [], 0
    for cut in cuts:
        insert_only(h, prefix[done:cut])
        done = cut
        win = voting.build_voting_window(h)
        if win is not None:
            wins.append(win)
    win = wins[-1]
    key = voting.bucket_key(win)
    # the bucket the node prewarms as the batcher's floor: the seeded
    # window is also run re-padded to it, i.e. through that very executable
    P = voting._bucket_mult(n, 8)
    floor = (128, 1024, P, 1, 32) if n >= 12 else (64, 512, P, 1, 16)
    at_floor = tuple(max(a, b) for a, b in zip(key, floor))
    t0 = time.perf_counter()
    want_fame, want_rr = oracle_expectation(prefix, peers, win)
    assert (want_fame != 0).any() and (want_rr >= 0).any(), (
        "window decides nothing"
    )
    for k in (key, at_floor):
        fame, rr = voting.run_sweep(voting.repad_window(win, k))
        np.testing.assert_array_equal(
            fame[: win.n_witnesses], want_fame, err_msg=f"_sweep_jit fame {k}")
        np.testing.assert_array_equal(
            rr[: win.n_events], want_rr, err_msg=f"_sweep_jit rr {k}")
    log(f"  _sweep_jit buckets {key} and {at_floor}: fame/rr == host oracle "
        f"({int((want_fame != 0).sum())} witnesses decided, "
        f"{int((want_rr >= 0).sum())} events received) "
        f"{time.perf_counter() - t0:.2f}s")

    # 3. _batched_sweep_jit at B=MAX_BATCH: every row == its own single sweep
    t0 = time.perf_counter()
    # as SweepBatcher pads a wave: elementwise max of the windows' buckets
    # and the prewarmed floor
    target = tuple(
        max([floor[d]] + [voting.bucket_key(w)[d] for w in wins])
        for d in range(5)
    )
    padded = [voting.repad_window(w, target) for w in wins]
    got = voting.read_batched(voting.launch_batched(padded, B), padded)
    for w, (b_fame, b_rr) in zip(wins, got):
        s_fame, s_rr = voting.run_sweep(w)
        np.testing.assert_array_equal(b_fame[: w.n_witnesses], s_fame)
        np.testing.assert_array_equal(b_rr[: w.n_events], s_rr)
    np.testing.assert_array_equal(got[-1][0][: win.n_witnesses], want_fame)
    log(f"  _batched_sweep_jit B={B} bucket {target}: {len(wins)} windows "
        f"bit-equal to their single sweeps {time.perf_counter() - t0:.2f}s")

    # 4. _resident_jit: consecutive delta sweeps, donation real
    t0 = time.perf_counter()
    state = resident_case(events, peers, sizes, None, on_tpu)
    log(f"  _resident_jit bucket {state.key}: consensus == host oracle "
        f"{time.perf_counter() - t0:.2f}s")

    # 5. the Pallas strongly-see kernel, COMPILED (interpret only in the
    #    explicit cpu rehearsal), vs the numpy einsum on the same window
    t0 = time.perf_counter()
    la, fd, member = win.la_w, win.fd_w, win.member
    if not rehearsal:
        text = member_ss_counts_pallas.lower(la, fd, member).compile().as_text()
        assert "tpu_custom_call" in text, "Pallas kernel did not lower to Mosaic"
    got = np.asarray(member_ss_counts_pallas(la, fd, member,
                                             interpret=rehearsal))
    ge = (la[:, None, :] >= fd[None, :, :]).astype(np.int64)
    want = np.einsum("vwp,sp->svw", ge, member.astype(np.int64))
    np.testing.assert_array_equal(got, want, err_msg="member_ss_counts_pallas")
    assert want.any(), "trivial window: no strongly-see pair"
    log(f"  member_ss_counts_pallas "
        f"({'interpret (rehearsal)' if rehearsal else 'compiled'}) "
        f"[S,W,P]=[{member.shape[0]},{la.shape[0]},{la.shape[1]}]: "
        f"bit-equal to the einsum {time.perf_counter() - t0:.2f}s")


# -- phase: replay ------------------------------------------------------------


def phase_replay(sizes, rehearsal, stream):
    from babble_tpu.hashgraph.accel import TensorConsensus

    events, peers = stream
    events = events[: sizes["replay_events"]]
    t0 = time.perf_counter()
    oracle = replay(events, peers)
    t_oracle = time.perf_counter() - t0
    # defaults on the chip; the rehearsal's tiny DAG needs the gate and
    # the sweep cadence scaled down with it (constructor arguments of the
    # rehearsal only — never an environment override)
    tc = (TensorConsensus(sweep_events=64, min_window=32)
          if rehearsal else TensorConsensus())
    t0 = time.perf_counter()
    accel = replay(events, peers, tc)
    t_accel = time.perf_counter() - t0
    s = tc.stats()
    log(f"  {len(events)} events, {sizes['validators']} validators: oracle "
        f"{t_oracle:.2f}s, TensorConsensus {t_accel:.2f}s; blocks="
        f"{oracle.store.last_block_index() + 1} rounds="
        f"{oracle.store.last_round() + 1}")
    log("  " + json.dumps({k: s.get(k) for k in (
        "accel_pipeline", "accel_batcher", "accel_resident",
        "accel_min_window", "accel_pallas", "accel_sweeps",
        "accel_fallbacks", "accel_compile_waits", "accel_small_windows",
        "accel_deferred", "accel_stale_drops", "accel_rebuilds",
        "accel_breaker_state", "accel_breaker_open", "batch_batches",
        "batch_singles", "batch_windows", "accel_stage_ms",
    )}))
    assert tc.sweep_events >= tc.min_window, "gate not exceeded by construction"
    o_ev, o_rounds, o_blocks, o_undet = consensus_state(oracle)
    a_ev, a_rounds, a_blocks, a_undet = consensus_state(accel)
    assert a_ev == o_ev, "events (round, lamport, round_received) differ"
    assert a_rounds == o_rounds, "rounds (witness/fame/received) differ"
    assert a_blocks == o_blocks, "block bodies differ"
    assert a_undet == o_undet, "undetermined sets differ"
    assert len(o_blocks) > 0
    assert s["accel_sweeps"] > 0, "no device sweep ran"
    assert s["accel_fallbacks"] == 0, "a sweep fell back to the oracle"
    assert s["accel_breaker_open"] == 0, "the breaker opened"


# -- phase: live --------------------------------------------------------------


def _free_ports(n: int):
    import socket

    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


_INFO_KEYS = (
    "accel_compile_waits", "accel_small_windows", "accel_deferred",
    "accel_contended", "accel_stale_drops", "accel_rebuilds",
    "accel_mesh_pad_rows", "accel_breaker_open", "accel_breaker_probes",
    "accel_breaker_skips", "accel_breaker_failures",
)
_BATCH_KEYS = (
    "batch_batches", "batch_singles", "batch_windows", "batch_max",
    "batch_compile_kicks", "batch_refused", "batch_target_decays",
    "copro_waves", "copro_windows", "copro_validators",
)


def phase_live(sizes, seed, rehearsal, mesh=0):
    from babble_tpu.config.config import Config
    from babble_tpu.dummy.state import State as DummyState
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.net.tcp import TCPTransport
    from babble_tpu.node.node import Node
    from babble_tpu.node.validator import Validator
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet
    from babble_tpu.proxy.proxy import InmemProxy

    n = sizes["validators"]
    keys = seeded_keys(n, seed + 2)
    ports = _free_ports(n)
    peers = PeerSet([
        Peer(f"127.0.0.1:{ports[i]}", k.public_key.hex(), f"v{i}")
        for i, k in enumerate(keys)
    ])
    nodes, proxies, states = [], [], []

    def start(i: int) -> None:
        # upstream defaults (heartbeat 10 ms / 1 s, SyncLimit 1000,
        # CacheSize 10000, SuspendLimit 100) — built as engine.py builds it
        conf = Config(
            bind_addr=f"127.0.0.1:{ports[i]}", moniker=f"v{i}",
            log_level="error", no_service=True, accelerator=True,
            accelerator_mesh=mesh,
        )
        st = DummyState()
        pr = InmemProxy(st)
        trans = TCPTransport(
            conf.bind_addr, max_pool=conf.max_pool,
            timeout=conf.tcp_timeout, join_timeout=conf.join_timeout,
        )
        node = Node(conf, Validator(keys[i], conf.moniker), peers, peers,
                    InmemStore(conf.cache_size), trans, pr)
        if rehearsal:
            # host XLA's own gate (256) is never crossed by a 4-validator
            # window; the rehearsal scales it down on the object
            node.core.hg.accel.min_window = 16
        node.init()
        warm = getattr(node, "_prewarm_thread", None)
        if warm is not None:
            warm.join()
        node.run_async()
        nodes.append(node)
        proxies.append(pr)
        states.append(st)

    t0 = time.perf_counter()
    for i in range(n - 1):
        start(i)
    log(f"  {n - 1} validators up (init + prewarm) in "
        f"{time.perf_counter() - t0:.2f}s; validator v{n - 1} starts late")
    warm_before = _ready_buckets()

    stop = threading.Event()
    sent = [0]

    def load() -> None:
        # closed loop: cap submitted-but-uncommitted transactions
        while not stop.is_set():
            if sent[0] - min_committed(states) < 2000:
                for _ in range(16):
                    i = sent[0]
                    tx = (b"live tx %d " % i).ljust(100, b"x")
                    proxies[i % len(proxies)].submit_tx(tx)
                    sent[0] += 1
            time.sleep(0.003)

    def known(node) -> int:
        return node.core.hg.topological_index

    pump = threading.Thread(target=load, daemon=True, name="smoke-load")
    t_load = time.monotonic()
    pump.start()
    try:
        # the late validator: started once the others hold a backlog well
        # over the device gate, so its catch-up crosses it by construction
        while known(nodes[0]) < sizes["late_after_events"]:
            assert time.monotonic() - t_load < 60, "cluster made no events"
            time.sleep(0.05)
        steady = [nd.get_stats_snapshot() for nd in nodes]
        t_late = time.monotonic() - t_load
        backlog = known(nodes[0])
        start(n - 1)
        log(f"  late validator started {t_late:.1f}s into the load, "
            f"{backlog} events behind")
        base, t_base = min_committed(states[:-1]), time.monotonic()
        # CacheSize 10000 is an LRU: stop before the audited validator
        # evicts the history its oracle replay needs
        while (time.monotonic() - t_base < sizes["live_s"]
               and known(nodes[0]) < 9000):
            time.sleep(0.1)
        window = time.monotonic() - t_base
        rate = (min_committed(states[:-1]) - base) / window
    finally:
        stop.set()
        pump.join()
    # let the tail commit everywhere, the late validator included
    t_settle = time.monotonic()
    while time.monotonic() - t_settle < 60:
        if min(nd.get_last_block_index() for nd in nodes) >= 0 and (
            len(states[-1].committed_txs) > 0
        ):
            break
        time.sleep(0.2)
    stats = [nd.get_stats_snapshot() for nd in nodes]
    for nd in nodes:
        nd.shutdown()

    def total(rows, k):
        return sum(int(r.get(k) or 0) for r in rows)

    first = stats[0]
    log("  choices on this device: " + json.dumps({k: first.get(k) for k in (
        "accel_pipeline", "accel_batcher", "accel_resident",
        "accel_min_window", "accel_pallas", "accel_mesh")}))
    log(f"  smoke output (not a benchmark): {rate:.1f} committed tx/s over "
        f"{window:.1f}s, {sent[0]} submitted, {known(nodes[0])} events on v0")
    log("  summed over validators: " + json.dumps(
        {k: total(stats, k) for k in
         ("accel_sweeps", "accel_fallbacks", "accel_mesh_fallbacks")
         + _INFO_KEYS}))
    log("  batcher (process-wide): " + json.dumps(
        {k: first.get(k) for k in _BATCH_KEYS}))
    stage = {}
    for r in stats:
        for k, v in (r.get("accel_stage_ms") or {}).items():
            stage[k] = round(stage.get(k, 0.0) + v, 1)
    log("  accel_stage_ms summed: " + json.dumps(stage))
    log("  window buckets first met (compiled) under load, not prewarmed: "
        f"{sorted(_ready_buckets() - warm_before)}")
    steady_sweeps = total(steady, "accel_sweeps")
    log(f"  device engagement: {steady_sweeps} sweeps across "
        f"{n - 1} validators in the first {t_late:.1f}s (steady state, "
        f"before the late start); {total(stats[:-1], 'accel_sweeps')} by the "
        f"end; late validator v{n - 1}: "
        f"{int(stats[-1].get('accel_sweeps') or 0)} sweeps on its backlog")

    # -- requirements
    last = [nd.get_last_block_index() for nd in nodes]
    assert min(last) >= 0, f"a validator committed no block: {last}"
    common = min(last)
    for b in range(common + 1):
        bodies = {block_bytes(nd.get_block(b)) for nd in nodes}
        assert len(bodies) == 1, f"block {b} differs across validators"
    log(f"  {n}/{n} validators committed; blocks 0..{common} byte-identical "
        f"across all {n} (last block index per validator: "
        f"{min(last)}..{max(last)})")
    assert total(stats, "accel_sweeps") > 0, "no device sweep in the live run"
    assert total(stats, "accel_fallbacks") == 0, "a live sweep fell back"
    assert total(stats, "accel_mesh_fallbacks") == 0
    assert total(stats, "accel_breaker_open") == 0, "a breaker opened"
    # no abandoned readback: an abandoned one is a TimeoutError fallback
    # (counted above); nothing may still be parked past the timeout either
    for nd in nodes:
        inf = nd.core.hg.accel._inflight
        assert inf is None or (
            time.perf_counter() - inf.t_launch
            < nd.core.hg.accel.readback_timeout_s
        ), "a readback is parked past its timeout"

    # one validator's events through the host oracle give the same blocks
    t0 = time.perf_counter()
    audited = nodes[0].core.hg
    evs = ordered_events(audited.store)
    assert len(evs) == audited.topological_index, "audited history evicted"
    oracle = replay(evs, peers)
    n_blocks = audited.store.last_block_index() + 1
    assert oracle.store.last_block_index() + 1 >= n_blocks
    for b in range(n_blocks):
        assert block_bytes(oracle.store.get_block(b), _ORACLE_BLOCK_KEYS) == (
            block_bytes(audited.store.get_block(b), _ORACLE_BLOCK_KEYS)
        ), f"host oracle disagrees with v0 on block {b}"
    log(f"  v0's {len(evs)} events replayed through the host oracle: the "
        f"same {n_blocks} blocks ({time.perf_counter() - t0:.2f}s)")
    return stats


def _ready_buckets() -> set:
    """Every (program, bucket) this process has compiled so far."""
    from babble_tpu.ops import voting
    from babble_tpu.parallel import voting_shard

    out = {("single",) + k for k in voting._ready_buckets}
    out |= {("batched", b) + k for b, k in voting._ready_batched}
    for keys in voting_shard._ready_buckets.values():
        out |= {("mesh",) + k for k in keys}
    return out


def min_committed(states) -> int:
    return min(len(s.committed_txs) for s in states)


# -- phase: mesh4 -------------------------------------------------------------


def phase_mesh4(sizes, seed, rehearsal, stream):
    import numpy as np

    import jax

    from babble_tpu.ops import voting
    from babble_tpu.parallel import voting_shard
    from babble_tpu.parallel.mesh import consensus_mesh

    events, peers = stream
    on_tpu = jax.devices()[0].platform == "tpu"
    mesh = consensus_mesh(4)
    shape = "x".join(str(d) for d in mesh.devices.shape)
    assert len({d.id for d in mesh.devices.flatten()}) == 4
    log(f"  mesh {shape} over {[str(d) for d in mesh.devices.flatten()]}")

    # sharded sweep vs the single-device sweep on the same windows
    h = fresh_hashgraph(peers)
    prefix = events[: sizes["program_events"]]
    done = 0
    for cut in (len(prefix) // 2, len(prefix)):
        insert_only(h, prefix[done:cut])
        done = cut
        win = voting.build_voting_window(h)
        assert win.n_witnesses % 4 == 0
        out = voting_shard._jitted(mesh)(*voting_shard.place_window(mesh, win))
        assert len(out.sharding.device_set) == 4, (
            f"sharded sweep output on {len(out.sharding.device_set)} device(s)"
        )
        fame_sh, rr_sh = voting_shard.run_sharded_sweep(mesh, win)
        fame, rr = voting.run_sweep(win)
        np.testing.assert_array_equal(fame_sh, fame, err_msg="sharded fame")
        np.testing.assert_array_equal(rr_sh, rr, err_msg="sharded rr")
        assert (fame != 0).any()
        log(f"  run_sharded_sweep bucket {voting.bucket_key(win)}: output on "
            f"4 devices, bit-equal to the single-device sweep")

    # the mesh resident program (donated per-shard buffers)
    state = resident_case(events, peers, sizes, mesh, on_tpu)
    if state.device is not None:
        for buf in state.device:
            assert len(buf.sharding.device_set) == 4, (
                "a resident buffer is not placed on all 4 devices"
            )
    log(f"  mesh resident program bucket {state.key}: buffers on 4 devices, "
        f"consensus == host oracle")

    # short live run with accelerator_mesh=4
    stats = phase_live({**sizes, "live_s": min(sizes["live_s"], 10.0)},
                       seed, rehearsal, mesh=4)
    meshes = {s.get("accel_mesh") for s in stats}
    assert meshes <= {"1x4", "2x2"} and meshes, f"accel_mesh = {meshes}"


# -- main ---------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mesh4", action="store_true",
                    help="four chips: only the witness-sharded path")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="host XLA, tiny shapes; never reads as a chip pass")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        # the rehearsal IS the explicit cpu pin (ops/device.cpu_pinned)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()

    import jax

    devs = jax.devices()  # first act
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)} jax {jax.__version__}")

    def finish(ok: bool) -> int:
        print(json.dumps({"ok": ok, "device": device}), flush=True)
        return 0 if ok else 1

    want = "cpu" if args.cpu_rehearsal else "tpu"
    if device["platform"] != want:
        log(f"FAIL: need platform {want!r}, jax found {device['platform']!r}")
        return finish(False)
    if args.mesh4 and device["count"] < 4:
        log(f"FAIL: --mesh4 needs 4 devices, jax found {device['count']}")
        return finish(False)

    try:
        from babble_tpu import native_crypto
        from babble_tpu.ops.device import ensure_device

        jax.monitoring.register_event_listener(_on_jax_event)
        ensure_device()  # places the compile cache
        assert native_crypto.available(), (
            "native crypto library could not be built from "
            "native/secp256k1.cc (g++ missing?)"
        )
        sizes = TINY if args.cpu_rehearsal else REAL
        t0 = time.perf_counter()
        stream = seeded_stream(
            sizes["validators"],
            max(sizes["replay_events"], sizes["resident_events"]), args.seed,
        )
        log(f"seeded stream: {len(stream[0])} events, seed {args.seed}, "
            f"{time.perf_counter() - t0:.2f}s (set-up)")
        if args.mesh4:
            phases = [("mesh4", lambda: phase_mesh4(
                sizes, args.seed, args.cpu_rehearsal, stream))]
        else:
            phases = [
                ("programs", lambda: phase_programs(
                    sizes, args.cpu_rehearsal, stream)),
                ("replay", lambda: phase_replay(
                    sizes, args.cpu_rehearsal, stream)),
                ("live", lambda: phase_live(
                    sizes, args.seed, args.cpu_rehearsal)),
            ]
        for name, fn in phases:
            log(f"phase {name}:")
            t0 = time.perf_counter()
            fn()
            log(f"phase {name}: PASS {time.perf_counter() - t0:.2f}s; "
                f"{cache_report()}")
    except Exception:
        traceback.print_exc(file=sys.stdout)
        log("FAIL")
        return finish(False)
    return finish(True)


if __name__ == "__main__":
    sys.exit(main())
