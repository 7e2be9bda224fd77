"""Driver kind ``live-ring``: all validators of the ring in the one process
that owns the chip, loopback ``TCPTransport``, the sweep batcher
multiplexing their windows onto the device (the layout ``chip_smoke.py``
proved), under a load the traffic file describes.

The generator is this (the main) thread and nothing else:

- ``loop: closed`` — at most ``outstanding_cap`` submitted-but-uncommitted
  transactions, ``pump_batch`` per ``pump_interval_s`` cycle
  (``bench._measure_rate``'s generator);
- ``loop: open`` — constant spacing at ``rate_tx_per_s`` whatever the ring
  does; each transaction is timed from its SCHEDULED send time, and how late
  the generator really sent it is kept as a sample (``bench_gossip``'s paced
  pump).

Transactions go round-robin over the validators, as ``bombard.sh`` sends M
to each of N. Commit stamps are taken by the application handler of every
validator, on the one monotonic clock of this process.
"""

from __future__ import annotations

import socket
import time
from typing import Dict, List, Tuple

from . import data, reference, stats
from .counters import node_snapshot, window_counters
from .nodes import build_node

class StampedState:
    """The dummy application, stamping each block's arrival."""

    def __init__(self) -> None:
        from babble_tpu.dummy.state import State

        self._inner = State()
        self.blocks: List[Tuple[float, int]] = []  # (commit time, txs)
        self.committed = 0

    def commit_handler(self, block):
        n = len(block.transactions())
        self.blocks.append((time.monotonic(), n))
        self.committed += n
        return self._inner.commit_handler(block)

    def snapshot_handler(self, block_index: int) -> bytes:
        return self._inner.snapshot_handler(block_index)

    def restore_handler(self, snapshot: bytes) -> bytes:
        return self._inner.restore_handler(snapshot)

    def state_change_handler(self, state) -> None:
        self._inner.state_change_handler(state)


def _free_ports(n: int) -> List[int]:
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _start_ring(env, conf: dict):
    n = int(conf["validators"])
    keys = data.seeded_keys(n, env.seed)
    addrs = [f"127.0.0.1:{p}" for p in _free_ports(n)]
    peers = data.peer_set(keys, addrs)
    nodes, proxies, states = [], [], []
    for i in range(n):
        st = StampedState()
        node, proxy = build_node(env, conf, keys[i], f"v{i}", peers,
                                 addrs[i], st, tcp=True)
        nodes.append(node)
        proxies.append(proxy)
        states.append(st)
    for node in nodes:
        node.run_async()
    return peers, nodes, proxies, states


class _Generator:
    """Submits on the calling thread; ``run_until`` returns at ``t_end``."""

    def __init__(self, traffic: dict, proxies, states, tx_bytes: int, span):
        self.proxies, self.states, self.span = proxies, states, span
        self.tx_bytes = tx_bytes
        self.loop = traffic["loop"]
        self.interval = float(traffic["pump_interval_s"])
        if self.loop == "closed":
            self.cap = int(traffic["outstanding_cap"])
            self.batch = int(traffic["pump_batch"])
        elif self.loop == "open":
            self.rate = float(traffic["rate_tx_per_s"])
        else:
            raise ValueError(f"unknown loop kind {self.loop!r}")
        self.sched: List[float] = []  # scheduled send time of tx i
        self.late: List[float] = []  # actual minus scheduled, tx i
        self.refused: set = set()
        self.t_sched0 = None
        self.outstanding: List[Tuple[float, int]] = []

    def min_committed(self) -> int:
        return min(s.committed for s in self.states)

    def _submit(self, sched: float, now: float) -> None:
        i = len(self.sched)
        tx = (b"tx %d " % i).ljust(self.tx_bytes, b"x")
        verdict = self.proxies[i % len(self.proxies)].submit_tx(tx)
        if verdict != "accepted":
            self.refused.add(i)
        self.sched.append(sched)
        self.late.append(now - sched)

    def cycle(self) -> None:
        now = time.monotonic()
        if self.loop == "closed":
            if len(self.sched) - self.min_committed() < self.cap:
                for _ in range(self.batch):
                    self._submit(now, now)
            return
        if self.t_sched0 is None:
            self.t_sched0 = now
        due = int((now - self.t_sched0) * self.rate)
        while len(self.sched) < due:
            self._submit(self.t_sched0 + len(self.sched) / self.rate, now)

    def run_until(self, t_end: float, every_s: float = 1.0,
                  on_tick=None) -> None:
        next_tick = time.monotonic()
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            with self.span("pump"):
                self.cycle()
            if now >= next_tick:
                next_tick = now + every_s
                self.outstanding.append(
                    (now, len(self.sched) - self.min_committed()))
                if on_tick is not None and on_tick(now):
                    return
            time.sleep(self.interval)


def _tx_index(tx: bytes) -> int:
    return int(tx.split(b" ", 2)[1])


def run(cell, env) -> dict:
    conf, traffic = env.sized(cell.config), env.sized(cell.traffic)
    peers, nodes, proxies, states = _start_ring(env, conf)
    n = len(nodes)
    env.log(f"{n} validators up (init + prewarm joined)")
    gen = _Generator(traffic, proxies, states, int(conf["tx_bytes"]),
                     env.span)
    accels = [nd.core.hg.accel for nd in nodes]

    # set-up, second half: the cell's own mix until sweeps run and no new
    # bucket has started compiling for ramp_quiet_s (ramp_max_s at most)
    quiet_s = float(traffic["ramp_quiet_s"])
    t_ramp = time.monotonic()
    seen = {"signal": -1, "since": t_ramp}

    def ramp_tick(now: float) -> bool:
        signal = sum(a.compile_waits for a in accels) + int(
            accels[0].stats().get("batch_compile_kicks", 0))
        if signal != seen["signal"]:
            seen["signal"], seen["since"] = signal, now
        return (sum(a.sweeps for a in accels) > 0
                and now - seen["since"] >= quiet_s)

    gen.run_until(t_ramp + float(traffic["ramp_max_s"]), 0.25, ramp_tick)
    env.log(f"ramp {time.monotonic() - t_ramp:.1f}s: "
            f"{len(gen.sched)} submitted, {gen.min_committed()} committed "
            f"by all, {sum(a.sweeps for a in accels)} sweeps, "
            f"{sum(a.compile_waits for a in accels)} compile waits")

    before = [node_snapshot(nd) for nd in nodes]
    gen.outstanding.clear()
    env.window_open()
    t0 = time.monotonic()
    first_tx = len(gen.sched)
    gen.run_until(t0 + env.seconds)
    t1 = time.monotonic()
    env.window_close()
    last_tx = len(gen.sched)
    after = [node_snapshot(nd) for nd in nodes]
    counters = window_counters(before, after)

    # drain: nothing more is submitted; wait for what was
    accepted = last_tx - len(gen.refused)
    t_drain = time.monotonic()
    with env.span("drain"):
        while (gen.min_committed() < accepted
               and time.monotonic() - t_drain < float(traffic["drain_max_s"])):
            time.sleep(0.05)
    env.log(f"drain {time.monotonic() - t_drain:.1f}s: "
            f"{gen.min_committed()} of {accepted} committed by all; "
            f"validator 0 holds {nodes[0].core.hg.topological_index} events")
    chosen = {k: nodes[0].get_stats_snapshot().get(k)
              for k in env.CHOICE_KEYS}
    # a validator that suspended itself (undetermined events over
    # SuspendLimit x validators) stops gossiping and holds "committed by
    # all" back for the rest of the run: name it
    not_babbling = {f"v{i}": str(nd.get_state())
                    for i, nd in enumerate(nodes)
                    if "babbling" not in str(nd.get_state()).lower()}
    for nd in nodes:
        nd.shutdown()

    notes: List[str] = []
    if not_babbling:
        notes.append(f"validators not babbling at the end: {not_babbling}")
    ok, note, n_common = reference.blocks_identical(nodes)
    notes.append(note)
    a_ok, a_note, _b, _e = reference.audit_against_oracle(
        nodes[0].core.hg, peers)
    notes.append("audit of validator 0: " + a_note)
    d_ok, d_notes = reference.device_path_held(counters)
    notes.extend(d_notes)
    ok = ok and a_ok and d_ok

    # which block each transaction landed in (blocks are identical)
    block_of: Dict[int, int] = {}
    for b in range(n_common):
        for tx in nodes[0].get_block(b).transactions():
            block_of[_tx_index(tx)] = b
    # the window's transactions: those due inside it (closed loop: those
    # submitted inside it)
    if gen.loop == "closed":
        due = list(range(first_tx, last_tx))
    else:
        due = [i for i, t in enumerate(gen.sched) if t0 <= t < t1]
    latencies, failed = [], 0
    for i in due:
        b = block_of.get(i)
        if b is None or i in gen.refused:
            failed += 1
            latencies.append(float("inf"))
            continue
        latencies.append(states[i % n].blocks[b][0] - gen.sched[i])

    steps = stats.all_commit_steps([s.blocks for s in states])
    e2e: Dict[str, float] = {}
    rate = stats.block_to_block_rate(steps, t0, t1)
    if rate is not None:
        e2e["committed_tx_per_s"] = rate[0]
        env.log(f"committed by all: {rate[0]:.1f} tx/s over {rate[1]} "
                f"blocks in {rate[2]:.2f}s between commits")
    for name, q in (("commit_p50_ms", 50), ("commit_p95_ms", 95)):
        v = stats.percentile(latencies, q)
        if v is not None and v != float("inf"):
            e2e[name] = 1e3 * v
    counters["harness.blocks_v0"] = float(
        sum(1 for t, _n in states[0].blocks if t0 <= t <= t1))
    env.log("submitted minus committed-by-all, each second: "
            f"{[o for _t, o in gen.outstanding]}")
    env.log(f"window {t1 - t0:.2f}s: due {len(due)}, failed {failed}, "
            f"refused {len(gen.refused)}, blocks at v0 "
            f"{counters['harness.blocks_v0']:.0f}")
    return {
        "correct": ok and failed == 0,
        "attempted": len(due),
        "failed": failed,
        "notes": notes,
        "end_to_end": e2e,
        "counters": counters,
        "samples": {"generator_late_s": [gen.late[i] for i in due]},
        "chosen": chosen,
    }
