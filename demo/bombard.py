#!/usr/bin/env python
"""Submit M transactions to each of N testnet nodes through their socket
proxies (reference: /root/reference/demo/scripts/bombard.sh, which pushes
JSON-RPC via netcat; here we speak the framed JSON-RPC directly).

SubmitTx answers with an admission verdict (docs/mempool.md). This
client honors it: `throttled`/`full` back off (jittered, capped) and
retry instead of hammering a shedding node; retries exhausted count as
shed. Totals (accepted / shed / duplicate / ...) print at exit.

Usage:  python demo/bombard.py [n_nodes] [txs_per_node] [--base-port 13000]
                               [--metrics=host:port,host:port,...]
                               [--subscribers=N] [--sub-addr=host:port,...]
                               [--stall-frac=0.0]

With ``--subscribers=N`` (docs/clients.md), N concurrent streaming
subscribers (one selector thread, N sockets — 10k+ is fine) attach to
the listed ``--sub-addr`` SubscriptionHubs (default
127.0.0.1:15000..+n, the demo/testnet.py layout) for the whole
bombardment; at exit the swarm reports blocks received, ordering gaps
(must be 0 on healthy subscribers), push-latency p50/p99, and how many
deliberately-stalled subscribers (``--stall-frac``) the hub shed.

With ``--metrics``, each listed node's ``GET /metrics`` (the service's
Prometheus endpoint, docs/observability.md) is scraped after the
bombardment and its commit-latency p50/p90/p99 printed — the quickest
way to see the north-star latency of a live testnet — followed by a
cluster healthview summary (SLO verdict vs the 500 ms target, worst-lag
node, per-node queue depths; obs/healthview.py).

With ``--trace=K`` (requires ``--metrics`` for the service addresses),
up to K of the submitted transactions that fall inside the cluster's
deterministic provenance sample (``--trace-sample`` must match the
nodes' ``trace_sample``; default 1/64) have their ``/trace/<txid>``
records fetched from every listed node after the commit settle, merged
into cross-node timelines (obs/traceview.py), and the per-hop
wire/queue/insert/consensus p50/p99 attribution printed at exit.

Byzantine mode — drive the adversary harness (babble_tpu.adversary)
against a live cluster outside pytest: point it at a compromised
validator's datadir (priv_key + peers.json — stop that node first, the
adversary takes over its identity and gossip address) and pick an attack
from the catalog (docs/robustness.md). Watch any honest node's
``/suspects`` endpoint to see the quarantine land.

Usage:  python demo/bombard.py --byzantine=equivocate --datadir=<dir>
                               [--duration=20] [--listen=host:port]
"""

from __future__ import annotations

import base64
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from babble_tpu.common.backoff import jittered_backoff  # noqa: E402
from babble_tpu.proxy.socket_proxy import JsonRpcClient  # noqa: E402

MAX_RETRIES = 8  # per transaction, on throttled/full


def scrape_commit_latency(endpoints: str, settle_s: float = 15.0) -> None:
    """GET /metrics from each ``host:port`` and print commit-latency
    percentiles computed from the Prometheus histogram buckets. Commits
    lag the final submit, so an empty histogram is re-polled for up to
    ``settle_s`` before being reported as empty."""
    import urllib.request

    from babble_tpu.obs.healthview import (
        hist_quantile,
        parse_prom,
        prom_histogram,
    )

    for ep in endpoints.split(","):
        ep = ep.strip()
        if not ep:
            continue
        deadline = time.monotonic() + settle_s
        hist = None
        while True:
            try:
                with urllib.request.urlopen(
                    f"http://{ep}/metrics", timeout=5.0
                ) as r:
                    text = r.read().decode()
            except Exception as err:
                print(f"{ep}: scrape failed ({err})", file=sys.stderr)
                hist = ()  # sentinel: failed scrape, not an empty histogram
                break
            hist = prom_histogram(parse_prom(text), "commit_latency_seconds")
            if (hist is not None and hist["count"] > 0) or (
                time.monotonic() >= deadline
            ):
                break
            time.sleep(0.5)
        if hist == ():
            continue  # scrape failure already reported above
        if hist is None or hist["count"] == 0:
            print(f"{ep}: commit_latency_seconds empty (no local commits)")
            continue
        p50, p90, p99 = (
            hist_quantile(hist, q) for q in (0.50, 0.90, 0.99)
        )
        print(
            f"{ep}: commit latency n={hist['count']:.0f} "
            f"p50={1e3 * p50:.0f}ms p90={1e3 * p90:.0f}ms "
            f"p99={1e3 * p99:.0f}ms"
        )


def healthview_summary(endpoints: str, window_s: float = 4.0) -> None:
    """Cluster healthview at exit (docs/observability.md §Cluster
    healthview): SLO verdict, worst-lag node, per-node queue depths."""
    from babble_tpu.obs import healthview

    eps = [ep.strip() for ep in endpoints.split(",") if ep.strip()]
    try:
        view = healthview.collect(eps, window_s=window_s)
    except Exception as err:  # noqa: BLE001 — diagnostics stay optional
        print(f"healthview failed: {err}", file=sys.stderr)
        return
    print(healthview.summary_line(view))
    for n in view["nodes"]:
        if n.get("down"):
            print(f"  node #{n['index']}: DOWN")
            continue
        q = n["queues"]
        print(
            f"  {n.get('moniker') or n.get('endpoint')}: lag="
            f"{n['lag_rounds']} queues submit={q['submit']:.0f} "
            f"pipeline={q['pipeline_inflight']:.0f}"
            f"/{q['pipeline_queue']:.0f} "
            f"mempool={q['mempool_pending']:.0f} "
            f"subs={n.get('subscribers', 0)} "
            f"shed={n.get('shed_subscribers', 0)} "
            f"quarantined={n['quarantined_peers']} "
            + ("ok" if n.get("healthy") else "UNHEALTHY")
        )


def trace_attribution(endpoints: str, accepted_txs: list, k: int,
                      sample: float, settle_s: float = 15.0) -> None:
    """Fetch provenance for up to ``k`` sampled accepted transactions
    from every service endpoint, merge cross-node, and print per-hop
    latency attribution (docs/observability.md §Causal tracing)."""
    import hashlib

    from babble_tpu.obs import traceview
    from babble_tpu.obs.provenance import sample_inverse, tx_sampled

    inv = sample_inverse(sample)
    picked = [tx for tx in accepted_txs if tx_sampled(tx, inv)][:k]
    if not picked:
        print(
            "trace: none of the accepted txs fall in the sample "
            f"(sample={sample}); raise --trace-sample on the nodes",
            file=sys.stderr,
        )
        return
    eps = [ep.strip() for ep in endpoints.split(",") if ep.strip()]
    merged = []
    deadline = time.monotonic() + settle_s
    for tx in picked:
        txid = hashlib.sha256(tx).hexdigest()
        while True:
            exports = []
            for ep in eps:
                try:
                    exp = traceview.fetch_node(ep, txid=txid)
                except Exception as err:  # noqa: BLE001 — skip dead nodes
                    print(f"{ep}: trace scrape failed ({err})",
                          file=sys.stderr)
                    continue
                if exp is not None:
                    exports.append(exp)
            m = traceview.merge_tx(txid, exports)
            # commits lag the final submit: re-poll an uncommitted trace
            if (m is not None and m["committed_on"]) or (
                time.monotonic() >= deadline
            ):
                break
            time.sleep(0.5)
        if m is None:
            print(f"trace: {txid[:16]}… not found on any node")
            continue
        merged.append(m)
        print(traceview.render(m))
    if merged:
        print(f"\ntrace attribution over {len(merged)} tx(s):")
        for stage, s in traceview.attribution_summary(merged).items():
            if s["n"]:
                print(
                    f"  {stage:<12} n={s['n']:<5} p50={s['p50_ms']}ms "
                    f"p99={s['p99_ms']}ms"
                )


def submit_with_backoff(client: JsonRpcClient, tx: bytes, counts: dict) -> str:
    """Submit one tx, backing off and retrying on overload verdicts;
    returns the final verdict."""
    attempt = 0
    while True:
        result = client.call(
            "Babble.SubmitTx", base64.b64encode(tx).decode("ascii")
        )
        verdict = "accepted" if result is True else str(result)
        if verdict in ("throttled", "full") and attempt < MAX_RETRIES:
            attempt += 1
            counts["backoffs"] += 1
            time.sleep(jittered_backoff(attempt, 0.005, 0.5))
            continue
        if verdict in ("throttled", "full"):
            counts["shed"] += 1
        counts[verdict] = counts.get(verdict, 0) + 1
        return verdict


def run_byzantine(
    attack: str, datadir: str, duration: float, listen: str = ""
) -> int:
    """Spawn one ByzantineNode with the compromised validator's identity
    and let it attack the live cluster for ``duration`` seconds."""
    from babble_tpu.adversary import ATTACKS, ByzantineNode
    from babble_tpu.config.config import Config
    from babble_tpu.crypto.keyfile import SimpleKeyfile
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.net.tcp import TCPTransport
    from babble_tpu.node.validator import Validator
    from babble_tpu.peers.json_peer_set import JSONPeerSet

    if attack not in ATTACKS:
        print(f"unknown attack {attack!r}; pick from {ATTACKS}", file=sys.stderr)
        return 2
    key = SimpleKeyfile(os.path.join(datadir, "priv_key")).read_key()
    peers = JSONPeerSet(datadir).peer_set()
    me = peers.by_pub_key.get(key.public_key.hex())
    if me is None:
        print("this key is not in peers.json — the adversary must own a "
              "validator identity", file=sys.stderr)
        return 2
    bind = listen or me.net_addr
    conf = Config(data_dir=datadir, moniker=f"byz-{me.moniker}")
    trans = TCPTransport(
        bind, max_pool=conf.max_pool, timeout=conf.tcp_timeout,
        join_timeout=conf.join_timeout,
    )
    byz = ByzantineNode(
        conf, Validator(key, f"byz-{me.moniker}"), peers, peers,
        InmemStore(conf.cache_size), trans, attack=attack,
    )
    print(f"byzantine[{attack}] as {me.moniker} on {bind} "
          f"for {duration:.0f}s ...")
    byz.run_async()
    try:
        time.sleep(duration)
    except KeyboardInterrupt:
        pass
    byz.stop()
    for k, v in byz.stats().items():
        print(f"{k}: {v}")
    return 0


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if len(args) > 0 else 4
    m = int(args[1]) if len(args) > 1 else 100
    base_port = 13000
    opts = {}
    for a in sys.argv[1:]:
        if a.startswith("--base-port"):
            base_port = int(a.split("=", 1)[1])
        elif a.startswith("--") and "=" in a:
            k, v = a[2:].split("=", 1)
            opts[k] = v

    if "byzantine" in opts:
        if "datadir" not in opts:
            print("--byzantine needs --datadir=<dir> (priv_key + peers.json)",
                  file=sys.stderr)
            return 2
        return run_byzantine(
            opts["byzantine"], opts["datadir"],
            float(opts.get("duration", "20")), opts.get("listen", ""),
        )

    swarm = None
    if "subscribers" in opts:
        from babble_tpu.client.swarm import SubscriberSwarm

        sub_addrs = [
            a.strip()
            for a in opts.get(
                "sub-addr",
                ",".join(f"127.0.0.1:{15000 + i}" for i in range(n)),
            ).split(",")
            if a.strip()
        ]
        swarm = SubscriberSwarm(
            sub_addrs,
            int(opts["subscribers"]),
            start=-1,
            stall_frac=float(opts.get("stall-frac", "0.0")),
        )
        swarm.start_all()
        print(
            f"subscribers: {len(swarm.members)} attached across "
            f"{len(sub_addrs)} hub(s) "
            f"({swarm.stall_count} deliberately stalled, "
            f"{swarm.connect_errors} connect errors)"
        )

    counts: dict = {"shed": 0, "backoffs": 0}
    sent = 0
    accepted_txs: list = []
    for i in range(n):
        client = JsonRpcClient(f"127.0.0.1:{base_port + i}")
        for j in range(m):
            tx = f"node{i} tx {j}".encode()
            if submit_with_backoff(client, tx, counts) == "accepted":
                accepted_txs.append(tx)
            sent += 1
        client.close()
        print(f"node{i}: {m} txs submitted")
    accepted = counts.get("accepted", 0)
    print(f"total: {sent}")
    print(
        f"verdicts: accepted={accepted} "
        f"shed={counts['shed']} "
        f"duplicate={counts.get('duplicate', 0)} "
        f"already_committed={counts.get('already_committed', 0)} "
        f"oversized={counts.get('oversized', 0)} "
        f"(backoffs={counts['backoffs']})"
    )
    if sent:
        print(f"shed rate: {counts['shed'] / sent:.3f}")
    if swarm is not None:
        # let the tail of the commits reach the stream before reporting
        time.sleep(float(opts.get("sub-settle", "5")))
        s = swarm.stats()
        swarm.stop()
        lat50 = s["push_latency_p50_s"]
        lat99 = s["push_latency_p99_s"]
        print(
            f"subscribers: {s['subscribers']} "
            f"({s['stalled']} stalled bait), blocks pushed to healthy: "
            f"{s['blocks_received']} (min/sub {s['min_blocks']}), "
            f"gaps {s['gaps']}, shed notices {s['shed_notices']}, "
            "push latency p50 "
            + (f"{1e3 * lat50:.0f}ms" if lat50 is not None else "-")
            + " p99 "
            + (f"{1e3 * lat99:.0f}ms" if lat99 is not None else "-")
        )
    if "metrics" in opts:
        scrape_commit_latency(opts["metrics"])
        healthview_summary(opts["metrics"])
    if "trace" in opts:
        if "metrics" not in opts:
            print("--trace needs --metrics=host:port,... for the service "
                  "addresses", file=sys.stderr)
            return 2
        from babble_tpu.obs.provenance import DEFAULT_SAMPLE

        trace_attribution(
            opts["metrics"], accepted_txs,
            k=int(opts.get("trace") or 8),
            sample=float(opts.get("trace-sample", DEFAULT_SAMPLE)),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
