"""Perf observatory (docs/observability.md §Perf ledger): the bench
ledger's schema + backfill, the regression gate's noise-aware verdicts
and --inject-regression self-proof, and the sampling profiler's stage
attribution + kill switch."""

import json
import os
import sys
import threading
import time

import pytest

# bench.py lives at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from babble_tpu.obs import ledger, perfgate
from babble_tpu.obs import profile as prof


# -- ledger ------------------------------------------------------------------


def test_record_schema_and_unit_inference():
    rec = ledger.make_record(
        "smoke",
        {
            "txs_per_s": 900.0,
            "latency_p50_ms": 210.0,
            "clat": {"p50": 250.0, "n": 400},
            "speedup": 1.4,
            "duration_s": 9.5,
            "ok": True,  # bools are flags, never metrics
        },
        config={"nodes": 4},
    )
    assert rec["schema"] == ledger.SCHEMA
    assert rec["host"]["fingerprint"] and rec["host"]["cpu_count"] >= 1
    assert rec["config"] == {"nodes": 4}
    m = ledger.results_map(rec)
    assert m["txs_per_s"] == (900.0, "/s")
    assert m["latency_p50_ms"] == (210.0, "ms")
    assert m["clat.p50"] == (250.0, "ms")  # nested dotted names
    assert m["speedup"] == (1.4, "x")
    assert m["duration_s"] == (9.5, "s")
    assert m["clat.n"] == (400.0, "count")
    assert "ok" not in m


def test_append_read_roundtrip_and_malformed_line_skip(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    r1 = ledger.make_record("smoke", {"txs_per_s": 100.0})
    r2 = ledger.make_record("smoke", {"txs_per_s": 110.0})
    ledger.append(r1, path)
    with open(path, "a") as f:
        f.write("{truncated garbage\n")  # interrupted append
    ledger.append(r2, path)
    recs = ledger.read(path)
    assert len(recs) == 2
    assert ledger.results_map(recs[1])["txs_per_s"][0] == 110.0


def test_ledger_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("BABBLE_BENCH_LEDGER", "0")
    assert not ledger.ledger_enabled()
    assert ledger.append(ledger.make_record("smoke", {"x_per_s": 1})) is None


def _driver_artifacts(root) -> list:
    """Five pre-ledger BENCH_r0N.json driver artifacts in the shapes the
    driver really wrote: r01 empty, r02/r03 with a full ``parsed``
    payload, r04/r05 with ``parsed: null`` and a tail cut mid-JSON."""
    cmd = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"

    def parsed(value, extra):
        return {"metric": "committed_txs_per_s_4node", "value": value,
                "unit": "tx/s", "vs_baseline": round(value / 333.0, 2),
                "extra": extra}

    p2 = parsed(907.7, {"committed_txs": 2582, "blocks": 60,
                        "duration_s": 2.8})
    p3 = parsed(875.7, {"committed_txs": 2529, "blocks": 55,
                        "duration_s": 2.9, "latency_p50_ms": 210.8,
                        "latency_p95_ms": 291.1})
    cut = ('ipelined_loop_ms": 25.0, "consensus_match": true}], '
           '"config3_16node_threads": {"oracle": {"txs_per_s": 134.6}, '
           '"accelerated": {"txs_per_s": 270.6, "accel_sweeps_total": 717')
    arts = [
        {"n": 1, "cmd": cmd, "rc": 0, "tail": "", "parsed": None},
        {"n": 2, "cmd": cmd, "rc": 0, "tail": json.dumps(p2) + "\n",
         "parsed": p2},
        {"n": 3, "cmd": cmd, "rc": 0, "tail": json.dumps(p3) + "\n",
         "parsed": p3},
        {"n": 4, "cmd": cmd, "rc": 0, "tail": cut, "parsed": None},
        {"n": 5, "cmd": cmd, "rc": 0, "tail": cut, "parsed": None},
    ]
    paths = []
    for art in arts:
        path = os.path.join(str(root), f"BENCH_r0{art['n']}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(art, f, indent=2)
        paths.append(path)
    return paths


def test_backfill_normalizes_real_artifacts(tmp_path):
    """The five pre-ledger BENCH_r*.json driver artifacts all land as
    schema-versioned records: full `parsed` payloads flatten like live
    runs, truncated tails degrade to the whitelist scan and say so."""
    arts = _driver_artifacts(tmp_path)
    assert len(arts) >= 5
    path = str(tmp_path / "hist.jsonl")
    recs = ledger.backfill(arts, path)
    assert len(recs) == len(arts)
    by_round = {r["round"]: r for r in recs}
    # r02/r03 carried parsed {metric,value,...}: the headline survives
    m2 = ledger.results_map(by_round[2])
    assert m2["committed_txs_per_s_4node"][0] > 0
    # r04/r05 tails are truncated mid-JSON: degraded, whitelist-only
    assert by_round[5].get("degraded") is True
    # idempotent: a second backfill adds nothing
    assert ledger.backfill(arts, path) == []
    assert len(ledger.read(path)) == len(arts)


# -- perfgate ----------------------------------------------------------------


def _rec(txs, p50, run="smoke"):
    return ledger.make_record(
        run, {"txs_per_s": txs, "latency_p50_ms": p50}
    )


def test_gate_passes_on_stable_metrics():
    base = [_rec(1000, 200), _rec(1050, 190), _rec(980, 210)]
    v = perfgate.gate(_rec(1010, 205), base)
    assert v["ok"] and not v["regressions"]
    assert v["checked"] == 2


def test_gate_fails_on_corroborated_regression():
    base = [_rec(1000, 200), _rec(1050, 190), _rec(980, 210)]
    v = perfgate.gate(_rec(500, 420), base)  # both metrics blown
    assert not v["ok"]
    assert {r["metric"] for r in v["regressions"]} == {
        "txs_per_s", "latency_p50_ms",
    }


def test_single_soft_regression_is_not_corroborated():
    base = [_rec(1000, 200), _rec(1050, 190), _rec(980, 210)]
    # one metric ~18% worse: outside the 15% band, inside 2x the band
    v = perfgate.gate(_rec(820, 200), base)
    assert v["regressions"] and v["regressions"][0]["severity"] == "soft"
    assert v["ok"]  # requires corroboration
    assert not perfgate.gate(_rec(820, 200), base, strict=True)["ok"]


def test_single_hard_regression_is_corroborated():
    base = [_rec(1000, 200), _rec(1050, 190), _rec(980, 210)]
    v = perfgate.gate(_rec(400, 200), base)  # -60%: beyond 2x band
    assert not v["ok"]
    assert v["regressions"][0]["severity"] == "hard"


def test_noisy_metric_earns_wider_band():
    # history swinging ±40%: MAD widens the band past the default 15%
    base = [_rec(600, 200), _rec(1400, 200), _rec(1000, 200)]
    v = perfgate.gate(_rec(700, 200), base)  # -30% vs median 1000
    assert v["ok"], v


def test_baseline_filters_host_and_kind():
    cur = _rec(1000, 200)
    other_kind = _rec(1, 9999, run="gossip_smoke")
    other_host = _rec(1, 9999)
    other_host["host"] = dict(other_host["host"], fingerprint="ffff")
    base = perfgate.baseline_for(
        [other_kind, other_host, _rec(990, 205), cur], cur, window=5
    )
    assert len(base) == 1


def test_inject_regression_fails_gate_end_to_end(tmp_path):
    """The CLI self-proof: a clean gate run exits 0, the injected
    regression exits nonzero — through main(), exactly as `make
    perfgate` drives it."""
    path = str(tmp_path / "hist.jsonl")
    for txs, p50 in ((1000, 200), (1010, 195), (990, 205)):
        ledger.append(_rec(txs, p50), path)
    assert perfgate.main(["--history", path]) == 0
    assert perfgate.main(["--history", path, "--inject-regression"]) == 1


def test_gate_refuses_stale_latest_record(tmp_path):
    """A silently failed ledger append must not let the gate re-gate
    old history as today's pass: a latest record older than
    --max-age-s exits 2; 0 disables the guard."""
    path = str(tmp_path / "hist.jsonl")
    old = ledger.make_record(
        "smoke", {"txs_per_s": 100.0}, ts=time.time() - 7200
    )
    ledger.append(old, path)
    assert perfgate.main(["--history", path]) == 2
    assert perfgate.main(["--history", path, "--max-age-s", "0"]) == 0


def test_gate_with_empty_and_baselineless_ledger(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    assert perfgate.main(["--history", path]) == 2  # no records: usage
    ledger.append(_rec(1000, 200), path)
    # a single record has no baseline — pass, the gate arms itself
    assert perfgate.main(["--history", path]) == 0


# -- sampling profiler -------------------------------------------------------


def test_classify_stage_taxonomy():
    assert prof.classify(
        [("insert_event", "/x/babble_tpu/hashgraph/hashgraph.py"),
         ("_finish_eager_sync", "/x/babble_tpu/node/node.py")]
    ) == "insert"
    assert prof.classify(
        [("acquire", "/x/babble_tpu/common/timed_lock.py"),
         ("commit", "/x/babble_tpu/node/core.py")]
    ) == "lock_wait"
    # idle only counts at the innermost frame
    assert prof.classify([("wait", "/usr/lib/python3.10/threading.py")]) == "idle"
    assert prof.classify(
        [("divide_rounds", "/x/babble_tpu/hashgraph/hashgraph.py"),
         ("wait", "/usr/lib/python3.10/threading.py")]
    ) == "divide_rounds"
    # "commit" means proxy_deliver only in core.py; elsewhere unmatched
    assert prof.classify([("commit", "/x/babble_tpu/node/core.py")]) == (
        "proxy_deliver"
    )
    assert prof.classify([("commit", "/somewhere/else.py")]) == "other"
    assert prof.classify([]) == "other"
    for frames in ([("x", "y.py")],):
        assert prof.classify(frames) == "other"


def test_sampler_capture_and_renders():
    s = prof.StackSampler(hz=250)
    s.start()
    try:
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                sum(i * i for i in range(500))

        t = threading.Thread(target=spin, daemon=True)
        t.start()
        deadline = time.monotonic() + 10.0
        while s.samples_total < 20 and time.monotonic() < deadline:
            time.sleep(0.02)
        stop.set()
        t.join()
        snap = s.snapshot()
        assert snap["samples"] >= 20
        assert snap["stages"] and snap["stacks"]
        text = prof.collapsed_text(snap["stacks"])
        # stage-attributed collapsed stacks: every line is rooted at a
        # stage bucket and ends in a count
        for line in text.strip().splitlines():
            assert line.startswith("stage:"), line
            assert line.rsplit(" ", 1)[1].isdigit(), line
        table = prof.cprofile_text(snap["stacks"], 1.0 / s.hz)
        assert "sampled profile:" in table and "self_s" in table
    finally:
        s.stop()


def test_capture_diffs_and_temporary_sampler():
    prof.stop()  # no process sampler: capture spins a temporary one
    cap = prof.capture(0.2, hz=200)
    assert cap["always_on"] is False
    assert cap["seconds"] == 0.2
    assert cap["samples"] >= 1  # at least this thread was sampled
    assert sum(cap["stages"].values()) == cap["samples"]
    assert prof.sampler() is None  # temporary sampler did not persist


def test_profiler_kill_switch(monkeypatch):
    from babble_tpu.obs import metrics

    prof.stop()
    monkeypatch.setattr(metrics, "_ENABLED", False)
    try:
        assert prof.ensure_started(50) is None
        assert "error" in prof.capture(0.1)
    finally:
        monkeypatch.setattr(metrics, "_ENABLED", True)
    assert prof.ensure_started(0) is None  # hz=0 disables too
    prof.stop()


def test_ensure_started_idempotent_and_instrumented():
    prof.stop()
    s1 = prof.ensure_started(100)
    s2 = prof.ensure_started(100)
    try:
        assert s1 is s2 and s1.running()
        from babble_tpu.obs.metrics import GLOBAL, wire_global

        wire_global()  # registers profile_stage_samples (catalog scope)
        deadline = time.monotonic() + 10.0
        while s1.samples_total == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        text = GLOBAL.render()
        assert "profile_stage_samples" in text
        # live per-stage sample rows render once the sampler ticks
        assert 'profile_stage_samples{stage="' in text
    finally:
        prof.stop()
        assert prof.stage_counts() == {}
