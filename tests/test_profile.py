"""The sampling profiler (docs/observability.md §Sampling profiler):
stage attribution, the collapsed-stack and pstats renders, the temporary
sampler behind /profile, and the kill switch."""

import threading
import time

from babble_tpu.obs import profile as prof


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
