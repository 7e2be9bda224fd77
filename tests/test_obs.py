"""Unit tests for the telemetry layer (babble_tpu/obs/):
registry instruments + Prometheus rendering, span tracer, mempool
latency feed, structured logging, catalog/docs lint, kill switch."""

import io
import json
import logging
import os

import pytest

from babble_tpu.obs import catalog as obs_catalog
from babble_tpu.obs import lint as obs_lint
from babble_tpu.obs import log as obs_log
from babble_tpu.obs.metrics import (
    GLOBAL,
    Counter,
    Gauge,
    Histogram,
    NULL,
    Registry,
)
from babble_tpu.obs.trace import Tracer, staged

DOCS = os.path.join(os.path.dirname(__file__), "..", "docs",
                    "observability.md")


# -- instruments -------------------------------------------------------------


def test_counter_gauge_basics():
    c = Counter()
    c.inc()
    c.inc(5)
    assert c.value == 6
    g = Gauge()
    g.set(3.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 4.0


def test_histogram_buckets_sum_count_and_quantiles():
    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(6.05)
    # counts: <=0.1 -> 1, <=1.0 -> 2, <=10 -> 1, +Inf -> 0
    assert h.counts == [1, 2, 1, 0]
    # p50 lands in the (0.1, 1.0] bucket, interpolated
    assert 0.1 < h.quantile(0.5) <= 1.0
    assert h.quantile(0.99) <= 10.0
    s = h.summary()
    assert s["count"] == 4 and s["p50"] is not None
    # Prometheus `le` is inclusive: a value ON a bound lands in that
    # bucket, not the next one up
    h.observe(1.0)
    assert h.counts == [1, 3, 1, 0]


def test_histogram_overflow_goes_to_inf_bucket():
    h = Histogram(buckets=(1.0,))
    h.observe(100.0)
    assert h.counts == [0, 1]
    assert h.quantile(0.5) == 1.0  # clamped to the largest finite bound


def test_empty_histogram_quantile_is_none():
    h = Histogram(buckets=(1.0,))
    assert h.quantile(0.5) is None
    assert h.summary()["p50"] is None


# -- registry + exposition ---------------------------------------------------


def test_registry_render_prometheus_text_shape():
    r = Registry(enabled=True)
    c = r.counter("foo_total", "help foo")
    c.inc(3)
    h = r.histogram("lat_seconds", "help lat", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    ls = r.histogram(
        "st_seconds", "help st", buckets=(1.0,), labelnames=("stage",)
    )
    ls.labels(stage="a").observe(0.1)
    r.func_gauge("depth", "help depth", lambda: 7)
    r.func_counter(
        "byc_total", "by cause", lambda: {"x": 2}, labelnames=("cause",)
    )
    text = r.render()
    assert "# HELP foo_total help foo" in text
    assert "# TYPE foo_total counter" in text
    assert "foo_total 3" in text
    # cumulative buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    assert 'st_seconds_bucket{stage="a",le="1"} 1' in text
    assert "depth 7" in text
    assert 'byc_total{cause="x"} 2' in text


def test_registry_get_and_summary_helpers():
    r = Registry(enabled=True)
    c = r.counter("x_total", "x")
    c.inc(2)
    assert r.get("x_total") == 2
    r.func_counter("y_total", "y", lambda: {"a": 4}, labelnames=("t",))
    assert r.get("y_total", t="a") == 4
    h = r.histogram("h_seconds", "h", buckets=(1.0,))
    h.observe(0.5)
    assert r.histogram_summary("h_seconds")["count"] == 1


def test_registry_same_name_returns_same_instrument():
    r = Registry(enabled=True)
    a = r.counter("dup_total", "d")
    b = r.counter("dup_total", "d")
    a.inc()
    assert b.value == 1


def test_disabled_registry_returns_null_and_renders_only_funcs():
    r = Registry(enabled=False)
    c = r.counter("hot_total", "h")
    assert c is NULL
    c.inc()  # no-op, no crash
    h = r.histogram("hot_seconds", "h")
    h.observe(1.0)
    assert h.labels(stage="x") is h
    r.func_counter("cold_total", "c", lambda: 9)
    text = r.render()
    assert "hot_total" not in text
    assert "cold_total 9" in text


def test_snapshot_is_json_serializable():
    r = Registry(enabled=True)
    r.histogram("h_seconds", "h", buckets=(1.0,)).observe(0.2)
    r.func_gauge("g", "g", lambda: None)  # failing/None reader tolerated
    json.dumps(r.snapshot())


# -- tracer ------------------------------------------------------------------


def test_tracer_stages_attach_to_active_trace_and_ring():
    seen = []
    t = Tracer(stage_sink=lambda s, d: seen.append(s), ring=4)
    tr = t.start("sync", peer_id=7)
    with tr.stage("request_sync"):
        pass
    t.observe("insert", 0.001)  # deep-pipeline observation, no explicit trace
    tr.finish()
    assert seen == ["request_sync", "insert"]
    assert t.active() is None
    recent = t.recent()
    assert len(recent) == 1
    rec = recent[0]
    assert rec["peer"] == 7 and rec["kind"] == "sync"
    assert [s for s, _ in tr.stages] == ["request_sync", "insert"]
    # ring is bounded
    for _ in range(10):
        t.start("sync", 1).finish()
    assert len(t.recent()) == 4


def test_observe_without_active_trace_only_hits_sink():
    seen = []
    t = Tracer(stage_sink=lambda s, d: seen.append((s, d)))
    t.observe("divide_rounds", 0.5)
    assert seen == [("divide_rounds", 0.5)]
    assert t.recent() == []


def test_staged_decorator_null_observer_is_clockless():
    calls = []

    class Obj:
        stage_observer = None

        @staged("insert")
        def work(self, x):
            return x * 2

    o = Obj()
    assert o.work(3) == 6
    o.stage_observer = Tracer(stage_sink=lambda s, d: calls.append((s, d)))
    assert o.work(4) == 8
    assert len(calls) == 1 and calls[0][0] == "insert"
    assert calls[0][1] >= 0.0


# -- the span tree -----------------------------------------------------------


class _FakeClock:
    """A clock the test advances by hand."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _tree_tracer():
    clock = _FakeClock()
    sinks = {"incl": [], "self": [], "cpu": []}
    tracer = Tracer(
        stage_sink=lambda s, d: sinks["incl"].append((s, d)),
        self_sink=lambda s, d: sinks["self"].append((s, d)),
        clock=clock,
    )
    return tracer, clock, sinks


def test_span_tree_nesting_and_self_time():
    """A span's self time is its duration minus what its children
    covered; the children's own children count for the children only."""
    tracer, clock, sinks = _tree_tracer()
    tr = tracer.start("sync", 3)
    with tracer.span("sync") as root:
        clock.now += 1.0
        with tracer.span("flush") as flush:
            assert flush.parent is root
            clock.now += 2.0
            with tracer.span("commit") as commit:
                assert commit.parent is flush
                clock.now += 4.0
            tracer.observe("request_sync", 0.5)  # a leaf measured elsewhere
            clock.now += 0.5
        clock.now += 8.0
    assert root.parent is None
    assert (root.t0, root.t1) == (0.0, 15.5)
    assert dict(sinks["incl"]) == {
        "commit": 4.0, "request_sync": 0.5, "flush": 6.5, "sync": 15.5}
    assert dict(sinks["self"]) == {
        "commit": 4.0, "request_sync": 0.5, "flush": 2.0, "sync": 9.0}
    # self times sum to the root's duration: every second counted once
    assert sum(d for _s, d in sinks["self"]) == root.seconds
    tr.finish()
    rec = tracer.recent()[-1]
    assert [s for s, _ in rec["stages"]] == [
        "commit", "request_sync", "flush", "sync"]
    assert dict(map(tuple, rec["self_ms"]))["sync"] == 9000.0
    assert dict(map(tuple, rec["stages"]))["sync"] == 15500.0


def test_span_stacks_are_per_thread():
    import threading

    tracer, clock, _sinks = _tree_tracer()
    seen = {}

    def other():
        with tracer.span("insert") as sp:
            seen["parent"] = sp.parent

    with tracer.span("sync"):
        t = threading.Thread(target=other)
        t.start()
        t.join(10)
    assert seen == {"parent": None}


def test_custom_sink_span_is_a_child_but_not_a_sync_stage():
    """The accel stages feed their own histogram, and still count as
    children of the span around them."""
    tracer, clock, sinks = _tree_tracer()
    accel = []
    with tracer.span("flush"):
        with tracer.span("apply", sink=lambda s, d: accel.append((s, d))):
            clock.now += 3.0
        clock.now += 1.0
    assert accel == [("apply", 3.0)]
    assert sinks["incl"] == [("flush", 4.0)]
    assert sinks["self"] == [("flush", 1.0)]


def test_coarse_spans_read_cpu_and_annotate_only_when_wired(monkeypatch):
    """CPU reads and profiler annotations: coarse spans only, and only
    on a tracer that was given a CPU sink / an owner (a simulated clock
    gets neither)."""
    import babble_tpu.obs.trace as trace_mod

    entered = []

    class _Ann:
        def __init__(self, stage, owner):
            self.what = (stage, owner)

        def __enter__(self):
            entered.append(self.what)

        def __exit__(self, *exc):
            entered.append("exit")

    monkeypatch.setattr(trace_mod, "annotation", _Ann)
    cpu = []
    wired = Tracer(cpu_sink=lambda s, d: cpu.append(s), owner="v7")
    with wired.span("sync"):
        with wired.span("insert"):  # per-event: neither
            pass
    assert cpu == ["sync"]
    assert entered == [("sync", "v7"), "exit"]

    def boom():
        raise AssertionError("CPU clock read on an unwired tracer")

    monkeypatch.setattr(trace_mod.time, "thread_time", boom)
    del entered[:]
    with Tracer().span("sync"):
        pass
    assert entered == []


def _stage_sum(node, name, stage):
    return node.telemetry.registry.histogram_summary(name, stage=stage)["sum"]


def test_self_event_no_longer_double_counts_its_insert():
    """``self_event`` contains its own insert + divide_rounds (+ flush):
    inclusive it still does, self it is what none of them covers, and
    ``record_heads`` around it has only its loop left."""
    node = _tiny_node()
    try:
        core = node.core
        core.add_self_event("")
        core.heads[core.validator.id()] = None
        core.record_heads()
        incl = {s: _stage_sum(node, "sync_stage_seconds", s)
                for s in ("self_event", "insert", "divide_rounds",
                          "mempool_drain", "flush", "record_heads")}
        self_s = {s: _stage_sum(node, "sync_stage_self_seconds", s)
                  for s in ("self_event", "record_heads")}
        children = (incl["insert"] + incl["divide_rounds"]
                    + incl["mempool_drain"] + incl["flush"])
        assert incl["self_event"] >= children > 0
        assert self_s["self_event"] == pytest.approx(
            incl["self_event"] - children, abs=2e-5)  # sums round to 1 us
        assert self_s["record_heads"] < incl["record_heads"]
        # the CPU clock: coarse spans only
        reg = node.telemetry.registry
        assert reg.get("sync_stage_cpu_seconds", stage="self_event") == 2
        assert reg.get("sync_stage_cpu_seconds", stage="insert") == 0
    finally:
        node.shutdown()


def test_kill_switch_skips_every_clock_read(monkeypatch):
    """BABBLE_OBS=0: no span opens, so the ingest path reads neither the
    stage clock nor the CPU clock."""
    import babble_tpu.obs.metrics as metrics_mod
    import babble_tpu.obs.trace as trace_mod
    from babble_tpu.common.clock import WallClock

    monkeypatch.setattr(metrics_mod, "_ENABLED", False)
    node = _tiny_node()
    try:
        core = node.core
        assert core.stage_observer is None
        assert core._span("self_event") is trace_mod.NULL_STAGE

        def boom(*_a):
            raise AssertionError("clock read under BABBLE_OBS=0")

        monkeypatch.setattr(WallClock, "perf_counter", boom)
        monkeypatch.setattr(trace_mod.time, "thread_time", boom)
        before = core.seq
        core.add_self_event("")
        core.process_sig_pool()
        core.prepare_sync([])
        assert core.seq == before + 1
    finally:
        monkeypatch.undo()
        node.shutdown()


def test_sim_clock_tracer_reads_no_real_clock_and_digests_repeat():
    """On a simulated clock the tracer gets no CPU sink and no owner, so
    same-seed runs keep byte-identical telemetry (self times included)."""
    from babble_tpu.sim.scenario import ScenarioSpec, run_scenario

    spec = ScenarioSpec(seed=77, nodes=3, duration_s=0.6, heartbeat_s=0.08,
                        tx_rate=5, settle_s=0.6)
    r1, r2 = run_scenario(spec), run_scenario(spec)
    assert r1.telemetry_digest == r2.telemetry_digest
    assert r1.event_log_digest == r2.event_log_digest

    from babble_tpu.sim.harness import SimCluster
    from babble_tpu.sim.scheduler import SimScheduler

    cluster = SimCluster(SimScheduler(5), 2, heartbeat_s=0.05)
    try:
        tracer = cluster.nodes[0].telemetry.tracer
        assert tracer.cpu_sink is None and tracer.owner is None
        assert tracer.self_sink is not None
    finally:
        cluster.shutdown()


# -- mempool latency feed ----------------------------------------------------


def test_mempool_commit_latency_observed_with_fake_clock():
    from babble_tpu.mempool import Mempool

    now = {"t": 100.0}
    m = Mempool(max_txs=10, max_bytes=10**6, clock=lambda: now["t"])
    lat, wait, cons = (
        Histogram(buckets=(0.5, 2.0, 10.0)),
        Histogram(buckets=(0.5, 2.0, 10.0)),
        Histogram(buckets=(0.5, 2.0, 10.0)),
    )
    m.attach_telemetry(lat, wait, cons)
    assert m.submit(b"tx1") == "accepted"
    now["t"] = 101.0  # 1 s in the pool
    drained = m.drain()
    assert drained == [b"tx1"]
    assert wait.count == 1 and wait.sum == pytest.approx(1.0)
    now["t"] = 103.0  # 2 s in consensus
    m.mark_committed([b"tx1"])
    assert lat.count == 1 and lat.sum == pytest.approx(3.0)
    assert cons.count == 1 and cons.sum == pytest.approx(2.0)
    # internals fully cleaned up
    assert not m._admit_ts and not m._drain_ts


def test_mempool_requeue_keeps_admit_clock_running():
    from babble_tpu.mempool import Mempool

    now = {"t": 0.0}
    m = Mempool(max_txs=10, max_bytes=10**6, clock=lambda: now["t"])
    lat, wait, cons = (Histogram((10.0,)), Histogram((10.0,)),
                       Histogram((10.0,)))
    m.attach_telemetry(lat, wait, cons)
    m.submit(b"tx")
    now["t"] = 1.0
    batch = m.drain()
    m.requeue(batch)  # event creation failed
    now["t"] = 2.0
    m.drain()
    # mempool_wait observed exactly ONCE per tx (admit t=0 → FIRST
    # drain t=1), never re-observed by the post-requeue drain
    assert wait.count == 1 and wait.sum == pytest.approx(1.0)
    now["t"] = 5.0
    m.mark_committed([b"tx"])
    # end-to-end from the ORIGINAL admit (t=0), not the requeue
    assert lat.sum == pytest.approx(5.0)
    # consensus leg from the FIRST drain (t=1): requeue interludes
    # count as consensus time, and wait+consensus == end-to-end
    assert cons.count == 1 and cons.sum == pytest.approx(4.0)
    assert not m._admit_ts and not m._drain_ts


def test_mempool_without_telemetry_records_no_timestamps():
    from babble_tpu.mempool import Mempool

    m = Mempool(max_txs=4, max_bytes=10**6)
    m.submit(b"a")
    m.drain()
    m.mark_committed([b"a"])
    assert not m._admit_ts and not m._drain_ts


# -- node wiring vs catalog --------------------------------------------------


def _tiny_node():
    from babble_tpu.config.config import Config
    from babble_tpu.crypto.keys import PrivateKey
    from babble_tpu.dummy.state import State
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.node import Node
    from babble_tpu.node.validator import Validator
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet
    from babble_tpu.proxy.proxy import InmemProxy

    key = PrivateKey(0xFEED)
    peers = PeerSet([Peer("inmem://solo", key.public_key.hex(), "solo")])
    net = InmemNetwork()
    conf = Config(heartbeat_timeout=0.01, log_level="error", moniker="solo")
    node = Node(
        conf, Validator(key, "solo"), peers, peers,
        InmemStore(conf.cache_size), net.new_transport("inmem://solo"),
        InmemProxy(State()),
    )
    return node


def test_node_registry_matches_catalog_exactly():
    """Every node-scope cataloged instrument is registered on a plain
    (oracle) node, and nothing outside the catalog can register — the
    two-way contract the docs lint rides on."""
    node = _tiny_node()
    try:
        registered = set(node.telemetry.registry.names())
        expected = {
            c.name for c in obs_catalog.CATALOG if c.scope == "node"
        }
        assert registered == expected
        global_expected = {
            c.name for c in obs_catalog.CATALOG if c.scope == "global"
        }
        assert global_expected <= set(GLOBAL.names())
    finally:
        node.shutdown()


def test_uncataloged_instrument_registration_raises():
    with pytest.raises(KeyError):
        obs_catalog.spec("totally_unknown_metric")


def test_get_stats_is_string_view_of_typed_snapshot():
    node = _tiny_node()
    try:
        snap = node.get_stats_snapshot()
        stats = node.get_stats()
        assert isinstance(snap["last_block_index"], int)
        assert isinstance(snap["mempool_pending"], int)
        assert set(stats) == set(snap)
        for k, v in snap.items():
            assert stats[k] == str(v)
        json.dumps(snap)  # the mobile surface contract
    finally:
        node.shutdown()


def test_kill_switch_disables_hot_path_only(monkeypatch):
    """BABBLE_OBS=0: stage observers are None (no clock reads), but the
    func-backed instruments keep serving /metrics and get_stats."""
    import babble_tpu.obs.metrics as metrics_mod

    monkeypatch.setattr(metrics_mod, "_ENABLED", False)
    node = _tiny_node()
    try:
        t = node.telemetry
        assert not t.enabled
        assert t.stage_observer is None
        assert t.lock_wait_observer is None
        assert node.core.hg.stage_observer is None
        assert t.start_sync_trace(1).trace_id == 0  # null trace
        text = t.render_metrics()
        assert "ingest_syncs_total 0" in text
        assert "commit_latency_seconds" not in text
        # legacy stats still intact
        assert node.get_stats()["ingest_syncs"] == "0"
    finally:
        node.shutdown()


# -- metrics lint ------------------------------------------------------------


def test_metrics_lint_passes_on_shipped_docs():
    assert obs_lint.run(DOCS) == 0


def test_metrics_lint_catches_drift(tmp_path):
    rows = "\n".join(
        f"| `{c.name}` | {c.kind} | | {c.scope} | x |"
        for c in obs_catalog.CATALOG
        if c.name != "commit_latency_seconds"
    )
    doc = tmp_path / "obs.md"
    doc.write_text(
        "<!-- metrics-table-start -->\n"
        f"{rows}\n| `made_up_metric` | counter | | node | x |\n"
        "<!-- metrics-table-end -->\n"
    )
    assert obs_lint.run(str(doc)) == 1


def test_lint_rejects_docs_without_markers(tmp_path):
    doc = tmp_path / "no_markers.md"
    doc.write_text("# nothing here\n")
    with pytest.raises(SystemExit):
        obs_lint.run(str(doc))


# -- structured logging ------------------------------------------------------


def test_log_configure_json_emits_parseable_lines():
    buf = io.StringIO()
    obs_log.configure(level="info", json_mode=True, node="n0", node_id=42,
                      stream=buf)
    # unique logger name: cluster suites set e.g. babble_tpu.node.n0 to
    # ERROR via Config.logger, which would swallow this INFO record
    logger = logging.getLogger("babble_tpu.node.obs_json_test")
    logger.info("hello %s", "world", extra={"peer": 7, "sync_id": 99})
    line = buf.getvalue().strip()
    rec = json.loads(line)
    assert rec["msg"] == "hello world"
    assert rec["level"] == "info"
    assert rec["node"] == "n0" and rec["node_id"] == 42
    assert rec["peer"] == 7 and rec["sync_id"] == 99
    assert rec["logger"] == "babble_tpu.node.obs_json_test"


def test_log_configure_is_idempotent_and_plain_mode_works():
    buf1 = io.StringIO()
    buf2 = io.StringIO()
    obs_log.configure(level="info", json_mode=False, stream=buf1)
    obs_log.configure(level="info", json_mode=False, stream=buf2)
    root = logging.getLogger(obs_log.ROOT)
    tagged = [
        h for h in root.handlers if getattr(h, "_babble_obs_handler", False)
    ]
    assert len(tagged) == 1  # reconfigure replaced, not stacked
    logging.getLogger("babble_tpu.test").warning("plain line")
    assert "plain line" in buf2.getvalue()
    assert buf1.getvalue() == ""


def test_config_logger_scopes_under_framework_root():
    from babble_tpu.config.config import Config

    conf = Config(moniker="m1", log_level="warning")
    lg = conf.logger("node")
    assert lg.name == "babble_tpu.node.m1"
    assert lg.level == logging.WARNING


@pytest.fixture(autouse=True)
def _reset_obs_logging():
    yield
    root = logging.getLogger(obs_log.ROOT)
    for h in list(root.handlers):
        if getattr(h, "_babble_obs_handler", False):
            root.removeHandler(h)
