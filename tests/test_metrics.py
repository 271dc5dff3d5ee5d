"""Metrics registry + query tracing + ACL tests.

Mirrors the reference's metrics tests (AbstractMetrics typed registration,
phase timings attached per query) and TraceContext's trace=true flow: a
traced query returns per-stage timings from broker AND servers in
response metadata.
"""
import tempfile

import pytest

from fixtures import build_segment

from pinot_tpu.broker import (BrokerRequestHandler, InProcessTransport,
                              RoutingManager)
from pinot_tpu.broker.access_control import (AccessControlFactory,
                                             RequesterIdentity,
                                             TableAclAccessControl)
from pinot_tpu.common.cluster_state import ONLINE, TableView
from pinot_tpu.common.metrics import (BrokerQueryPhase, MetricsRegistry,
                                      ServerQueryPhase)
from pinot_tpu.server import ServerInstance


# -- registry unit tests ----------------------------------------------------

def test_meter_counts_and_rate():
    reg = MetricsRegistry("t")
    reg.meter("queries").mark()
    reg.meter("queries").mark(4)
    assert reg.meter("queries").count == 5
    assert reg.meter("queries").rate() > 0


def test_gauge_value_and_callable():
    reg = MetricsRegistry("t")
    reg.gauge("docs").set(42)
    assert reg.gauge("docs").value == 42.0
    reg.gauge("docs").set_callable(lambda: 7)
    assert reg.gauge("docs").value == 7.0


def test_timer_stats_and_percentiles():
    reg = MetricsRegistry("t")
    t = reg.timer("phase")
    for ms in [1.0, 2.0, 3.0, 4.0]:
        t.update(ms)
    assert t.count == 4
    assert t.total_ms == pytest.approx(10.0)
    assert t.mean_ms == pytest.approx(2.5)
    assert t.percentile_ms(50) == pytest.approx(2.5)
    with t.time():
        pass
    assert t.count == 5


def test_table_scoped_metrics_are_distinct():
    reg = MetricsRegistry("t")
    reg.meter("queries", table="a_OFFLINE").mark()
    reg.meter("queries", table="b_OFFLINE").mark(2)
    assert reg.meter("queries", table="a_OFFLINE").count == 1
    assert reg.meter("queries", table="b_OFFLINE").count == 2
    snap = reg.snapshot()
    assert snap["meter.a_OFFLINE.queries.count"] == 1


# -- integration: broker + server phases ------------------------------------

@pytest.fixture(scope="module")
def cluster():
    base = tempfile.mkdtemp()
    server = ServerInstance("server_0")
    seg, _ = build_segment(f"{base}/seg0", n=800, seed=11, name="m_0")
    server.data_manager.table("metricsT_OFFLINE",
                              create=True).add_segment(seg)
    view = TableView("metricsT_OFFLINE", {"m_0": {"server_0": ONLINE}})
    routing = RoutingManager()
    routing.update_view(view)
    handler = BrokerRequestHandler(routing,
                                   InProcessTransport({"server_0": server}))
    yield handler, server
    server.stop()
    handler.close()


def test_broker_phase_timers_populate(cluster):
    handler, server = cluster
    resp = handler.handle("SELECT COUNT(*) FROM metricsT")
    assert not resp.exceptions
    m = handler.metrics
    assert m.meter("queries").count >= 1
    for phase in (BrokerQueryPhase.REQUEST_COMPILATION,
                  BrokerQueryPhase.QUERY_ROUTING,
                  BrokerQueryPhase.SCATTER_GATHER,
                  BrokerQueryPhase.REDUCE,
                  BrokerQueryPhase.QUERY_TOTAL):
        assert m.timer(phase).count >= 1, phase
    assert m.timer(BrokerQueryPhase.QUERY_TOTAL).total_ms > 0


def test_server_phase_timers_populate(cluster):
    handler, server = cluster
    handler.handle("SELECT COUNT(*) FROM metricsT")
    m = server.metrics
    assert m.meter("queries").count >= 1
    for phase in (ServerQueryPhase.REQUEST_DESERIALIZATION,
                  ServerQueryPhase.SCHEDULER_WAIT,
                  ServerQueryPhase.QUERY_PROCESSING,
                  ServerQueryPhase.RESPONSE_SERIALIZATION):
        assert m.timer(phase).count >= 1, phase
    assert m.gauge("segmentCount").value == 1.0


def test_trace_option_returns_phase_spans(cluster):
    handler, _ = cluster
    resp = handler.handle("SELECT COUNT(*) FROM metricsT WHERE runs > 50 "
                          "OPTION(trace=true)")
    assert not resp.exceptions
    info = resp.trace_info
    assert info is not None
    broker_spans = {s["name"] for s in info["broker"]}
    assert {"requestCompilation", "queryRouting", "scatterGather",
            "reduce"} <= broker_spans
    assert "server_0" in info
    server_spans = {s["name"] for s in info["server_0"]}
    assert "schedulerWait" in server_spans
    assert "queryProcessing" in server_spans
    assert "traceInfo" in resp.to_json()


def test_untraced_query_has_no_trace_info(cluster):
    handler, _ = cluster
    resp = handler.handle("SELECT COUNT(*) FROM metricsT")
    assert resp.trace_info is None
    assert "traceInfo" not in resp.to_json()


# -- ACL --------------------------------------------------------------------

def test_acl_denies_without_token(cluster):
    handler, server = cluster
    acl = TableAclAccessControl({"metricsT": ["sekrit"]})
    old = handler.access_control
    handler.access_control = acl
    try:
        resp = handler.handle("SELECT COUNT(*) FROM metricsT")
        assert resp.exceptions
        assert "AccessDenied" in resp.exceptions[0]["message"]
        ok = handler.handle("SELECT COUNT(*) FROM metricsT",
                            identity=RequesterIdentity(token="sekrit"))
        assert not ok.exceptions
        other = handler.handle("SELECT COUNT(*) FROM unknownT",
                               identity=RequesterIdentity(token="x"))
        # unknown table passes ACL (not mapped) then fails at routing
        assert "TableDoesNotExistError" in other.exceptions[0]["message"]
    finally:
        handler.access_control = old


def test_acl_factory():
    acl = AccessControlFactory.create("allowall")
    assert acl.has_access(None, None)
    acl2 = AccessControlFactory.create(
        "tableacl", table_tokens={"t": ["a"]})
    assert isinstance(acl2, TableAclAccessControl)
    with pytest.raises(ValueError):
        AccessControlFactory.create("nope")


# -- XLA compile counters (jax.monitoring -> the server's registry) ---------

def test_xla_compile_counters_exist_at_zero_from_boot():
    server = ServerInstance("server_boot")
    try:
        snap = server.metrics.snapshot()
        assert snap["meter.xlaCompiles.count"] == 0
        assert snap["meter.xlaCompileCacheHits.count"] == 0
        assert snap["timer.xlaCompile.count"] == 0
        assert snap["timer.xlaCompile.totalMs"] == 0
    finally:
        server.stop()


def test_xla_compiles_grow_by_a_fresh_shapes_programs_then_by_zero(cluster):
    """`xlaCompiles` counts JAX's backend-compile events: one a program
    this process meets for the first time, none on its second run."""
    import jax.monitoring
    handler, server = cluster
    seen = []

    def listener(event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration_secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        m = server.metrics
        before = m.meter("xlaCompiles").count
        # a predicate tree no other test of this process compiles
        pql = ("SELECT SUM(hits) FROM metricsT WHERE runs > {} AND "
               "(yearID > 1993 OR salary > 12345.5) AND hits < 240")
        resp = handler.handle(pql.format(17))
        assert not resp.exceptions
        grown = m.meter("xlaCompiles").count - before
        assert grown == len(seen) >= 1
        assert m.timer("xlaCompile").count >= grown
        assert m.timer("xlaCompile").total_ms >= sum(seen[:grown]) * 1e3 \
            - 1e-6
        # the same shape with another literal: the same programs
        resp = handler.handle(pql.format(18))
        assert not resp.exceptions
        assert m.meter("xlaCompiles").count - before == grown == len(seen)
        assert m.meter("xlaCompileCacheHits").count >= 0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# -- lane cache meters (segment/loader.py _device -> obs/residency.py) ------

def test_lane_cache_meters_exist_at_zero_from_boot():
    server = ServerInstance("server_lanes")
    try:
        snap = server.metrics.snapshot()
        assert snap["meter.laneCacheHits.count"] == 0
        assert snap["meter.laneCacheMisses.count"] == 0
    finally:
        server.stop()


def test_cube_descent_meters_exist_at_zero_from_boot():
    server = ServerInstance("server_cubes")
    try:
        snap = server.metrics.snapshot()
        assert snap["meter.cubeDescentsNative.count"] == 0
        assert snap["meter.cubeDescentsNumpy.count"] == 0
    finally:
        server.stop()


def test_lane_cache_meters_count_a_querys_lanes(cluster):
    """A scan's first run misses each lane once; the same lanes under
    another literal are all hits, as many as the first run looked up."""
    handler, server = cluster
    m = server.metrics

    def counts():
        return (m.meter("laneCacheHits").count,
                m.meter("laneCacheMisses").count)

    # a summed column no other test of this module touches
    pql = "SELECT SUM(salary) FROM metricsT WHERE average > {}"
    h0, m0 = counts()
    resp = handler.handle(pql.format(0.25))
    assert not resp.exceptions
    h1, m1 = counts()
    lanes = (h1 - h0) + (m1 - m0)
    assert m1 - m0 >= 1 and lanes >= 2
    resp = handler.handle(pql.format(0.5))
    assert not resp.exceptions
    h2, m2 = counts()
    assert m2 == m1 and h2 - h1 == lanes
    snap = m.snapshot()
    assert snap["meter.laneCacheHits.count"] == h2
    assert snap["meter.laneCacheMisses.count"] == m2


def test_compile_listeners_register_once_and_hold_registries_weakly():
    import gc
    import jax.monitoring
    from jax._src import monitoring as jax_monitoring
    from pinot_tpu.obs import profiler

    def ours():
        return [cb for cb in
                jax_monitoring.get_event_duration_listeners()
                if cb is profiler._on_compile_duration]

    reg = MetricsRegistry("server")
    profiler.bind_compile_metrics(reg)
    profiler.bind_compile_metrics(reg)
    profiler.bind_compile_metrics(MetricsRegistry("server"))
    assert len(ours()) == 1
    assert sum(1 for m in profiler._compile_registries() if m is reg) == 1
    # the event reaches every live registry, and only its own event
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25)
    jax.monitoring.record_event_duration_secs("/jax/other", 1.0)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert reg.meter("xlaCompiles").count == 1
    assert reg.timer("xlaCompile").total_ms == pytest.approx(250.0)
    assert reg.meter("xlaCompileCacheHits").count == 1
    del reg
    gc.collect()
    assert all(m.component == "server"
               for m in profiler._compile_registries())
