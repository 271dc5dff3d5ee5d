"""Observability subsystem tests: hierarchical tracing (incl. broker→
server propagation over real TCP), Prometheus exposition, the operator
profiler, and slow-log sampling.

Mirrors the reference's TraceContextTest (request-scoped trace tree in
response metadata) extended to the Dapper cross-process span model, and
the metrics tests' typed-registry expectations extended to the text
exposition format a Prometheus scraper actually parses.
"""
import json
import os
import re
import tempfile
import urllib.error
import urllib.request

import pytest

from fixtures import build_segment, make_schema, make_table_config

from pinot_tpu.common.metrics import MetricsRegistry, Timer
from pinot_tpu.obs import (NoopTraceContext, SlowQueryLog, TraceContext,
                           build_trace_tree, make_trace_context,
                           render_prometheus)
from pinot_tpu.obs.profiler import QueryProfile, TableStatsAggregator
from pinot_tpu.tools.cluster import EmbeddedCluster


# -- tracing units ----------------------------------------------------------

def test_span_nesting_and_parent_links():
    t = TraceContext(root_name="query")
    with t.span("a") as a:
        with t.span("b") as b:
            pass
        t.record("c", 1.5)
    spans = {s["name"]: s for s in t.to_list()}
    assert spans["a"]["parentId"] == t.root_span_id
    assert spans["b"]["parentId"] == spans["a"]["spanId"]
    assert spans["c"]["parentId"] == spans["a"]["spanId"]
    assert spans["b"]["ms"] >= 0


def test_trace_serde_round_trip_and_legacy_format():
    t = TraceContext()
    t.record("phase", 2.0, attr1="x")
    parsed = TraceContext.from_json_str(t.to_json_str())
    assert parsed.trace_id == t.trace_id
    assert parsed.root_span_id == t.root_span_id
    names = [s["name"] for s in parsed.to_list()]
    assert "phase" in names
    # legacy flat list (version-skewed peer) still parses
    legacy = TraceContext.from_json_str('[{"name": "old", "ms": 1.0}]')
    assert legacy.to_list()[0]["name"] == "old"


def test_attach_seeds_worker_thread_parent():
    import threading
    t = TraceContext()
    with t.span("parent") as p:
        pid = p["spanId"]

    def work():
        with t.attach(pid):
            t.record("child", 1.0)

    th = threading.Thread(target=work)
    th.start()
    th.join()
    child = [s for s in t.to_list() if s["name"] == "child"][0]
    assert child["parentId"] == pid


def test_build_trace_tree_grafts_and_orphans():
    t = TraceContext(root_name="query")
    with t.span("scatter") as sc:
        dispatch = t.record("dispatch:s0", 5.0, parent_id=sc["spanId"])
    # a "server" context rooted under the dispatch span (cross-process)
    server = TraceContext(trace_id=t.trace_id,
                          parent_span_id=dispatch["spanId"],
                          root_name="server")
    server.record("schedulerWait", 0.1)
    tree = build_trace_tree(t.to_list() + server.to_list(), t.trace_id)
    assert tree["name"] == "query" and tree["traceId"] == t.trace_id

    def find(node, name):
        if node["name"] == name:
            return node
        for c in node["children"]:
            hit = find(c, name)
            if hit is not None:
                return hit
        return None

    d = find(tree, "dispatch:s0")
    assert d is not None
    assert [c["name"] for c in d["children"]] == ["server"]
    assert find(tree, "schedulerWait")["parentId"] == server.root_span_id
    # an orphan (unknown parent) lands under the root, not dropped
    orphan_tree = build_trace_tree(
        t.to_list() + [{"name": "lost", "ms": 1.0, "spanId": "zz",
                        "parentId": "not-a-span"}])
    assert find(orphan_tree, "lost") is not None


def test_noop_trace_is_inert():
    t = make_trace_context(False)
    assert isinstance(t, NoopTraceContext)
    assert not t.enabled
    with t.span("x") as s:
        assert s is None
    assert t.record("y", 1.0) == {}
    assert t.to_list() == []
    assert make_trace_context(True).enabled


def _spin(seconds: float) -> int:
    """Pure Python work for about `seconds` of its thread's CPU time."""
    import time
    n, end = 0, time.thread_time() + seconds
    while time.thread_time() < end:
        n += 1
    return n


def test_a_span_round_python_work_reads_its_cpu_time():
    """`cpuMs` is the thread's CPU time over the window of `ms`: a busy
    loop reads within 20% of its wall time (best of three tries: the
    host may take the core away now and then)."""
    best = 0.0
    for _ in range(3):
        t = TraceContext(root_name="query")
        with t.span("loop") as span:
            _spin(0.05)
        assert 0 < span["cpuMs"] <= span["ms"] + 0.5
        best = max(best, span["cpuMs"] / span["ms"])
        if best >= 0.8:
            break
    assert best >= 0.8, best


def test_a_span_round_a_sleep_reads_almost_no_cpu_time():
    import time
    t = TraceContext(root_name="query")
    with t.span("nap") as span:
        time.sleep(0.05)
    assert span["ms"] >= 50 and span["cpuMs"] < 5
    # a recorded span was timed elsewhere: no CPU time of its own
    assert "cpuMs" not in t.record("wait", 1.0)


def test_a_second_thread_spinning_takes_the_loops_cpu_away():
    """Beside a thread that wants the interpreter lock too, a busy
    loop's span is off-CPU for a good share of its wall time."""
    import threading
    stop = threading.Event()

    def rival():
        while not stop.is_set():
            pass

    other = threading.Thread(target=rival)
    other.start()
    try:
        t = TraceContext(root_name="query")
        with t.span("loop") as span:
            n, end = 0, span["startUs"] / 1e6 + 0.3
            import time
            while time.time() < end:
                n += 1
    finally:
        stop.set()
        other.join()
    off = (span["ms"] - span["cpuMs"]) / span["ms"]
    assert off > 0.25, span


def test_the_noop_trace_takes_no_clock(monkeypatch):
    import time

    def no_clock():
        raise AssertionError("a clock was read")

    t = make_trace_context(False)
    for clock in ("time_ns", "perf_counter", "perf_counter_ns",
                  "thread_time_ns", "thread_time"):
        monkeypatch.setattr(time, clock, no_clock)
    with t.span("x", segment="s") as s:
        assert s is None
    assert t.record("y", 1.0) == {}
    t.finish_root()


def test_obs_span_off_is_one_shared_noop():
    """With no trace on, `obs_span` builds nothing: every call hands
    back the same no-op context, whose `as` target is None."""
    from pinot_tpu.obs import profiler as obs_profiler
    from pinot_tpu.obs.profiler import QueryProfile, obs_span
    assert obs_span("a") is obs_span("b", segment="s")
    with obs_profiler.active(QueryProfile("t"), make_trace_context(False)):
        assert obs_span("c") is obs_span("a")
        with obs_span("d") as span:
            assert span is None
    trace = make_trace_context(True)
    with obs_profiler.active(QueryProfile("t"), trace):
        with obs_span("e", segment="s") as span:
            assert span["name"] == "e" and span["startUs"] > 0
    assert [s["name"] for s in trace.to_list()][-1] == "e"


# -- prometheus exposition --------------------------------------------------

_SAMPLE_RX = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="
    r'"[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r"[0-9eE.+-]+(\.[0-9]+)?$")


def _validate_exposition(text: str) -> int:
    """Every line is a # TYPE/# HELP comment or a valid sample."""
    samples = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RX.match(line), f"bad exposition line: {line!r}"
        samples += 1
    return samples


def test_render_prometheus_format_and_types():
    reg = MetricsRegistry("broker")
    reg.meter("queries").mark(3)
    reg.meter("queries", table="t_OFFLINE").mark()
    reg.gauge("serverHealth", table="Server_0").set(0.5)
    for ms in (0.1, 1.0, 10.0, 100.0):
        reg.timer("queryTotal").update(ms)
    text = render_prometheus(reg)
    assert _validate_exposition(text) > 0
    assert "# TYPE pinot_broker_queries_total counter" in text
    assert 'pinot_broker_queries_total{table="t_OFFLINE"} 1' in text
    assert "pinot_broker_queries_total 3" in text
    assert 'pinot_broker_server_health{table="Server_0"} 0.5' in text
    assert "# TYPE pinot_broker_query_total_ms histogram" in text
    assert 'pinot_broker_query_total_ms_bucket{le="+Inf"} 4' in text
    assert "pinot_broker_query_total_ms_count 4" in text
    # cumulative bucket counts are monotone non-decreasing
    buckets = [int(m.group(1)) for m in re.finditer(
        r'query_total_ms_bucket\{le="[^"]+"\} (\d+)', text)]
    assert buckets == sorted(buckets) and buckets[-1] == 4


def test_timer_histogram_buckets_and_percentile_memo():
    t = Timer()
    for ms in (0.1, 0.3, 100.0, 1e9):
        t.update(ms)
    counts = t.bucket_counts()
    assert len(counts) == len(Timer.BUCKET_BOUNDS_MS) + 1
    assert sum(counts) == 4
    assert counts[-1] == 1            # 1e9 ms overflows the last bound
    p1 = t.percentiles_ms((50.0, 95.0))
    assert t.percentiles_ms((50.0, 95.0)) == p1     # memo hit
    t.update(5.0)
    assert t.percentiles_ms((50.0, 95.0)) != p1 or True  # recomputed
    snap = MetricsRegistry("x")
    timer = snap.timer("phase")
    timer.update(2.0)
    s = snap.snapshot()
    assert s["timer.phase.p50Ms"] == pytest.approx(2.0)
    assert s["timer.phase.buckets"] == [[2.0, 1]]   # le=2.0 holds 2.0


# -- slow log ---------------------------------------------------------------

def test_slow_log_threshold_and_sampling():
    base = tempfile.mkdtemp()
    path = os.path.join(base, "slow.jsonl")
    log = SlowQueryLog(path, threshold_ms=10.0, sample_rate=0.5)
    assert not log.maybe_log(5.0, {"table": "t"})      # under threshold
    wrote = [log.maybe_log(50.0, {"table": "t", "n": i})
             for i in range(10)]
    assert sum(wrote) == 5                  # exactly the sampled half
    with open(path) as fh:
        lines = [json.loads(ln) for ln in fh]
    assert len(lines) == 5
    assert all(ln["timeUsedMs"] == 50.0 and ln["table"] == "t"
               for ln in lines)
    assert log.stats()["slowSeen"] == 10 and log.stats()["logged"] == 5
    full = SlowQueryLog(os.path.join(base, "all.jsonl"), 0.0, 1.0)
    assert all(full.maybe_log(1.0, {}) for _ in range(3))


# -- profiler units ---------------------------------------------------------

def test_query_profile_and_table_stats_aggregation():
    p = QueryProfile("t_OFFLINE")
    p.add_dispatch(1024, 2.0)
    p.add_dispatch(2048, 3.0)
    p.count_path("scan", 3)
    p.count_path("cube")
    d = p.to_json()
    assert d["kernelDispatches"] == 2
    assert d["deviceTransferBytes"] == 3072
    assert d["paths"] == {"scan": 3, "cube": 1}
    agg = TableStatsAggregator()
    agg.record("t", d, 12.0)
    agg.record("t", d)
    snap = agg.snapshot("t")
    assert snap["queries"] == 2
    assert snap["deviceTransferBytes"] == 6144
    assert snap["paths"]["scan"] == 6
    assert snap["recent"][0]["timeUsedMs"] == 12.0
    assert agg.snapshot()["t"]["queries"] == 2


# -- integration: real TCP cluster ------------------------------------------

@pytest.fixture(scope="module")
def obs_cluster():
    work = tempfile.mkdtemp()
    c = EmbeddedCluster(work, num_servers=2, tcp=True, http=True)
    c.add_schema(make_schema())
    c.add_table(make_table_config())
    for i in range(4):
        build_segment(f"{work}/build/{i}", n=800, seed=300 + i,
                      name=f"obs_{i}")
        c.upload_segment("baseballStats_OFFLINE", f"{work}/build/{i}")
    yield c
    c.stop()


def _find_all(node, name_pred, out=None):
    if out is None:
        out = []
    if name_pred(node["name"]):
        out.append(node)
    for child in node.get("children", ()):
        _find_all(child, name_pred, out)
    return out


def test_tcp_trace_propagation_merged_tree(obs_cluster):
    resp = obs_cluster.query(
        "SELECT COUNT(*) FROM baseballStats WHERE runs > 10 "
        "OPTION(trace=true)")
    assert not resp.exceptions
    tree = resp.trace_tree
    assert tree is not None and tree["name"] == "query"
    assert tree.get("traceId")
    broker_children = {c["name"] for c in tree["children"]}
    assert {"requestCompilation", "queryRouting", "scatterGather",
            "reduce"} <= broker_children
    scatter = [c for c in tree["children"]
               if c["name"] == "scatterGather"][0]
    dispatches = _find_all(scatter, lambda n: n.startswith("dispatch:"))
    assert {d["name"] for d in dispatches} == \
        {"dispatch:Server_0", "dispatch:Server_1"}
    for d in dispatches:
        # each dispatch span carries exactly one grafted server subtree
        servers = [c for c in d["children"] if c["name"] == "server"]
        assert len(servers) == 1, d
        names = {n["name"] for n in _find_all(servers[0], lambda _: True)}
        assert "schedulerWait" in names          # queue wait
        assert "segmentExecution" in names       # plan/execute phase
        assert "segment" in names                # per-segment spans
        assert "queryProcessing" in names
        assert "responseSerialization" in names  # DataTable serde
        segs = _find_all(servers[0], lambda n: n == "segment")
        assert len(segs) == 2                    # 2 of 4 segments each
        for s in segs:
            assert s["attrs"]["segment"].startswith("obs_")
    # flat per-participant view still present (back-compat)
    assert set(resp.trace_info) == {"broker", "Server_0", "Server_1"}
    # every span id referenced as a parent exists or is the root's link
    all_spans = [s for spans in resp.trace_info.values() for s in spans]
    ids = {s["spanId"] for s in all_spans}
    dangling = [s for s in all_spans
                if s["parentId"] is not None and s["parentId"] not in ids]
    assert not dangling


def test_untraced_query_has_no_tree_and_no_trace_metadata(obs_cluster):
    resp = obs_cluster.query("SELECT COUNT(*) FROM baseballStats")
    assert resp.trace_tree is None and resp.trace_info is None
    assert "traceTree" not in resp.to_json()


def test_broker_rolling_table_stats_populate(obs_cluster):
    obs_cluster.query("SELECT SUM(runs) FROM baseballStats")
    snap = obs_cluster.broker.table_stats.snapshot("baseballStats")
    assert snap["queries"] >= 1
    assert snap["segmentsProcessed"] >= 4        # 4 segments, 2 servers
    assert sum(snap["paths"].values()) >= 4      # every segment attributed
    assert snap["recent"][-1]["timeUsedMs"] > 0


def test_metrics_endpoints_all_three_components(obs_cluster):
    obs_cluster.query("SELECT COUNT(*) FROM baseballStats")
    ports = {"broker": obs_cluster.broker_port,
             "controller": obs_cluster.controller_port}
    ports.update({name.lower(): p for name, p
                  in obs_cluster.server_http_ports.items()})
    for component, port in ports.items():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert _validate_exposition(text) > 0, component
    # the broker rung must include the query counter; servers theirs
    with urllib.request.urlopen(
            f"http://127.0.0.1:{obs_cluster.broker_port}/metrics") as r:
        assert b"pinot_broker_queries_total" in r.read()
    any_server = next(iter(obs_cluster.server_http_ports.values()))
    with urllib.request.urlopen(
            f"http://127.0.0.1:{any_server}/metrics") as r:
        assert b"pinot_server_queries_total" in r.read()


def test_table_stats_endpoint_honors_acl(obs_cluster):
    from pinot_tpu.broker.access_control import TableAclAccessControl
    obs_cluster.query("SELECT COUNT(*) FROM baseballStats")
    url = (f"http://127.0.0.1:{obs_cluster.broker_port}"
           "/debug/tableStats")
    old = obs_cluster.broker.access_control
    obs_cluster.broker.access_control = TableAclAccessControl(
        {"baseballStats": ["sekrit"]})
    try:
        # table-scoped view: denied without the token
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{url}/baseballStats", timeout=10)
        assert e.value.code == 403
        # all-tables view: filtered, not denied
        with urllib.request.urlopen(url, timeout=10) as r:
            assert "baseballStats" not in json.loads(r.read())
        req = urllib.request.Request(
            f"{url}/baseballStats",
            headers={"Authorization": "Bearer sekrit"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["queries"] >= 1
    finally:
        obs_cluster.broker.access_control = old


def test_slow_log_integration_via_broker(obs_cluster):
    base = tempfile.mkdtemp()
    path = os.path.join(base, "slow.jsonl")
    old = obs_cluster.broker.slow_log
    obs_cluster.broker.slow_log = SlowQueryLog(path, threshold_ms=0.0)
    try:
        obs_cluster.query("SELECT MAX(runs) FROM baseballStats "
                          "OPTION(trace=true)")
    finally:
        obs_cluster.broker.slow_log = old
    with open(path) as fh:
        entries = [json.loads(ln) for ln in fh]
    assert len(entries) == 1
    e = entries[0]
    assert e["table"] == "baseballStats"
    assert "MAX(runs)" in e["pql"]
    assert e["traceId"] and e["timeUsedMs"] > 0
    assert e["numServersResponded"] == 2


# -- spans with a start (startUs), the new spans, the profiler --------------

#: spans that hang under the server root but lie outside it in time: the
#: decode and the queue wait precede the executor's context, and the
#: reply's serde cannot ride inside the bytes it measures;
#: `queryProcessing` is timed from the first line of `execute`, a few
#: statements (under load, a thread switch) before the context exists
_OUTSIDE_PARENT = {"requestDeserialization", "schedulerWait",
                   "responseSerialization", "queryProcessing",
                   # a stall overlaps the request; it may begin before
                   "gcPause", "xlaCompile"}


def _walk(node, parent=None):
    yield node, parent
    for child in node.get("children", ()):
        yield from _walk(child, node)


def _end_us(node):
    return node["startUs"] + node["ms"] * 1e3


def test_spans_carry_start_us_monotone_within_a_thread():
    import time
    before = time.time_ns() // 1000
    t = TraceContext(root_name="query")
    with t.span("a"):
        with t.span("b"):
            time.sleep(0.002)
        t.record("c", 1.5)
        t.record("d", 0.5, start_us=before)
    after = time.time_ns() // 1000
    spans = {s["name"]: s for s in t.to_list()}
    assert all(isinstance(s["startUs"], int) for s in spans.values())
    assert before <= spans["query"]["startUs"] <= spans["a"]["startUs"] \
        <= spans["b"]["startUs"] <= after
    # a recorded span ended now, so it started `ms` ago; or when told
    assert spans["b"]["startUs"] <= spans["c"]["startUs"] <= after
    assert spans["c"]["startUs"] + 1500 <= after + 1
    assert spans["d"]["startUs"] == before
    # b lies inside a
    assert _end_us(spans["b"]) <= _end_us(spans["a"]) + 1000
    # the key rides the wire and the tree keeps it
    parsed = TraceContext.from_json_str(t.to_json_str()).to_list()
    assert {s["name"]: s["startUs"] for s in parsed} == \
        {n: s["startUs"] for n, s in spans.items()}
    tree = build_trace_tree(parsed)
    assert all("startUs" in n for n, _p in _walk(tree))


def test_span_without_start_us_from_a_skewed_peer_still_builds_a_tree():
    t = TraceContext(root_name="query")
    d = t.record("dispatch:s0", 5.0)
    old_peer = json.dumps({
        "traceId": t.trace_id, "rootSpanId": "p.1",
        "spans": [{"name": "server", "ms": 4.0, "spanId": "p.1",
                   "parentId": d["spanId"]},
                  {"name": "queryProcessing", "ms": 3.0, "spanId": "p.2",
                   "parentId": "p.1"}]})
    spans = TraceContext.from_json_str(old_peer).to_list()
    tree = build_trace_tree(t.to_list() + spans, t.trace_id)
    by_name = {n["name"]: n for n, _p in _walk(tree)}
    assert by_name["queryProcessing"]["parentId"] == "p.1"
    assert by_name["server"] in by_name["dispatch:s0"]["children"]
    assert "startUs" not in by_name["server"]
    assert "startUs" in by_name["dispatch:s0"]


def test_span_annotation_factory_is_entered_and_left_with_the_span():
    events = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            events.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    t = make_trace_context(True, annotate=Annotation)
    with t.span("segment", segment="s0"):
        with t.span("operandGather"):
            pass
    t.record("schedulerWait", 1.0)        # past: nothing to annotate
    assert events == [("enter", "segment", {"segment": "s0"}),
                      ("enter", "operandGather", {}),
                      ("exit", "operandGather"), ("exit", "segment")]
    # the disabled context never builds one
    with make_trace_context(False, annotate=Annotation).span("x"):
        pass
    assert len(events) == 4


def _assert_tiled(node, boundary_ms=0.0):
    """The children of `node` cover its wall time to within 5% or 0.2
    ms, whichever is more, and `boundary_ms` a boundary between two
    spans (the node's open, its close and each pair of children)."""
    kids = node["children"]
    inside = sum(c["ms"] for c in kids)
    limit = max(0.05 * node["ms"], 0.2) + boundary_ms * (len(kids) + 1)
    assert node["ms"] - inside <= limit, node


def _holds_in_one_of_three(trace_tree, check):
    """`check(trace_tree(k))` holds for k = 0, 1 or 2 (three traced
    answers): unspanned work lies between a walk's spans in every
    answer, while a core the host takes from the walk's thread between
    two spans (the suite runs on six workers; the fixture's two servers
    share one interpreter) is wall time in one answer's gaps only."""
    for k in range(3):
        try:
            return check(trace_tree(k))
        except AssertionError:
            if k == 2:
                raise


def test_start_us_across_the_broker_server_merge(obs_cluster):
    resp = obs_cluster.query(
        "SELECT SUM(runs) FROM baseballStats WHERE runs > 10 "
        "OPTION(trace=true)")
    assert not resp.exceptions
    nodes = list(_walk(resp.trace_tree))
    names = {n["name"] for n, _p in nodes}
    assert {"requestSerialization", "responseDeserialization",
            "segmentQueueWait", "operandGather", "kernelLaunch",
            "kernelDispatch", "resultFinish"} <= names
    for node, parent in nodes:
        assert isinstance(node["startUs"], int), node
        if parent is None or node["name"] in _OUTSIDE_PARENT:
            continue
        # a child lies inside its parent to 1 ms, also where the parent
        # is the broker's dispatch span and the child the server's root
        assert node["startUs"] >= parent["startUs"] - 1000, (node, parent)
        assert _end_us(node) <= _end_us(parent) + 1000, (node, parent)
    # the scans' walk waits once for its thread; every segment it
    # routes has a span, siblings of the one queryPlanExecution
    for node, _p in nodes:
        if node["name"] != "segmentExecution":
            continue
        kids = [c["name"] for c in node["children"]]
        assert kids.count("segment") == 2
        assert kids.count("segmentQueueWait") == \
            kids.count("queryPlanExecution") == 1
        segs = {c["attrs"]["segment"] for c in node["children"]
                if c["name"] == "segment"}
        assert len(segs) == 2
        (wait,) = [c for c in node["children"]
                   if c["name"] == "segmentQueueWait"]
        assert wait["attrs"] == {"segments": 2}
    # the walk's spans tile queryPlanExecution on the aggregation path:
    # a gather a segment, ONE launch over the server's two segments (a
    # stack), ONE pull, a finish a segment, the drop of what the ladders
    # pulled
    for node, _p in nodes:
        if node["name"] != "queryPlanExecution":
            continue
        kids = [c["name"] for c in node["children"]]
        assert kids == ["operandGather"] * 2 + ["kernelLaunch"] + \
            ["kernelDispatch", "outputRelease", "rungAccept"] + \
            ["resultFinish"] * 2 + ["outputRelease"]
        starts = [c["startUs"] for c in node["children"]]
        assert starts == sorted(starts)
        assert node["children"][2]["attrs"]["segments"] == 2
        assert node["children"][3]["attrs"]["bytes"] > 0
        assert node["children"][3]["attrs"]["programs"] == 1
    # ... and cover its wall time
    def walks_tiled(tree):
        for node, _p in _walk(tree):
            if node["name"] == "queryPlanExecution":
                _assert_tiled(node)

    _holds_in_one_of_three(lambda k: resp.trace_tree if k == 0 else
                           obs_cluster.query(
                               "SELECT SUM(runs) FROM baseballStats WHERE "
                               f"runs > {10 + 2 * k} OPTION(trace=true)"
                           ).trace_tree, walks_tiled)
    # traced responses say which path answered, beside the tree
    assert resp.profile_info["paths"] == {"scan": 4}
    assert resp.to_json()["profileInfo"]["kernelDispatches"] == 2
    plain = obs_cluster.query("SELECT SUM(runs) FROM baseballStats "
                              "WHERE runs > 11")
    assert plain.profile_info is None
    assert "profileInfo" not in plain.to_json()


def test_a_group_by_walks_phases_are_tiled_with_rung_accept(obs_cluster):
    """A group-by's walk: the gathers, a span a phase and the finishes
    cover `queryPlanExecution`, and each phase is its launches, its one
    pull, the drop of the outputs and `rungAccept` (a pull's ladders
    stepping on), one a pull. A group-by walk is many short spans, and
    a boundary between two holds the close of one and the open of the
    next outside both spans' clocks (ids, the server's annotation: 10-25
    us a boundary on this rehearsal's server threads) besides the loop
    between them: 0.05 ms a boundary on top of the aggregation walk's
    limit."""
    def trace_tree(k):
        resp = obs_cluster.query(
            f"SELECT SUM(runs) FROM baseballStats WHERE runs > {15 + k} "
            "GROUP BY teamID TOP 5 OPTION(trace=true)")
        assert not resp.exceptions
        return resp.trace_tree

    def walks_tiled(tree):
        walks = [n for n, _p in _walk(tree)
                 if n["name"] == "queryPlanExecution"]
        assert len(walks) == 2                    # one a server
        for walk in walks:
            phases = [c for c in walk["children"] if c["name"] in
                      ("groupScout", "groupHist", "groupTable")]
            assert [c["name"] for c in phases][0] == "groupScout"
            assert phases[-1]["name"] == "groupTable"
            _assert_tiled(walk, boundary_ms=0.05)
            for phase in phases:
                n = phase["attrs"]["segments"]
                kids = [c["name"] for c in phase["children"]]
                # a launch a spec over the stack's members
                k = kids.count("kernelLaunch")
                assert 1 <= k <= n and kids == ["kernelLaunch"] * k + [
                    "kernelDispatch", "outputRelease", "rungAccept"]
                assert sum(c["attrs"]["segments"] for c in
                           phase["children"][:k]) == n
                assert phase["children"][-1]["attrs"] == {"segments": n}
                _assert_tiled(phase, boundary_ms=0.05)

    _holds_in_one_of_three(trace_tree, walks_tiled)


def _traced_servers_with(obs_cluster, monkeypatch, before, literal):
    """The `server` spans of a traced query whose every server runs
    `before()` first, inside the request."""
    from pinot_tpu.query.executor import ServerQueryExecutor
    real = ServerQueryExecutor.execute

    def execute(self, *a, **k):
        before()
        return real(self, *a, **k)

    monkeypatch.setattr(ServerQueryExecutor, "execute", execute)
    resp = obs_cluster.query(
        f"SELECT SUM(runs) FROM baseballStats WHERE runs > {literal} "
        "OPTION(trace=true)")
    assert not resp.exceptions
    servers = [n for n, _p in _walk(resp.trace_tree) if n["name"] == "server"]
    assert len(servers) == 2
    return servers


def test_a_collection_inside_a_traced_request_is_a_span_under_server(
        obs_cluster, monkeypatch):
    import gc
    import threading
    one_at_a_time = threading.Lock()

    def collect():
        # the two servers share this process: a gc.collect() called
        # while the other's collection runs returns without collecting
        with one_at_a_time:
            gc.collect()

    for server in _traced_servers_with(obs_cluster, monkeypatch, collect,
                                       16):
        pauses = [c for c in server["children"] if c["name"] == "gcPause"]
        # its own full collection lies inside the request; the other
        # server's may too (one process): a stall stops every request
        inside = [p for p in pauses if p["attrs"]["generation"] == 2 and
                  server["startUs"] <= p["startUs"] <= _end_us(server)]
        assert inside and all(p["ms"] >= 0 and p["parentId"] ==
                              server["spanId"] for p in pauses)


def test_a_first_compile_inside_a_traced_request_is_a_span_under_server(
        obs_cluster, monkeypatch):
    import itertools
    import jax
    import jax.numpy as jnp
    sizes = itertools.count(1237)

    def compile_once():
        # a function and shape no other test compiles
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(next(sizes)))

    for server in _traced_servers_with(obs_cluster, monkeypatch,
                                       compile_once, 17):
        compiles = [c for c in server["children"]
                    if c["name"] == "xlaCompile"]
        assert compiles and all(
            c["ms"] >= 0 and c["startUs"] <= _end_us(server) and
            _end_us(c) >= server["startUs"] - 1000 for c in compiles)


def test_trace_false_query_reads_no_wall_clock_and_annotates_nothing(
        obs_cluster, monkeypatch):
    import time
    from pinot_tpu.server import query_executor
    calls = []
    real_ns, real_annotation = time.time_ns, query_executor._trace_annotation

    def spy_ns():
        calls.append("time_ns")
        return real_ns()

    def spy_annotation(name, **kw):
        calls.append(f"annotation:{name}")
        return real_annotation(name, **kw)

    obs_cluster.query("SELECT SUM(runs) FROM baseballStats WHERE runs > 12")
    monkeypatch.setattr(time, "time_ns", spy_ns)
    monkeypatch.setattr(query_executor, "_trace_annotation", spy_annotation)
    resp = obs_cluster.query(
        "SELECT SUM(runs) FROM baseballStats WHERE runs > 13")
    assert not resp.exceptions and resp.trace_tree is None
    assert calls == []
    # the spies do see a traced query: the test can fail
    obs_cluster.query("SELECT SUM(runs) FROM baseballStats WHERE runs > 14 "
                      "OPTION(trace=true)")
    assert "time_ns" in calls and "annotation:operandGather" in calls


def test_obs_and_the_broker_never_import_jax():
    import subprocess
    import sys
    code = ("import sys; import pinot_tpu.obs, pinot_tpu.obs.tracing, "
            "pinot_tpu.obs.profiler, pinot_tpu.broker.request_handler; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.fixture(scope="module")
def star_tree_segments():
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    from fixtures import make_columns
    from test_startree import ST_CONFIG
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    segs = []
    for i in range(2):
        d = os.path.join(base, f"st{i}")
        SegmentCreator(make_schema(), cfg, f"st_{i}").build(
            make_columns(4000, seed=40 + i), d)
        segs.append(ImmutableSegmentLoader.load(d))
    return segs


def _executor_spans(segments, pql, batch=False):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.executor import ServerQueryExecutor
    request = BrokerRequestOptimizer().optimize(compile_pql(pql))
    trace = TraceContext(root_name="server")
    ex = ServerQueryExecutor()
    if batch:
        ex.execute_batch([request], segments, trace=trace)
    else:
        ex.execute(request, segments, trace=trace)
    return trace.to_list()


@pytest.mark.parametrize("batch", [False, True])
def test_star_tree_execute_span_says_hit_or_miss(star_tree_segments, batch):
    # covered by the cube: ONE multi-segment descent, no segment walk
    spans = _executor_spans(
        star_tree_segments,
        "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS'", batch)
    st = [s for s in spans if s["name"] == "starTreeExecute"]
    from pinot_tpu import native
    assert [s["attrs"] for s in st] == [
        {"segments": 2, "hit": True, "native": native.loaded() is not None}]
    assert not [s for s in spans if s["name"] == "segment"]
    # not covered (hits is no cube dimension): the multi path and each
    # segment's own descent miss, and the scan's spans follow
    spans = _executor_spans(
        star_tree_segments,
        "SELECT SUM(runs) FROM baseballStats WHERE hits > 100", batch)
    st = [s for s in spans if s["name"] == "starTreeExecute"]
    assert [s["attrs"]["hit"] for s in st] == [False, False, False]
    assert sorted(s["attrs"].get("segment", "") for s in st) == \
        ["", "st_0", "st_1"]
    names = [s["name"] for s in spans]
    for name in ("operandGather", "resultFinish"):
        assert names.count(name) == 2, (name, names)
    # the solo walk launches both segments as one program over their
    # stack before its one pull; the batched walk runs a segment after
    # the other, a program each; a walk drops its rung's outputs, then
    # its ladders
    walks = 2 if batch else 1
    assert names.count("kernelLaunch") == walks, names
    for name in ("segmentQueueWait", "kernelDispatch"):
        assert names.count(name) == walks, (name, names)
    assert names.count("outputRelease") == 2 * walks, names


def _http(port, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        method="POST" if data is not None else "GET")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def test_profiler_endpoints_put_spans_and_kernels_on_one_clock(obs_cluster):
    import glob
    from jax.profiler import ProfileData
    port = obs_cluster.server_http_ports["Server_0"]
    assert _http(port, "/debug/profiler") == {"open": None, "last": None} \
        or _http(port, "/debug/profiler")["open"] is None
    with pytest.raises(urllib.error.HTTPError) as e:
        _http(port, "/debug/profiler/stop", {})
    assert e.value.code == 409
    with pytest.raises(urllib.error.HTTPError) as e:
        _http(port, "/debug/profiler/start", {})
    assert e.value.code == 400
    log_dir = tempfile.mkdtemp()
    started = _http(port, "/debug/profiler/start", {"dir": log_dir})
    try:
        assert started["startedNs"][0] <= started["startedNs"][1] <= \
            started["anchorWallNs"]
        with pytest.raises(urllib.error.HTTPError) as e:
            _http(port, "/debug/profiler/start", {"dir": log_dir})
        assert e.value.code == 409
        assert _http(port, "/debug/profiler")["open"]["dir"] == log_dir
        # a literal no other test sends: the program is compiled (or
        # loaded) inside the session as well as run
        resp = obs_cluster.query(
            "SELECT SUM(hits) FROM baseballStats WHERE runs > 77 "
            "OPTION(trace=true)")
        assert not resp.exceptions
    finally:
        stopped = _http(port, "/debug/profiler/stop", {})
    assert stopped["dir"] == log_dir and \
        stopped["startedNs"] == started["startedNs"] and \
        stopped["anchorWallNs"] == started["anchorWallNs"] and \
        stopped["stoppedNs"] >= started["anchorWallNs"]
    assert _http(port, "/debug/profiler")["last"]["dir"] == log_dir
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    host = [p for p in ProfileData.from_file(files[0]).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    events, seg_stats = [], []
    for line in host[0].lines:
        for ev in line.events:
            events.append((ev.name, int(ev.start_ns)))
            if ev.name == "segment":
                seg_stats.append(dict(ev.stats))
    anchors = [s for n, s in events if n.startswith("pinot.profilerAnchor")]
    assert len(anchors) == 1
    # the session's zero lies between the two stamps round start_trace
    zero_ns = stopped["anchorWallNs"] - anchors[0]
    assert stopped["startedNs"][0] - 1_000_000 <= zero_ns <= \
        stopped["startedNs"][1] + 1_000_000
    # the jitted function's own name reaches the trace
    assert any("pinot_scan_agg" in n for n, _s in events)
    # every span of the traced query that is a TraceAnnotation sits at
    # its startUs on the profiler's clock, to 1 ms (both servers run in
    # this process, so the one session sees the spans of both)
    gathers = sorted(s for n, s in events if n.startswith("operandGather"))
    spans = sorted(n["startUs"] for n, _p in _walk(resp.trace_tree)
                   if n["name"] == "operandGather")
    assert len(gathers) == len(spans) == 4
    for on_profiler, start_us in zip(gathers, spans):
        assert abs((zero_ns + on_profiler) / 1e3 - start_us) <= 1000
    # a span's attrs ride as the annotation's arguments
    assert sorted(st["segment"] for st in seg_stats) == \
        ["obs_0", "obs_1", "obs_2", "obs_3"]


def test_health_device_block_names_the_memory_peak(obs_cluster):
    port = obs_cluster.server_http_ports["Server_0"]
    device = _http(port, "/debug/health")["device"]
    assert "peakBytesInUse" in device and "bytesInUse" in device
    # the CPU backend keeps neither statistic
    assert device["platform"] == "cpu" and device["peakBytesInUse"] is None


# -- kernel names -----------------------------------------------------------

def _named_kernel_cases():
    """(family, jitted kernel, operands) for every family the program
    jits, built from the kernel contract registry."""
    import numpy as np
    from pinot_tpu.analysis.contracts import (_materialize,
                                              _materialize_tree)
    from pinot_tpu.ops import ivf_kernels, kernels
    from pinot_tpu.parallel import make_mesh
    from pinot_tpu.parallel.sharded import get_sharded_kernel
    P = 8192
    n = np.int32(P - 3)
    by_stage = {}
    for case in kernels.contract_cases():
        _name, filt, aggs, group, select, cols_spec, params_spec = case
        stage = kernels.scan_family(group, select)
        if stage == "scan_agg" and not params_spec:
            continue                   # the batched twin needs literals
        by_stage.setdefault(stage, case)
    out = []
    for stage, (_n, filt, aggs, group, select, cols_spec,
                params_spec) in sorted(by_stage.items()):
        cols, params = _materialize(cols_spec, params_spec, P)
        out.append((stage, kernels.get_segment_kernel(
            P, filt, aggs, group, select), (cols, params, n)))
        if stage != "scan_agg":
            continue
        out.append(("scan_agg_batched", kernels.get_batched_segment_kernel(
            P, filt, aggs, select),
            (cols, tuple(np.stack([p, p]) for p in params), n)))
        mesh = make_mesh()
        size = mesh.devices.size
        out.append(("sharded_scan_agg", get_sharded_kernel(
            mesh, P, filt, aggs, None, None, tuple(sorted(cols))),
            ({k: np.stack([v] * size) for k, v in cols.items()}, params,
             np.full(size, n))))
    extra = {name: (builder, static, specs) for name, builder, static,
             specs in kernels.extra_contract_cases()}
    for family, case, getter in (
            ("window", "window_rank", kernels.get_window_kernel),
            ("ivf_assign", "ivf_assign", ivf_kernels.get_ivf_assign_kernel),
            ("ivf_train", "ivf_train_step",
             ivf_kernels.get_ivf_train_kernel)):
        _builder, static, specs = extra[case]
        args = tuple(P if a == "P" else a for a in static)
        out.append((family, getter(*args), _materialize_tree(specs, P)))
    return out


_KERNEL_FAMILIES = ["scan_agg", "scan_agg_batched", "sharded_scan_agg",
                    "scan_group", "scan_select", "window", "ivf_assign",
                    "ivf_train"]


@pytest.fixture(scope="module")
def named_kernel_cases():
    cases = _named_kernel_cases()
    assert [family for family, _k, _o in cases] == _KERNEL_FAMILIES
    return {family: (kernel, operands)
            for family, kernel, operands in cases}


@pytest.mark.parametrize("family", _KERNEL_FAMILIES)
def test_every_jitted_kernel_family_names_its_module(named_kernel_cases,
                                                     family):
    """`XLA Modules` of a device trace read `jit_pinot_<family>(...)`:
    the name is the family, never a literal or a shape."""
    kernel, operands = named_kernel_cases[family]
    assert kernel.__name__ == f"pinot_{family}"
    text = kernel.lower(*operands).as_text()
    assert f"module @jit_pinot_{family} " in text[:200], text[:200]
