"""The lanes a device SUM or AVG reads, under meters and span attributes
(PR 37).

`query/plan.py` `_agg_device_spec` gives every aggregation a strategy:
integer part lanes behind a dictionary (`parts`, `psums`), a raw lane,
a decoded value lane of a dictionary (`vlane`, `csums`), or a dictId
histogram (`hist`, `vals`). `obs/profiler.py` `mark_sum_lanes` marks one
of `sumLanesParts`, `sumLanesRaw`, `sumLanesValue`, `sumLanesHist` a SUM
or AVG a segment the device answered, and a traced `kernelLaunch` or
`groupTable` says how many part lanes and value lanes it carried. Held
here to what ran: SSB's 13 shapes over small dbgen segments built as
the benchmark builds them, with every column behind a dictionary
(`ssb_flat_nocube_dict`), with `lo_revenue` raw (`ssb_flat_nocube`) and
with cubes (`ssb_flat_startree`), on the CPU. Nothing here is a
measurement.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from pinot_tpu.common.metrics import (MetricsRegistry,      # noqa: E402
                                      ServerMeter, ServerQueryPhase)
from pinot_tpu.obs import profiler as obs_profiler          # noqa: E402
from pinot_tpu.obs.tracing import TraceContext, build_trace_tree  # noqa: E402

ROWS, SEGMENTS, SEED = 60_000, 2, 5
KINDS = tuple(ServerMeter.SUM_LANES)            # parts, raw, value, hist
SCANS = ["q1.1", "q1.2", "q1.3"]
GROUP_BYS = ["q2.1", "q2.2", "q2.3", "q3.1", "q3.2", "q3.3", "q3.4",
             "q4.1", "q4.2", "q4.3"]
# (configuration, summed column) -> the meter its SUM marks
EXPECTED_KIND = {("ssb_flat_nocube_dict", "lo_revenue"): "parts",
                 ("ssb_flat_nocube_dict", "lo_supplycost"): "parts",
                 ("ssb_flat_nocube", "lo_revenue"): "raw",
                 ("ssb_flat_nocube", "lo_supplycost"): "parts"}
# one-byte slices of a summed integer column (`int_part_info`)
PART_LANES = {"lo_revenue": 4, "lo_supplycost": 3}


def _walk(node, parent=None):
    yield node, parent
    for child in node.get("children") or ():
        yield from _walk(child, node)


class Rig:
    """A registry of this file's own with the sum lanes' meters bound,
    and small SSB segments of each configuration, built once."""

    def __init__(self, base: str):
        self.base = base
        self.metrics = MetricsRegistry("server")
        obs_profiler.bind_sum_lane_metrics(self.metrics)
        self._segments = {}
        from harness import shapes, tables
        table = tables.make_table(tables.load_generator("ssb_dbgen"),
                                  ROWS, SEGMENTS, SEED)
        self.shapes = {s.name: s for s in shapes.load_family(
            BENCH_DIR, "ssb", table.pools)}

    def segments(self, config_name: str):
        if config_name not in self._segments:
            from harness import build, cells, tables
            from pinot_tpu.segment.loader import ImmutableSegmentLoader
            config = dict(cells.load_json(BENCH_DIR, "configs",
                                          f"{config_name}.json"),
                          rows=ROWS, segments=SEGMENTS)
            out = os.path.join(self.base, config_name)
            os.makedirs(out)
            self._segments[config_name] = [
                ImmutableSegmentLoader.load(build.build_segment(
                    (config, SEED, i, hi - lo, out)))
                for i, (lo, hi) in enumerate(
                    tables.segment_bounds(ROWS, SEGMENTS))]
        return self._segments[config_name]

    def meters(self) -> dict:
        return {k: self.metrics.meter(ServerMeter.SUM_LANES[k]).count
                for k in KINDS}

    def run(self, segments, pql: str, traced: bool = True, **executor_kw):
        """-> (the reduced answer as JSON, the query's profile, the
        trace's spans, what the four meters grew by)."""
        from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
        from pinot_tpu.pql.parser import compile_pql
        from pinot_tpu.query.executor import ServerQueryExecutor
        from pinot_tpu.query.plan import preprocess_request
        from pinot_tpu.query.reduce import BrokerReduceService
        request = preprocess_request(
            segments, BrokerRequestOptimizer().optimize(compile_pql(pql)))
        profile = obs_profiler.QueryProfile(request.table_name)
        trace = TraceContext(root_name="server") if traced else None
        before = self.meters()
        with obs_profiler.active(profile, None):
            block = ServerQueryExecutor(**executor_kw).execute(
                request, segments, trace=trace)
        grown = {k: n - before[k] for k, n in self.meters().items()}
        answer = BrokerReduceService().reduce(request, [block]).to_json()
        for key in ("timeUsedMs", "traceInfo"):
            answer.pop(key, None)
        return (answer, profile.to_json(),
                trace.to_list() if traced else [], grown)

    def shape(self, config_name: str, name: str, traced: bool = True):
        s = self.shapes[name]
        return self.run(self.segments(config_name), s.pql(s.spec["ssb"]),
                        traced)


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    return Rig(str(tmp_path_factory.mktemp("sum_lane_segments")))


@pytest.fixture(scope="module")
def baseball(tmp_path_factory):
    """A segment with a DOUBLE metric behind a dictionary (`average`)
    and a FLOAT one without (`salary`)."""
    from fixtures import build_segment
    seg, _cols = build_segment(
        str(tmp_path_factory.mktemp("sum_lane_baseball")), n=20_000)
    return [seg]


def spans_named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_the_sum_lanes_meters_read_zero_at_boot():
    from pinot_tpu.server.instance import ServerInstance
    server = ServerInstance("server_sum_lanes")
    try:
        snap = server.metrics.snapshot()
        assert set(ServerMeter.SUM_LANES.values()) == {
            "sumLanesParts", "sumLanesRaw", "sumLanesValue", "sumLanesHist"}
        for meter in ServerMeter.SUM_LANES.values():
            assert snap[f"meter.{meter}.count"] == 0, meter
    finally:
        server.stop()


@pytest.mark.parametrize("config_name", ["ssb_flat_nocube_dict",
                                         "ssb_flat_nocube"])
@pytest.mark.parametrize("name", SCANS + GROUP_BYS)
def test_a_shape_marks_one_meter_a_sum_a_segment_the_device_answered(
        rig, config_name, name):
    """A Q1.x scan sums in its one launch a segment; a group-by sums in
    its table, which a segment whose filter matched nothing never runs.
    The marks add up to the device's SUM aggregations, each on the meter
    of the strategy its plan took; the launch's attributes say what it
    carried."""
    from pinot_tpu.query.plan import InstancePlanMaker, preprocess_request
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    shape = rig.shapes[name]
    aggregates = shape.spec["aggregates"]
    _answer, profile, spans, grown = rig.shape(config_name, name)
    assert profile["paths"] == {"scan": SEGMENTS}
    tables = spans_named(spans, ServerQueryPhase.GROUP_TABLE)
    # a table's phase is one span over the segments that ran one
    summed = sum(t["attrs"]["segments"] for t in tables) \
        if shape.spec["group_by"] else SEGMENTS
    expected = dict.fromkeys(KINDS, 0)
    for col in aggregates:
        expected[EXPECTED_KIND[config_name, col]] += summed
    assert grown == expected
    assert sum(grown.values()) == len(aggregates) * summed
    # every plan's SUM takes the strategy the meter says
    segments = rig.segments(config_name)
    request = preprocess_request(segments, BrokerRequestOptimizer().optimize(
        compile_pql(shape.pql(shape.spec["ssb"]))))
    plan = InstancePlanMaker().make_segment_plan(segments[0], request)
    specs = plan.group_spec[3] if plan.group_spec else plan.agg_specs
    assert [obs_profiler.sum_lane_kind(s) for s in specs] == [
        EXPECTED_KIND[config_name, col] for col in aggregates]
    # what a launch carried: the slices of its integer sums, its raw lanes
    carried = {
        "partLanes": sum(PART_LANES[c] for c in aggregates
                         if EXPECTED_KIND[config_name, c] == "parts"),
        "valueLanes": sum(EXPECTED_KIND[config_name, c] == "raw"
                          for c in aggregates)}
    tree = build_trace_tree(spans)
    launches = [(n, p) for n, p in _walk(tree)
                if n["name"] == ServerQueryPhase.KERNEL_LAUNCH]
    assert launches
    for node, parent in launches:
        sums_here = parent["name"] in (ServerQueryPhase.GROUP_TABLE,
                                       ServerQueryPhase.QUERY_PLAN_EXECUTION)
        assert node["attrs"] == (carried if sums_here else
                                 {"partLanes": 0, "valueLanes": 0}), \
            parent["name"]
    for table in tables:
        assert {k: table["attrs"][k] for k in carried} == carried
        assert {"layout", "g", "runs", "scouted"} < set(table["attrs"])


def test_a_q4_shaped_group_by_marks_two_a_segment(rig):
    for name in ("q4.1", "q4.2", "q4.3"):
        _a, _p, spans, grown = rig.shape("ssb_flat_nocube_dict", name)
        tables = sum(t["attrs"]["segments"] for t in spans_named(
            spans, ServerQueryPhase.GROUP_TABLE))
        assert tables > 0
        assert grown == {"parts": 2 * tables, "raw": 0, "value": 0,
                         "hist": 0}


@pytest.mark.parametrize("pql,kind,marks", [
    # a DOUBLE dictionary of 1000 values: the MXU histogram and a float64
    # dot on the host; in a group table the decoded value lane
    ("SELECT SUM(average) FROM baseballStats WHERE runs > 10", "hist", 1),
    ("SELECT AVG(average), SUM(average) FROM baseballStats", "hist", 2),
    ("SELECT SUM(average) FROM baseballStats GROUP BY league", "value", 1),
    # FLOAT without dictionary, INT and LONG behind one
    ("SELECT SUM(salary), AVG(salary) FROM baseballStats", "raw", 2),
    ("SELECT SUM(salary) FROM baseballStats GROUP BY teamID", "raw", 1),
    ("SELECT SUM(runs), AVG(hits) FROM baseballStats GROUP BY league",
     "parts", 2),
    # what is no SUM or AVG marks nothing
    ("SELECT COUNT(*), MAX(runs), MIN(salary) FROM baseballStats", None, 0),
])
def test_other_value_kinds_mark_the_meter_of_their_strategy(
        rig, baseball, pql, kind, marks):
    _answer, profile, _spans, grown = rig.run(baseball, pql)
    assert profile["paths"] == {"scan": 1}
    assert grown == dict(dict.fromkeys(KINDS, 0), **({kind: marks}
                                                     if kind else {}))


def test_what_the_device_did_not_sum_marks_nothing(rig):
    none = dict.fromkeys(KINDS, 0)
    shape = rig.shapes["q2.1"]
    pql = shape.pql(shape.spec["ssb"])
    # a cube answers
    _a, profile, _s, grown = rig.run(rig.segments("ssb_flat_startree"), pql)
    assert profile["paths"] == {"cube": SEGMENTS} and grown == none
    # the host path answers
    segments = rig.segments("ssb_flat_nocube_dict")
    _a, profile, _s, grown = rig.run(segments, pql, use_device=False)
    assert profile["paths"] == {"host": SEGMENTS} and grown == none
    # a group-by whose filter matches nothing: the scout runs, no table
    empty = ("SELECT SUM(lo_revenue) FROM lineorder WHERE lo_quantity < 2 "
             "AND lo_discount > 9 AND d_year = 1992 AND c_city = "
             "'UNITED KI1' AND s_city = 'UNITED KI5' GROUP BY d_year")
    answer, profile, spans, grown = rig.run(segments, empty)
    assert profile["paths"] == {"scan": SEGMENTS}
    (scout,) = spans_named(spans, ServerQueryPhase.GROUP_SCOUT)
    assert scout["attrs"] == {"segments": SEGMENTS}
    assert not spans_named(spans, ServerQueryPhase.GROUP_TABLE)
    assert not answer["aggregationResults"][0]["groupByResult"]
    assert grown == none
    # a scan whose filter matches nothing still ran its sum
    _a, _p, _s, grown = rig.run(
        segments, "SELECT SUM(lo_revenue) FROM lineorder WHERE "
        "lo_quantity < 2 AND lo_discount > 9 AND c_city = 'UNITED KI1' "
        "AND s_city = 'UNITED KI5'")
    assert grown == dict(none, parts=SEGMENTS)
    # a literal no dictionary holds folds the plan away: nothing launched
    _a, profile, spans, grown = rig.run(
        segments, "SELECT SUM(lo_revenue) FROM lineorder WHERE d_year = 1800")
    assert not spans_named(spans, ServerQueryPhase.KERNEL_LAUNCH)
    assert grown == none


@pytest.mark.parametrize("name", ["q1.1", "q3.1", "q4.2"])
def test_tracing_changes_no_answer_dispatch_byte_or_mark(rig, name):
    on = rig.shape("ssb_flat_nocube_dict", name, traced=True)
    off = rig.shape("ssb_flat_nocube_dict", name, traced=False)
    assert on[0] == off[0]
    for key in ("kernelDispatches", "deviceTransferBytes", "paths"):
        assert on[1][key] == off[1][key], key
    assert on[3] == off[3] and sum(on[3].values()) > 0
    assert off[2] == []


def test_a_batched_scan_marks_each_member(rig):
    """N same-shape scans over one segment share one dispatch; each
    member's sum is an answer of the device."""
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.plan import preprocess_request
    segments = rig.segments("ssb_flat_nocube_dict")
    shape = rig.shapes["q1.1"]
    requests = [preprocess_request(
        segments, BrokerRequestOptimizer().optimize(compile_pql(
            shape.pql(shape.literals(i))))) for i in (0, 1, 2)]
    before = rig.meters()
    blocks = ServerQueryExecutor().execute_batch(requests, segments)
    assert len(blocks) == 3
    grown = {k: n - before[k] for k, n in rig.meters().items()}
    assert grown == {"parts": 3 * SEGMENTS, "raw": 0, "value": 0, "hist": 0}
