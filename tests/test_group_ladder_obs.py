"""The device group-by ladder under spans and meters (PR 35), walked
in phases (PR 38).

`query/plan.py` `walk_ladders` drives a query's `SegmentLadder`s: a
scout, where it pays a histogram rung, and a group table with its kmax
re-runs; each phase is ONE span for all the query's segments
(`groupScout`, `groupHist`, `groupTable`) around their launches and the
phase's one pull, and every ladder marks the ladder's meters once
(`obs/profiler.py` `mark_group_ladder`). The segments of a stack whose
next programs share a spec are one launch of one program: a phase's
launches are programs, its ladders' meters still segments. Held here to what ran: SSB's
13 shapes over small dbgen segments without cubes, built as the
benchmark builds them, on the CPU. Nothing here is a measurement.
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
                                      ServerMeter, ServerQueryPhase,
                                      ServerTimer)
from pinot_tpu.obs import profiler as obs_profiler          # noqa: E402
from pinot_tpu.obs.tracing import TraceContext, build_trace_tree  # noqa: E402

ROWS, SEGMENTS, SEED = 60_000, 2, 5
GROUP_SPANS = (ServerQueryPhase.GROUP_SCOUT, ServerQueryPhase.GROUP_HIST,
               ServerQueryPhase.GROUP_TABLE)
DISPATCH_METERS = (ServerMeter.GROUP_SCOUT_DISPATCHES,
                   ServerMeter.GROUP_HIST_DISPATCHES,
                   ServerMeter.GROUP_TABLE_DISPATCHES)
WALK_METERS = (ServerMeter.SCAN_WALK_SEGMENTS, ServerMeter.SCAN_POOL_SEGMENTS,
               ServerMeter.DEVICE_PROGRAMS, ServerMeter.DEVICE_PULLS,
               ServerMeter.WALK_STACKED_SEGMENTS,
               ServerMeter.WALK_STACKED_PROGRAMS)
LADDER_METERS = (ServerMeter.GROUP_SEGMENTS, *DISPATCH_METERS,
                 ServerMeter.GROUP_ESCALATIONS, ServerMeter.GROUP_EMPTY,
                 *ServerMeter.GROUP_TABLES.values(), *WALK_METERS)


def _walk(node, parent=None):
    yield node, parent
    for child in node.get("children") or ():
        yield from _walk(child, node)


class Ladder:
    """Small SSB segments without cubes, an executor over them, and a
    registry of this test's own with the ladder's meters bound."""

    def __init__(self, base: str, rows: int = ROWS,
                 segments: int = SEGMENTS):
        from harness import build, cells, shapes, tables
        from pinot_tpu.segment.loader import ImmutableSegmentLoader
        config = dict(cells.load_json(BENCH_DIR, "configs",
                                      "ssb_flat_nocube.json"),
                      rows=rows, segments=segments)
        self.segments = [
            ImmutableSegmentLoader.load(build.build_segment(
                (config, SEED, i, hi - lo, base)))
            for i, (lo, hi) in enumerate(
                tables.segment_bounds(rows, segments))]
        self.table = tables.make_table(tables.load_generator("ssb_dbgen"),
                                       rows, segments, SEED)
        self.pools = self.table.pools
        self.shapes = {s.name: s for s in shapes.load_family(
            BENCH_DIR, "ssb", self.pools)}
        self.metrics = MetricsRegistry("server")
        obs_profiler.bind_server_metrics(self.metrics)

    def meters(self) -> dict:
        return {m: self.metrics.meter(m).count for m in LADDER_METERS}

    def run(self, pql: str, traced: bool, **executor_kw):
        """-> (the reduced answer as JSON, the query's profile, the
        trace's spans, what the ladder's meters grew by)."""
        from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
        from pinot_tpu.pql.parser import compile_pql
        from pinot_tpu.query.executor import ServerQueryExecutor
        from pinot_tpu.query.plan import preprocess_request
        from pinot_tpu.query.reduce import BrokerReduceService
        request = preprocess_request(
            self.segments,
            BrokerRequestOptimizer().optimize(compile_pql(pql)))
        profile = obs_profiler.QueryProfile("lineorder")
        trace = TraceContext(root_name="server") if traced else None
        before = self.meters()
        with obs_profiler.active(profile, None):
            block = ServerQueryExecutor(**executor_kw).execute(
                request, self.segments, trace=trace)
        grown = {m: n - before[m] for m, n in self.meters().items()}
        answer = BrokerReduceService().reduce(request, [block]).to_json()
        for key in ("timeUsedMs", "traceInfo"):
            answer.pop(key, None)
        return (answer, profile.to_json(),
                trace.to_list() if traced else [], grown)

    def shape(self, name: str, traced: bool = True):
        s = self.shapes[name]
        return self.run(s.pql(s.spec["ssb"]), traced)


@pytest.fixture(scope="module")
def ladder(tmp_path_factory):
    return Ladder(str(tmp_path_factory.mktemp("ladder_segments")))


GROUP_BYS = ["q2.1", "q2.2", "q2.3", "q3.1", "q3.2", "q3.3", "q3.4",
             "q4.1", "q4.2", "q4.3"]


@pytest.fixture(scope="module")
def traced_runs(ladder):
    """Every SSB shape once, traced: {shape: (answer, profile, spans,
    grown meters)}."""
    return {name: ladder.shape(name)
            for name in ["q1.1", "q1.2", "q1.3"] + GROUP_BYS}


def _phases(spans):
    """(the walk's `queryPlanExecution` node, its group phase spans)."""
    tree = build_trace_tree(spans)
    (plan,) = [n for n, _p in _walk(tree)
               if n["name"] == ServerQueryPhase.QUERY_PLAN_EXECUTION]
    return plan, [c for c in plan["children"] if c["name"] in GROUP_SPANS]


@pytest.mark.parametrize("shape", GROUP_BYS)
def test_a_traced_group_by_carries_the_ladders_spans(traced_runs, shape):
    _answer, profile, spans, grown = traced_runs[shape]
    # ONE walk a query: the phases nest under its queryPlanExecution
    plan, phases = _phases(spans)
    assert plan["attrs"] == {"segments": SEGMENTS}
    names = [c["name"] for c in phases]
    # a scout always; a table unless every filter matched nothing; the
    # histogram rung's span exactly where the rung ran
    assert names[0] == ServerQueryPhase.GROUP_SCOUT
    assert names.count(ServerQueryPhase.GROUP_SCOUT) == 1
    assert names.count(ServerQueryPhase.GROUP_TABLE) <= 1
    assert names.count(ServerQueryPhase.GROUP_HIST) == \
        bool(grown[ServerMeter.GROUP_HIST_DISPATCHES])
    launches = 0
    for phase in phases:
        inside = [c["name"] for c in phase["children"]]
        n = phase["attrs"]["segments"]
        runs = max(phase["attrs"].get("runs", [1]))
        # every launch of the phase (a program over the segments of one
        # spec), then its one pull (and the drop of the device outputs);
        # a re-run is a launch and a pull more
        k = inside.index(ServerQueryPhase.KERNEL_DISPATCH)
        assert 1 <= k <= n
        assert inside[:k + 2] == [ServerQueryPhase.KERNEL_LAUNCH] * k + \
            [ServerQueryPhase.KERNEL_DISPATCH,
             ServerQueryPhase.OUTPUT_RELEASE]
        assert sum(c["attrs"]["segments"]
                   for c in phase["children"][:k]) == n
        assert inside.count(ServerQueryPhase.KERNEL_DISPATCH) == runs
        launches += inside.count(ServerQueryPhase.KERNEL_LAUNCH)
        pull = phase["children"][k]
        assert pull["attrs"]["programs"] == k and pull["attrs"]["bytes"] > 0
    assert launches == profile["kernelDispatches"]
    # no launch of a group-by outside its phase's span
    assert ServerQueryPhase.KERNEL_LAUNCH not in \
        [c["name"] for c in plan["children"]]
    assert sum(c["ms"] for c in phases) <= plan["ms"]
    for table in phases:
        if table["name"] != ServerQueryPhase.GROUP_TABLE:
            continue
        attrs = table["attrs"]
        assert len(attrs["layout"]) == len(attrs["g"]) == \
            len(attrs["runs"]) == attrs["segments"]
        assert set(attrs["layout"]) <= set(ServerMeter.GROUP_TABLES)
        assert attrs["scouted"] is True and min(attrs["runs"]) >= 1
        assert all(g >= 1 and g & (g - 1) == 0 for g in attrs["g"])


@pytest.mark.parametrize("shape", ["q1.1"] + GROUP_BYS)
def test_a_phase_is_one_pull_over_all_its_programs(traced_runs, shape):
    """`devicePulls` counts the phases a query stopped for, and
    `devicePrograms` what they brought home: the profile's
    `kernelDispatches`, which still counts PROGRAMS."""
    _answer, profile, spans, grown = traced_runs[shape]
    _plan, phases = _phases(spans)
    re_runs = grown[ServerMeter.GROUP_ESCALATIONS]
    assert grown[ServerMeter.DEVICE_PULLS] == \
        (len(phases) + bool(re_runs) if shape != "q1.1" else 1)
    assert grown[ServerMeter.DEVICE_PROGRAMS] == \
        profile["kernelDispatches"]
    assert grown[ServerMeter.DEVICE_PULLS] == sum(
        1 for s in spans if s["name"] == ServerQueryPhase.KERNEL_DISPATCH)
    assert grown[ServerMeter.SCAN_WALK_SEGMENTS] == SEGMENTS
    assert grown[ServerMeter.SCAN_POOL_SEGMENTS] == 0


@pytest.mark.parametrize("shape", ["q1.1", "q2.2", "q3.1"])
def test_a_walk_untraced_updates_its_host_timers_once(ladder, shape):
    """`walkHostCpu` and `walkOffCpu` take one update a walk (the
    query's eight segments walked by one thread), with tracing off."""
    timers = [ladder.metrics.timer(name) for name in
              (ServerTimer.WALK_HOST_CPU, ServerTimer.WALK_OFF_CPU)]
    before = [(t.count, t.total_ms) for t in timers]
    ladder.shape(shape, traced=False)
    (cpu_n, cpu_ms), (off_n, off_ms) = [
        (t.count - n, t.total_ms - ms) for t, (n, ms) in zip(timers, before)]
    assert cpu_n == off_n == 1
    # work is CPU time outside the pulls; off-CPU time is whatever is
    # left of the wall time outside them (a clock's grain below 0)
    assert cpu_ms > 0 and off_ms > -0.5


def test_the_histogram_rung_runs_for_q31_and_not_for_q21(traced_runs):
    # q3.1's five nations of a region lie scattered in the sorted
    # dictionary of 25; q2.1's brands of one category lie side by side
    assert traced_runs["q3.1"][3][ServerMeter.GROUP_HIST_DISPATCHES] == \
        SEGMENTS
    assert traced_runs["q2.1"][3][ServerMeter.GROUP_HIST_DISPATCHES] == 0


def test_meters_add_up_to_what_the_profiles_counted(traced_runs):
    total = {m: 0 for m in LADDER_METERS}
    dispatches = 0
    for name in GROUP_BYS:
        _answer, profile, spans, grown = traced_runs[name]
        assert profile["paths"] == {"scan": SEGMENTS}
        # every rung a segment took is a phase of the ladder, run as a
        # member of one of the query's stacked programs
        assert sum(grown[m] for m in DISPATCH_METERS) == \
            grown[ServerMeter.WALK_STACKED_SEGMENTS]
        assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
            profile["kernelDispatches"]
        # one table at most a segment, in one layout
        tables = sum(grown[m] for m in ServerMeter.GROUP_TABLES.values())
        assert tables + grown[ServerMeter.GROUP_EMPTY] == SEGMENTS
        assert grown[ServerMeter.GROUP_TABLE_DISPATCHES] == \
            tables + grown[ServerMeter.GROUP_ESCALATIONS]
        assert tables == sum(s["attrs"]["segments"] for s in spans
                             if s["name"] == ServerQueryPhase.GROUP_TABLE)
        dispatches += grown[ServerMeter.WALK_STACKED_SEGMENTS]
        for m in LADDER_METERS:
            total[m] += grown[m]
    assert total[ServerMeter.GROUP_SEGMENTS] == len(GROUP_BYS) * SEGMENTS
    assert total[ServerMeter.GROUP_SCOUT_DISPATCHES] == \
        len(GROUP_BYS) * SEGMENTS
    assert sum(total[m] for m in DISPATCH_METERS) == dispatches


@pytest.mark.parametrize("shape", ["q1.1", "q1.2", "q1.3"])
def test_a_scan_without_group_by_marks_nothing(traced_runs, shape):
    _answer, profile, spans, grown = traced_runs[shape]
    assert profile["kernelDispatches"] == 1
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == SEGMENTS
    assert not any(n for m, n in grown.items() if m not in WALK_METERS)
    assert not [s for s in spans if s["name"] in GROUP_SPANS]


def test_a_filter_that_matches_nothing_marks_empty_and_runs_no_table(ladder):
    # every value is in the dictionaries, so each predicate alone
    # matches rows; no customer of the first city lives in the last
    # nation, which only the device can tell
    city, nation = ladder.pools["c_city"][0], ladder.pools["c_nation"][-1]
    assert not str(city).startswith(str(nation)[:9])
    pql = (f"SELECT SUM(lo_revenue) FROM lineorder WHERE c_city = '{city}' "
           f"AND c_nation = '{nation}' GROUP BY d_year TOP 100")
    answer, profile, spans, grown = ladder.run(pql, traced=True)
    assert answer["aggregationResults"][0]["groupByResult"] == []
    assert grown[ServerMeter.GROUP_SEGMENTS] == SEGMENTS
    assert grown[ServerMeter.GROUP_EMPTY] == SEGMENTS
    assert grown[ServerMeter.GROUP_SCOUT_DISPATCHES] == SEGMENTS
    assert grown[ServerMeter.GROUP_TABLE_DISPATCHES] == 0
    assert not any(grown[m] for m in ServerMeter.GROUP_TABLES.values())
    assert profile["kernelDispatches"] == 1
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == SEGMENTS
    assert not [s for s in spans
                if s["name"] == ServerQueryPhase.GROUP_TABLE]


@pytest.mark.parametrize("shape", ["q1.2", "q2.1", "q3.1", "q3.4", "q4.3"])
def test_tracing_changes_neither_the_answer_nor_what_is_launched(
        ladder, traced_runs, shape):
    answer, profile, _spans, grown = traced_runs[shape]
    plain, plain_profile, no_spans, plain_grown = ladder.shape(
        shape, traced=False)
    assert no_spans == []
    assert plain == answer
    assert plain_profile["kernelDispatches"] == profile["kernelDispatches"]
    assert plain_profile["deviceTransferBytes"] == \
        profile["deviceTransferBytes"]
    assert plain_grown == grown


def test_an_unscouted_group_by_is_a_table_that_says_so(ladder):
    # no filter: nothing for a scout to narrow, the table runs alone
    answer, profile, spans, grown = ladder.run(
        "SELECT COUNT(*) FROM lineorder GROUP BY d_year TOP 10", traced=True)
    assert len(answer["aggregationResults"][0]["groupByResult"]) == 7
    (table,) = [s for s in spans
                if s["name"] == ServerQueryPhase.GROUP_TABLE]
    assert table["attrs"]["scouted"] is False and \
        table["attrs"]["layout"] == ["dense"] * SEGMENTS
    assert not [s for s in spans
                if s["name"] in (ServerQueryPhase.GROUP_SCOUT,
                                 ServerQueryPhase.GROUP_HIST)]
    assert grown[ServerMeter.GROUP_SEGMENTS] == SEGMENTS
    assert grown[ServerMeter.GROUP_SCOUT_DISPATCHES] == 0
    assert grown[ServerMeter.GROUP_TABLE_DISPATCHES] == SEGMENTS == \
        grown[ServerMeter.WALK_STACKED_SEGMENTS]
    assert profile["kernelDispatches"] == 1
    assert grown[ServerMeter.GROUP_TABLES["dense"]] == SEGMENTS


def test_a_barely_selective_float_group_by_goes_dense_and_equals_the_host(
        ladder):
    """q3.1's shape under a filter that keeps over 6% of the rows (two
    supplier regions): more than 128 rows a block, so `_adaptive_kmax`
    sends the table to the dense layout, whose one pass sums the raw INT
    lane `lo_revenue` as a value lane beside the count (PR 36). The
    answer is `query/host_exec.py`'s."""
    pql = ("SELECT SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' "
           "AND s_region IN ('ASIA', 'EUROPE') GROUP BY c_nation, s_nation, "
           "d_year TOP 10000")
    answer, profile, spans, grown = ladder.run(pql, traced=True)
    assert answer["numDocsScanned"] > 0.06 * answer["totalDocs"]
    (table,) = [s for s in spans
                if s["name"] == ServerQueryPhase.GROUP_TABLE]
    assert table["attrs"]["layout"] == ["dense"] * SEGMENTS and \
        table["attrs"]["scouted"] and table["attrs"]["runs"] == [1, 1]
    assert grown[ServerMeter.GROUP_TABLES["dense"]] == SEGMENTS
    assert profile["paths"] == {"scan": SEGMENTS}
    host, host_profile, _spans, _grown = ladder.run(pql, traced=False,
                                                    use_device=False)
    assert host_profile["paths"] == {"host": SEGMENTS}
    groups = answer["aggregationResults"][0]["groupByResult"]
    assert len(groups) == 5 * 10 * 7
    assert answer["aggregationResults"] == host["aggregationResults"]
    assert answer["numDocsScanned"] == host["numDocsScanned"]


# -- eight segments a query: what one phase does for all of them -------------

EIGHT = 8


@pytest.fixture(scope="module")
def ladder8(tmp_path_factory):
    return Ladder(str(tmp_path_factory.mktemp("ladder_segments8")),
                  rows=80_000, segments=EIGHT)


def _nation_and_city_some_segments_lack(ladder):
    """(c_nation, s_city, segments they meet in): each value occurs in
    every segment (so no planner folds the predicate away), the pair in
    some segments only, which only the device can tell."""
    import numpy as np
    nations, cities = ladder.pools["c_nation"], ladder.pools["s_city"]
    ids = [seg[0] for seg in ladder.table.segments]
    for n in range(len(nations)):
        for c in range(len(cities)):
            both = [int(np.count_nonzero((i["c_nation"] == n) &
                                         (i["s_city"] == c))) for i in ids]
            each = all(np.any(i["c_nation"] == n) and np.any(i["s_city"] == c)
                       for i in ids)
            if each and 2 <= sum(b == 0 for b in both) <= EIGHT - 2:
                return nations[n], cities[c], [b > 0 for b in both]
    raise AssertionError("no such pair in these rows")


def test_a_segment_that_matches_nothing_runs_no_table_beside_siblings_that_do(
        ladder8):
    nation, city, meets = _nation_and_city_some_segments_lack(ladder8)
    pql = (f"SELECT SUM(lo_revenue) FROM lineorder WHERE c_nation = "
           f"'{nation}' AND s_city = '{city}' GROUP BY d_year TOP 100")
    answer, profile, spans, grown = ladder8.run(pql, traced=True)
    host, *_ = ladder8.run(pql, traced=False, use_device=False)
    assert answer["aggregationResults"] == host["aggregationResults"]
    assert answer["aggregationResults"][0]["groupByResult"]
    tables, empty = sum(meets), EIGHT - sum(meets)
    # the ladder's meters once a segment, whichever way it left
    assert grown[ServerMeter.GROUP_SEGMENTS] == EIGHT
    assert grown[ServerMeter.GROUP_SCOUT_DISPATCHES] == EIGHT
    assert grown[ServerMeter.GROUP_EMPTY] == empty
    assert grown[ServerMeter.GROUP_TABLE_DISPATCHES] == tables
    assert sum(grown[m] for m in ServerMeter.GROUP_TABLES.values()) == tables
    # two phases, two pulls; the table's phase holds the siblings alone,
    # each phase's segments members of its stacked programs
    assert grown[ServerMeter.DEVICE_PULLS] == 2
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == EIGHT + tables
    assert grown[ServerMeter.DEVICE_PROGRAMS] == \
        grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        profile["kernelDispatches"]
    _plan, (scout, table) = _phases(spans)
    assert scout["attrs"] == {"segments": EIGHT}
    assert table["attrs"]["segments"] == tables == \
        len(table["attrs"]["layout"])
    assert sum(c["attrs"]["segments"] for c in table["children"]
               if c["name"] == ServerQueryPhase.KERNEL_LAUNCH) == tables
    assert profile["paths"] == {"scan": EIGHT}


def test_a_forced_re_run_of_one_segment_of_eight_re_runs_it_alone(
        ladder8, monkeypatch):
    """The third member's first table reports overflow (forced here:
    about one table in 8,000 does): the table's phase launches that
    segment's next rung alone and pulls once more; the answer is what
    the walk without the re-run gives."""
    from pinot_tpu.ops import kernels
    shape = ladder8.shapes["q2.2"]
    pql = shape.pql(shape.spec["ssb"])
    want, _profile, _spans, plain = ladder8.run(pql, traced=False)
    assert plain[ServerMeter.GROUP_TABLES["compacted"]] == EIGHT
    real, tables = kernels.run_stacked_segment_kernel, []

    def overflow_once(padded, filt, aggs, group_spec, *rest):
        outs = real(padded, filt, aggs, group_spec, *rest)
        if group_spec is not None:
            tables.append((group_spec[4], len(rest[-1])))
            if len(tables) == 1:
                outs = dict(outs, **{"group.overflow":
                                     outs["group.overflow"].at[2].set(1)})
        return outs
    monkeypatch.setattr(kernels, "run_stacked_segment_kernel",
                        overflow_once)
    answer, profile, spans, grown = ladder8.run(pql, traced=True)
    assert answer == want
    # eight tables in the phase's programs, then the one segment's at
    # four times the slots, a program of its own
    (kmax, _n), *first, (kmax2, alone) = tables
    assert _n + sum(n for _k, n in first) == EIGHT
    assert alone == 1 and kmax2 >= 4 * kmax
    k = 1 + len(first)
    assert grown[ServerMeter.GROUP_ESCALATIONS] == 1
    assert grown[ServerMeter.GROUP_TABLE_DISPATCHES] == EIGHT + 1
    assert grown[ServerMeter.GROUP_SEGMENTS] == EIGHT
    assert grown[ServerMeter.DEVICE_PULLS] == 3
    assert grown[ServerMeter.DEVICE_PROGRAMS] == \
        grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        profile["kernelDispatches"]
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == 2 * EIGHT + 1
    _plan, (_scout, table) = _phases(spans)
    assert sorted(table["attrs"]["runs"]) == [1] * (EIGHT - 1) + [2]
    assert [c["name"] for c in table["children"]] == \
        [ServerQueryPhase.KERNEL_LAUNCH] * k + \
        [ServerQueryPhase.KERNEL_DISPATCH, ServerQueryPhase.OUTPUT_RELEASE,
         ServerQueryPhase.RUNG_ACCEPT,
         ServerQueryPhase.KERNEL_LAUNCH, ServerQueryPhase.KERNEL_DISPATCH,
         ServerQueryPhase.OUTPUT_RELEASE, ServerQueryPhase.RUNG_ACCEPT]
    assert [c["attrs"]["segments"] for c in table["children"]
            if c["name"] == ServerQueryPhase.RUNG_ACCEPT] == [EIGHT, 1]
    assert [c["attrs"]["programs"] for c in table["children"]
            if c["name"] == ServerQueryPhase.KERNEL_DISPATCH] == [k, 1]


@pytest.mark.parametrize("shape", ["q1.2", "q2.2", "q3.1", "q4.1"])
def test_eight_segments_in_phases_answer_as_eight_walked_alone(
        ladder8, shape, monkeypatch):
    """The walk over a query's eight ladders against each ladder
    walked alone (`_walks` refused: a segment a piece, a pull a
    program, what a pool task did before PR 38): the same answer, the
    same marks on the ladder's meters, a segment program alone for each
    member of the walk's stacked programs, and the same bytes but for
    the stacked outputs' rows no member ran; the pulls differ."""
    from fixtures import spy_dead_rows
    from pinot_tpu.query.executor import ServerQueryExecutor
    s = ladder8.shapes[shape]
    pql = s.pql(s.spec["ssb"])
    dead = spy_dead_rows(monkeypatch)
    answer, profile, _spans, grown = ladder8.run(pql, traced=False)
    monkeypatch.setattr(ServerQueryExecutor, "_walks",
                        lambda self, seg: False)
    n_launches = len(dead)
    alone, alone_profile, _s, alone_grown = ladder8.run(pql, traced=False)
    assert answer == alone
    for key in ("paths", "docsScanned", "segmentsMatched"):
        assert profile[key] == alone_profile[key], key
    assert {m: n for m, n in grown.items() if m not in WALK_METERS} == \
        {m: n for m, n in alone_grown.items() if m not in WALK_METERS}
    assert len(dead) == n_launches == profile["kernelDispatches"]
    assert profile["deviceTransferBytes"] == \
        alone_profile["deviceTransferBytes"] + sum(dead)
    assert grown[ServerMeter.DEVICE_PROGRAMS] == \
        grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        profile["kernelDispatches"]
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        alone_grown[ServerMeter.DEVICE_PROGRAMS] == \
        alone_grown[ServerMeter.DEVICE_PULLS] == \
        alone_profile["kernelDispatches"]
    assert alone_grown[ServerMeter.WALK_STACKED_SEGMENTS] == 0
    assert grown[ServerMeter.DEVICE_PULLS] <= 3
    assert (grown[ServerMeter.SCAN_WALK_SEGMENTS],
            alone_grown[ServerMeter.SCAN_POOL_SEGMENTS]) == (EIGHT, EIGHT)


def test_a_kmax_re_run_is_counted_as_an_escalation():
    """The ladder's own loop, with a kernel that reports overflow
    twice: three launches under one table, two of them re-runs."""
    from pinot_tpu.query import plan
    reg = MetricsRegistry("server")
    obs_profiler.bind_server_metrics(reg)
    spec = ((("d0", "mvids", 0, 8),), (1,), 8, (), 1024)   # unscouted
    launched = []

    def run(agg_specs, group_spec, extra):
        launched.append(group_spec[4])
        return {"group.overflow": int(len(launched) < 3),
                "stats.num_docs_matched": 1}

    trace = TraceContext(root_name="server")
    with obs_profiler.active(obs_profiler.QueryProfile("t"), trace):
        # no aggregation, so no lane of a segment is asked for
        _outs, final = plan.drive_group_execution(run, spec, 1 << 20, 1000,
                                                  None)
    assert launched == [1024, 4096, 16384] and final[4] == 16384
    (table,) = [s for s in trace.to_list()
                if s["name"] == ServerQueryPhase.GROUP_TABLE]
    assert table["attrs"] == {"segments": 1, "layout": ["compacted"],
                              "g": [8], "runs": [3], "scouted": False,
                              "partLanes": 0, "valueLanes": 0}
    count = {m: reg.meter(m).count for m in LADDER_METERS}
    assert count[ServerMeter.GROUP_TABLE_DISPATCHES] == 3
    assert count[ServerMeter.GROUP_ESCALATIONS] == 2
    assert count[ServerMeter.GROUP_SEGMENTS] == 1
    assert count[ServerMeter.GROUP_TABLES["compacted"]] == 1


def test_a_kmax_re_run_keeps_the_slot_count_a_power_of_two():
    """A re-run's capacity is at least four times the last, with the
    kernel's slots a block (r = ceil(kmax / blocks)) a power of two
    also where the block count is none: at the benchmark's 6,250,496
    padded rows (3052 blocks) r 32 goes to 128, not to 172, whose
    compaction program with 7 part planes takes the TPU compiler 31-34 s
    (PERF.md section 6, PR 37: a request's deadline is 15 s)."""
    from pinot_tpu.ops import kernels
    from pinot_tpu.query.plan import escalate_group_kmax, group_layout
    for padded in (6_250_496, 12_500_992, 1 << 20, 8192):
        blocks = max(padded // kernels.CBLOCK, 1)
        spec = ((), (), 1024, (), blocks * 32 if padded > 8192 else 1024)
        while True:
            nxt = escalate_group_kmax(spec, padded)
            if nxt is None:
                break
            assert nxt[:4] == spec[:4]
            assert min(4 * spec[4], padded) <= nxt[4] <= padded
            r = -(-nxt[4] // blocks)
            assert r & (r - 1) == 0, (padded, nxt[4], r)
            spec = nxt
        assert spec[4] == padded
    first = escalate_group_kmax(((), (), 1024, (), 3052 * 32), 6_250_496)
    assert first[4] == 3052 * 128
    assert group_layout(first, 6_250_496) == "compacted"


def test_layout_names_are_the_kernels_own_cases():
    """`plan.group_layout` names a spec's layout as `ops/kernels.py`
    names the contract case that exercises it."""
    from pinot_tpu.ops import kernels
    from pinot_tpu.query.plan import group_layout
    seen = set()
    for name, _filt, _aggs, group_spec, *_rest in kernels.contract_cases():
        layout = name.split("_", 1)[1] if name.startswith("group_") \
            else None
        if layout in ServerMeter.GROUP_TABLES:
            for bucket in kernels.CONTRACT_SHAPE_BUCKETS:
                assert group_layout(group_spec, bucket) == layout, name
            seen.add(layout)
    assert seen == {"dense", "scatter", "compacted", "ranked"}
    # past r = 256 rows a block the compacted kernel sorts instead
    spec = ((("d0", "ids", 0, 8),), (1,), 8, (), 8192 * 512 // 4)
    assert group_layout(spec, 8192) == "sorted"


# -- one filter structure a query template ---------------------------------

Q31 = ("SELECT SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' AND "
       "s_region = 'ASIA'{years} GROUP BY c_nation, s_nation, d_year "
       "TOP 10000")


def _segment_plan(ladder, pql):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.plan import InstancePlanMaker, preprocess_request
    request = preprocess_request(
        ladder.segments,
        BrokerRequestOptimizer().optimize(compile_pql(pql)))
    return InstancePlanMaker().make_segment_plan(ladder.segments[0], request)


def test_a_covering_range_keeps_a_group_bys_filter_structure(ladder):
    """dbgen's years are 1992-1998, so `d_year BETWEEN 1992 AND 1998`
    covers the dictionary. Beside other conjuncts of a group-by it stays
    a runtime `range_ids` predicate: the widest band of a template runs
    the programs every other band compiled, and answers as the query
    without the predicate does."""
    from pinot_tpu.query import plan
    narrow = _segment_plan(ladder, Q31.format(
        years=" AND d_year BETWEEN 1993 AND 1997"))
    covering = _segment_plan(ladder, Q31.format(
        years=" AND d_year BETWEEN 1992 AND 1998"))
    without = _segment_plan(ladder, Q31.format(years=""))
    assert covering.filter_spec == narrow.filter_spec != without.filter_spec
    assert covering.group_spec == narrow.group_spec
    assert [type(p) for p in covering.params] == \
        [type(p) for p in narrow.params]
    card = ladder.segments[0].data_source("d_year").dictionary.cardinality
    at = 0                      # params are the leaves', depth first
    for leaf in covering.filter_spec[1]:
        if leaf == ("pred", "range_ids", "d_year", "sv", None):
            break
        at += {"eq_id": 1, "range_ids": 2}[leaf[1]]
    else:
        raise AssertionError(covering.filter_spec)
    assert [int(p) for p in covering.params[at:at + 2]] == [0, card]
    answer, _profile, _spans, grown = ladder.run(
        Q31.format(years=" AND d_year BETWEEN 1992 AND 1998"), False)
    plain, *_ = ladder.run(Q31.format(years=""), False)
    # the same groups and sums; the stats count one more filter leaf
    assert answer["aggregationResults"] == plain["aggregationResults"]
    assert answer["aggregationResults"][0]["groupByResult"]
    assert answer["numDocsScanned"] == plain["numDocsScanned"]
    assert grown[ServerMeter.GROUP_SEGMENTS] == SEGMENTS
    # alone, and outside a group-by, it folds as it always did
    alone = _segment_plan(
        ladder, "SELECT SUM(lo_revenue) FROM lineorder WHERE d_year "
        "BETWEEN 1992 AND 1998 GROUP BY d_year TOP 10")
    assert alone.filter_spec == plan.MATCH_ALL and not alone.params
    scalar = _segment_plan(
        ladder, "SELECT SUM(lo_revenue) FROM lineorder WHERE d_year "
        "BETWEEN 1992 AND 1998 AND lo_discount BETWEEN 1 AND 3")
    assert ("pred", "range_ids", "d_year", "sv", None) != \
        scalar.filter_spec and "d_year" not in repr(scalar.filter_spec)
    count = _segment_plan(
        ladder, "SELECT COUNT(*) FROM lineorder WHERE d_year BETWEEN 1992 "
        "AND 1998")
    assert count.fast_path_result is not None
