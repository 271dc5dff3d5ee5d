"""Star-tree (pre-aggregated cube) tests.

Mirrors StarTreeClusterIntegrationTest: every eligible query must return
EXACTLY the same answer with and without the star-tree path, and the
star-tree path must scan orders of magnitude fewer rows.
"""
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np
import pytest

from fixtures import make_columns, make_schema, make_table_config

from pinot_tpu.engine import QueryEngine
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import ImmutableSegmentLoader

ST_CONFIG = {
    "dimensionsSplitOrder": ["teamID", "league", "yearID"],
    "functionColumnPairs": ["SUM__runs", "SUM__hits", "MAX__average"],
    "maxSize": 1 << 20,
}

QUERIES = [
    "SELECT COUNT(*) FROM baseballStats",
    "SELECT SUM(runs), COUNT(*) FROM baseballStats WHERE teamID = 'BOS'",
    "SELECT SUM(runs) FROM baseballStats WHERE yearID >= 2000 AND "
    "league = 'AL'",
    "SELECT MIN(average), MAX(average), AVG(hits) FROM baseballStats "
    "WHERE teamID IN ('BOS', 'NYA', 'SEA')",
    "SELECT MINMAXRANGE(runs) FROM baseballStats WHERE yearID <> 1995",
    "SELECT SUM(runs) FROM baseballStats GROUP BY teamID TOP 100",
    "SELECT SUM(hits), COUNT(*) FROM baseballStats "
    "WHERE league = 'NL' GROUP BY teamID, yearID TOP 1000",
    "SELECT AVG(runs) FROM baseballStats GROUP BY league "
    "HAVING AVG(runs) > 0 TOP 10",
    # expression filter whose source column is a cube dimension
    "SELECT SUM(runs) FROM baseballStats "
    "WHERE time_convert(yearID,'DAYS','HOURS') >= 48000",
]


@pytest.fixture(scope="module")
def segments():
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    d_st = os.path.join(base, "with_st")
    d_plain = os.path.join(base, "plain")
    cols = make_columns(20_000, seed=23)
    SegmentCreator(make_schema(), cfg, "st_seg").build(dict(cols), d_st)
    SegmentCreator(make_schema(), make_table_config(),
                   "plain_seg").build(dict(cols), d_plain)
    return (ImmutableSegmentLoader.load(d_st),
            ImmutableSegmentLoader.load(d_plain), cols)


def _result_key(resp):
    out = []
    if resp.aggregation_results is None:
        return sorted(map(tuple, resp.selection_results.results))
    for a in resp.aggregation_results:
        if a.group_by_result is not None:
            out.append(sorted((tuple(g["group"]), g["value"])
                              for g in a.group_by_result))
        else:
            out.append(a.value)
    return out


def test_cubes_built_and_loaded(segments):
    seg_st, seg_plain, _ = segments
    assert len(seg_st.star_trees) == 1
    cube = seg_st.star_trees[0]
    assert cube.dimensions == ["teamID", "league", "yearID"]
    assert set(cube.metrics) == {"runs", "hits", "average"}
    assert 0 < cube.n_groups < seg_st.num_docs
    assert int(cube.counts.sum()) == seg_st.num_docs
    assert seg_plain.star_trees == []


def test_star_tree_same_answers_as_plain_path(segments):
    """The StarTreeClusterIntegrationTest contract."""
    seg_st, seg_plain, _ = segments
    eng_st = QueryEngine([seg_st])
    eng_plain = QueryEngine([seg_plain])
    for q in QUERIES:
        r_st = _result_key(eng_st.query(q))
        r_plain = _result_key(eng_plain.query(q))
        assert r_st == r_plain, q


def test_star_tree_disable_option(segments):
    seg_st, _, _ = segments
    eng = QueryEngine([seg_st])
    q = "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS'"
    on = eng.query(q)
    off = eng.query(q + " OPTION(useStarTree=false)")
    assert on.aggregation_results[0].value == \
        off.aggregation_results[0].value
    # the cube path scans groups, not docs
    assert on.num_docs_scanned < off.num_docs_scanned


def test_star_tree_ineligible_falls_back(segments):
    seg_st, seg_plain, cols = segments
    eng_st = QueryEngine([seg_st])
    eng_plain = QueryEngine([seg_plain])
    # uncovered metric (salary), uncovered dim (playerName), percentile,
    # selection — all must silently take the normal path
    for q in [
        "SELECT SUM(salary) FROM baseballStats WHERE teamID = 'BOS'",
        "SELECT SUM(runs) FROM baseballStats WHERE playerName = "
        "'player_001'",
        "SELECT PERCENTILE50(runs) FROM baseballStats",
        "SELECT DISTINCTCOUNT(runs) FROM baseballStats "
        "WHERE teamID = 'BOS'",
        "SELECT teamID, runs FROM baseballStats LIMIT 5",
    ]:
        r_st = _result_key(eng_st.query(q))
        r_plain = _result_key(eng_plain.query(q))
        assert r_st == r_plain, q


def test_star_tree_group_by_vs_numpy(segments):
    seg_st, _, cols = segments
    eng = QueryEngine([seg_st])
    resp = eng.query("SELECT SUM(runs) FROM baseballStats "
                     "WHERE league = 'AL' GROUP BY teamID TOP 100")
    m = cols["league"] == "AL"
    runs = cols["runs"].astype(np.float64)
    expected = {}
    for t in np.unique(cols["teamID"][m]):
        expected[str(t)] = float(runs[m & (cols["teamID"] == t)].sum())
    got = {g["group"][0]: float(g["value"])
           for g in resp.aggregation_results[0].group_by_result}
    assert got == expected


def test_star_tree_through_cluster_upload():
    """Cube files travel with the segment through deep store + download."""
    from pinot_tpu.tools.cluster import EmbeddedCluster

    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    seg_dir = os.path.join(base, "seg")
    cols = make_columns(5000, seed=29)
    SegmentCreator(make_schema(), cfg, "st_up").build(cols, seg_dir)
    cluster = EmbeddedCluster(os.path.join(base, "cluster"), num_servers=1)
    try:
        cluster.add_schema(make_schema())
        cluster.add_table(cfg)
        cluster.upload_segment("baseballStats_OFFLINE", seg_dir)
        server = cluster.servers["Server_0"]
        tdm = server.data_manager.table("baseballStats_OFFLINE")
        acquired, _ = tdm.acquire_segments(["st_up"])
        try:
            assert len(acquired[0].segment.star_trees) == 1
        finally:
            for sdm in acquired:
                tdm.release_segment(sdm)
        resp = cluster.query("SELECT SUM(runs) FROM baseballStats "
                             "WHERE teamID = 'BOS'")
        exp = float(cols["runs"][cols["teamID"] == "BOS"].sum())
        assert float(resp.aggregation_results[0].value) == exp
    finally:
        cluster.stop()


def test_rebuild_removes_stale_cubes():
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    d = os.path.join(base, "seg")
    cols1 = make_columns(2000, seed=31)
    SegmentCreator(make_schema(), cfg, "reb").build(cols1, d)
    assert len(ImmutableSegmentLoader.load(d).star_trees) == 1
    # rebuild same dir WITHOUT star-tree config: stale cubes must vanish
    cols2 = make_columns(2000, seed=32)
    SegmentCreator(make_schema(), make_table_config(), "reb").build(cols2, d)
    seg = ImmutableSegmentLoader.load(d)
    assert seg.star_trees == []
    eng = QueryEngine([seg])
    resp = eng.query("SELECT SUM(runs) FROM baseballStats "
                     "WHERE teamID = 'BOS'")
    exp = float(cols2["runs"][cols2["teamID"] == "BOS"].sum())
    assert float(resp.aggregation_results[0].value) == exp


def test_broken_cube_files_do_not_brick_segment():
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    d = os.path.join(base, "seg")
    SegmentCreator(make_schema(), cfg, "brk").build(
        make_columns(2000, seed=33), d)
    os.remove(os.path.join(d, "startree.0.npz"))    # crash-torn save
    seg = ImmutableSegmentLoader.load(d)            # must not raise
    assert seg.star_trees == []


def test_max_leaf_records_does_not_disable_cube():
    from pinot_tpu.startree.cube import StarTreeConfig
    c = StarTreeConfig.from_json({
        "dimensionsSplitOrder": ["teamID"],
        "functionColumnPairs": ["SUM__runs"],
        "maxLeafRecords": 10000})
    assert c.max_groups > 10000     # Pinot's split threshold is not a cap


def test_multi_segment_repeated_column_aggs():
    """Regression: MIN(x), MAX(x) (two functions, one column) over the
    multi-segment cube path double-appended x's stat lanes, breaking the
    counts/stats alignment (IndexError at 2 segments)."""
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    segs, plain = [], []
    for i in range(3):
        cols = make_columns(5_000, seed=40 + i)
        d_st = os.path.join(base, f"st{i}")
        d_pl = os.path.join(base, f"pl{i}")
        SegmentCreator(make_schema(), cfg, f"st{i}").build(dict(cols), d_st)
        SegmentCreator(make_schema(), make_table_config(),
                       f"pl{i}").build(dict(cols), d_pl)
        segs.append(ImmutableSegmentLoader.load(d_st))
        plain.append(ImmutableSegmentLoader.load(d_pl))
    eng_st, eng_plain = QueryEngine(segs), QueryEngine(plain)
    for q in ("SELECT COUNT(*), MIN(runs), MAX(runs) FROM baseballStats "
              "WHERE teamID = 'BOS'",
              "SELECT MIN(average), MAX(average), AVG(hits) "
              "FROM baseballStats WHERE league = 'AL'",
              "SELECT MINMAXRANGE(runs), MIN(runs) FROM baseballStats "
              "GROUP BY league TOP 10"):
        assert _result_key(eng_st.query(q)) == \
            _result_key(eng_plain.query(q)), q


def test_prefix_descent_narrows_and_matches():
    """Sorted-prefix cube descent (binary-search blocks) must agree with
    the plain path AND examine far fewer rows than the full cube."""
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.startree.executor import (_QueryLeaves, _cube_select,
                                             _eligible_cube, _query_needs)
    from pinot_tpu.query.aggregation import make_functions

    base = tempfile.mkdtemp()
    cfg = make_table_config()
    # filter dims first: teamID/league EQ/IN queries become prefix blocks
    cfg.indexing_config.star_tree_configs = [{
        "dimensionsSplitOrder": ["teamID", "league", "yearID"],
        "functionColumnPairs": ["SUM__runs", "SUM__hits", "MAX__average"],
    }]
    cols = make_columns(30_000, seed=51)
    d_st = os.path.join(base, "st")
    d_pl = os.path.join(base, "pl")
    SegmentCreator(make_schema(), cfg, "st").build(dict(cols), d_st)
    SegmentCreator(make_schema(), make_table_config(),
                   "pl").build(dict(cols), d_pl)
    seg = ImmutableSegmentLoader.load(d_st)
    seg_pl = ImmutableSegmentLoader.load(d_pl)
    cube = seg.star_trees[0]

    # cube rows must be sorted by split order (the descent's invariant)
    key = np.zeros(cube.n_groups, np.int64)
    for dim in cube.dimensions:
        card = seg.data_source(dim).metadata.cardinality
        key = key * card + cube.dim_ids[dim]
    assert (np.diff(key) > 0).all()

    eng_st, eng_pl = QueryEngine([seg]), QueryEngine([seg_pl])
    prefix_qs = [
        "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS'",
        "SELECT SUM(runs), COUNT(*) FROM baseballStats WHERE teamID IN "
        "('BOS', 'SEA') AND league = 'AL' GROUP BY yearID TOP 100",
        "SELECT MAX(average) FROM baseballStats WHERE teamID = 'NYA' AND "
        "league = 'NL' AND yearID >= 2000",
        # RANGE on the first dim: one interval block, residual on yearID
        "SELECT SUM(hits) FROM baseballStats WHERE teamID >= 'NYA' AND "
        "yearID < 2005 GROUP BY league TOP 10",
    ]
    for q in prefix_qs:
        assert _result_key(eng_st.query(q)) == _result_key(eng_pl.query(q)), q

    # and the descent really narrows: examined rows << full cube
    req = BrokerRequestOptimizer().optimize(compile_pql(prefix_qs[1]))
    fns = make_functions(req.aggregations)
    leaves = _QueryLeaves([seg], req.filter)
    chosen, levels, _ = _eligible_cube(seg, _query_needs(req, fns),
                                       leaves, 0)
    assert chosen is cube
    sel, examined = _cube_select(seg, cube, req.filter, leaves.leaves,
                                 levels)
    assert examined < cube.n_groups / 4
    assert len(sel) <= examined


def test_star_tree_in_v3_container():
    """Cubes built at seal time must ride the v3 single-file container
    (creator runs the v3 conversion LAST so startree members land in
    columns.psf) and keep the prefix-descent path working after load."""
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    cfg.indexing_config.segment_version = "v3"
    d = os.path.join(base, "v3st")
    cols = make_columns(8_000, seed=55)
    SegmentCreator(make_schema(), cfg, "v3st").build(dict(cols), d)
    # single-file layout: no loose startree files outside the container
    names = sorted(os.listdir(d))
    assert any(n.startswith("columns.psf") for n in names), names
    assert not [n for n in names if n.startswith("startree.") and
                n.endswith(".npz")], names
    seg = ImmutableSegmentLoader.load(d)
    assert len(seg.star_trees) == 1
    eng = QueryEngine([seg], use_device=False)
    q = ("SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS' "
         "GROUP BY yearID TOP 100")
    resp = eng.query(q)
    exp = {}
    mask = cols["teamID"] == "BOS"
    for y, r in zip(np.asarray(cols["yearID"])[mask],
                    np.asarray(cols["runs"])[mask]):
        exp[str(int(y))] = exp.get(str(int(y)), 0.0) + float(r)
    got = {str(g["group"][0]): float(g["value"])
           for g in resp.aggregation_results[0].group_by_result}
    assert got == exp
    # the cube path engaged (scanned far fewer rows than the segment)
    assert resp.num_entries_scanned_in_filter < seg.num_docs / 4


# ---------------------------------------------------------------------------
# The one native select-and-gather call (seglib.cpp cube_select_gather)
# against its stepwise numpy twin: exactly equal, whichever answers
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
CUBE_SHAPES = ["q2.1", "q2.2", "q2.3", "q3.1", "q3.2", "q3.3", "q3.4",
               "q4.1", "q4.2", "q4.3"]


def _request(pql):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    return BrokerRequestOptimizer().optimize(compile_pql(pql))


@pytest.fixture(scope="module")
def native_lib():
    from pinot_tpu import native
    if native.lib() is None:
        pytest.skip("no compiler: the native descent cannot be built")
    return native


@pytest.fixture(scope="module")
def ssb():
    """8 small segments of the benchmark's configuration (its generator,
    its nine cubes; each segment's dictionaries are its own) and the
    benchmark's shapes."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    from harness import build, shapes as shapes_mod, tables
    with open(os.path.join(BENCH_DIR, "configs",
                           "ssb_flat_startree.json")) as fh:
        config = json.load(fh)
    # 3,000 rows a segment: every region, nation and city is in every
    # segment's dictionary, but not every one of the 1,000 brands
    config["rows"] = 24_000
    out = tempfile.mkdtemp()
    segs = [ImmutableSegmentLoader.load(build.build_segment(
        (config, 11, i, hi - lo, out))) for i, (lo, hi) in enumerate(
            tables.segment_bounds(config["rows"], config["segments"]))]
    gen = tables.load_generator(config["generator"])
    shapes = {s.name: s for s in shapes_mod.load_family(
        BENCH_DIR, "ssb", gen.pools())}
    return segs, shapes


@pytest.fixture(scope="module")
def baseball():
    """3 segments with one cube (teamID, league, yearID), small enough
    beside its segment to answer unnarrowed."""
    base = tempfile.mkdtemp()
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    segs = []
    for i in range(3):
        d = os.path.join(base, f"bb{i}")
        SegmentCreator(make_schema(), cfg, f"bb{i}").build(
            dict(make_columns(30_000, seed=60 + i)), d)
        segs.append(ImmutableSegmentLoader.load(d))
        segs[-1].built_in = d
    return segs


@pytest.fixture
def cube_meters():
    """() -> (cubeDescentsNative, cubeDescentsNumpy) of a registry bound
    for this test."""
    from pinot_tpu.common.metrics import MetricsRegistry, ServerMeter
    from pinot_tpu.obs.profiler import bind_cube_metrics
    reg = MetricsRegistry("server")
    bind_cube_metrics(reg)
    return lambda: (reg.meter(ServerMeter.CUBE_DESCENTS_NATIVE).count,
                    reg.meter(ServerMeter.CUBE_DESCENTS_NUMPY).count)


def _descend(segs, req):
    from pinot_tpu.startree.executor import (try_star_tree_execute,
                                             try_star_tree_execute_multi)
    if len(segs) == 1:
        return try_star_tree_execute(segs[0], req)
    return try_star_tree_execute_multi(segs, req)


def _fused_and_twin(monkeypatch, native, segs, req):
    fused = _descend(segs, req)
    with monkeypatch.context() as m:
        m.setattr(native, "loaded", lambda: None)
        twin = _descend(segs, req)
    return fused, twin


def _assert_same_answer(fused, twin):
    assert fused is not None and twin is not None
    assert (fused.cube_native, twin.cube_native) == (True, False)
    assert (fused.group_map is None) == (twin.group_map is None)
    if fused.group_map is not None:
        # items, not the dicts: the insertion order is part of the answer
        assert list(fused.group_map.items()) == list(twin.group_map.items())
    assert fused.agg_intermediates == twin.agg_intermediates
    assert dataclasses.asdict(fused.stats) == dataclasses.asdict(twin.stats)


@pytest.mark.parametrize("n_segments", [2, 8])
@pytest.mark.parametrize("shape", CUBE_SHAPES)
def test_fused_descent_equals_numpy_twin_on_ssb_shapes(
        monkeypatch, native_lib, ssb, shape, n_segments):
    segs, shapes = ssb
    s = shapes[shape]
    rng = np.random.default_rng([n_segments, CUBE_SHAPES.index(shape)])
    matched = 0
    for pick in rng.choice(s.domain_size, 4, replace=False):
        req = _request(s.pql(s.literals(int(pick))))
        fused, twin = _fused_and_twin(monkeypatch, native_lib,
                                      segs[:n_segments], req)
        _assert_same_answer(fused, twin)
        matched += fused.stats.num_docs_scanned
    # q3.3 and q3.4 name two cities a side (and a month): at 8,000 rows
    # a segment their descents end in empty blocks, an edge of its own
    assert matched > 0 or shape in ("q3.3", "q3.4"), \
        "no drawn literal matched a cube row"


def _brand_in_first_only(segs):
    d0, d1 = (s.data_source("p_brand1").dictionary.values
              for s in segs[:2])
    only = sorted(set(d0.tolist()) - set(d1.tolist()))
    assert only, "the two segments' p_brand1 dictionaries do not differ"
    return only[0]


SSB_EDGES = {
    "absent_from_one_segment": lambda segs: (
        "SELECT SUM(lo_revenue), COUNT(*) FROM lineorder WHERE "
        "s_region IN ('ASIA', 'EUROPE', 'AMERICA') AND p_brand1 IN "
        f"('{_brand_in_first_only(segs)}', 'MFGR#2221', 'MFGR#1101') "
        "GROUP BY d_year, p_brand1 TOP 100"),
    "absent_from_all": lambda segs: (
        "SELECT SUM(lo_revenue) FROM lineorder WHERE c_city = 'NOWHERE 0' "
        "AND s_city = 'UNITED KI1' GROUP BY d_year TOP 100"),
    "range_covers_nothing": lambda segs: (
        "SELECT SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' AND "
        "s_region = 'ASIA' AND d_year BETWEEN 2050 AND 2060 "
        "GROUP BY c_nation, s_nation, d_year TOP 100"),
    "leading_range_covers_nothing": lambda segs: (
        "SELECT COUNT(*) FROM lineorder WHERE c_region > 'ZZZ' AND "
        "s_region = 'ASIA'"),
    "residual_beyond_the_prefix": lambda segs: (
        "SELECT SUM(lo_revenue), COUNT(*) FROM lineorder WHERE "
        "c_region = 'AMERICA' AND s_region = 'ASIA' AND "
        "s_nation IN ('CHINA', 'INDIA', 'JAPAN') "
        "AND d_year BETWEEN 1993 AND 1996 "
        "GROUP BY c_nation, s_nation TOP 1000"),
    "two_leaves_on_one_dimension": lambda segs: (
        "SELECT SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' AND "
        "s_region = 'ASIA' AND d_year >= 1993 AND d_year < 1997 "
        "GROUP BY d_year TOP 100"),
    "no_group_by": lambda segs: (
        "SELECT SUM(lo_revenue), SUM(lo_supplycost), COUNT(*) FROM "
        "lineorder WHERE c_region = 'AMERICA' AND s_region = 'AMERICA' "
        "AND p_mfgr IN ('MFGR#1', 'MFGR#2')"),
}


@pytest.mark.parametrize("n_segments", [1, 2, 8])
@pytest.mark.parametrize("edge", sorted(SSB_EDGES))
def test_fused_descent_equals_numpy_twin_on_edges(
        monkeypatch, native_lib, ssb, edge, n_segments):
    segs = ssb[0][:n_segments]
    req = _request(SSB_EDGES[edge](ssb[0]))
    _assert_same_answer(*_fused_and_twin(monkeypatch, native_lib, segs, req))


def test_fused_descent_with_an_empty_in(monkeypatch, native_lib, ssb):
    """No PQL spells `IN ()`; an optimizer pass may leave one."""
    segs = ssb[0]
    req = _request("SELECT COUNT(*) FROM lineorder WHERE c_region = 'ASIA' "
                   "AND s_region IN ('ASIA', 'EUROPE')")
    leaf = [c for c in req.filter.children if c.column == "s_region"][0]
    leaf.values = []
    fused, twin = _fused_and_twin(monkeypatch, native_lib, segs, req)
    _assert_same_answer(fused, twin)
    assert fused.agg_intermediates == [0]


@pytest.mark.parametrize("n_segments", [1, 3])
@pytest.mark.parametrize("pql", [
    "SELECT COUNT(*), MIN(runs), MAX(runs) FROM baseballStats "
    "WHERE teamID = 'BOS'",
    "SELECT MIN(average), MAX(average), AVG(hits) FROM baseballStats "
    "WHERE teamID IN ('BOS', 'NYA', 'SEA') AND league = 'AL'",
    "SELECT MINMAXRANGE(runs), MIN(runs), AVG(runs) FROM baseballStats "
    "WHERE teamID >= 'NYA' AND yearID < 2005 GROUP BY league TOP 10",
    "SELECT SUM(hits), COUNT(*) FROM baseballStats WHERE teamID = 'SEA' "
    "AND league = 'AL' AND yearID BETWEEN 1990 AND 2005 "
    "GROUP BY teamID, yearID TOP 1000",
])
def test_fused_descent_reads_every_stat_kind(monkeypatch, native_lib,
                                             baseball, pql, n_segments):
    _assert_same_answer(*_fused_and_twin(
        monkeypatch, native_lib, baseball[:n_segments], _request(pql)))


FALLBACKS = {
    "or": "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS' OR "
          "league = 'NL'",
    "not_in": "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS' "
              "AND yearID NOT IN (1995, 1996)",
    "regexp": "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS' "
              "AND REGEXP_LIKE(league, 'A.*')",
    "not": "SELECT MINMAXRANGE(runs) FROM baseballStats "
           "WHERE teamID = 'BOS' AND yearID <> 1995",
    "expression": "SELECT SUM(runs) FROM baseballStats WHERE "
                  "teamID = 'BOS' AND "
                  "time_convert(yearID,'DAYS','HOURS') >= 48000",
    "free_leading_dimension": "SELECT SUM(runs) FROM baseballStats "
                              "WHERE league = 'AL' GROUP BY teamID TOP 100",
    "no_filter": "SELECT SUM(runs) FROM baseballStats "
                 "GROUP BY teamID TOP 100",
}


@pytest.mark.parametrize("n_segments", [1, 3])
@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_what_the_native_call_does_not_take_lands_on_numpy(
        native_lib, baseball, cube_meters, case, n_segments):
    segs = baseball[:n_segments]
    before = cube_meters()
    blk = _descend(segs, _request(FALLBACKS[case]))
    assert blk is not None and blk.cube_native is False
    assert cube_meters() == (before[0], before[1] + n_segments)
    # and the engine's answer agrees with the plain path's
    plain = QueryEngine(segs)
    q = FALLBACKS[case]
    assert _result_key(plain.query(q)) == _result_key(plain.query(
        q + " OPTION(useStarTree=false)")), q


@pytest.mark.parametrize("n_segments", [1, 3])
def test_descent_past_the_block_limit_lands_on_numpy(
        monkeypatch, native_lib, baseball, cube_meters, n_segments):
    from pinot_tpu.startree import executor
    segs = baseball[:n_segments]
    req = _request("SELECT SUM(runs) FROM baseballStats WHERE teamID IN "
                   "('BOS', 'NYA', 'SEA') AND league = 'AL'")
    native_blk = _descend(segs, req)
    assert native_blk.cube_native is True
    # three team blocks, and a limit of two: the league level stays a
    # residual in the twin, and the native call declines
    monkeypatch.setattr(executor, "_PREFIX_BLOCK_LIMIT", 2)
    before = cube_meters()
    blk = _descend(segs, req)
    assert blk.cube_native is False
    assert cube_meters() == (before[0], before[1] + n_segments)
    assert blk.agg_intermediates == native_blk.agg_intermediates
    assert blk.stats.num_docs_scanned == native_blk.stats.num_docs_scanned
    assert blk.stats.num_entries_scanned_in_filter > \
        native_blk.stats.num_entries_scanned_in_filter


def test_no_native_switch_lands_on_numpy(monkeypatch, native_lib, baseball,
                                         cube_meters):
    req = _request("SELECT SUM(runs) FROM baseballStats "
                   "WHERE teamID = 'BOS'")
    before = cube_meters()
    assert _descend(baseball, req).cube_native is True
    assert cube_meters() == (before[0] + 3, before[1])
    # the library as a process started with the switch finds it
    monkeypatch.setenv("PINOT_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native_lib, "_LIB", None)
    monkeypatch.setattr(native_lib, "_TRIED", False)
    assert native_lib.lib() is None and native_lib.loaded() is None
    blk = _descend(baseball, req)
    assert blk.cube_native is False
    assert cube_meters() == (before[0] + 3, before[1] + 3)


def test_a_miss_marks_no_meter_and_resolves_no_literal(
        monkeypatch, native_lib, baseball, cube_meters):
    """A query no cube covers (`hits` is no cube dimension) stops before
    any literal is searched: the Q1.x scans' nine misses stay cheap."""
    from pinot_tpu.startree import executor
    searched = []
    monkeypatch.setattr(executor, "_leaf_id_intervals",
                        lambda *a: searched.append(a))
    before = cube_meters()
    for segs in (baseball, baseball[:1]):
        assert _descend(segs, _request(
            "SELECT SUM(runs) FROM baseballStats WHERE teamID = 'BOS' "
            "AND hits > 100")) is None
    assert not searched and cube_meters() == before


def test_cubes_load_the_native_library(monkeypatch, native_lib, baseball):
    """`load_star_trees` builds the library; a query never does."""
    monkeypatch.setattr(native_lib, "_LIB", None)
    monkeypatch.setattr(native_lib, "_TRIED", False)
    req = _request("SELECT SUM(runs) FROM baseballStats "
                   "WHERE teamID = 'BOS'")
    assert _descend(baseball, req).cube_native is False
    assert native_lib.loaded() is None           # the query built nothing
    from pinot_tpu.startree.cube import load_star_trees
    load_star_trees(baseball[0].built_in)
    assert native_lib.loaded() is not None
    assert _descend(baseball, req).cube_native is True


def test_reloaded_segment_never_reads_through_stale_addresses(
        native_lib, baseball):
    """The cached lane addresses of a (segment set, cubes) hold their
    cubes: a segment loaded again under the same name and CRC brings new
    arrays, and the entry is rebuilt for them."""
    from pinot_tpu.startree import executor
    req = _request("SELECT SUM(runs), COUNT(*) FROM baseballStats WHERE "
                   "teamID = 'BOS' GROUP BY yearID TOP 1000")
    first = _descend(baseball, req)
    again = [ImmutableSegmentLoader.load(s.built_in) for s in baseball]
    old_key = tuple(executor._segment_identity(s) + (0,) for s in baseball)
    assert executor._cache_get(old_key)[0][0] is baseball[0].star_trees[0]
    second = _descend(again, req)
    assert second.cube_native is True
    assert executor._cache_get(old_key)[0][0] is again[0].star_trees[0]
    assert list(first.group_map.items()) == list(second.group_map.items())


# -- the native entry point alone, on a hand-made cube ----------------------

def _hand_cube():
    """Two 'segments' of one sorted two-dimension cube each."""
    a = {"d0": np.array([0, 0, 0, 1, 1, 2, 2, 2], np.int32),
         "d1": np.array([0, 1, 3, 0, 2, 1, 2, 3], np.int32),
         "counts": np.arange(1, 9, dtype=np.int64),
         "sum": np.arange(8, dtype=np.float64) * 1.5}
    b = {"d0": np.array([1, 1, 2], np.int32),
         "d1": np.array([1, 2, 2], np.int32),
         "counts": np.array([10, 20, 30], np.int64),
         "sum": np.array([0.25, 0.5, 0.75])}
    lut = np.array([5, 6, 7, 8], np.int64)       # b's d1 ids -> codes
    return a, b, lut


def _hand_tables(a, b, lut):
    """d0 IN {1, 2} descends; d1 in [1, 3) is the residual; group by d1
    (a's ids as they are, b's through `lut`); SUM's lane."""
    seg_hdr, preds, gcols, stats = [], [], [], []
    for seg, table in ((a, None), (b, lut)):
        seg_hdr += [len(seg["d0"]), seg["counts"].ctypes.data, 1, 1]
        preds += [seg["d0"].ctypes.data, 0, 2, seg["d1"].ctypes.data, 2, 3]
        gcols += [seg["d1"].ctypes.data,
                  0 if table is None else table.ctypes.data,
                  0 if table is None else len(table)]
        stats += [seg["sum"].ctypes.data]
    return seg_hdr, preds, [1, 2, 2, 3, 1, 3], gcols, stats


@pytest.mark.parametrize("cap", [1, 5, 6, 4096])
def test_native_select_gather_at_any_capacity(native_lib, cap):
    a, b, lut = _hand_cube()
    codes, counts, lanes, per_seg = native_lib.cube_select_gather(
        _hand_tables(a, b, lut), 1, 1, cap, 512)
    # a: rows 3..7 examined, d1 in {1, 2} keeps rows 4, 5, 6; b: all 3
    assert per_seg == [3, 5, 3, 3]
    assert codes.tolist() == [[2, 1, 2, 6, 7, 7]]
    assert counts.tolist() == [5, 6, 7, 10, 20, 30]
    assert lanes.tolist() == [[6.0, 7.5, 9.0, 0.25, 0.5, 0.75]]


def test_native_select_gather_declines(native_lib):
    a, b, lut = _hand_cube()
    tables = _hand_tables(a, b, lut)
    # two intervals at the first level, and a limit of one block
    assert native_lib.cube_select_gather(tables, 1, 1, 64, 1) is None
    # a lane id outside its table
    assert native_lib.cube_select_gather(
        _hand_tables(a, b, lut[:2]), 1, 1, 64, 512) is None


# -- the property the fused call is for -------------------------------------

@pytest.fixture(scope="module")
def descent_contention():
    spec = importlib.util.spec_from_file_location(
        "descent_contention",
        os.path.join(REPO, "scripts", "descent_contention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("shape", CUBE_SHAPES)
def test_array_calls_of_a_descent_do_not_grow_with_segments(
        native_lib, ssb, descent_contention, shape):
    """Every call into numpy or the native library may hand the
    interpreter lock to another thread; a descent made ~250 of them at 8
    segments (143 `searchsorted`), a number that grew with segments x
    levels x blocks. Hold it to a constant."""
    segs, shapes = ssb
    s = shapes[shape]
    req = _request(s.pql(s.literals(s.domain_size // 3)))
    counts = {}
    for n in (2, 8):
        _descend(segs[:n], req)                       # the caches warm
        with descent_contention.ArrayCalls() as calls:
            blk = _descend(segs[:n], req)
        assert blk.cube_native is True
        assert calls.native == 1
        counts[n] = calls.total
    assert counts[2] == counts[8] <= 60, counts


def test_descents_side_by_side_agree(native_lib, ssb, baseball):
    """More threads than cores over two tables and changing segment
    sets, a short switch interval, the shared cache emptied underneath
    them: every answer is the one a thread alone gets."""
    import threading
    from pinot_tpu.startree import executor
    segs, shapes = ssb
    jobs = []
    for n, name in ((8, "q2.2"), (5, "q3.2"), (8, "q4.2"), (2, "q4.3")):
        s = shapes[name]
        jobs.append((segs[:n], _request(s.pql(s.literals(7)))))
    jobs.append((baseball, _request(
        "SELECT SUM(hits), COUNT(*) FROM baseballStats WHERE teamID = 'SEA' "
        "GROUP BY yearID TOP 1000")))

    def answer(blk):
        return (list(blk.group_map.items()), dataclasses.asdict(blk.stats),
                blk.cube_native)

    alone = [answer(_descend(*job)) for job in jobs]
    wrong, stop = [], time.monotonic() + 2.0
    def work(k):
        i = k
        while time.monotonic() < stop and not wrong:
            i = (i + 1) % len(jobs)
            if answer(_descend(*jobs[i])) != alone[i]:
                wrong.append(i)
            if k == 0:
                with executor._SEGMENT_SET_LOCK:
                    executor._SEGMENT_SET_CACHE.clear()

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong, wrong
