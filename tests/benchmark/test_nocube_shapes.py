"""Configuration `ssb_flat_nocube`, shape by shape, against the reference.

The rows are dbgen's from a seed, at a small size; the segments are
built through `harness/build.py` with the configuration's own table
config (no star-tree, `lo_revenue` raw), as a run builds them. Each of
the 13 SSB shapes then goes as PQL through the server's executor and
the broker's reduce, and the answer is compared with the numpy
reference (`harness/shapes.py`) by `harness/compare.py`'s own numbers
at the configuration's own limits. Every segment must have been scanned
on the device path: no cube, no host fallback. On the CPU, in x64:
nothing here is a measurement, and `revenue_rel_err` on the chip (x32)
is what a run of the cell reads.
"""
import os

import pytest

from conftest import BENCH_DIR

CONFIG = "ssb_flat_nocube"
ROWS, SEGMENTS, SEED = 240_000, 3, 2**31 + 35
SHAPES = ["q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
          "q3.3", "q3.4", "q4.1", "q4.2", "q4.3"]


@pytest.fixture(scope="module")
def nocube(tmp_path_factory):
    """(the configuration, its loaded segments, the reference's table,
    the shape family over the table's pools)."""
    from harness import build, cells, shapes, tables
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    config = dict(cells.load_json(BENCH_DIR, "configs", f"{CONFIG}.json"),
                  rows=ROWS, segments=SEGMENTS)
    base = str(tmp_path_factory.mktemp("nocube_segments"))
    segments = [ImmutableSegmentLoader.load(build.build_segment(
        (config, SEED, i, hi - lo, base)))
        for i, (lo, hi) in enumerate(tables.segment_bounds(ROWS, SEGMENTS))]
    table = tables.make_table(tables.load_generator(config["generator"]),
                              ROWS, SEGMENTS, SEED)
    family = {s.name: s for s in shapes.load_family(BENCH_DIR, "ssb",
                                                    table.pools)}
    return config, segments, table, family


def answer(segments, pql: str):
    """-> (the broker's JSON for `pql`, the profile's path counters)."""
    from pinot_tpu.obs import profiler
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.plan import preprocess_request
    from pinot_tpu.query.reduce import BrokerReduceService
    request = preprocess_request(
        segments, BrokerRequestOptimizer().optimize(compile_pql(pql)))
    profile = profiler.QueryProfile("lineorder")
    with profiler.active(profile, None):
        block = ServerQueryExecutor().execute(request, segments)
    body = BrokerReduceService().reduce(request, [block]).to_json()
    return body, profile.to_json()["paths"]


def test_the_configuration_is_the_sibling_with_one_key_of_the_index_changed():
    from harness import cells
    mine = cells.load_json(BENCH_DIR, "configs", f"{CONFIG}.json")
    sibling = cells.load_json(BENCH_DIR, "configs", "ssb_flat_startree.json")
    assert mine["star_tree_configs"] == [] and sibling["star_tree_configs"]
    assert mine["paths"] == dict(sibling["paths"], cube="none")
    assert mine["trace_slice_s"] == 5
    # the source's shapes and the cut of scale are the sibling's
    for key in ("table", "rows", "segments", "generator",
                "no_dictionary_columns", "servers", "replication", "layout",
                "reduced", "sample_per_shape"):
        assert mine[key] == sibling[key], key
    assert set(mine["reduced_why"]) == set(mine["reduced"])
    # no limit of `correct` is looser than the sibling's
    assert set(mine["correct"]) == set(sibling["correct"])
    for number, spec in mine["correct"].items():
        assert spec["limit"] <= sibling["correct"][number]["limit"], number
        assert spec["why"]
    bench = cells.load_json(os.path.dirname(BENCH_DIR), "BENCHMARK.json")
    entry = cells.find(bench["configs"], CONFIG, "configuration")
    assert entry["source"] == mine["source"] and len(mine["source"]) <= 200
    assert entry["reduced"] == mine["reduced"]


@pytest.mark.parametrize("name", SHAPES)
def test_shape_scans_on_the_device_and_agrees_with_the_reference(nocube,
                                                                 name):
    from harness import compare
    config, segments, table, family = nocube
    shape = family[name]
    # every number of the family at nought, as a run starts them: a
    # shape that sums no supplycost leaves cost_rel_err there
    numbers = compare.fresh_numbers(family.values())
    observed = dict.fromkeys(config["paths"], 0)
    # the published literals and two more of the shape's own domain
    for literals in [shape.spec["ssb"]] + [
            shape.literals(i) for i in (0, shape.domain_size // 2)]:
        body, paths = answer(segments, shape.pql(literals))
        assert not body["exceptions"]
        assert paths == {"scan": SEGMENTS}, (literals, paths)
        for path, n_segments in paths.items():
            observed[path] += n_segments
        n = len(shape.spec["aggregates"])
        differ, errs = compare.rel_errs(
            compare.canon(body, n, bool(shape.spec["group_by"])),
            shape.reference(literals, table), n)
        compare.fold(numbers, shape, literals, differ, errs)
    numbers["failed_requests"] = 0
    numbers["path_violations"] = sum(
        1 for p, rule in config["paths"].items()
        if (rule == "none") != (observed[p] == 0))
    verdict = compare.verdict(numbers, config["correct"])
    assert verdict["correct"], (verdict["compared"], numbers["worst"])
    assert numbers["answers_compared"] == 3
