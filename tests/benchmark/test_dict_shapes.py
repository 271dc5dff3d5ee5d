"""Configuration `ssb_flat_nocube_dict`, shape by shape, against the
reference.

The rows are dbgen's from a seed, at a small size; the segments are
built through `harness/build.py` with the configuration's own table
config (no star-tree, NO raw column: `lo_revenue` and `lo_supplycost`
both behind their segment's dictionary), as a run builds them. Each of
the 13 SSB shapes then goes as PQL through the server's executor and
the broker's reduce, and the answer is compared with the numpy
reference (`harness/shapes.py`) by `harness/compare.py`'s own numbers
at the configuration's own limits, which are 0 for both sums: every
digit of an integer sum has to reach the broker's JSON. Every segment
must have been scanned on the device path, and every plan's SUM must
read integer part lanes (`parts` in a scan, `psums` in a group table).
On the CPU, in x64: nothing here is a measurement.
"""
import os

import pytest

from conftest import BENCH_DIR

CONFIG, SIBLING = "ssb_flat_nocube_dict", "ssb_flat_nocube"
CELL = "ssb_nocube_dict.mix13_c4"
ROWS, SEGMENTS, SEED = 240_000, 3, 2**31 + 37
SHAPES = ["q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
          "q3.3", "q3.4", "q4.1", "q4.2", "q4.3"]


@pytest.fixture(scope="module")
def dict_table(tmp_path_factory):
    """(the configuration, its loaded segments, the reference's table,
    the shape family over the table's pools)."""
    from harness import build, cells, shapes, tables
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    config = dict(cells.load_json(BENCH_DIR, "configs", f"{CONFIG}.json"),
                  rows=ROWS, segments=SEGMENTS)
    base = str(tmp_path_factory.mktemp("dict_segments"))
    segments = [ImmutableSegmentLoader.load(build.build_segment(
        (config, SEED, i, hi - lo, base)))
        for i, (lo, hi) in enumerate(tables.segment_bounds(ROWS, SEGMENTS))]
    table = tables.make_table(tables.load_generator(config["generator"]),
                              ROWS, SEGMENTS, SEED)
    family = {s.name: s for s in shapes.load_family(BENCH_DIR, "ssb",
                                                    table.pools)}
    return config, segments, table, family


def answer(segments, pql: str):
    """-> (the broker's JSON for `pql`, the profile's path counters, the
    strategies of the first segment's plan's SUMs)."""
    from pinot_tpu.obs import profiler
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.plan import InstancePlanMaker, preprocess_request
    from pinot_tpu.query.reduce import BrokerReduceService
    request = preprocess_request(
        segments, BrokerRequestOptimizer().optimize(compile_pql(pql)))
    profile = profiler.QueryProfile("lineorder")
    with profiler.active(profile, None):
        block = ServerQueryExecutor().execute(request, segments)
    body = BrokerReduceService().reduce(request, [block]).to_json()
    plan = InstancePlanMaker().make_segment_plan(segments[0], request)
    specs = plan.group_spec[3] if plan.group_spec else plan.agg_specs
    return body, profile.to_json()["paths"], [s[3][0] for s in specs]


def test_the_configuration_is_the_sibling_with_every_column_behind_a_dictionary():
    from harness import cells
    mine = cells.load_json(BENCH_DIR, "configs", f"{CONFIG}.json")
    sibling = cells.load_json(BENCH_DIR, "configs", f"{SIBLING}.json")
    assert mine["no_dictionary_columns"] == [] and \
        sibling["no_dictionary_columns"] == ["lo_revenue"]
    # one key of the table differs; with it the name, the source, what
    # is said of the dictionaries, the reasons, and the one limit
    differ = {k for k in set(mine) | set(sibling)
              if mine.get(k) != sibling.get(k)}
    assert differ == {"name", "source", "dictionaries",
                      "no_dictionary_columns", "reduced_why", "assumed",
                      "guarantees", "correct"}
    assert set(mine["reduced_why"]) == set(mine["reduced"]) == \
        {"rows", "servers", "replication"}
    for key in ("servers", "replication"):
        assert mine["reduced_why"][key] == sibling["reduced_why"][key]
    assert {k for k in mine["assumed"]
            if mine["assumed"][k] != sibling["assumed"].get(k)} == {"indexes"}
    assert [a == b for a, b in zip(mine["guarantees"],
                                   sibling["guarantees"])] == \
        [True, True, True, False, True]
    assert mine["guarantees"][3].startswith("SUM(lo_revenue) exact")
    assert mine["paths"] == {"scan": "some", "host": "none", "cube": "none",
                             "sharded": "none"}
    assert mine["trace_slice_s"] == 5 and mine["star_tree_configs"] == []
    # the limits: the sibling's, and both sums exact
    assert set(mine["correct"]) == set(sibling["correct"])
    for number, spec in mine["correct"].items():
        assert spec["limit"] == (0 if number == "revenue_rel_err" else
                                 sibling["correct"][number]["limit"]), number
        assert spec["limit"] == 0 and spec["why"], number
    bench = cells.load_json(os.path.dirname(BENCH_DIR), "BENCHMARK.json")
    entry = cells.find(bench["configs"], CONFIG, "configuration")
    assert entry["source"] == mine["source"] and len(mine["source"]) <= 200
    assert entry["reduced"] == mine["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = cells.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "mix13_c4", 1)
    mine_only = [m for m in bench["per_layer"]
                 if CELL in m.get("workloads", ())]
    assert 1 <= len(mine_only) <= 18
    assert all(m["workloads"] == [CELL] and m["name"].startswith("dict_")
               for m in mine_only)


@pytest.mark.parametrize("name", SHAPES)
def test_shape_sums_integers_on_the_device_and_agrees_exactly(dict_table,
                                                              name):
    from harness import compare
    config, segments, table, family = dict_table
    shape = family[name]
    numbers = compare.fresh_numbers(family.values())
    observed = dict.fromkeys(config["paths"], 0)
    # the published literals and two more of the shape's own domain
    for literals in [shape.spec["ssb"]] + [
            shape.literals(i) for i in (0, shape.domain_size // 2)]:
        body, paths, strategies = answer(segments, shape.pql(literals))
        assert not body["exceptions"]
        assert paths == {"scan": SEGMENTS}, (literals, paths)
        assert strategies == ["psums" if shape.spec["group_by"] else
                              "parts"] * len(shape.spec["aggregates"])
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
    assert numbers["revenue_rel_err"] == 0.0 == numbers["cost_rel_err"]
    assert numbers["answers_compared"] == 3
