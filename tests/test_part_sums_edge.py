"""Exact integer sums at the edge of their slices (PR 37).

A SUM over an INT column behind a dictionary reads `value - least` as
7-bit slices on int8 lanes, sums each slice in int32 on the device and
joins the slices in int64 on the host (`ops/kernels.py` `_part_sums`,
`_dense_group_sums`, the block compaction; `query/execution.py`). Held
here at the widest the benchmark's `lo_revenue` can be: a dictionary
whose least value is 0 and whose greatest is the greatest 24-bit value,
so that every matched row carries 127 in three slices and 7 in the
fourth, against Python's own integers; and the head-room of the int32
partial sums is read from the code's own limit.
"""
import numpy as np
import pytest

from pinot_tpu.common.metrics import ServerQueryPhase
from pinot_tpu.obs import profiler as obs_profiler
from pinot_tpu.obs.tracing import TraceContext

TOP = (1 << 24) - 1             # 16,777,215 = slices 127, 127, 127, 7
ROWS = 70_000                   # nine blocks of 8192 rows, the last short
BENCH_SEGMENT_ROWS = 6_250_496  # a padded segment of the benchmark's cells


@pytest.fixture(scope="module")
def edge(tmp_path_factory):
    """(segment, columns): `m` is TOP on every row but the first, which
    holds the dictionary's least value 0 and matches no filter below."""
    from pinot_tpu.common.datatype import DataType
    from pinot_tpu.common.schema import Schema, dimension, metric
    from pinot_tpu.common.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    rows = np.arange(ROWS)
    cols = {"m": np.where(rows == 0, 0, TOP).astype(np.int32),
            "g": (rows % 5).astype(np.int32),
            # 0 on the first row, 2 on a hundredth of the rest, else 1
            "f": np.where(rows == 0, 0,
                          np.where(rows % 101 == 7, 2, 1)).astype(np.int32)}
    schema = Schema("edge", [dimension("g", DataType.INT),
                             dimension("f", DataType.INT),
                             metric("m", DataType.INT)])
    path = str(tmp_path_factory.mktemp("edge_segment"))
    SegmentCreator(schema, TableConfig("edge"),
                   segment_name="edge_0").build(cols, path)
    seg = ImmutableSegmentLoader.load(path)
    assert seg.data_source("m").int_part_info() == (4, 0)
    return seg, cols


def answer(seg, pql):
    """-> (the broker's JSON, the layouts of the traced group tables)."""
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.plan import preprocess_request
    from pinot_tpu.query.reduce import BrokerReduceService
    request = preprocess_request(
        [seg], BrokerRequestOptimizer().optimize(compile_pql(pql)))
    profile = obs_profiler.QueryProfile("edge")
    trace = TraceContext(root_name="server")
    with obs_profiler.active(profile, None):
        block = ServerQueryExecutor().execute(request, [seg], trace=trace)
    assert profile.to_json()["paths"] == {"scan": 1}
    body = BrokerReduceService().reduce(request, [block]).to_json()
    assert not body["exceptions"]
    # a `groupTable` span a query, a layout a segment that ran a table
    return body, [layout for s in trace.to_list()
                  if s["name"] == ServerQueryPhase.GROUP_TABLE
                  for layout in s["attrs"]["layout"]]


def python_sum(cols, mask, group=None):
    """Python's own integers over the matched rows: a sum, or {g: sum}."""
    m, g = cols["m"].tolist(), cols["g"].tolist()
    picked = np.nonzero(mask)[0].tolist()
    if group is None:
        return sum(m[i] for i in picked)
    out = {}
    for i in picked:
        out[g[i]] = out.get(g[i], 0) + m[i]
    return out


def test_a_scan_of_saturated_slices_is_pythons_integer_sum(edge):
    seg, cols = edge
    body, _layouts = answer(seg, "SELECT SUM(m) FROM edge WHERE f >= 1")
    want = python_sum(cols, cols["f"] >= 1)
    assert want == (ROWS - 1) * TOP > 2**40
    # float32 could not hold it: the next float32 is 131,072 away
    assert int(np.float32(want)) != want
    assert int(float(body["aggregationResults"][0]["value"])) == want


@pytest.mark.parametrize("where,layout", [("f >= 1", "dense"),
                                          ("f = 2", "compacted")])
def test_a_group_table_of_saturated_slices_is_pythons_integer_sum(
        edge, where, layout):
    seg, cols = edge
    body, layouts = answer(
        seg, f"SELECT SUM(m) FROM edge WHERE {where} GROUP BY g TOP 10")
    assert layouts == [layout]
    mask = cols["f"] >= 1 if where == "f >= 1" else cols["f"] == 2
    want = python_sum(cols, mask, group=True)
    assert len(want) == 5
    assert int(mask.sum()) == ROWS - 1 or 0 < int(mask.sum()) < ROWS // 100
    got = {int(g["group"][0]): int(float(g["value"]))
           for g in body["aggregationResults"][0]["groupByResult"]}
    assert got == want


def test_int32_partials_have_head_room_at_the_benchmarks_segment_size():
    """A slice is at most 127, so a full int32 reduce of a segment's
    slice is exact while 127 x padded rows < 2**31: `_part_sums`' own
    test, and `DENSE_ROWS_LIMIT`'s for the carry of a dense table. The
    benchmark's padded segments lie under it by 2.7 times; beyond it a
    scan keeps per-block partials for the host's int64 (`partsT`)."""
    import jax
    import jax.numpy as jnp
    from pinot_tpu.ops import kernels
    assert 127 * BENCH_SEGMENT_ROWS == 793_812_992 < 2**31 - 1
    assert BENCH_SEGMENT_ROWS <= kernels.DENSE_ROWS_LIMIT
    assert 127 * kernels.DENSE_ROWS_LIMIT < 2**31

    def reduced_shape(padded):
        lanes = jax.ShapeDtypeStruct((4, padded), jnp.int8)
        mask = jax.ShapeDtypeStruct((padded,), jnp.bool_)
        flags = []

        def trace(lanes, mask):
            out, reduced = kernels._part_sums(lanes, mask)
            flags.append(reduced)
            return out
        return jax.eval_shape(trace, lanes, mask), flags[0]

    out, reduced = reduced_shape(BENCH_SEGMENT_ROWS)
    assert reduced and out.shape == (4,) and out.dtype == jnp.int32
    # the last padded size under the limit, and the first over it
    edge_rows = (2**31 - 1) // 127 // kernels.BLOCK * kernels.BLOCK
    assert reduced_shape(edge_rows)[1] is True
    out, reduced = reduced_shape(edge_rows + kernels.BLOCK)
    assert reduced is False
    assert out.shape == (4, edge_rows // kernels.BLOCK + 1)
    assert 127 * kernels.BLOCK < 2**20
