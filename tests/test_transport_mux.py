"""Multiplexed data-plane tests.

The serving-plane contract this file pins down (reference parity:
ServerChannels.java requestId correlation + CombineOperator's parallel
per-segment plans):

- many requests share ONE broker→server connection and complete OUT OF
  ORDER — a slow query never head-of-line-blocks a fast one,
- a per-request timeout abandons only its own future; the connection and
  every other in-flight request stay live (late replies are discarded by
  correlation id, never misread as another query's reply),
- ≥8 in-flight requests on one connection round-trip correctly, and the
  fault-injection classes from common/faults.py still yield the
  correct-or-flagged-partial contract over the real TCP mux,
- the columnar (v2) DataTable wire format round-trips value-equal to the
  row (v1) path, and old v1 payloads still decode.

Determinism: ordering is driven by asyncio.Events, not sleeps.
"""
import asyncio
import concurrent.futures
import tempfile
import threading

import numpy as np
import pytest

from fixtures import build_segment
from oracle import Oracle

from pinot_tpu.broker import BrokerRequestHandler, RoutingManager
from pinot_tpu.broker.request_handler import TcpTransport
from pinot_tpu.broker.routing import RoutingTableBuilder
from pinot_tpu.common.cluster_state import ONLINE, TableView
from pinot_tpu.common.datatable import DataTable
from pinot_tpu.common.faults import (CORRUPT, DROP, LATENCY,
                                     MISSING_SEGMENTS,
                                     FaultInjectingTransport, FaultSpec)
from pinot_tpu.query.blocks import IntermediateResultsBlock
from pinot_tpu.pql.parser import compile_pql
from pinot_tpu.server import ServerInstance
from pinot_tpu.transport.tcp import QueryServer, ServerConnection

TABLE = "baseballStats_OFFLINE"


# ---------------------------------------------------------------------------
# transport-level: one connection, many in-flight requests
# ---------------------------------------------------------------------------

def _run(coro):
    return asyncio.run(coro)


def test_mux_out_of_order_completion_no_hol_blocking():
    """A delayed query and a fast query issued on the SAME connection:
    the fast one completes FIRST; the slow one finishes when released."""
    async def main():
        release = asyncio.Event()
        started = asyncio.Event()

        async def handler(payload: bytes) -> bytes:
            if payload == b"slow":
                started.set()
                await release.wait()
            return b"reply:" + payload

        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        conn = ServerConnection("127.0.0.1", server.port)
        try:
            slow = asyncio.ensure_future(conn.request(b"slow", timeout=30))
            await started.wait()          # slow frame is being handled
            fast = await conn.request(b"fast", timeout=30)
            assert fast == b"reply:fast"
            assert not slow.done()        # ...while slow is in flight
            release.set()
            assert await slow == b"reply:slow"
        finally:
            await conn.close()
            await server.stop()

    _run(main())


def test_mux_timeout_cancels_only_its_own_request():
    """A timed-out request abandons ONE future: the connection is not
    torn down, other in-flight requests survive, and the late reply to
    the dead request is discarded instead of desynchronizing the
    stream."""
    async def main():
        release = asyncio.Event()

        async def handler(payload: bytes) -> bytes:
            if payload.startswith(b"wait"):
                await release.wait()
            return b"ok:" + payload

        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        conn = ServerConnection("127.0.0.1", server.port)
        try:
            doomed = asyncio.ensure_future(
                conn.request(b"wait-doomed", timeout=0.2))
            survivor = asyncio.ensure_future(
                conn.request(b"wait-survivor", timeout=30))
            with pytest.raises(asyncio.TimeoutError):
                await doomed
            writer_before = conn._writer
            assert writer_before is not None       # connection kept
            # a fresh request on the same (untouched) connection works
            assert await conn.request(b"echo", timeout=30) == b"ok:echo"
            assert conn._writer is writer_before   # no reconnect
            # releasing produces the survivor's reply AND the doomed
            # request's late reply — which must be dropped by corr id
            release.set()
            assert await survivor == b"ok:wait-survivor"
            assert await conn.request(b"echo2", timeout=30) == b"ok:echo2"
            assert conn._writer is writer_before
            assert conn.num_pending == 0
        finally:
            await conn.close()
            await server.stop()

    _run(main())


def test_mux_many_in_flight_round_trip():
    """≥8 requests simultaneously in flight on ONE connection, each
    correlated back to its own payload. The handler refuses to answer
    until every request has ARRIVED, so completion proves true
    multiplexing, not pipelined turn-taking."""
    n = 12

    async def main():
        arrived = 0
        barrier = asyncio.Event()

        async def handler(payload: bytes) -> bytes:
            nonlocal arrived
            arrived += 1
            if arrived >= n:
                barrier.set()
            await barrier.wait()
            return b"echo:" + payload

        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        conn = ServerConnection("127.0.0.1", server.port)
        try:
            reqs = [asyncio.ensure_future(
                conn.request(b"req-%d" % i, timeout=30)) for i in range(n)]
            results = await asyncio.gather(*reqs)
            assert results == [b"echo:req-%d" % i for i in range(n)]
        finally:
            await conn.close()
            await server.stop()

    _run(main())


def test_mux_connection_loss_fails_all_pending():
    """A transport-level failure (server gone mid-flight) fails every
    pending request promptly so the broker can fail over — no hang."""
    async def main():
        gate = asyncio.Event()

        async def handler(payload: bytes) -> bytes:
            await gate.wait()
            return payload

        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        conn = ServerConnection("127.0.0.1", server.port)
        try:
            reqs = [asyncio.ensure_future(conn.request(b"x%d" % i,
                                                       timeout=30))
                    for i in range(4)]
            await asyncio.sleep(0)        # let the writes flush
            while conn.num_pending < 4:
                await asyncio.sleep(0.01)
            await server.stop()           # hard-closes the channel
            for r in reqs:
                with pytest.raises((ConnectionError, OSError,
                                    asyncio.IncompleteReadError)):
                    await r
            assert conn.num_pending == 0
        finally:
            await conn.close()
            await server.stop()

    _run(main())


# ---------------------------------------------------------------------------
# cluster-level: real TCP mux under fault injection
# ---------------------------------------------------------------------------

class _FixedRoutingBuilder(RoutingTableBuilder):
    def __init__(self, table):
        self.table = table

    def build(self, view, rng):
        return [{srv: list(segs) for srv, segs in self.table.items()}]


@pytest.fixture(scope="module")
def tcp_cluster():
    """2 TCP servers, 2 segments, replication 2 (both segments on both
    servers) — the QPS_r05 topology at test scale."""
    base = tempfile.mkdtemp()
    servers = {f"server_{i}": ServerInstance(f"server_{i}")
               for i in range(2)}
    view = TableView(TABLE, {})
    all_cols = []
    for i, name in enumerate(["seg_a", "seg_b"]):
        seg, cols = build_segment(f"{base}/seg{i}", n=600, seed=70 + i,
                                  name=name)
        all_cols.append(cols)
        for srv in servers.values():
            srv.data_manager.table(TABLE, create=True).add_segment(seg)
        view.segment_states[name] = {s: ONLINE for s in servers}
    endpoints = {name: ("127.0.0.1", srv.start(port=0))
                 for name, srv in servers.items()}
    merged = {k: (np.concatenate([c[k] for c in all_cols])
                  if isinstance(all_cols[0][k], np.ndarray)
                  else sum((c[k] for c in all_cols), []))
              for k in all_cols[0]}
    yield servers, endpoints, view, Oracle(merged)
    for s in servers.values():
        s.stop()


def _tcp_handler(endpoints, view, routing_table, seed=0):
    routing = RoutingManager(builder=_FixedRoutingBuilder(routing_table))
    routing.update_view(view)
    transport = FaultInjectingTransport(TcpTransport(endpoints), seed=seed)
    handler = BrokerRequestHandler(routing, transport,
                                   default_timeout_s=10.0)
    return handler, transport


def _correct_or_flagged(resp, oracle) -> bool:
    full = resp.aggregation_results and \
        resp.aggregation_results[0].value == \
        str(oracle.count(oracle.mask(lambda r: True)))
    flagged = resp.partial_response or bool(resp.exceptions)
    return bool(full or flagged)


def test_mux_tcp_concurrent_queries_under_fault_injection(tcp_cluster):
    """≥8 concurrent queries through the real TCP mux while the fault
    injector throws latency / drops / corrupt frames / missing segments:
    every response is the correct full answer or an honestly flagged
    partial — never a silent wrong answer, never a hang."""
    servers, endpoints, view, oracle = tcp_cluster
    handler, transport = _tcp_handler(
        endpoints, view,
        {"server_0": ["seg_a"], "server_1": ["seg_b"]}, seed=11)
    transport.inject("server_0", FaultSpec(LATENCY, latency_s=0.02,
                                           probability=0.5))
    transport.inject("server_0", FaultSpec(DROP, times=2))
    transport.inject("server_1", FaultSpec(CORRUPT, times=2))
    transport.inject("server_1", FaultSpec(
        MISSING_SEGMENTS, segments=("seg_b",), times=2))

    n = 10
    results = [None] * n

    def one(i):
        results[i] = handler.handle("SELECT COUNT(*) FROM baseballStats")

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert all(r is not None for r in results)
        for resp in results:
            assert _correct_or_flagged(resp, oracle), resp.to_json()
        # the faults actually fired
        assert transport.injected_count("server_0", DROP) == 2
        assert transport.injected_count("server_1", CORRUPT) == 2
    finally:
        handler.close()


def test_mux_tcp_shares_one_connection_per_server(tcp_cluster):
    """Concurrent queries reuse the per-server channel (the mux point of
    the whole exercise) instead of serializing on a connection lock."""
    servers, endpoints, view, oracle = tcp_cluster
    handler, transport = _tcp_handler(
        endpoints, view,
        {"server_0": ["seg_a", "seg_b"]}, seed=3)
    try:
        def one(_):
            return handler.handle("SELECT COUNT(*) FROM baseballStats")

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            responses = list(pool.map(one, range(8)))
        for resp in responses:
            assert _correct_or_flagged(resp, oracle)
        inner = transport.inner
        assert len(inner._conns) == 1          # one channel, many queries
    finally:
        handler.close()


# ---------------------------------------------------------------------------
# parallel per-segment execution
# ---------------------------------------------------------------------------

def _build_engine_segments(n_segments=4, rows=400):
    base = tempfile.mkdtemp()
    segs, all_cols = [], []
    for i in range(n_segments):
        seg, cols = build_segment(f"{base}/s{i}", n=rows, seed=90 + i,
                                  name=f"ps_{i}")
        segs.append(seg)
        all_cols.append(cols)
    merged = {k: (np.concatenate([c[k] for c in all_cols])
                  if isinstance(all_cols[0][k], np.ndarray)
                  else sum((c[k] for c in all_cols), []))
              for k in all_cols[0]}
    return segs, Oracle(merged)


def test_parallel_segment_execution_matches_sequential():
    from pinot_tpu.query.executor import ServerQueryExecutor

    segs, oracle = _build_engine_segments()
    pool = concurrent.futures.ThreadPoolExecutor(4)
    try:
        seq = ServerQueryExecutor(use_device=False)
        par = ServerQueryExecutor(use_device=False, segment_executor=pool)
        for pql in (
                "SELECT COUNT(*), SUM(runs) FROM baseballStats "
                "WHERE yearID >= 2000",
                "SELECT SUM(hits) FROM baseballStats GROUP BY teamID "
                "TOP 500",
                "SELECT playerName, runs FROM baseballStats ORDER BY "
                "runs DESC LIMIT 13"):
            request = compile_pql(pql)
            b_seq = seq.execute(request, segs)
            b_par = par.execute(request, segs)
            assert b_par.exceptions == b_seq.exceptions == []
            assert b_par.stats.num_segments_processed == \
                b_seq.stats.num_segments_processed
            if b_seq.group_map is not None:
                assert b_par.group_map == b_seq.group_map
            elif b_seq.agg_intermediates is not None:
                assert b_par.agg_intermediates == b_seq.agg_intermediates
            if b_seq.selection_rows is not None:
                assert sorted(b_par.selection_rows) == \
                    sorted(b_seq.selection_rows)
    finally:
        pool.shutdown(wait=False)


def test_parallel_segment_execution_deadline_truncates():
    import time as _time
    from pinot_tpu.query.executor import ServerQueryExecutor

    segs, _ = _build_engine_segments()
    pool = concurrent.futures.ThreadPoolExecutor(4)
    try:
        par = ServerQueryExecutor(use_device=False, segment_executor=pool)
        request = compile_pql("SELECT COUNT(*) FROM baseballStats")
        blk = par.execute(request, segs,
                          deadline=_time.monotonic() - 0.001)
        assert any("DeadlineExceededError" in e for e in blk.exceptions)
        assert blk.stats.num_segments_processed < len(segs)
    finally:
        pool.shutdown(wait=False)


def test_parallel_gather_abandons_a_straggler_and_keeps_what_finished():
    """The budget runs out while the gather waits for ONE slow segment:
    it is abandoned, every segment that finished still counts."""
    import threading
    import time as _time
    from pinot_tpu.query.executor import ServerQueryExecutor

    segs, _ = _build_engine_segments()
    assert len(segs) >= 3
    pool = concurrent.futures.ThreadPoolExecutor(len(segs))
    release = threading.Event()
    try:
        par = ServerQueryExecutor(use_device=False, segment_executor=pool)
        work = par._segment_work

        def slow_second(seg, request):
            if seg is segs[1]:
                release.wait(timeout=30)
            return work(seg, request)
        par._segment_work = slow_second
        request = compile_pql("SELECT COUNT(*) FROM baseballStats")
        blk = par.execute(request, segs, deadline=_time.monotonic() + 0.5)
        done = len(segs) - 1
        assert any(f"truncated at {done}/{len(segs)}" in e
                   for e in blk.exceptions), blk.exceptions
        assert blk.stats.num_segments_processed == done
        assert blk.agg_intermediates[0] == sum(
            s.num_docs for s in segs if s is not segs[1])
    finally:
        release.set()
        pool.shutdown(wait=True)


@pytest.fixture(scope="module")
def eight_segments():
    segs, _ = _build_engine_segments(n_segments=8, rows=300)
    return segs


@pytest.fixture
def pooled_executor():
    from pinot_tpu.query.executor import ServerQueryExecutor
    pool = concurrent.futures.ThreadPoolExecutor(4)
    yield ServerQueryExecutor(segment_executor=pool)
    pool.shutdown(wait=True)


_ONE_LAUNCH_PQL = ("SELECT SUM(salary), COUNT(*) FROM baseballStats "
                   "WHERE runs > '30'")


def _profiled(executor, request, segments, **kw):
    from pinot_tpu.obs import profiler as obs_profiler
    profile = obs_profiler.QueryProfile("t")
    with obs_profiler.active(profile, None):
        return executor.execute(request, segments, **kw), profile


def test_a_launch_finds_its_integer_scalars_on_the_device(
        eight_segments, pooled_executor, monkeypatch):
    """DictId bounds and doc counts go to the program as device
    scalars, uploaded once a value: a warm launch transfers nothing."""
    import jax
    from pinot_tpu.ops import kernels
    from pinot_tpu.query import execution
    seen = []
    real = kernels.run_segment_kernel

    def spy(padded, filt, aggs, group, select, cols, params, num_docs):
        seen.append(tuple(params) + (num_docs,))
        return real(padded, filt, aggs, group, select, cols, params,
                    num_docs)
    monkeypatch.setattr(kernels, "run_segment_kernel", spy)
    request = compile_pql(_ONE_LAUNCH_PQL)
    _profiled(pooled_executor, request, eight_segments)
    first, seen[:] = list(seen), []
    uploads = []
    real_put = jax.device_put
    # the launch's OWN uploads: `_device_scalar` hands `jax.device_put`
    # a numpy integer scalar. Whatever else in the process uploads
    # meanwhile (a lane, by another test's leftover thread under
    # `--dist loadfile`) is not this launch's and is not counted
    monkeypatch.setattr(
        jax, "device_put",
        lambda *a, **k: uploads.append(a[0]) or real_put(*a, **k))
    misses = execution._device_scalar.cache_info().misses
    _profiled(pooled_executor, request, eight_segments)
    assert len(first) == len(seen) == 8
    assert [u for u in uploads if isinstance(u, np.integer)] == []
    assert execution._device_scalar.cache_info().misses == misses
    for a in first:
        assert len(a) >= 2 and all(isinstance(p, jax.Array) for p in a)
    # the same arrays (pool workers launch in any order)
    assert {tuple(map(id, a)) for a in first} == \
        {tuple(map(id, b)) for b in seen}
    # one table entry a device: a scalar lives where the lanes do
    here, there = jax.devices()[0], jax.devices()[1]
    assert execution._device_scalar("int32", 7, here) is \
        execution._device_scalar("int32", 7, here)
    assert execution._device_scalar("int32", 7, there).devices() == {there}
    assert all(p.devices() == {here} for p in seen[0])


def test_a_ladders_launches_take_no_host_integer_scalar(
        eight_segments, pooled_executor, monkeypatch):
    """A group-by ladder's rungs launch through the same table: no
    integer numpy scalar reaches the program, whatever else a rung
    hands over (arrays, floats), and the answer is the host twin's."""
    import jax
    import numpy as np
    from pinot_tpu.ops import kernels
    from pinot_tpu.query.executor import ServerQueryExecutor
    seen = []
    real = kernels.run_segment_kernel

    def spy(padded, filt, aggs, group, select, cols, params, num_docs):
        seen.append(tuple(params) + (num_docs,))
        return real(padded, filt, aggs, group, select, cols, params,
                    num_docs)
    request = compile_pql("SELECT SUM(salary) FROM baseballStats "
                          "WHERE runs > '30' GROUP BY teamID TOP 40")
    want, _ = _profiled(ServerQueryExecutor(use_device=False), request,
                        eight_segments)
    monkeypatch.setattr(kernels, "run_segment_kernel", spy)
    blk, profile = _profiled(pooled_executor, request, eight_segments)
    assert len(seen) >= 8 and profile.paths == {"scan": 8}
    for operands in seen:
        assert not any(isinstance(p, np.integer) for p in operands)
        assert isinstance(operands[-1], jax.Array)      # the doc count
    assert blk.exceptions == []
    assert sorted(blk.group_map) == sorted(want.group_map)


def test_a_plan_refusing_while_running_lands_on_the_host_twin(
        eight_segments, pooled_executor, monkeypatch):
    from pinot_tpu.query import execution
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.plan import UnsupportedOnDevice
    request = compile_pql(_ONE_LAUNCH_PQL)
    want, _ = _profiled(ServerQueryExecutor(use_device=False), request,
                        eight_segments)
    real_gather = execution.gather_operands

    def refuse_the_third(plan):
        if plan.segment is eight_segments[2]:
            raise UnsupportedOnDevice("found while running")
        return real_gather(plan)
    monkeypatch.setattr(execution, "gather_operands", refuse_the_third)
    blk, profile = _profiled(pooled_executor, request, eight_segments)
    assert blk.exceptions == []
    assert profile.paths == {"scan": 7, "host": 1}
    assert blk.stats.num_segments_processed == 8
    assert blk.agg_intermediates[1] == want.agg_intermediates[1]
    assert blk.agg_intermediates[0] == pytest.approx(
        want.agg_intermediates[0], rel=1e-6)


# ---------------------------------------------------------------------------
# DataTable wire-format compatibility
# ---------------------------------------------------------------------------

ALL_VERSIONS = (1, 2, 3)


def _sample_tables():
    group_by = DataTable(
        kind=2, columns=["d1", "d2", "sum(m)", "avg(m)", "fasthll(x)"],
        num_group_cols=2,
        rows=[("x", 1, 10.0, (10.0, 2), None),
              ("y", 2, 5.5, (5.5, 1), True),
              ("z", -3, float("inf"), (0.0, 0), 2 ** 90)],
        metadata={"numDocsScanned": "3", "totalDocs": "10"},
        exceptions=["boom"])
    selection = DataTable(
        kind=3, columns=["name", "year", "score"],
        rows=[(f"p{i}", 1990 + i, i * 1.5) for i in range(64)],
        metadata={"selectionDisplayCols": "2"})
    aggregation = DataTable(
        kind=1, columns=["count(*)"], rows=[(123,)],
        metadata={"numDocsScanned": "123"})
    empty = DataTable()
    return [group_by, selection, aggregation, empty]


def test_datatable_cross_version_matrix():
    """Every (encode version → decoder) pair in the rollout matrix —
    old server → new broker AND new server → old-style payloads —
    decodes value-equal: same rows, same schema, same metadata."""
    for dt in _sample_tables():
        decoded = {v: DataTable.from_bytes(dt.to_bytes(version=v))
                   for v in ALL_VERSIONS}
        for v, rt in decoded.items():
            assert list(rt.rows) == list(dt.rows), f"v{v}"
            assert rt.columns == dt.columns
            assert rt.metadata == dt.metadata
            assert rt.exceptions == dt.exceptions
            assert rt.num_group_cols == dt.num_group_cols
        # blocks rebuilt from every version agree with each other
        from pinot_tpu.query.combine import (group_map_of,
                                             selection_rows_of)
        blocks = {v: rt.to_block() for v, rt in decoded.items()}
        for v, b in blocks.items():
            ref = blocks[1]
            assert group_map_of(b) == group_map_of(ref), f"v{v}"
            assert b.agg_intermediates == ref.agg_intermediates
            assert selection_rows_of(b) == selection_rows_of(ref)


def test_datatable_v3_reencode_roundtrips_all_versions():
    """A decoded v3 table re-encodes (from its column blocks, rows
    never materialized) to every version bit-compatibly."""
    for dt in _sample_tables():
        v3 = DataTable.from_bytes(dt.to_bytes(version=3))
        for v in ALL_VERSIONS:
            rt = DataTable.from_bytes(v3.to_bytes(version=v))
            assert list(rt.rows) == list(dt.rows)
            assert rt.columns == dt.columns


def test_datatable_columnar_preserves_python_types():
    for version in (2, 3):
        dt = DataTable(kind=3, columns=["i", "f", "s", "o"],
                       rows=[(np.int64(7), np.float64(2.5), "a", True),
                             (8, 3.5, "b", False)])
        rt = DataTable.from_bytes(dt.to_bytes(version=version))
        assert list(rt.rows) == [(7, 2.5, "a", True), (8, 3.5, "b", False)]
        assert type(rt.rows[0][0]) is int
        assert type(rt.rows[0][1]) is float
        assert type(rt.rows[0][3]) is bool


def test_datatable_from_block_to_block_roundtrip():
    from pinot_tpu.query.combine import group_map_of

    request = compile_pql(
        "SELECT SUM(m) FROM t GROUP BY d1, d2 TOP 10")
    blk = IntermediateResultsBlock()
    blk.group_map = {("a", 1): [2.0], ("b", 2): [3.0]}
    dt = DataTable.from_block(request, blk)
    rt = DataTable.from_bytes(dt.to_bytes())
    assert group_map_of(rt.to_block()) == blk.group_map


def test_datatable_v3_zero_copy_aliasing_safety():
    """The aliasing contract: decoding from an immutable bytes frame
    may alias (and must keep the frame alive); decoding from a REUSED
    writable buffer must copy — clobbering the buffer afterwards cannot
    change the decoded values."""
    dt = DataTable(kind=3, columns=["a", "b"],
                   rows=[(i, float(i) * 0.5) for i in range(256)])
    payload = dt.to_bytes(version=3)

    # immutable bytes: views may alias; frame stays alive via the array
    rt = DataTable.from_bytes(payload)
    assert rt.col_data is not None
    del payload                       # only the decoded table holds it
    assert list(rt.rows)[:3] == [(0, 0.0), (1, 0.5), (2, 1.0)]

    # writable frame arena (the reuse case): decode, clobber, re-check
    arena = bytearray(dt.to_bytes(version=3))
    rt2 = DataTable.from_bytes(memoryview(arena))
    before = [tuple(r) for r in rt2.rows]
    arena[:] = b"\xee" * len(arena)   # simulate frame-buffer reuse
    rt2._rows = None                  # re-materialize from col_data
    assert [tuple(r) for r in rt2.rows] == before
    for col in rt2.col_data:
        if isinstance(col, np.ndarray):
            assert col.base is None or col.base.obj is not arena


# ---------------------------------------------------------------------------
# columnar-vs-row reduce bit-parity
# ---------------------------------------------------------------------------

def _reduce_both_ways(pql, blocks_rows):
    """Reduce the same per-server payloads decoded via the row path
    (v2) and the columnar path (v3); returns both response JSONs."""
    from pinot_tpu.query.reduce import BrokerReduceService

    request = compile_pql(pql)
    out = []
    for version in (2, 3):
        tables = []
        for blk in blocks_rows:
            dt = DataTable.from_block(request, blk)
            tables.append(DataTable.from_bytes(dt.to_bytes(version)))
        resp = BrokerReduceService().reduce(
            request, [t.to_block() for t in tables],
            num_servers_queried=len(tables),
            num_servers_responded=len(tables))
        out.append(resp.to_json())
    return out


def _stats_block(**kw):
    blk = IntermediateResultsBlock(**kw)
    blk.stats.num_docs_scanned = 10
    blk.stats.total_docs = 100
    return blk


def test_reduce_parity_aggregation_count_sum():
    b1 = _stats_block(agg_intermediates=[7, 12.5])
    b2 = _stats_block(agg_intermediates=[3, 2.25])
    row, col = _reduce_both_ways(
        "SELECT COUNT(*), SUM(m) FROM t", [b1, b2])
    assert row == col


def test_reduce_parity_group_by_all_folds():
    """COUNT/SUM/MIN/MAX group-by over 3 servers with overlapping and
    disjoint keys: the vectorized fold must be bit-identical to the
    dict merge, including top-N order and formatted values."""
    import random
    rng = random.Random(5)
    blocks = []
    for _ in range(3):
        gm = {}
        for k in rng.sample(range(40), 25):
            gm[(f"g{k}", k)] = [rng.randint(1, 9),
                                round(rng.uniform(-50, 50), 3),
                                float(rng.randint(-20, 20)),
                                float(rng.randint(-20, 20))]
        blocks.append(_stats_block(group_map=gm))
    row, col = _reduce_both_ways(
        "SELECT COUNT(*), SUM(m), MIN(m), MAX(m) FROM t "
        "GROUP BY d1, d2 TOP 12", blocks)
    assert row == col


def test_reduce_parity_group_by_obj_intermediates_fall_back():
    """AVG pairs cannot fold vectorized — the columnar payload must
    fall back to the row engine and still match exactly."""
    b1 = _stats_block(group_map={("a",): [(10.0, 2)],
                                 ("b",): [(3.0, 1)]})
    b2 = _stats_block(group_map={("a",): [(2.0, 2)],
                                 ("c",): [(9.0, 3)]})
    row, col = _reduce_both_ways(
        "SELECT AVG(m) FROM t GROUP BY d TOP 5", [b1, b2])
    assert row == col


def test_reduce_parity_group_by_obj_trim_does_not_crash():
    """A single columnar AVG payload exceeding 4×trim must trim through
    the row engine (object intermediates cannot fold vectorized)."""
    gm = {(f"g{i}",): [(float(i), 2)] for i in range(20_050)}
    row, col = _reduce_both_ways(
        "SELECT AVG(m) FROM t GROUP BY d TOP 3", [_stats_block(group_map=gm)])
    assert row == col
    assert len(row["aggregationResults"][0]["groupByResult"]) == 3


def test_reduce_parity_group_by_int64_exact_past_2_53():
    """int64 COUNT folds stay EXACT past 2^53 (no float64 accumulation
    in the columnar engine — COUNT finals format as exact ints), and
    ordering ties exactly where the row oracle's float sort key ties."""
    big = (1 << 60)
    b1 = _stats_block(group_map={("a",): [big + 3], ("b",): [big + 1]})
    b2 = _stats_block(group_map={("a",): [1], ("c",): [big + 2]})
    row, col = _reduce_both_ways(
        "SELECT COUNT(*) FROM t GROUP BY d TOP 3", [b1, b2])
    assert row == col
    vals = [g["value"]
            for g in col["aggregationResults"][0]["groupByResult"]]
    # exact values AND exact (int-semantics) descending order
    assert vals == [str(big + 4), str(big + 2), str(big + 1)]


def test_reduce_parity_zero_row_block_keeps_columnar_engine():
    """A server that matched nothing must not demote the merge: the
    result equals the row engine AND the merged block stays columnar."""
    from pinot_tpu.query.combine import combine_blocks

    empty = _stats_block(group_map={})
    full = _stats_block(group_map={("a",): [5], ("b",): [7]})
    request = compile_pql("SELECT COUNT(*) FROM t GROUP BY d TOP 5")
    tables = []
    for blk in (empty, full, empty):
        dt = DataTable.from_block(request, blk)
        tables.append(DataTable.from_bytes(dt.to_bytes(3)))
    merged = combine_blocks(request, [t.to_block() for t in tables])
    assert merged.group_cols is not None     # columnar path survived
    row, col = _reduce_both_ways(
        "SELECT COUNT(*) FROM t GROUP BY d TOP 5",
        [_stats_block(group_map={}),
         _stats_block(group_map={("a",): [5], ("b",): [7]}),
         _stats_block(group_map={})])
    assert row == col


def test_reduce_parity_group_by_mixed_type_keys_fall_back():
    """A key column mixing str and int (or None) serializes as an
    object-tagged block; the columnar gate must reject it so '5' and 5
    stay DISTINCT groups (np.unique would stringify-collapse them)."""
    b1 = _stats_block(group_map={("5",): [4], (5,): [2]})
    b2 = _stats_block(group_map={(5,): [1], (None,): [3]})
    row, col = _reduce_both_ways(
        "SELECT COUNT(*) FROM t GROUP BY d TOP 5", [b1, b2])
    assert row == col
    groups = {tuple(g["group"]): g["value"] for g in
              col["aggregationResults"][0]["groupByResult"]}
    assert groups[("5",)] == "4" and groups[(5,)] == "3"


def test_reduce_parity_group_by_nan_keys_fall_back():
    """np.unique treats every NaN as equal; the dict oracle keeps NaN
    keys distinct — NaN-keyed payloads must use the row engine."""
    import json as _json
    nan = float("nan")
    b1 = _stats_block(group_map={(nan,): [10], (1.0,): [20]})
    b2 = _stats_block(group_map={(nan,): [5], (2.0,): [7]})
    row, col = _reduce_both_ways(
        "SELECT COUNT(*) FROM t GROUP BY d TOP 5", [b1, b2])
    # dict equality is poisoned by nan != nan — compare the serialized
    # responses instead
    assert _json.dumps(row) == _json.dumps(col)
    vals = sorted(g["value"] for g in
                  col["aggregationResults"][0]["groupByResult"])
    # two DISTINCT NaN groups (10 and 5), never one merged 15
    assert vals == ["10", "20", "5", "7"]


def test_reduce_parity_group_by_int64_sum_overflow_falls_back():
    """Per-server int sums that would wrap an int64 fold across the
    merge must take the row engine's unbounded python-int path."""
    big = 1 << 62
    blocks = [_stats_block(group_map={("a",): [big]}) for _ in range(2)]
    row, col = _reduce_both_ways(
        "SELECT SUM(m) FROM t GROUP BY d TOP 2",
        [_stats_block(group_map={("a",): [big]}) for _ in range(2)])
    del blocks
    assert row == col
    v = col["aggregationResults"][0]["groupByResult"][0]["value"]
    assert float(v) > 0          # never the wrapped negative int64


def test_reduce_parity_selection_order_by():
    import random
    rng = random.Random(11)
    blocks = []
    for _ in range(3):
        rows = [(rng.randint(0, 50), f"n{rng.randint(0, 99)}",
                 round(rng.uniform(0, 1), 6)) for _ in range(40)]
        blocks.append(_stats_block(
            selection_rows=rows, selection_columns=["x", "name", "s"]))
    for pql in (
            "SELECT x, name, s FROM t ORDER BY x DESC LIMIT 17",
            "SELECT x, name, s FROM t ORDER BY name, s DESC LIMIT 9",
            "SELECT x, name, s FROM t LIMIT 30"):
        row, col = _reduce_both_ways(pql, [
            _stats_block(selection_rows=list(b.selection_rows),
                         selection_columns=list(b.selection_columns))
            for b in blocks])
        assert row == col, pql


def test_reduce_parity_vector_similarity_merge():
    """Vector top-k merge order (score desc, segment/docId asc) through
    the lexsort engine matches the row-tuple oracle."""
    import random
    rng = random.Random(3)
    cols = ["id", "$score", "$segmentName", "$docId"]
    blocks = []
    for s in range(3):
        rows = [(rng.randint(0, 1000), round(rng.uniform(0, 1), 6),
                 f"seg_{s}", d) for d in range(20)]
        # duplicate scores across segments exercise the tiebreaker
        rows[0] = (1, 0.5, f"seg_{s}", 0)
        blocks.append(_stats_block(
            selection_rows=rows, selection_columns=list(cols)))
    row, col = _reduce_both_ways(
        "SELECT id, VECTOR_SIMILARITY(emb, [1.0, 0.0], 15) FROM t",
        blocks)
    assert row == col


# ---------------------------------------------------------------------------
# shared-memory reply transport (colocated broker↔server)
# ---------------------------------------------------------------------------

def test_shm_reply_round_trip_and_unlink(monkeypatch):
    """A reply over the threshold rides shared memory: the broker-side
    connection resolves the reference, the decoder copies out of the
    writable segment, and the segment is unlinked after consumption."""
    from multiprocessing import shared_memory

    from pinot_tpu.broker.request_handler import TcpTransport
    from pinot_tpu.common.serde import instance_request_to_bytes
    from pinot_tpu.common.request import InstanceRequest

    monkeypatch.setenv("PINOT_TPU_SHM_MIN_BYTES", "1024")

    big = DataTable(kind=3, columns=["a", "b"],
                    rows=[(i, float(i)) for i in range(4096)])
    payload_len = len(big.to_bytes())
    assert payload_len > 1024
    names = []

    async def handler(payload: bytes) -> bytes:
        return big.to_bytes()

    async def main():
        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        transport = TcpTransport(
            {"s0": ("127.0.0.1", server.port)})
        try:
            req = instance_request_to_bytes(InstanceRequest(
                request_id=1, query=compile_pql(
                    "SELECT a, b FROM t LIMIT 10")))
            from pinot_tpu.transport.shm import ShmReply
            raw = await transport.query("s0", req, timeout=30)
            assert isinstance(raw, ShmReply)
            names.append(raw._seg.name)
            dt = DataTable.from_bytes(raw.view)
            raw.close()
            assert list(dt.rows) == list(big.rows)
        finally:
            await transport.close()
            await server.stop()

    _run(main())
    # consumed segment must be gone from the system
    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=names[0])


def test_shm_small_replies_stay_inline(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_SHM_MIN_BYTES", "1048576")

    from pinot_tpu.broker.request_handler import TcpTransport
    from pinot_tpu.transport.shm import ShmReply

    async def handler(payload: bytes) -> bytes:
        return b"tiny-reply"

    async def main():
        server = QueryServer("127.0.0.1", 0, handler=None,
                             async_handler=handler)
        await server.start()
        transport = TcpTransport({"s0": ("127.0.0.1", server.port)})
        try:
            raw = await transport.query("s0", b"x", timeout=30)
            assert not isinstance(raw, ShmReply)
            assert bytes(raw) == b"tiny-reply"
        finally:
            await transport.close()
            await server.stop()

    _run(main())
