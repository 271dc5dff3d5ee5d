"""Server-plane tests: serde, DataTable, refcounted segments, scheduler,
and the full request path (bytes in → DataTable bytes out, over TCP too).

Mirrors the reference's server-side unit tiers: data-manager refcount
semantics, QueryScheduler behavior, DataTable round-trips, and
ScheduledRequestHandler-style end-to-end request handling.
"""
import asyncio
import tempfile
import threading
import time

import numpy as np
import pytest

from fixtures import build_segment
from oracle import Oracle

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.common.request import InstanceRequest
from pinot_tpu.common.serde import (instance_request_from_bytes,
                                    instance_request_to_bytes,
                                    obj_from_bytes, obj_to_bytes,
                                    request_from_json, request_to_json)
from pinot_tpu.pql.parser import compile_pql
from pinot_tpu.query.reduce import BrokerReduceService
from pinot_tpu.server import (ServerInstance, TableDataManager,
                              make_scheduler)
from pinot_tpu.transport.tcp import EventLoopThread, ServerConnection


# -- serde ------------------------------------------------------------------

def test_object_serde_roundtrip():
    cases = [
        None, 0, -1, 2**62, 2**100, 3.14, float("inf"), "héllo", b"\x00\xff",
        (1, 2.5, "x"), [1, [2, [3]]], {1, 2, 3}, {"a", "b"},
        {"k": 1, "j": (2.0, 3)}, {(1, 2): {3, 4}},
        (None, set(), {}, []),
        True, False, (True, 1, False, 0), {"flag": True},
    ]
    for v in cases:
        assert obj_from_bytes(obj_to_bytes(v)) == v, v
    # booleans must keep their type across the wire (distinct tag), not
    # collapse to 1/0 like the round-1 int encoding did
    for v in (True, False):
        rt = obj_from_bytes(obj_to_bytes(v))
        assert isinstance(rt, bool) and rt is v
    rt = obj_from_bytes(obj_to_bytes((True, 1)))
    assert isinstance(rt[0], bool) and not isinstance(rt[1], bool)


def test_request_json_roundtrip():
    pqls = [
        "SELECT COUNT(*) FROM t WHERE a = 'x' AND b IN (1,2,3) OR c > 5",
        "SELECT SUM(m), PERCENTILE95(m) FROM t WHERE x BETWEEN 1 AND 9 "
        "GROUP BY d1, d2 HAVING SUM(m) > 100 TOP 42",
        "SELECT c1, c2 FROM t ORDER BY c1 DESC LIMIT 7, 21",
    ]
    for pql in pqls:
        r = compile_pql(pql)
        r2 = request_from_json(request_to_json(r))
        assert request_to_json(r2) == request_to_json(r), pql


def test_instance_request_bytes_roundtrip():
    req = InstanceRequest(
        request_id=42, query=compile_pql("SELECT MAX(x) FROM t"),
        search_segments=["s1", "s2"], enable_trace=True, broker_id="b0")
    r2 = instance_request_from_bytes(instance_request_to_bytes(req))
    assert r2.request_id == 42
    assert r2.search_segments == ["s1", "s2"]
    assert r2.enable_trace is True
    assert r2.query.aggregations[0].function_name == "MAX"


def test_datatable_roundtrip_group_by():
    req = compile_pql("SELECT SUM(m), AVG(m) FROM t GROUP BY d1, d2")
    dt = DataTable(kind=2, columns=["d1", "d2", "sum(m)", "avg(m)"],
                   num_group_cols=2,
                   rows=[("x", 1, 10.0, (10.0, 2)), ("y", 2, 5.5, (5.5, 1))],
                   metadata={"numDocsScanned": "3", "totalDocs": "10"},
                   exceptions=["boom"])
    dt2 = DataTable.from_bytes(dt.to_bytes())
    assert dt2.rows == dt.rows
    assert dt2.columns == dt.columns
    assert dt2.exceptions == ["boom"]
    blk = dt2.to_block()
    assert blk.group_map[("x", 1)] == [10.0, (10.0, 2)]
    assert blk.stats.num_docs_scanned == 3


# -- data manager -----------------------------------------------------------

def test_refcounted_segment_swap():
    base = tempfile.mkdtemp()
    seg1, _ = build_segment(base + "/a", n=1000, seed=1, name="seg_a")
    tdm = TableDataManager("t")
    tdm.add_segment(seg1)
    acquired, missing = tdm.acquire_segments(["seg_a", "nope"])
    assert [s.name for s in acquired] == ["seg_a"]
    assert missing == ["nope"]

    # replace while acquired: old manager stays alive until released
    seg1b, _ = build_segment(base + "/b", n=500, seed=2, name="seg_a")
    tdm.add_segment(seg1b)
    assert acquired[0].refcount == 1           # table dropped its ref
    assert acquired[0].segment.num_docs == 1000
    acquired2, _ = tdm.acquire_segments(["seg_a"])
    assert acquired2[0].segment.num_docs == 500
    tdm.release_segment(acquired[0])
    assert acquired[0].refcount == 0
    tdm.release_segment(acquired2[0])
    tdm.remove_segment("seg_a")
    assert tdm.segment_names() == []


def test_scheduler_fcfs_and_tokenbucket():
    for algo in ("fcfs", "tokenbucket"):
        sched = make_scheduler(algo, num_workers=2)
        futures = [sched.submit("t", lambda i=i: i * i) for i in range(8)]
        assert sorted(f.result(timeout=5) for f in futures) == \
            [i * i for i in range(8)]
        err = sched.submit("t", lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            err.result(timeout=5)
        sched.shutdown()


def test_tokenbucket_prefers_higher_token_group():
    sched = make_scheduler("tokenbucket", num_workers=1)
    release = threading.Event()
    blocked = sched.submit("warm", lambda: release.wait(5))
    # pin balances: "hog" deeply in debt, "idle" fresh — then queue both
    # while the single worker is occupied so the drain order is decided
    # purely by token priority
    sched.queue.group("hog").available_tokens = -1e6
    sched.queue.group("idle").available_tokens = 100.0
    order = []
    f_hog = sched.submit("hog", lambda: order.append("hog"))
    f_idle = sched.submit("idle", lambda: order.append("idle"))
    release.set()
    f_hog.result(timeout=5)
    f_idle.result(timeout=5)
    blocked.result(timeout=5)
    sched.shutdown()
    assert order == ["idle", "hog"]


# -- end-to-end server path -------------------------------------------------

@pytest.fixture(scope="module")
def server_with_data():
    base = tempfile.mkdtemp()
    segs, all_cols = [], []
    for i in range(3):
        seg, cols = build_segment(f"{base}/seg{i}", n=2000, seed=50 + i,
                                  name=f"bs_{i}")
        segs.append(seg)
        all_cols.append(cols)
    merged = {k: (np.concatenate([c[k] for c in all_cols])
                  if isinstance(all_cols[0][k], np.ndarray)
                  else sum((c[k] for c in all_cols), []))
              for k in all_cols[0]}
    server = ServerInstance("server_0")
    tdm = server.data_manager.table("baseballStats", create=True)
    for seg in segs:
        tdm.add_segment(seg)
    yield server, Oracle(merged)
    server.stop()


def _query_server(server, pql, segments=None):
    req = InstanceRequest(request_id=1, query=compile_pql(pql),
                          search_segments=segments)
    dt = DataTable.from_bytes(
        server.handle_request_bytes(instance_request_to_bytes(req)))
    return dt


def test_server_executes_aggregation(server_with_data):
    server, oracle = server_with_data
    m = oracle.mask(lambda r: r["yearID"] >= 2005)
    dt = _query_server(server,
                       "SELECT COUNT(*), SUM(runs) FROM baseballStats "
                       "WHERE yearID >= 2005")
    blk = dt.to_block()
    assert blk.agg_intermediates[0] == oracle.count(m)
    assert blk.agg_intermediates[1] == pytest.approx(oracle.sum("runs", m))
    assert blk.stats.num_segments_processed == 3
    assert dt.metadata["requestId"] == "1"


def test_server_respects_search_segments(server_with_data):
    server, _ = server_with_data
    dt = _query_server(server, "SELECT COUNT(*) FROM baseballStats",
                       segments=["bs_0", "bs_2"])
    blk = dt.to_block()
    assert blk.agg_intermediates[0] == 4000


def test_server_reports_missing_segments(server_with_data):
    server, _ = server_with_data
    dt = _query_server(server, "SELECT COUNT(*) FROM baseballStats",
                       segments=["bs_0", "gone_1"])
    assert any("SegmentMissingError" in e for e in dt.exceptions)
    assert dt.to_block().agg_intermediates[0] == 2000


_LADDER_PQL = ("SELECT SUM(runs) FROM baseballStats "
               "WHERE yearID >= 1999 GROUP BY teamID TOP 5")


@pytest.mark.parametrize("walk,pql", [
    # one launch a segment
    ("one_launch", "SELECT SUM(runs) FROM baseballStats "
                   "WHERE yearID >= 1999"),
    # a group-by ladder a segment: two or three launches
    ("ladder", _LADDER_PQL),
    # the fault only in phase B: every scout launched and pulled, the
    # first table's launch fails
    ("ladder_table", _LADDER_PQL)])
def test_device_fault_surfaces_and_never_reaches_the_host_twin(
        server_with_data, monkeypatch, walk, pql):
    """Only the planner's own verdicts (UnsupportedOnDevice,
    GroupsLimitExceeded) may route a segment to host_exec. A runtime
    fault of the device — compile failure, RESOURCE_EXHAUSTED — must
    come back as an exception, not as a clean-looking host answer."""
    from pinot_tpu.ops import kernels
    from pinot_tpu.query import host_exec
    server, _ = server_with_data
    real, scouts = kernels.run_segment_kernel, []

    def device_fault(padded, filt, aggs, group_spec, *rest):
        if walk == "ladder_table" and group_spec is None:
            scouts.append(aggs)
            return real(padded, filt, aggs, group_spec, *rest)
        raise RuntimeError("RESOURCE_EXHAUSTED: injected device fault")

    def host_twin(*a, **k):
        raise AssertionError("a device fault fell through to host_exec")

    # every launch goes through it
    monkeypatch.setattr(kernels, "run_segment_kernel", device_fault)
    monkeypatch.setattr(host_exec, "execute_host", host_twin)
    dt = _query_server(server, pql)
    assert any("RESOURCE_EXHAUSTED" in e for e in dt.exceptions), \
        dt.exceptions
    assert dt.num_rows() == 0
    assert len(scouts) == (3 if walk == "ladder_table" else 0)


@pytest.mark.parametrize("pooled", [True, False])
def test_a_deadline_between_phases_truncates_and_waits_for_nothing(
        monkeypatch, pooled):
    """The budget runs out while the scans' walk waits for its scouts:
    no table is launched, the reply carries the truncation exception
    and the segments that finished (with a pool: the one gated to the
    host, a task of its own), and the runner does not wait for the
    walk."""
    import concurrent.futures
    import time
    from pinot_tpu.ops import kernels
    from pinot_tpu.query import plan as plan_mod
    from pinot_tpu.query.executor import ServerQueryExecutor
    base = tempfile.mkdtemp()
    segs = [build_segment(f"{base}/seg{i}", n=600, seed=80 + i,
                          name=f"dl_{i}")[0] for i in range(3)]
    request = compile_pql(_LADDER_PQL)
    real_get, real_run = plan_mod.profiled_device_get, \
        kernels.run_segment_kernel
    tables = []

    def slow_pull(x, programs=1):
        time.sleep(0.6)
        return real_get(x, programs)

    def spy(padded, filt, aggs, group_spec, *rest):
        if group_spec is not None:
            tables.append(group_spec)
        return real_run(padded, filt, aggs, group_spec, *rest)
    pool = concurrent.futures.ThreadPoolExecutor(4) if pooled else None
    try:
        ex = ServerQueryExecutor(segment_executor=pool)
        ex.device_gate = lambda seg: seg is not segs[1]
        monkeypatch.setattr(kernels, "run_segment_kernel", spy)
        ex.execute(request, segs)               # programs compiled
        assert len(tables) == 2
        monkeypatch.setattr(plan_mod, "profiled_device_get", slow_pull)
        t0 = time.monotonic()
        blk = ex.execute(request, segs, deadline=t0 + 0.3)
        waited = time.monotonic() - t0
    finally:
        if pool is not None:
            pool.shutdown(wait=True)            # the abandoned walk ends
    done = 1 if pooled else 0
    assert any(f"truncated at {done}/3" in e for e in blk.exceptions), \
        blk.exceptions
    assert blk.stats.num_segments_processed == done
    # the walk stopped between its phases: no table after the deadline
    assert len(tables) == 2
    # the runner left at the deadline, before the scouts' pull came home
    assert waited < (0.55 if pooled else 1.5)


def test_server_unknown_table(server_with_data):
    server, _ = server_with_data
    dt = _query_server(server, "SELECT COUNT(*) FROM nope")
    assert any("TableDoesNotExistError" in e for e in dt.exceptions)


def test_server_over_tcp_and_broker_reduce(server_with_data):
    server, oracle = server_with_data
    port = server.start(port=0)
    loop = EventLoopThread()
    conn = ServerConnection("127.0.0.1", port)
    try:
        pql = ("SELECT AVG(hits) FROM baseballStats WHERE league = 'AL' "
               "GROUP BY teamID TOP 500")
        req = InstanceRequest(request_id=7, query=compile_pql(pql))
        payload = instance_request_to_bytes(req)
        raw = loop.run(conn.request(payload, timeout=30))
        dt = DataTable.from_bytes(raw)
        resp = BrokerReduceService().reduce(compile_pql(pql),
                                            [dt.to_block()])
        m = oracle.mask(lambda r: r["league"] == "AL")
        expected = oracle.group_by(["teamID"], m, ("avg", "hits"))
        got = {tuple(g["group"]): float(g["value"])
               for g in resp.aggregation_results[0].group_by_result}
        for k, v in expected.items():
            assert got[k] == pytest.approx(v), k
    finally:
        loop.run(conn.close())
        loop.stop()


# ---------------------------------------------------------------------------
# Instance-level execution-path coverage (VERDICT r2 #9): with a mesh
# present, shardable sets ride the ICI combine and un-shardable sets fall
# back to sequential per-segment execution — both answering identically,
# both RECORDING which path served (reference behavior: per-segment
# combine, CombineOperator.java:27)
# ---------------------------------------------------------------------------


def test_instance_executor_records_sharded_and_fallback_paths():
    import tempfile as _tf

    from fixtures import make_schema, make_table_config
    from pinot_tpu.common.request import InstanceRequest
    from pinot_tpu.parallel import make_mesh
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.realtime.mutable_segment import MutableSegmentImpl
    from pinot_tpu.server.data_manager import InstanceDataManager
    from pinot_tpu.server.query_executor import InstanceQueryExecutor

    base = _tf.mkdtemp()
    dm = InstanceDataManager()
    tdm = dm.table("baseballStats", create=True)
    all_cols = []
    # independently built segments: different dictionaries, SAME padded
    # size — the union remap keeps these on the sharded device path
    for i in range(3):
        seg, cols = build_segment(f"{base}/p{i}", n=2048, seed=70 + i,
                                  name=f"path_{i}")
        tdm.add_segment(seg)
        all_cols.append(cols)
    ex = InstanceQueryExecutor(dm, mesh=make_mesh())

    def ask():
        req = InstanceRequest(request_id=9, query=compile_pql(
            "SELECT COUNT(*), SUM(runs) FROM baseballStats "
            "WHERE yearID >= 1990"))
        return ex.execute(req)

    runs = np.concatenate([c["runs"] for c in all_cols])
    years = np.concatenate([c["yearID"] for c in all_cols])
    exp_cnt = int((years >= 1990).sum())
    exp_sum = float(runs[years >= 1990].sum())

    dt = ask()
    blk = dt.to_block()
    assert dt.metadata["executionPath"] == "sharded"
    assert blk.agg_intermediates[0] == exp_cnt
    assert blk.agg_intermediates[1] == pytest.approx(exp_sum)

    # a consuming (mutable) segment in the set is genuinely un-stackable:
    # the executor must serve the same query via the sequential fallback
    # and say so
    mseg = MutableSegmentImpl(make_schema(), make_table_config(),
                              "cons_path")
    extra = {"teamID": "BOS", "league": "AL", "playerName": "x",
             "position": ["P"], "runs": 7, "hits": 3, "average": 0.3,
             "salary": 1.0, "yearID": 1999}
    mseg.index_row(extra)
    tdm.add_segment(mseg)
    dt2 = ask()
    blk2 = dt2.to_block()
    assert dt2.metadata["executionPath"] == "sequential"
    assert blk2.agg_intermediates[0] == exp_cnt + 1
    assert blk2.agg_intermediates[1] == pytest.approx(exp_sum + 7)


def test_server_admin_http_api():
    """Parity: pinot-server api/resources — TablesResource,
    TableSizeResource, HealthCheckResource, and the MmapDebugResource
    analogue (/debug/memory reports HBM-resident lane bytes — the TPU
    build's native-memory accounting)."""
    import json as _json
    import tempfile as _tf
    import urllib.request

    from pinot_tpu.engine import QueryEngine
    from pinot_tpu.server.http_api import ServerApiServer
    from pinot_tpu.server.instance import ServerInstance

    base = _tf.mkdtemp()
    seg, _cols = build_segment(f"{base}/adm", n=1024, seed=91,
                               name="adm_seg")
    srv = ServerInstance("adm_srv")
    srv.data_manager.table("baseballStats", create=True).add_segment(seg)
    api = ServerApiServer(srv)
    port = api.start()

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            body = r.read()
            return r.status, body

    try:
        st, body = get("/health")
        assert st == 200 and body == b"OK"
        st, body = get("/tables")
        assert _json.loads(body)["tables"] == ["baseballStats"]
        st, body = get("/tables/baseballStats/segments")
        segs = _json.loads(body)["segments"]
        assert segs["adm_seg"]["totalDocs"] == 1024
        assert segs["adm_seg"]["mutable"] is False
        st, body = get("/tables/baseballStats/size")
        size = _json.loads(body)
        assert size["totalHostBytes"] > 0
        # nothing uploaded yet → zero HBM residency
        st, body = get("/debug/memory")
        mem = _json.loads(body)
        assert mem["totalHbmResidentBytes"] == 0
        # run a device query → lanes become HBM-resident
        engine = QueryEngine([seg])
        engine.query("SELECT SUM(runs) FROM baseballStats "
                     "WHERE yearID >= 1990")
        st, body = get("/debug/memory")
        mem = _json.loads(body)
        assert mem["totalHbmResidentBytes"] > 0
        t = mem["tables"]["baseballStats"]["adm_seg"]
        assert t["hbmResidentBytes"] > 0 and t["hostBytes"] > 0
    finally:
        api.stop()
        srv.stop()


def test_retry_policies():
    """Parity: common/utils/retry/ — fixed/exponential/random policies,
    attempt() contract (N tries, policy-shaped sleeps, last failure
    chained when exhausted)."""
    import random as _random

    from pinot_tpu.common.retry import (ExponentialBackoffRetryPolicy,
                                        FixedDelayRetryPolicy,
                                        RandomDelayRetryPolicy,
                                        RetryExhaustedError)

    calls = []
    sleeps = []

    def flaky_then_ok():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    p = FixedDelayRetryPolicy(attempts=5, delay_s=0.01)
    assert p.attempt(flaky_then_ok, sleep=sleeps.append) == "ok"
    assert len(calls) == 3 and sleeps == [0.01, 0.01]

    def always_fails():
        raise ValueError("nope")

    with pytest.raises(RetryExhaustedError) as ei:
        FixedDelayRetryPolicy(attempts=2, delay_s=0).attempt(
            always_fails, sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, ValueError)

    # a non-retryable exception propagates immediately
    n = []
    with pytest.raises(KeyError):
        FixedDelayRetryPolicy(attempts=3, delay_s=0).attempt(
            lambda: (n.append(1), {}["x"])[1],
            retry_on=(ConnectionError,), sleep=lambda s: None)
    assert len(n) == 1

    exp = ExponentialBackoffRetryPolicy(attempts=4, initial_delay_s=1.0,
                                        scale=2.0,
                                        rng=_random.Random(7))
    d0, d1, d2 = exp.delay_for(0), exp.delay_for(1), exp.delay_for(2)
    assert 0.5 <= d0 < 1.0 and 1.0 <= d1 < 2.0 and 2.0 <= d2 < 4.0

    rnd = RandomDelayRetryPolicy(attempts=3, min_delay_s=0.2,
                                 max_delay_s=0.4,
                                 rng=_random.Random(3))
    assert all(0.2 <= rnd.delay_for(i) <= 0.4 for i in range(5))


def test_deep_store_fetch_retries_transient_failures(tmp_path):
    """The participant's remote segment fetch survives transient
    deep-store failures (SegmentFetcherAndLoader retry parity)."""
    import os

    from pinot_tpu.common import filesystem as fsmod
    from pinot_tpu.server.participant import ServerParticipant

    class FlakyFS(fsmod.PinotFS):
        fails = 2                       # class-level: get_fs instantiates

        def copy(self, src, dst):
            if FlakyFS.fails > 0:
                FlakyFS.fails -= 1
                raise ConnectionError("deep store hiccup")
            os.makedirs(dst, exist_ok=True)
            with open(os.path.join(dst, "ok"), "w") as fh:
                fh.write("1")

    fsmod.register_fs("flaky", FlakyFS)
    try:
        part = ServerParticipant.__new__(ServerParticipant)
        part.work_dir = str(tmp_path)

        class _Srv:
            instance_id = "s0"
        part.server = _Srv()

        class _Mgr:          # no controller in this unit: identity resolve
            @staticmethod
            def resolve_download_path(p):
                return p
        part.manager = _Mgr()
        local = part._fetch_segment_dir(
            "t_OFFLINE", "seg0", "flaky://deep/t/seg0")
        assert os.path.isfile(os.path.join(local, "ok"))
        assert FlakyFS.fails == 0
    finally:
        fsmod._REGISTRY.pop("flaky", None)
