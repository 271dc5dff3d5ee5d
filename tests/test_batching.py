"""Cross-query device batching: the dispatch coalescer.

Same-plan-shape queries that overlap in flight share ONE (vmapped)
kernel execution per segment. The contracts under test:

- coalescer state machine: solo queries pay nothing, overlapping
  same-shape queries lead/join a bounded window, members whose budget
  cannot survive the window bypass, seal() is idempotent;
- batched results are BIT-IDENTICAL to the sequential twin's — on the
  host, device, and mesh-sharded paths, and with an upsert validDocIds
  mask active (the mask rides the cols side, shared across members);
- `batchWindowMs=0` disables coalescing entirely (today's behavior);
- single-flight dedup: N identical concurrent queries on a cold cache
  execute once, the rest are served the leader's cache entry;
- a hedged duplicate that can join an open batch window is admitted
  past the low-watermark hedge shed (it rides the primary's dispatch).
"""
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from fixtures import build_segment, spy_dead_rows

from pinot_tpu.common.datatable import DataTable, RESULT_CACHE_HIT_KEY
from pinot_tpu.common.metrics import ServerMeter, ServerTimer
from pinot_tpu.common.request import InstanceRequest
from pinot_tpu.common.serde import instance_request_to_bytes
from pinot_tpu.pql.parser import compile_pql
from pinot_tpu.server import ServerInstance
from pinot_tpu.server.scheduler import DispatchCoalescer


# ---------------------------------------------------------------------------
# Coalescer state machine (fake clock, no server)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_coalescer_solo_costs_nothing():
    clk = FakeClock()
    c = DispatchCoalescer(0.002, clock=clk)
    state, group = c.arrive("k", "m1", None)
    assert state == "solo" and group is None
    c.leave("k")
    # after leave the key is idle again: next arrival is solo too
    assert c.arrive("k", "m2", None)[0] == "solo"


def test_coalescer_lead_join_seal():
    clk = FakeClock()
    occupancies = []
    c = DispatchCoalescer(0.002, clock=clk,
                          on_dispatch=occupancies.append)
    assert c.arrive("k", "solo", None)[0] == "solo"   # in flight now
    state, g = c.arrive("k", "m1", None)
    assert state == "lead" and g is not None
    assert c.joinable("k")
    assert c.arrive("k", "m2", None) == ("joined", g)
    assert c.arrive("k", "m3", None) == ("joined", g)
    # a different key is unaffected
    assert c.arrive("other", "x", None)[0] == "solo"
    clk.t += 0.001
    assert c.remaining_window_s(g) == pytest.approx(0.001)
    members = c.seal(g)
    assert members == ["m1", "m2", "m3"]
    assert occupancies == [3]
    assert not c.joinable("k")
    # idempotent: the abandon callback racing the runner gets []
    assert c.seal(g) == []
    assert occupancies == [3]
    # the sealed group counts as in flight until leave(): a new arrival
    # while the batch (and the original solo) run becomes a fresh lead
    assert c.arrive("k", "m4", None)[0] == "lead"


def test_coalescer_deadline_bypass():
    clk = FakeClock()
    bypasses = []
    c = DispatchCoalescer(0.010, clock=clk,
                          on_bypass=lambda: bypasses.append(1))
    assert c.arrive("k", "solo", None)[0] == "solo"
    # min_slack_windows=2: under 20ms of budget cannot ride a 10ms
    # window and still execute — bypass, executing immediately
    state, _ = c.arrive("k", "tight", clk.t + 0.015)
    assert state == "bypass" and len(bypasses) == 1
    # a comfortable budget leads a window instead
    state, g = c.arrive("k", "roomy", clk.t + 10.0)
    assert state == "lead"
    # the group deadline is the TIGHTEST member's
    c.arrive("k", "tighter", clk.t + 5.0)
    assert g.deadline_s == pytest.approx(clk.t + 5.0)
    c.arrive("k", "looser", clk.t + 8.0)
    assert g.deadline_s == pytest.approx(clk.t + 5.0)


def test_coalescer_leave_accounting_survives_interleaving():
    c = DispatchCoalescer(0.002, clock=FakeClock())
    assert c.arrive("k", "a", None)[0] == "solo"
    _, g = c.arrive("k", "b", None)
    c.seal(g)              # two in flight now: solo + sealed batch
    c.leave("k")           # solo done
    assert c.arrive("k", "c", None)[0] == "lead"   # batch still runs
    c.leave("k")           # batch done


# ---------------------------------------------------------------------------
# End-to-end: batched results are bit-identical to sequential ones
# ---------------------------------------------------------------------------

# same plan shape (COUNT + SUM + filter literal), different literals —
# the coalescer's target workload; integer-exact so "bit-identical"
# is meaningful even across summation orders
BATCH_PQLS = [
    "SELECT COUNT(*), SUM(hits) FROM baseballStats_OFFLINE "
    "WHERE runs > '%d'" % lit for lit in (10, 40, 75, 110, 130)
]


def _request_bytes(pql, request_id=1, **kw):
    return instance_request_to_bytes(InstanceRequest(
        request_id=request_id, query=compile_pql(pql), **kw))


def _payload_of(dt: DataTable):
    # executionPath is provenance, not result content: a mesh twin
    # reports "sharded" while batch members ran the per-segment
    # kernels — the ROWS must still agree bitwise
    meta = {k: v for k, v in dt.metadata.items()
            if k not in ("requestId", RESULT_CACHE_HIT_KEY, "timeUsedMs",
                         "profileInfo", "executionPath")}
    return dt.kind, dt.columns, dt.rows, meta, dt.exceptions


def _server(batch_window_ms, mesh=None, use_device=True,
            num_segments=2, vdoc=False):
    s = ServerInstance("batch0", mesh=mesh, use_device=use_device,
                       batch_window_ms=batch_window_ms)
    for i in range(num_segments):
        seg, _ = build_segment(tempfile.mkdtemp(), n=700, seed=70 + i,
                               name=f"bt_{i}")
        if vdoc:
            from pinot_tpu.realtime.upsert import ValidDocIds
            seg.valid_doc_ids = ValidDocIds()
            for doc in range(0, 700, 7):       # mask 100 rows
                seg.valid_doc_ids.invalidate(doc)
        s.data_manager.table("baseballStats_OFFLINE",
                             create=True).add_segment(seg)
    return s


def _concurrent_replies(server, pqls, window_warm_s=0.0):
    """Fire one request per pql from its own thread, roughly at once."""
    barrier = threading.Barrier(len(pqls))

    def fire(i_pql):
        i, pql = i_pql
        barrier.wait()
        return DataTable.from_bytes(server.handle_request_bytes(
            _request_bytes(pql, 100 + i)))

    with ThreadPoolExecutor(max_workers=len(pqls)) as pool:
        return list(pool.map(fire, enumerate(pqls)))


@pytest.mark.parametrize("path", ["host", "device", "sharded"])
def test_batched_equals_sequential_bitwise(path):
    if path == "sharded":
        from pinot_tpu.parallel.sharded import make_mesh
        batched = _server(250.0, mesh=make_mesh())
        twin = _server(0.0, mesh=make_mesh())
    else:
        batched = _server(250.0, use_device=(path == "device"))
        twin = _server(0.0, use_device=(path == "device"))
    try:
        # sequential twin first: same segments (same seeds → same CRC),
        # strictly per-query dispatch (window 0 → no coalescer at all)
        assert twin.coalescer is None
        expected = [_payload_of(DataTable.from_bytes(
            twin.handle_request_bytes(_request_bytes(p, 10 + i))))
            for i, p in enumerate(BATCH_PQLS)]
        got = _concurrent_replies(batched, BATCH_PQLS)
        for pql, dt, want in zip(BATCH_PQLS, got, expected):
            assert not dt.exceptions, (pql, dt.exceptions)
            assert _payload_of(dt) == want, pql
        # the concurrent run really coalesced: at least one dispatch
        # served >1 query (the first arrival may have gone solo)
        assert batched.metrics.meter(
            ServerMeter.BATCHED_DISPATCHES).count >= 1
        occ = batched.metrics.timer(ServerTimer.BATCH_OCCUPANCY)
        assert occ.count >= 1 and occ.percentile_ms(100) >= 2
    finally:
        batched.stop()
        twin.stop()


def test_batched_equals_sequential_with_vdoc_mask():
    """The upsert validDocIds mask rides the shared cols side of the
    batched dispatch — every member must see the same masked view."""
    batched = _server(250.0, vdoc=True)
    twin = _server(0.0, vdoc=True)
    try:
        expected = [_payload_of(DataTable.from_bytes(
            twin.handle_request_bytes(_request_bytes(p, 10 + i))))
            for i, p in enumerate(BATCH_PQLS)]
        got = _concurrent_replies(batched, BATCH_PQLS)
        for pql, dt, want in zip(BATCH_PQLS, got, expected):
            assert not dt.exceptions, (pql, dt.exceptions)
            assert _payload_of(dt) == want, pql
        assert batched.metrics.meter(
            ServerMeter.BATCHED_DISPATCHES).count >= 1
    finally:
        batched.stop()
        twin.stop()


def test_batch_members_report_batch_size_in_profile():
    import json
    s = _server(250.0)
    try:
        got = _concurrent_replies(s, BATCH_PQLS)
        sizes = [json.loads(dt.metadata["profileInfo"])["batchSize"]
                 for dt in got]
        # at least one member rode a >1 batch; every member reports a
        # positive size, and solo members report exactly 1
        assert max(sizes) >= 2
        assert all(b >= 1 for b in sizes)
    finally:
        s.stop()


def test_window_zero_disables_coalescing():
    s = _server(0.0)
    try:
        assert s.coalescer is None
        got = _concurrent_replies(s, BATCH_PQLS)
        for dt in got:
            assert not dt.exceptions
        assert s.metrics.meter(ServerMeter.BATCHED_DISPATCHES).count == 0
        assert s.metrics.timer(ServerTimer.BATCH_OCCUPANCY).count == 0
    finally:
        s.stop()


def test_sequential_queries_never_wait_for_a_window():
    """An idle server (nothing same-shape in flight) executes every
    query immediately — the window costs an unbatched workload
    nothing, even with a deliberately huge window configured."""
    s = _server(batch_window_ms=10_000.0)
    try:
        t0 = time.perf_counter()
        for i, pql in enumerate(BATCH_PQLS):
            dt = DataTable.from_bytes(s.handle_request_bytes(
                _request_bytes(pql, 10 + i)))
            assert not dt.exceptions
            time.sleep(0.01)    # let the leave() done-callback land
        assert time.perf_counter() - t0 < 5.0   # no 10s sleeps anywhere
        # nothing overlapped → nothing batched
        assert s.metrics.meter(ServerMeter.BATCHED_DISPATCHES).count == 0
    finally:
        s.stop()


def test_group_by_queries_stay_unbatched_but_correct():
    """GROUP BY plans are excluded from the batched dispatch (their
    scout phases are value-dependent) — concurrent same-shape group-bys
    must still answer correctly through the coalescer plumbing."""
    pqls = ["SELECT SUM(hits) FROM baseballStats_OFFLINE "
            "WHERE runs > '%d' GROUP BY teamID TOP 30" % lit
            for lit in (10, 40, 75, 110)]
    batched = _server(250.0)
    twin = _server(0.0)
    try:
        expected = [_payload_of(DataTable.from_bytes(
            twin.handle_request_bytes(_request_bytes(p, 10 + i))))
            for i, p in enumerate(pqls)]
        got = _concurrent_replies(batched, pqls)
        for pql, dt, want in zip(pqls, got, expected):
            assert not dt.exceptions, (pql, dt.exceptions)
            assert _payload_of(dt) == want, pql
    finally:
        batched.stop()
        twin.stop()


# ---------------------------------------------------------------------------
# Single-flight dedup (satellite): identical concurrent queries
# ---------------------------------------------------------------------------


def test_single_flight_dedups_identical_cold_queries():
    s = _server(0.0)    # no coalescer: isolates the single-flight path
    try:
        pql = BATCH_PQLS[0]
        n = 6
        barrier = threading.Barrier(n)

        def fire(i):
            barrier.wait()
            return DataTable.from_bytes(s.handle_request_bytes(
                _request_bytes(pql, 200 + i)))

        with ThreadPoolExecutor(max_workers=n) as pool:
            got = list(pool.map(fire, range(n)))
        rows = {tuple(map(tuple, dt.rows)) for dt in got}
        assert len(rows) == 1       # every reply has the same result rows
        # followers waited on the leader and were served its entry
        waits = s.metrics.meter(ServerMeter.SINGLE_FLIGHT_WAITS).count
        hits = s.metrics.meter(ServerMeter.RESULT_CACHE_HITS).count
        assert waits >= 1 and hits >= 1
        # every reply carries its OWN requestId (fresh DataTable per
        # follower, no shared mutable reply)
        assert {dt.metadata["requestId"] for dt in got} == \
            {str(200 + i) for i in range(n)}
    finally:
        s.stop()


def test_single_flight_follower_falls_through_on_leader_failure():
    from pinot_tpu.server.result_cache import SingleFlight
    sf = SingleFlight()
    is_leader, ev = sf.begin(("k",))
    assert is_leader
    is_leader2, ev2 = sf.begin(("k",))
    assert not is_leader2 and ev2 is ev
    # leader "fails" (stores nothing) — done() still releases waiters
    sf.done(("k",))
    assert ev.wait(0.1)
    # the key is retired: a new arrival leads again
    assert sf.begin(("k",))[0]
    sf.done(("k",))
    # done() on an unknown key is harmless
    sf.done(("nope",))


# ---------------------------------------------------------------------------
# Hedge-join admission carve-out (satellite)
# ---------------------------------------------------------------------------


def test_hedged_duplicate_joins_open_batch_instead_of_shedding():
    """At the low watermark hedges are shed — UNLESS this server holds
    an open batch window for the hedge's plan shape, in which case it
    rides the primary's dispatch. Exercises the real `_admit` gate
    with a hand-opened window (the scheduler never runs here)."""
    s = _server(batch_window_ms=30_000.0, num_segments=1)
    depth = s.admission.low           # sit exactly at the low watermark
    try:
        for i in range(depth):
            assert s.admission.admit("baseballStats_OFFLINE", f"t{i}")
        pql = BATCH_PQLS[0]
        hedge = InstanceRequest(request_id=1, query=compile_pql(pql),
                                hedge=True)
        # no open window: the hedge is shed at the low watermark
        decision, busy, _ = s._admit(hedge)
        assert not decision and decision.cause == "hedge"
        assert busy is not None
        # open a window for that plan shape (a primary is in flight and
        # a same-shape query led a window)
        key = s._batch_key(hedge)
        assert s.coalescer.arrive(key, "primary", None)[0] == "solo"
        _, group = s.coalescer.arrive(key, "leader", None)
        assert s.coalescer.joinable(key)
        # the same hedge is now admitted: it will ride the open batch
        decision2, busy2, tenant2 = s._admit(hedge)
        assert decision2 and busy2 is None
        s.admission.release(tenant2)
        # ...while a hedge with a DIFFERENT plan shape is still shed
        other = InstanceRequest(
            request_id=2, hedge=True,
            query=compile_pql(
                "SELECT MAX(runs) FROM baseballStats_OFFLINE"))
        decision3, busy3, _ = s._admit(other)
        assert not decision3 and decision3.cause == "hedge"
        s.coalescer.seal(group)
    finally:
        for i in range(depth):
            s.admission.release(f"t{i}")
        s.stop()


# ---------------------------------------------------------------------------
# One ladder, one frame: whatever route a segment takes, a request
# executed alone and the same request inside a batch agree
# ---------------------------------------------------------------------------

_SUM_PQLS = ["SELECT SUM(hits), COUNT(*) FROM baseballStats "
             "WHERE runs > '%d'" % lit for lit in (10, 75, 130)]
_CUBE_PQLS = ["SELECT SUM(runs) FROM baseballStats WHERE teamID = '%s'"
              % team for team in ("BOS", "NYA", "SEA")]
_GROUP_PQLS = ["SELECT SUM(hits) FROM baseballStats WHERE runs > '%d' "
               "GROUP BY teamID TOP 30" % lit for lit in (10, 75, 130)]


def _plain_segments(n_segs=2):
    return [build_segment(tempfile.mkdtemp(), n=600, seed=80 + i,
                          name=f"rt_{i}")[0] for i in range(n_segs)]


def _cube_segments(n_segs):
    import os
    from fixtures import make_columns, make_schema, make_table_config
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    from test_startree import ST_CONFIG
    cfg = make_table_config()
    cfg.indexing_config.star_tree_configs = [ST_CONFIG]
    segs = []
    for i in range(n_segs):
        d = os.path.join(tempfile.mkdtemp(), f"rc{i}")
        SegmentCreator(make_schema(), cfg, f"rc_{i}").build(
            make_columns(2000, seed=90 + i), d)
        segs.append(ImmutableSegmentLoader.load(d))
    return segs


def _consuming_segment():
    """A consuming segment with a frozen prefix AND a tail."""
    from fixtures import make_schema, make_table_config
    from test_realtime import make_rows
    from pinot_tpu.realtime.mutable_segment import MutableSegmentImpl
    seg = MutableSegmentImpl(make_schema(), make_table_config(), "rt_cons")
    seg.FREEZE_MIN_ROWS = 512
    rows = make_rows(700, seed=33)
    for r in rows[:600]:
        seg.index_row(r)
    frozen, _ = seg.device_view()
    assert frozen is not None and frozen.num_docs == 600
    for r in rows[600:]:
        seg.index_row(r)
    frozen, tail = seg.device_view()
    assert frozen.num_docs == 600 and tail.num_docs == 100
    return seg


def _failing_plan_maker(where):
    from pinot_tpu.query.plan import (GroupsLimitExceeded,
                                      InstancePlanMaker,
                                      UnsupportedOnDevice)

    class Maker(InstancePlanMaker):
        def make_segment_plan(self, segment, request):
            if where == "plan":
                raise UnsupportedOnDevice("refused by the test")
            plan = super().make_segment_plan(segment, request)
            # refuses at its first launch, whoever walks it
            plan.params = _RefusesWhenRead(plan.params)
            return plan

    class _RefusesWhenRead(list):
        def __iter__(self):
            raise GroupsLimitExceeded("found while running")
    return Maker()


# route → (segments, pqls, executor keywords, device gate, the profile's
# path totals for the three requests)
_ROUTES = {
    "cube": (lambda: _cube_segments(1), _CUBE_PQLS, {}, None,
             {"cube": 3}),
    "cube_multi": (lambda: _cube_segments(2), _CUBE_PQLS, {}, None,
                   {"cube": 6}),
    "scan": (_plain_segments, _SUM_PQLS, {}, None, {"scan": 6}),
    "plan_unsupported": (
        _plain_segments, _SUM_PQLS,
        {"plan_maker": lambda: _failing_plan_maker("plan")}, None,
        {"host": 6}),
    "run_groups_limit": (
        _plain_segments, _GROUP_PQLS,
        {"plan_maker": lambda: _failing_plan_maker("run")}, None,
        {"host": 6}),
    "consuming_frozen_tail": (
        lambda: [_consuming_segment()] + _plain_segments(1), _SUM_PQLS,
        {}, None, {"scan": 6, "host": 3}),
    "gated_off_device": (_plain_segments, _SUM_PQLS, {},
                         lambda seg: False, {"host": 6}),
}


def _seen(request, blk):
    """What a client sees of a block, and its stats but the clock's."""
    import dataclasses
    stats = dataclasses.asdict(blk.stats)
    del stats["time_used_ms"]
    dt = DataTable.from_block(request, blk)
    return dt.kind, dt.columns, dt.rows, blk.exceptions, stats


@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_alone_and_batched_agree_on_every_route(route):
    from pinot_tpu.obs import profiler as obs_profiler
    from pinot_tpu.obs.profiler import QueryProfile
    from pinot_tpu.query.executor import ServerQueryExecutor
    make_segments, pqls, kw, gate, want_paths = _ROUTES[route]
    segments = make_segments()
    requests = [compile_pql(p) for p in pqls]
    ex = ServerQueryExecutor(**{k: v() for k, v in kw.items()})
    ex.device_gate = gate

    alone_profile, batch_profile = QueryProfile("t"), QueryProfile("t")
    with obs_profiler.active(alone_profile, None):
        alone = [_seen(r, ex.execute(r, segments)) for r in requests]
    with obs_profiler.active(batch_profile, None):
        batched = [_seen(r, b) for r, b in
                   zip(requests, ex.execute_batch(requests, segments))]
    for pql, a, b in zip(pqls, alone, batched):
        assert not a[3], (pql, a[3])
        assert a == b, pql
    assert alone_profile.paths == want_paths
    assert batch_profile.paths == want_paths


# pql → the profile's path totals over the seven segments of
# `_mixed_route_segments` (a cube hit counts `cube`; the consuming
# segment's frozen part scans, its tail takes the host twin)
_MIXED_PQLS = {
    # a cube covers it: the four plain segments are one-launch scans
    "cube_covered_sum": (
        "SELECT SUM(runs), COUNT(*) FROM baseballStats "
        "WHERE teamID = 'BOS'", {"scan": 5, "cube": 1, "host": 2}),
    # no cube holds salary: six scans; a raw FLOAT lane summed in
    # float block sums, so the order of the combine shows in the answer
    "float_sum": (
        "SELECT SUM(salary), COUNT(*) FROM baseballStats "
        "WHERE runs > '40'", {"scan": 6, "host": 2}),
    # a group-by ladder a segment: four of them walked in phases beside
    # the cube hit, the consuming segment's and the gated one
    "group_by": (
        "SELECT SUM(hits) FROM baseballStats WHERE teamID IN "
        "('BOS', 'NYA', 'SEA') GROUP BY teamID TOP 30",
        {"scan": 5, "cube": 1, "host": 2}),
    "selection": (
        "SELECT playerName, runs FROM baseballStats WHERE runs > '120' "
        "ORDER BY runs DESC, playerName LIMIT 15", {"scan": 6, "host": 2}),
}


@pytest.fixture(scope="module")
def mixed_route_segments():
    """[plain_0, cube, consuming (frozen + tail), plain_1 (gated off
    the device), plain_2, plain_3, plain_4], of unequal sizes, and the
    gate."""
    plain = [build_segment(tempfile.mkdtemp(), n=500 + 100 * i,
                           seed=70 + i, name=f"mx_{i}")[0]
             for i in range(5)]
    segments = [plain[0], _cube_segments(1)[0], _consuming_segment(),
                plain[1], plain[2], plain[3], plain[4]]
    return segments, lambda seg: seg is not plain[1]


@pytest.mark.parametrize("shape", sorted(_MIXED_PQLS))
def test_parallel_walk_equals_the_sequential_one_on_mixed_routes(
        mixed_route_segments, shape, monkeypatch):
    """One query whose segments take every route, walked three ways:
    on the calling thread; with a pool (the scans' walk one task, the
    consuming and the gated segment a task each); and with a pool and
    the walk refused, a segment a task and a pull a program (the pool
    walk up to PR 37). All give what the first gives, combined in the
    segments' order."""
    from pinot_tpu.obs import profiler as obs_profiler
    from pinot_tpu.obs.profiler import QueryProfile
    from pinot_tpu.query import executor as executor_mod
    from pinot_tpu.query.executor import ServerQueryExecutor
    segments, gate = mixed_route_segments
    pql, want_paths = _MIXED_PQLS[shape]
    request = compile_pql(pql)
    combined = []
    real_combine = executor_mod.combine_blocks

    def spy_combine(req, blocks):
        combined.append([b.stats.total_docs for b in blocks])
        return real_combine(req, blocks)
    monkeypatch.setattr(executor_mod, "combine_blocks", spy_combine)

    class SegmentATask(ServerQueryExecutor):
        def _walks(self, seg):
            return False

    dead = spy_dead_rows(monkeypatch)
    pool = ThreadPoolExecutor(4)
    try:
        seen, inters, paths, dispatches = [], [], [], []
        for cls, kw in ((ServerQueryExecutor, {}),
                        (ServerQueryExecutor, {"segment_executor": pool}),
                        (SegmentATask, {"segment_executor": pool})):
            ex = cls(**kw)
            ex.device_gate = gate
            profile = QueryProfile("t")
            del dead[:]
            with obs_profiler.active(profile, None):
                blk = ex.execute(request, segments)
            seen.append(_seen(request, blk))
            inters.append(blk.agg_intermediates)
            paths.append(profile.paths)
            dispatches.append((profile.dispatches, profile.transfer_bytes,
                               len(dead), sum(dead)))
    finally:
        pool.shutdown(wait=True)
    assert not seen[0][3], seen[0][3]
    assert seen[0] == seen[1] == seen[2]
    assert inters[0] == inters[1] == inters[2]   # the same float sums
    assert paths[0] == paths[1] == paths[2] == want_paths
    # the same programs and the same bytes on the calling thread or a
    # pool's; a segment a task runs its own program where the walk runs
    # one over a stack's members of a spec, and pulls the same bytes
    # but for the stacked outputs' rows no member ran
    assert dispatches[0] == dispatches[1]
    (programs, nbytes, stacked, dead_bytes), \
        (alone_programs, alone_bytes, none, _) = dispatches[1:]
    assert none == 0 and 0 < stacked <= programs <= alone_programs
    assert nbytes == alone_bytes + dead_bytes
    # eight blocks (the consuming segment gives two), in `selected`'s
    # order
    assert len(combined) == 3 and combined[0] == combined[1] == combined[2]
    assert len(combined[0]) == 8


@pytest.mark.parametrize("case", ["missing_table", "expired_deadline"])
def test_unserved_replies_carry_the_same_metadata(case):
    """A request answered without touching a segment gets the same
    reply alone and in a batch: the exception, and the requestId the
    broker matches replies by."""
    from pinot_tpu.server.data_manager import InstanceDataManager
    from pinot_tpu.server.query_executor import InstanceQueryExecutor
    dm = InstanceDataManager()
    if case == "expired_deadline":
        seg, _ = build_segment(tempfile.mkdtemp(), n=200, seed=5,
                               name="un_0")
        dm.table("baseballStats", create=True).add_segment(seg)
        deadline, want = time.monotonic() - 1.0, "DeadlineExceededError"
    else:
        deadline, want = None, "TableDoesNotExistError"
    iqe = InstanceQueryExecutor(dm)
    reqs = [InstanceRequest(request_id=40 + i, query=compile_pql(p))
            for i, p in enumerate(_SUM_PQLS[:2])]
    alone = [iqe.execute(r, deadline=deadline) for r in reqs]
    batched = iqe.execute_batch(reqs, [0.0, 0.0], deadline)
    for r, a, b in zip(reqs, alone, batched):
        assert a.exceptions == b.exceptions
        assert a.exceptions[0].startswith(want)
        assert a.metadata == b.metadata
        assert a.metadata["requestId"] == str(r.request_id)
