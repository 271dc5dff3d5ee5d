"""Server-side query executor.

Parity: pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:100-267 —
acquire segments → prune → plan → execute per segment → combine → result
block with execution stats. Device-unsupported query shapes fall back to the
host (numpy) executor per segment, the way the reference falls back from
index-based to scan-based operators.

`_route` decides a segment's path (cube, device scan, host twin) and hands
it back as a value, `_run_route` runs it: the solo and the batched walk
both go through the pair and end in `_finish_query`. Sharded or sequential
is chosen in `execute`, nowhere else.

A query's device scans are ONE walk (`_walk_scans`, PR 38): one thread
routes the segments, launches every segment's next program without
waiting and pulls once a rung (`execution.execute_segment_plans`), so
the device has the query's programs queued while the host is between a
pull and the next launches, and a query stops for the host two or three
times, not 2.09 times a segment behind a pool's hand-offs. What is no
device scan fans out a segment a task on the scheduler's query-worker
pool (CombineOperator parity: per-segment plans on an ExecutorService,
CombineOperator.java:27): consuming and gated segments, host routes;
the walk is one task of that pool beside them. Without a pool (the
engine's default, or one segment) the same walk runs on the calling
thread and the rest follows it in order.
"""
from __future__ import annotations

import concurrent.futures
import time
from contextlib import contextmanager, nullcontext
from typing import List, Optional, Tuple

from pinot_tpu.common.metrics import ServerQueryPhase
from pinot_tpu.common.request import VECTOR_RESULT_COLUMNS, BrokerRequest
from pinot_tpu.obs import profiler as obs_profiler
from pinot_tpu.obs.profiler import QueryProfile, obs_span
from pinot_tpu.obs.tracing import TraceContext, make_trace_context
from pinot_tpu.query.blocks import IntermediateResultsBlock
from pinot_tpu.query.combine import combine_blocks
from pinot_tpu.query import execution, host_exec
from pinot_tpu.query.plan import (GroupsLimitExceeded, InstancePlanMaker,
                                  UnsupportedOnDevice, batch_signature,
                                  upsert_mask_active)
from pinot_tpu.query.pruner import SegmentPrunerService
from pinot_tpu.segment.loader import ImmutableSegment


#: ONE logical segment's work: (blocks, extra_parts, extra_matched) — a
#: consuming segment's frozen+tail pair yields two blocks that stay
#: paired for stats accounting
SegmentResult = Tuple[List[IntermediateResultsBlock], int, int]


@contextmanager
def _query_context(table: str, trace: Optional[TraceContext]):
    """Keep whatever ambient profile the instance layer activated;
    direct callers (engine, tests) get a private throwaway so the
    per-dispatch accounting hooks always have a target."""
    trace = trace if trace is not None else make_trace_context(False)
    ambient = obs_profiler.current()
    profile = ambient[0] if ambient is not None else QueryProfile(table)
    with obs_profiler.active(profile, trace):
        yield trace


def _stamp(blk: IntermediateResultsBlock, num_pruned: int,
           t0: float) -> IntermediateResultsBlock:
    blk.stats.num_segments_pruned = num_pruned
    blk.stats.time_used_ms = (time.perf_counter() - t0) * 1e3
    return blk


def _finish_query(request: BrokerRequest, selected,
                  results: List[SegmentResult], num_pruned: int,
                  t0: float) -> IntermediateResultsBlock:
    """The per-query frame behind the solo and the batched walk;
    `results` is shorter than `selected` where the deadline cut it."""
    blocks = [b for seg_blocks, _, _ in results for b in seg_blocks]
    if not blocks:
        blk = IntermediateResultsBlock()
        if request.is_group_by:
            blk.group_map = {}
        elif request.is_aggregation:
            blk.agg_intermediates = None
        if request.is_selection:
            blk.selection_rows = []
            blk.selection_columns = list(request.selection.columns)
            if request.vector is not None:
                blk.selection_columns += list(VECTOR_RESULT_COLUMNS)
    else:
        blk = combine_blocks(request, blocks)
    if len(results) < len(selected):
        blk.exceptions.append(
            "DeadlineExceededError: segment execution truncated at "
            f"{len(results)}/{len(selected)} segments (budget "
            "expired mid-query)")
    # frozen+tail pairs are ONE logical consuming segment: both
    # processed always, matched only when both halves matched
    blk.stats.num_segments_processed -= sum(p for _, p, _ in results)
    blk.stats.num_segments_matched -= sum(m for _, _, m in results)
    # realtime freshness over the consuming segments this query saw
    # (parity: ServerQueryExecutorV1Impl minConsumingFreshness)
    consuming_ts = [int(s_.last_indexed_time_ms) for s_ in selected
                    if getattr(s_, "is_mutable", False) and
                    hasattr(s_, "last_indexed_time_ms")]
    blk.stats.num_consuming_segments_processed = len(consuming_ts)
    if consuming_ts:
        blk.stats.min_consuming_freshness_ms = min(consuming_ts)
    return _stamp(blk, num_pruned, t0)


class ServerQueryExecutor:
    def __init__(self, plan_maker: Optional[InstancePlanMaker] = None,
                 pruner: Optional[SegmentPrunerService] = None,
                 use_device: bool = True,
                 segment_executor: Optional[
                     concurrent.futures.Executor] = None,
                 sharded=None):
        self.plan_maker = plan_maker or InstancePlanMaker()
        self.pruner = pruner or SegmentPrunerService()
        self.use_device = use_device
        # the scheduler's query-worker pool; None → sequential loop
        self.segment_executor = segment_executor
        # parallel/sharded.py ShardedQueryExecutor (a mesh), or None:
        # multi-segment queries try its combine first (`execute`)
        self.sharded = sharded
        # residency gates (server/residency_manager.py): device_gate
        # routes host/disk-tier segments through host_exec instead of
        # the device kernels; mutable_gate blocks frozen-snapshot
        # uploads under HBM pressure. None (the default) keeps the
        # ungated device-first behavior.
        self.device_gate = None
        self.mutable_gate = None

    def _on_device(self, seg) -> bool:
        return self.device_gate is None or self.device_gate(seg)

    def execute(self, request: BrokerRequest,
                segments: List[ImmutableSegment],
                trace: Optional[TraceContext] = None,
                deadline: Optional[float] = None
                ) -> IntermediateResultsBlock:
        """`request` arrives preprocessed (`preprocess_request`: the
        request frame or `QueryEngine.query` applies it, once).

        `deadline`: absolute time.monotonic() instant; the
        per-segment fan-out stops (with an honest truncation exception)
        once it passes — a deadline-expired query must not keep a
        worker pinned computing rows its broker stopped listening for.
        """
        with _query_context(request.table_name, trace) as trace:
            # the one place that chooses sharded or sequential: the
            # sharded combine stacks ALL segments' lanes in HBM — it
            # only applies when every segment is device-tier (a demoted
            # segment must not be re-uploaded through the stack path);
            # what it cannot take it says by raising
            if self.sharded is not None and len(segments) > 1 and \
                    all(self._on_device(s) for s in segments):
                from pinot_tpu.parallel.sharded import NotShardable
                try:
                    with trace.span(ServerQueryPhase.SHARDED_EXECUTION):
                        blk = self.sharded.execute(request, segments)
                    blk.execution_path = "sharded"
                    obs_profiler.count_path("sharded", len(segments))
                    return blk
                except (NotShardable, GroupsLimitExceeded,
                        UnsupportedOnDevice):
                    pass
            blk = self._execute(request, segments, trace, deadline)
            blk.execution_path = "sequential"
            return blk

    def _execute(self, request: BrokerRequest,
                 segments: List[ImmutableSegment],
                 trace: TraceContext,
                 deadline: Optional[float]) -> IntermediateResultsBlock:
        t0 = time.perf_counter()
        with trace.span(ServerQueryPhase.SEGMENT_PRUNING):
            selected = self.pruner.prune(segments, request)
        num_pruned = len(segments) - len(selected)

        blk = self._try_star_tree_multi(selected, request)
        if blk is not None:
            return _stamp(blk, num_pruned, t0)

        with trace.span(ServerQueryPhase.SEGMENT_EXECUTION):
            results = self._run_segments(selected, request, deadline, trace)
        return _finish_query(request, selected, results, num_pruned, t0)

    # -- per-segment work ---------------------------------------------------
    def _segment_work(self, seg, request: BrokerRequest) -> SegmentResult:
        with obs_span("segment",
                      segment=getattr(seg, "segment_name", "?")):
            return self._logical_segment(seg, request)

    def _logical_segment(self, seg, request: BrokerRequest
                         ) -> SegmentResult:
        if self.use_device and getattr(seg, "is_mutable", False) and \
                hasattr(seg, "device_view") and \
                (self.mutable_gate is None or self.mutable_gate(seg)):
            # consuming segment: the periodic sorted snapshot serves the
            # frozen prefix on the DEVICE kernels and the post-freeze
            # tail host-side; the two parts combine like any other pair
            # of segments (reference: consuming segments are first-class
            # engine targets, MutableSegmentImpl.java:64-198)
            frozen, tail = seg.device_view()
            blocks: List[IntermediateResultsBlock] = []
            fb = tb = None
            if frozen is not None:
                fb = self._execute_segment(frozen, request)
                blocks.append(fb)
            if tail.num_docs > 0 or frozen is None:
                tb = self._execute_segment(tail, request)
                blocks.append(tb)
            if fb is not None and tb is not None:
                matched = 1 if (fb.stats.num_segments_matched and
                                tb.stats.num_segments_matched) else 0
                return blocks, 1, matched
            return blocks, 0, 0
        if getattr(seg, "is_mutable", False) and \
                hasattr(seg, "snapshot_view"):
            # consuming segment: freeze (num_docs, cardinalities) so the
            # filter mask and every column lane agree while the consumer
            # thread keeps appending
            seg = seg.snapshot_view()
        return [self._execute_segment(seg, request)], 0, 0

    @staticmethod
    def _record_queue_wait(trace: Optional[TraceContext], t_queued: float,
                           parent_id: Optional[str] = None,
                           **attrs) -> None:
        """`segmentQueueWait`: how long a piece of a query's work waited
        for its turn since `t_queued` (perf_counter): for a worker of
        the pool (the scans' walk, `attrs.segments`; a segment that is
        no device scan, `attrs.segment`), or for what ran before it on
        the calling thread. A sibling of the spans it precedes."""
        if trace is not None and trace.enabled:
            trace.record(ServerQueryPhase.SEGMENT_QUEUE_WAIT,
                         (time.perf_counter() - t_queued) * 1e3,
                         parent_id=parent_id, **attrs)

    def _walks(self, seg) -> bool:
        """Whether `seg`'s route can be a device scan (`_route` says
        whether it is): the scans' walk takes it. A consuming segment
        (its frozen and tail halves) and one gated off the device stay
        a task of their own."""
        return self.use_device and not getattr(seg, "is_mutable", False) \
            and self._on_device(seg)

    def _run_segments(self, selected, request: BrokerRequest,
                      deadline: Optional[float],
                      trace: Optional[TraceContext] = None
                      ) -> List[SegmentResult]:
        """Every selected segment's result, in `selected`'s order and
        without those the deadline cut: the scans' walk as ONE piece of
        work, every other segment a piece of its own. With a pool
        (CombineOperator parity) the pieces are its tasks while this
        (runner) thread gathers; without, they run here in turn.
        Deadline truncation: a piece not yet started when the budget
        expires returns unexecuted (the pool's queue order makes "stop
        submitting" and "reject on pick-up" equivalent), the walk checks
        between segments and between rungs, and the gather abandons
        stragglers instead of waiting past the deadline (a program that
        compiles for 29 s must not hold the reply)."""
        pool = self.segment_executor if len(selected) > 1 else None
        slots: List[Optional[SegmentResult]] = [None] * len(selected)
        # worker threads don't inherit the runner's ambient profile or
        # its span stack — capture both here, re-establish per task so
        # the pieces' spans parent under segmentExecution and dispatch
        # accounting lands on the right query's profile
        ambient = obs_profiler.current()
        parent_id = trace.current_span_id() if trace is not None else None
        attached = trace is not None and trace.enabled and pool is not None
        futures: List[concurrent.futures.Future] = []

        def spawn(piece, **attrs) -> None:
            t_queued = time.perf_counter()

            def work():
                if deadline is not None and time.monotonic() >= deadline:
                    return                  # budget gone before start
                self._record_queue_wait(trace, t_queued, parent_id, **attrs)
                with obs_profiler.reactivate(ambient), \
                        trace.attach(parent_id) if attached \
                        else nullcontext():
                    piece()

            if pool is None:
                work()
            else:
                # the walk hands its host routes over from its worker
                # while the runner waits on the walk, the first future
                futures.append(pool.submit(work))

        def solo(i: int, run) -> None:
            def piece():
                slots[i] = run(selected[i], request)
            spawn(piece, segment=getattr(selected[i], "segment_name", "?"))

        walked, alone = [], []
        for i, seg in enumerate(selected):
            (walked if self._walks(seg) else alone).append(i)
        if walked:
            spawn(lambda: self._walk_scans(selected, walked, request,
                                           deadline, slots, solo),
                  segments=len(walked))
        for i in alone:
            solo(i, self._segment_work)

        abandoned = False
        for fut in futures:
            if not abandoned:
                try:
                    fut.result(timeout=None if deadline is None else
                               max(deadline - time.monotonic(), 0.0))
                    continue
                except concurrent.futures.TimeoutError:
                    # budget expired mid-gather: abandon this straggler
                    # and cancel everything not yet started; whatever
                    # already finished still counts (drain-what's-done
                    # semantics)
                    abandoned = True
            fut.cancel()
        return [res for res in slots if res is not None]

    def _walk_scans(self, selected, walked, request: BrokerRequest,
                    deadline: Optional[float], slots, solo) -> None:
        """ONE thread walks the query's device scans: route every
        segment of `walked` (a `segment` span each: a cube's descent
        where the segment has one and it covers, else `buildQueryPlan`),
        then run all the `scan` routes' plans in phases under one
        `queryPlanExecution` (`execution.execute_segment_plans`: every
        launch of a rung before its ONE pull). A per-segment cube hit is
        answered where it is routed; a `host` route, and a plan that
        refuses while running, goes to `solo` as a piece of its own.
        Fills `slots`; what the deadline cut stays None."""
        plans = {}
        for i in walked:
            if deadline is not None and time.monotonic() >= deadline:
                break
            seg = selected[i]
            with obs_span("segment",
                          segment=getattr(seg, "segment_name", "?")):
                path, payload = self._route(seg, request)
            if path == "cube":
                slots[i] = ([payload], 0, 0)
            elif path == "scan":
                plans[i] = payload
            else:
                solo(i, self._host_segment)
        if not plans:
            return
        with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION,
                      segments=len(plans)):
            blocks = execution.execute_segment_plans(list(plans.values()),
                                                     deadline)
        for i, blk in zip(plans, blocks):
            if isinstance(blk, Exception):
                solo(i, self._host_segment)
            elif blk is not None:
                obs_profiler.count_path("scan")
                obs_profiler.mark_scan_segments(True)
                slots[i] = ([blk], 0, 0)

    def _host_segment(self, seg, request: BrokerRequest) -> SegmentResult:
        return [self._run_route(seg, request, ("host", None))], 0, 0

    # -- the ladder: one segment, one request -------------------------------
    def _route(self, segment, request: BrokerRequest):
        """ONE segment's path, as a value: ("cube", block) where a
        star-tree cube answered, ("scan", plan) where the planner made
        a device plan, ("host", None) otherwise (no device, a segment
        gated off it, or a shape the planner refuses: caught here and
        nowhere else)."""
        blk = self._try_star_tree(segment, request)
        if blk is not None:
            return "cube", blk
        if self.use_device and self._on_device(segment):
            try:
                with obs_span(ServerQueryPhase.BUILD_QUERY_PLAN):
                    return "scan", self.plan_maker.make_segment_plan(
                        segment, request)
            except (GroupsLimitExceeded, UnsupportedOnDevice):
                pass
        return "host", None

    def _run_route(self, segment, request: BrokerRequest, route
                   ) -> IntermediateResultsBlock:
        """Run what `_route` decided and count the path taken; a plan
        that refuses while running falls back to the host twin."""
        path, payload = route
        if path == "cube":
            return payload
        if path == "scan":
            try:
                with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                    blk = payload.execute()
                obs_profiler.count_path("scan")
                obs_profiler.mark_scan_segments(False)
                return blk
            except (GroupsLimitExceeded, UnsupportedOnDevice):
                pass
        obs_profiler.count_path("host")
        return host_exec.execute_host(segment, request)

    def _execute_segment(self, segment: ImmutableSegment,
                         request: BrokerRequest) -> IntermediateResultsBlock:
        # ONE segment routed and run where it stands: a consuming
        # segment's halves, a batch's member
        return self._run_route(segment, request,
                               self._route(segment, request))

    # -- cross-query batched execution --------------------------------------
    def execute_batch(self, requests: List[BrokerRequest],
                      segments: List[ImmutableSegment],
                      trace: Optional[TraceContext] = None,
                      deadline: Optional[float] = None
                      ) -> List[IntermediateResultsBlock]:
        """Execute N same-shape requests (preprocessed, as for
        `execute`) over one segment set, sharing device dispatches
        wherever their per-segment plans compile to equal specs
        (query/plan.py:batch_signature).

        The coalescer (server/scheduler.py) guarantees the members
        share a table, segment list, and plan-shape key; this layer
        still prunes/plans per member (literals steer pruning and can
        constant-fold a plan) and re-groups by COMPILED signature, so a
        key collision degrades to sequential execution, never to a
        wrong answer. Members that fall off the batchable path (star
        trees, mutable segments, host fallback, group-by) run exactly
        the sequential ladder. Returns blocks aligned with `requests`.
        """
        table = requests[0].table_name if requests else "?"
        with _query_context(table, trace) as trace:
            t0 = time.perf_counter()
            members = []
            for req in requests:
                m = _BatchMember(req, self.pruner.prune(segments, req),
                                 len(segments))
                # the multi-segment cube fast path, as in _execute: a
                # member it answers never reaches the batch loop
                m.final = self._try_star_tree_multi(m.selected, req)
                members.append(m)
            pending = [m for m in members if m.final is None]

            t_queued = time.perf_counter()
            for seg in segments:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                takers = [m for m in pending if id(seg) in m.selected_ids]
                if takers:
                    self._record_queue_wait(
                        trace, t_queued,
                        segment=getattr(seg, "segment_name", "?"))
                    self._batch_segment(seg, takers)
            return [m.finish(t0) for m in members]

    def _batch_segment(self, seg, takers) -> None:
        """One segment, many members: batch the device plans whose
        compiled signatures agree, run every other route as the solo
        walk does."""
        if getattr(seg, "is_mutable", False):
            # consuming segments (frozen/tail or snapshot views) keep
            # their per-member walk
            for m in takers:
                m.results.append(self._segment_work(seg, m.request))
            return

        groups: dict = {}
        for m in takers:
            path, payload = route = self._route(seg, m.request)
            # fast-path / group-by plans have no signature: per member
            sig = batch_signature(payload) if path == "scan" else None
            if sig is None:
                m.results.append(
                    ([self._run_route(seg, m.request, route)], 0, 0))
            else:
                groups.setdefault(sig, []).append((m, payload))

        for group in groups.values():
            with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                blocks = execution.execute_segment_plans_batched(
                    [plan for _, plan in group])
            obs_profiler.count_path("scan", len(group))
            obs_profiler.mark_scan_segments(False, len(group))
            for (m, _), blk in zip(group, blocks):
                m.results.append(([blk], 0, 0))

    # -- star-tree cubes ----------------------------------------------------
    @staticmethod
    def _cube_answer(descend, n_segments: int, **attrs):
        """A cube descent under a `starTreeExecute` span (`attrs.hit`
        false where no cube covers the query; on a hit `attrs.native`)."""
        with obs_span(ServerQueryPhase.STAR_TREE_EXECUTE, **attrs) as span:
            blk = descend()
            if span is not None:
                span["attrs"]["hit"] = blk is not None
                if blk is not None:
                    span["attrs"]["native"] = blk.cube_native
        if blk is not None:
            obs_profiler.count_path("cube", n_segments)
        return blk

    def _try_star_tree(self, segment, request):
        """One segment's cube descent (on a miss the segment goes on to
        the scan)."""
        if request.is_aggregation and not request.is_selection and \
                not upsert_mask_active(segment) and \
                getattr(segment, "star_trees", None):
            from pinot_tpu.startree.executor import try_star_tree_execute
            return self._cube_answer(
                lambda: try_star_tree_execute(segment, request), 1,
                segment=getattr(segment, "segment_name", "?"))
        return None

    def _try_star_tree_multi(self, selected, request):
        """The multi-segment cube fast path (every selected segment
        must hold a covering cube), one `starTreeExecute` span for the
        lot (`attrs.segments`)."""
        if request.is_aggregation and not request.is_selection and \
                len(selected) > 1 and \
                not any(upsert_mask_active(s) for s in selected) and \
                all(getattr(s, "star_trees", None) for s in selected):
            from pinot_tpu.startree.executor import \
                try_star_tree_execute_multi
            return self._cube_answer(
                lambda: try_star_tree_execute_multi(selected, request),
                len(selected), segments=len(selected))
        return None


class _BatchMember:
    """Per-request accumulator for the batched execution loop."""
    __slots__ = ("request", "selected", "selected_ids", "num_pruned",
                 "results", "final")

    def __init__(self, request, selected, num_total: int):
        self.request = request
        self.selected = selected
        self.selected_ids = {id(s) for s in selected}
        self.num_pruned = num_total - len(selected)
        # one SegmentResult a segment walked, as the solo walk keeps
        self.results: List[SegmentResult] = []
        self.final: Optional[IntermediateResultsBlock] = None

    def finish(self, t0: float) -> IntermediateResultsBlock:
        if self.final is not None:
            return _stamp(self.final, self.num_pruned, t0)
        return _finish_query(self.request, self.selected, self.results,
                             self.num_pruned, t0)
