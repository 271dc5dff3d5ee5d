"""Server-side query executor.

Parity: pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:100-267 —
acquire segments → prune → plan → execute per segment → combine → result
block with execution stats. Device-unsupported query shapes fall back to the
host (numpy) executor per segment, the way the reference falls back from
index-based to scan-based operators.

Per-segment execution fans out on the scheduler's query-worker pool
(CombineOperator parity: per-segment plans on an ExecutorService,
CombineOperator.java:27). Device dispatches serialize on the chip anyway,
so the workers overlap host-side planning/decoding/finishing with device
work — the win the reference gets from planNodes.parallelStream().
"""
from __future__ import annotations

import concurrent.futures
import time
from typing import List, Optional, Tuple

from pinot_tpu.common.metrics import ServerQueryPhase
from pinot_tpu.common.request import BrokerRequest
from pinot_tpu.obs import profiler as obs_profiler
from pinot_tpu.obs.profiler import QueryProfile, obs_span
from pinot_tpu.obs.tracing import TraceContext, make_trace_context
from pinot_tpu.query.blocks import IntermediateResultsBlock
from pinot_tpu.query.combine import combine_blocks
from pinot_tpu.query import host_exec
from pinot_tpu.query.plan import (GroupsLimitExceeded, InstancePlanMaker,
                                  UnsupportedOnDevice)
from pinot_tpu.query.pruner import SegmentPrunerService
from pinot_tpu.segment.loader import ImmutableSegment


class ServerQueryExecutor:
    def __init__(self, plan_maker: Optional[InstancePlanMaker] = None,
                 pruner: Optional[SegmentPrunerService] = None,
                 use_device: bool = True,
                 segment_executor: Optional[
                     concurrent.futures.Executor] = None):
        self.plan_maker = plan_maker or InstancePlanMaker()
        self.pruner = pruner or SegmentPrunerService()
        self.use_device = use_device
        # the scheduler's query-worker pool; None → sequential loop
        self.segment_executor = segment_executor
        # residency gates (server/residency_manager.py): device_gate
        # routes host/disk-tier segments through host_exec instead of
        # the device kernels; mutable_gate blocks frozen-snapshot
        # uploads under HBM pressure. None (the default) keeps the
        # ungated device-first behavior.
        self.device_gate = None
        self.mutable_gate = None

    def execute(self, request: BrokerRequest,
                segments: List[ImmutableSegment],
                trace: Optional[TraceContext] = None,
                deadline: Optional[float] = None
                ) -> IntermediateResultsBlock:
        """`deadline`: absolute time.monotonic() instant; the
        per-segment fan-out stops (with an honest truncation exception)
        once it passes — a deadline-expired query must not keep a
        worker pinned computing rows its broker stopped listening for."""
        trace = trace if trace is not None else make_trace_context(False)
        # keep whatever ambient profile the instance layer activated;
        # direct callers (engine, tests) get a private throwaway so the
        # per-dispatch accounting hooks always have a target
        ambient = obs_profiler.current()
        profile = ambient[0] if ambient is not None else \
            QueryProfile(request.table_name)
        with obs_profiler.active(profile, trace):
            return self._execute(request, segments, trace, deadline)

    def _execute(self, request: BrokerRequest,
                 segments: List[ImmutableSegment],
                 trace: TraceContext,
                 deadline: Optional[float]) -> IntermediateResultsBlock:
        t0 = time.perf_counter()
        from pinot_tpu.query.plan import preprocess_request
        # FASTHLL derived rewrite — returns a copy when it rewrites, so
        # the broker's shared request never changes under our feet
        request = preprocess_request(segments, request)
        with trace.span(ServerQueryPhase.SEGMENT_PRUNING):
            selected = self.pruner.prune(segments, request)
        num_pruned = len(segments) - len(selected)

        blk = self._try_star_tree_multi(selected, request)
        if blk is not None:
            blk.stats.num_segments_pruned = num_pruned
            blk.stats.time_used_ms = (time.perf_counter() - t0) * 1e3
            return blk

        with trace.span(ServerQueryPhase.SEGMENT_EXECUTION):
            if self.segment_executor is not None and len(selected) > 1:
                blocks, extra_parts, extra_matched, executed = \
                    self._run_parallel(selected, request, deadline, trace)
            else:
                blocks, extra_parts, extra_matched, executed = \
                    self._run_sequential(selected, request, deadline,
                                         trace)
        truncated = executed < len(selected)

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
                    from pinot_tpu.common.request import \
                        VECTOR_RESULT_COLUMNS
                    blk.selection_columns += list(VECTOR_RESULT_COLUMNS)
        else:
            blk = combine_blocks(request, blocks)
        if truncated:
            blk.exceptions.append(
                "DeadlineExceededError: segment execution truncated at "
                f"{executed}/{len(selected)} segments (budget "
                "expired mid-query)")
        if extra_parts:
            # frozen+tail pairs are ONE logical consuming segment: both
            # processed always, matched only when both halves matched
            blk.stats.num_segments_processed -= extra_parts
            blk.stats.num_segments_matched -= extra_matched
        # realtime freshness over the consuming segments this query saw
        # (parity: ServerQueryExecutorV1Impl minConsumingFreshness)
        consuming_ts = [int(s_.last_indexed_time_ms) for s_ in selected
                        if getattr(s_, "is_mutable", False) and
                        hasattr(s_, "last_indexed_time_ms")]
        blk.stats.num_consuming_segments_processed = len(consuming_ts)
        if consuming_ts:
            blk.stats.min_consuming_freshness_ms = min(consuming_ts)
        blk.stats.num_segments_pruned = num_pruned
        blk.stats.time_used_ms = (time.perf_counter() - t0) * 1e3
        return blk

    # -- per-segment work ---------------------------------------------------
    def _segment_work(self, seg, request: BrokerRequest
                      ) -> Tuple[List[IntermediateResultsBlock], int, int]:
        """Execute ONE logical segment; returns (blocks, extra_parts,
        extra_matched) — a consuming segment's frozen+tail pair yields
        two blocks that stay paired for stats accounting."""
        with obs_span("segment",
                      segment=getattr(seg, "segment_name", "?")):
            return self._segment_work_inner(seg, request)

    def _segment_work_inner(self, seg, request: BrokerRequest
                            ) -> Tuple[List[IntermediateResultsBlock],
                                       int, int]:
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
                           seg, parent_id: Optional[str] = None) -> None:
        """`segmentQueueWait`: how long this segment's work waited for
        its turn since `t_queued` (perf_counter): for a worker of the
        pool (the second wave of 8 segments on 4 workers), or for the
        segments before it in a sequential walk. A sibling of the
        `segment` span it precedes."""
        if trace is not None and trace.enabled:
            trace.record(ServerQueryPhase.SEGMENT_QUEUE_WAIT,
                         (time.perf_counter() - t_queued) * 1e3,
                         parent_id=parent_id,
                         segment=getattr(seg, "segment_name", "?"))

    def _run_sequential(self, selected, request: BrokerRequest,
                        deadline: Optional[float],
                        trace: Optional[TraceContext] = None):
        blocks: List[IntermediateResultsBlock] = []
        extra_parts = extra_matched = 0
        executed = 0
        t_queued = time.perf_counter()
        for seg in selected:
            if deadline is not None and time.monotonic() >= deadline:
                break
            self._record_queue_wait(trace, t_queued, seg)
            segment_blocks, parts, matched = self._segment_work(seg,
                                                                request)
            blocks.extend(segment_blocks)
            extra_parts += parts
            extra_matched += matched
            executed += 1
        return blocks, extra_parts, extra_matched, executed

    def _run_parallel(self, selected, request: BrokerRequest,
                      deadline: Optional[float],
                      trace: Optional[TraceContext] = None):
        """CombineOperator parity: every segment plan runs as a task on
        the scheduler's query-worker pool while this (runner) thread
        gathers. Deadline truncation: tasks not yet started when the
        budget expires return unexecuted (the pool's queue order makes
        "stop submitting" and "reject on pick-up" equivalent), and the
        gather abandons stragglers instead of waiting past the deadline.
        """
        # worker threads don't inherit the runner's ambient profile or
        # its span stack — capture both here, re-establish per task so
        # per-segment spans parent under segmentExecution and dispatch
        # accounting lands on the right query's profile
        ambient = obs_profiler.current()
        parent_id = trace.current_span_id() if trace is not None else None

        t_queued = time.perf_counter()

        def work(seg):
            if deadline is not None and time.monotonic() >= deadline:
                return None                 # budget gone before start
            self._record_queue_wait(trace, t_queued, seg, parent_id)
            with obs_profiler.reactivate(ambient):
                if trace is not None and trace.enabled:
                    with trace.attach(parent_id):
                        return self._segment_work(seg, request)
                return self._segment_work(seg, request)

        futures = [self.segment_executor.submit(work, seg)
                   for seg in selected]
        results: List[Optional[tuple]] = [None] * len(selected)
        abandoned = False
        for i, fut in enumerate(futures):
            if abandoned:
                fut.cancel()
                continue
            budget = None if deadline is None else \
                deadline - time.monotonic()
            try:
                results[i] = fut.result(
                    timeout=None if budget is None else max(budget, 0.0))
            except concurrent.futures.TimeoutError:
                # budget expired mid-gather: abandon this straggler and
                # cancel everything not yet started; whatever already
                # finished still counts (drain-what's-done semantics)
                abandoned = True
                fut.cancel()
        if abandoned:
            for i, fut in enumerate(futures):
                if results[i] is None and fut.done() and \
                        not fut.cancelled():
                    try:
                        results[i] = fut.result(timeout=0)
                    except (concurrent.futures.TimeoutError,
                            concurrent.futures.CancelledError):
                        pass
        blocks: List[IntermediateResultsBlock] = []
        extra_parts = extra_matched = 0
        executed = 0
        for res in results:
            if res is None:
                continue
            segment_blocks, parts, matched = res
            blocks.extend(segment_blocks)
            extra_parts += parts
            extra_matched += matched
            executed += 1
        return blocks, extra_parts, extra_matched, executed

    def _execute_segment(self, segment: ImmutableSegment,
                         request: BrokerRequest) -> IntermediateResultsBlock:
        blk = self._try_star_tree(segment, request)
        if blk is not None:
            return blk
        if self.use_device and \
                (self.device_gate is None or self.device_gate(segment)):
            try:
                with obs_span(ServerQueryPhase.BUILD_QUERY_PLAN):
                    plan = self.plan_maker.make_segment_plan(segment,
                                                             request)
                with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                    blk = plan.execute()
                obs_profiler.count_path("scan")
                return blk
            except (GroupsLimitExceeded, UnsupportedOnDevice):
                pass
        obs_profiler.count_path("host")
        return host_exec.execute_host(segment, request)

    # -- cross-query batched execution --------------------------------------
    def execute_batch(self, requests: List[BrokerRequest],
                      segments: List[ImmutableSegment],
                      trace: Optional[TraceContext] = None,
                      deadline: Optional[float] = None
                      ) -> List[IntermediateResultsBlock]:
        """Execute N same-shape requests over one segment set, sharing
        device dispatches wherever their per-segment plans compile to
        equal specs (query/plan.py:batch_signature).

        The coalescer (server/scheduler.py) guarantees the members
        share a table, segment list, and plan-shape key; this layer
        still prunes/plans per member (literals steer pruning and can
        constant-fold a plan) and re-groups by COMPILED signature, so a
        key collision degrades to sequential execution, never to a
        wrong answer. Members that fall off the batchable path (star
        trees, mutable segments, host fallback, group-by) run exactly
        the sequential ladder. Returns blocks aligned with `requests`.
        """
        trace = trace if trace is not None else make_trace_context(False)
        ambient = obs_profiler.current()
        profile = ambient[0] if ambient is not None else \
            QueryProfile(requests[0].table_name if requests else "?")
        with obs_profiler.active(profile, trace):
            return self._execute_batch(requests, segments, deadline)

    def _execute_batch(self, requests, segments, deadline):
        t0 = time.perf_counter()
        from pinot_tpu.query.plan import preprocess_request
        members = []
        for req in requests:
            req = preprocess_request(segments, req)
            selected = self.pruner.prune(segments, req)
            members.append(_BatchMember(req, selected, len(segments)))

        # per-member multi-segment star-tree fast path (mirrors
        # _execute; a member it answers never reaches the batch loop)
        for m in members:
            m.final = self._try_star_tree_multi(m.selected, m.request)
        pending = [m for m in members if m.final is None]

        ambient = obs_profiler.current()
        trace = ambient[1] if ambient is not None else None
        t_queued = time.perf_counter()
        for seg in segments:
            if deadline is not None and time.monotonic() >= deadline:
                break
            takers = [m for m in pending if id(seg) in m.selected_ids]
            if not takers:
                continue
            self._record_queue_wait(trace, t_queued, seg)
            self._batch_segment(seg, takers)
            for m in takers:
                m.executed += 1

        return [m.final if m.final is not None
                else m.finish(t0) for m in members]

    def _batch_segment(self, seg, takers) -> None:
        """One segment, many members: batch the plans whose compiled
        signatures agree, run everything else down the sequential
        ladder unchanged."""
        from pinot_tpu.query import execution
        from pinot_tpu.query.plan import batch_signature

        if getattr(seg, "is_mutable", False) or not self.use_device or \
                (self.device_gate is not None and
                 not self.device_gate(seg)):
            # consuming segments (frozen/tail or snapshot views) and
            # gated-off-device segments keep their per-member path
            for m in takers:
                m.add(*self._segment_work(seg, m.request))
            return

        groups: dict = {}
        for m in takers:
            blk = self._try_star_tree(seg, m.request)
            if blk is not None:
                m.add([blk], 0, 0)
                continue
            try:
                with obs_span(ServerQueryPhase.BUILD_QUERY_PLAN):
                    plan = self.plan_maker.make_segment_plan(seg,
                                                             m.request)
            except (GroupsLimitExceeded, UnsupportedOnDevice):
                obs_profiler.count_path("host")
                m.add([host_exec.execute_host(seg, m.request)], 0, 0)
                continue
            sig = batch_signature(plan)
            if sig is None:
                # fast-path / group-by plans execute per member
                try:
                    with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                        blk = plan.execute()
                    obs_profiler.count_path("scan")
                except (GroupsLimitExceeded, UnsupportedOnDevice):
                    obs_profiler.count_path("host")
                    blk = host_exec.execute_host(seg, m.request)
                m.add([blk], 0, 0)
                continue
            groups.setdefault(sig, []).append((m, plan))

        for group in groups.values():
            plans = [plan for _, plan in group]
            with obs_span(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                blocks = execution.execute_segment_plans_batched(plans)
            obs_profiler.count_path("scan", len(group))
            for (m, _), blk in zip(group, blocks):
                m.add([blk], 0, 0)

    def _try_star_tree(self, segment, request):
        """One segment's cube descent under a `starTreeExecute` span
        (`attrs.hit` false where no cube covers the query and the
        segment goes on to the scan)."""
        from pinot_tpu.query.plan import upsert_mask_active
        if request.is_aggregation and not request.is_selection and \
                not upsert_mask_active(segment) and \
                getattr(segment, "star_trees", None):
            from pinot_tpu.startree.executor import try_star_tree_execute
            with obs_span(ServerQueryPhase.STAR_TREE_EXECUTE,
                          segment=getattr(segment, "segment_name",
                                          "?")) as span:
                blk = try_star_tree_execute(segment, request)
                if span is not None:
                    span["attrs"]["hit"] = blk is not None
                    if blk is not None:
                        span["attrs"]["native"] = blk.cube_native
            if blk is not None:
                obs_profiler.count_path("cube")
                return blk
        return None

    def _try_star_tree_multi(self, selected, request):
        """The multi-segment cube fast path (every selected segment
        must hold a covering cube), one `starTreeExecute` span for the
        lot (`attrs.segments`)."""
        from pinot_tpu.query.plan import upsert_mask_active
        if request.is_aggregation and not request.is_selection and \
                len(selected) > 1 and \
                not any(upsert_mask_active(s) for s in selected) and \
                all(getattr(s, "star_trees", None) for s in selected):
            from pinot_tpu.startree.executor import \
                try_star_tree_execute_multi
            with obs_span(ServerQueryPhase.STAR_TREE_EXECUTE,
                          segments=len(selected)) as span:
                blk = try_star_tree_execute_multi(selected, request)
                if span is not None:
                    span["attrs"]["hit"] = blk is not None
                    if blk is not None:
                        span["attrs"]["native"] = blk.cube_native
            if blk is not None:
                obs_profiler.count_path("cube", len(selected))
                return blk
        return None


class _BatchMember:
    """Per-request accumulator for the batched execution loop."""
    __slots__ = ("request", "selected", "selected_ids", "num_pruned",
                 "blocks", "extra_parts", "extra_matched", "executed",
                 "final")

    def __init__(self, request, selected, num_total: int):
        self.request = request
        self.selected = selected
        self.selected_ids = {id(s) for s in selected}
        self.num_pruned = num_total - len(selected)
        self.blocks: List[IntermediateResultsBlock] = []
        self.extra_parts = 0
        self.extra_matched = 0
        self.executed = 0
        self.final: Optional[IntermediateResultsBlock] = None

    def add(self, blocks, parts: int, matched: int) -> None:
        self.blocks.extend(blocks)
        self.extra_parts += parts
        self.extra_matched += matched

    def finish(self, t0: float) -> IntermediateResultsBlock:
        """Combine + stats, mirroring ServerQueryExecutor._execute's
        tail for one member."""
        request = self.request
        if not self.blocks:
            blk = IntermediateResultsBlock()
            if request.is_group_by:
                blk.group_map = {}
            elif request.is_aggregation:
                blk.agg_intermediates = None
            if request.is_selection:
                blk.selection_rows = []
                blk.selection_columns = list(request.selection.columns)
                if request.vector is not None:
                    from pinot_tpu.common.request import \
                        VECTOR_RESULT_COLUMNS
                    blk.selection_columns += list(VECTOR_RESULT_COLUMNS)
        else:
            blk = combine_blocks(request, self.blocks)
        if self.executed < len(self.selected):
            blk.exceptions.append(
                "DeadlineExceededError: segment execution truncated at "
                f"{self.executed}/{len(self.selected)} segments (budget "
                "expired mid-query)")
        if self.extra_parts:
            blk.stats.num_segments_processed -= self.extra_parts
            blk.stats.num_segments_matched -= self.extra_matched
        consuming_ts = [int(s_.last_indexed_time_ms)
                        for s_ in self.selected
                        if getattr(s_, "is_mutable", False) and
                        hasattr(s_, "last_indexed_time_ms")]
        blk.stats.num_consuming_segments_processed = len(consuming_ts)
        if consuming_ts:
            blk.stats.min_consuming_freshness_ms = min(consuming_ts)
        blk.stats.num_segments_pruned = self.num_pruned
        blk.stats.time_used_ms = (time.perf_counter() - t0) * 1e3
        return blk
