"""Server query executor: acquire → prune → execute → DataTable.

Parity: pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:100-267
— refcounted segment acquisition, pruning, per-segment execution (device
kernels, with the mesh-sharded combine when segments are homogeneous),
timeout accounting, execution-stats metadata on the DataTable.
"""
from __future__ import annotations

import json
import time
from typing import List, Optional

from pinot_tpu.common.datatable import (DataTable, MISSING_SEGMENTS_KEY,
                                        SEGMENT_MISSING_EXC_PREFIX)
from pinot_tpu.common.metrics import (MetricsRegistry, ServerMeter,
                                      ServerQueryPhase)
from pinot_tpu.common.request import InstanceRequest
from pinot_tpu.obs import profiler as obs_profiler
from pinot_tpu.obs.profiler import QueryProfile
from pinot_tpu.obs.tracing import TraceContext, make_trace_context
from pinot_tpu.query.blocks import IntermediateResultsBlock
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.server.data_manager import InstanceDataManager


def _trace_annotation(name: str, **attrs):
    """The server's span annotation: every `TraceContext.span` of a
    traced query is also a `jax.profiler.TraceAnnotation`, so an open
    profiler session (`/debug/profiler/*`) shows the spans on the host
    threads beside `XLA Ops`, on the profiler's own clock. Handed to
    the context here because `obs/tracing.py` must stay off jax."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **attrs)


class InstanceQueryExecutor:
    """Executes InstanceRequests against this server's tables."""

    def __init__(self, data_manager: InstanceDataManager,
                 mesh=None, use_device: bool = True,
                 default_timeout_ms: float = 15_000.0,
                 metrics: Optional[MetricsRegistry] = None,
                 segment_executor=None, residency=None):
        self.data_manager = data_manager
        # segment_executor: the scheduler's query-worker pool — per-
        # segment plans fan out on it (CombineOperator parity); None
        # keeps the sequential per-segment loop
        self.executor = ServerQueryExecutor(
            use_device=use_device, segment_executor=segment_executor)
        # residency manager: heat accounting, tier routing (host/disk-
        # tier segments execute through host_exec), query pins so a
        # concurrent demotion never releases a lane mid-read. Defaults
        # to the process-global manager, which is unbudgeted (= the
        # pre-manager behavior) until someone configures a budget.
        from pinot_tpu.server import residency_manager
        self.residency = residency if residency is not None \
            else residency_manager.MANAGER
        self.executor.device_gate = self.residency.device_allowed
        self.executor.mutable_gate = self.residency.mutable_device_allowed
        self.sharded = None
        if mesh is not None:
            from pinot_tpu.parallel.sharded import ShardedQueryExecutor
            self.sharded = ShardedQueryExecutor(mesh=mesh)
            data_manager.add_removal_listener(self.sharded.evict_segment)
        self.default_timeout_ms = default_timeout_ms
        self.metrics = metrics or MetricsRegistry("server")

    def execute(self, request: InstanceRequest,
                scheduler_wait_ms: float = 0.0,
                deadline: Optional[float] = None,
                deser_ms: float = 0.0) -> DataTable:
        """`deadline`: absolute time.monotonic() instant from the
        broker-propagated budget; expired work is dropped or truncated
        instead of computing answers nobody will read."""
        t_start = time.perf_counter()
        self.metrics.meter(ServerMeter.QUERIES).mark()
        vec = request.query.vector
        if vec is not None and int(getattr(vec, "nprobe", 0) or 0) > 0:
            self.metrics.meter(ServerMeter.IVF_NPROBE_QUERIES).mark()
        self.metrics.timer(ServerQueryPhase.SCHEDULER_WAIT).update(
            scheduler_wait_ms)
        if deadline is not None and time.monotonic() >= deadline:
            self.metrics.meter(ServerMeter.DEADLINE_EXPIRED_QUERIES).mark()
            dt = DataTable()
            dt.metadata["requestId"] = str(request.request_id)
            dt.exceptions.append(
                "DeadlineExceededError: query budget expired before "
                "execution started; dropped without executing")
            return dt
        # the server's span subtree roots under the broker's dispatch
        # span (parent_span_id) so the reduce step can merge one
        # cross-process trace tree with correct parent links
        trace = make_trace_context(request.enable_trace,
                                   trace_id=request.trace_id,
                                   parent_span_id=request.parent_span_id,
                                   root_name="server",
                                   annotate=_trace_annotation)
        if trace.enabled:
            # both ended before this context existed: the wait just
            # now, the decode before the wait began
            wait = trace.record(ServerQueryPhase.SCHEDULER_WAIT,
                                scheduler_wait_ms)
            if deser_ms:
                trace.record(ServerQueryPhase.REQUEST_DESERIALIZATION,
                             deser_ms, start_us=wait["startUs"] -
                             int(deser_ms * 1e3))
        query = request.query
        if query.windows and request.exchange_sources is not None:
            # window stage 2 (coordinator): all data arrives through the
            # exchange — no local segment acquisition at all
            return self._execute_window_stage(request, deadline)
        timeout_ms = query.query_options.timeout_ms or self.default_timeout_ms
        if request.deadline_budget_ms is not None:
            # the broker's remaining budget caps the server-side timeout
            timeout_ms = min(timeout_ms, request.deadline_budget_ms)
        tdm = self.data_manager.table(query.table_name)
        if tdm is None:
            dt = DataTable()
            dt.exceptions.append(
                f"TableDoesNotExistError: {query.table_name}")
            return dt

        profile = QueryProfile(query.table_name)
        acquired, missing = tdm.acquire_segments(request.search_segments)
        # residency entry: bump heat, reload disk-tier segments, pin
        # lane epochs so demotion drains us before releasing (paired
        # end_query in the finally below)
        residency_token = self.residency.begin_query(
            [s.segment for s in acquired])
        try:
            segments = [s.segment for s in acquired]
            # capture result-cache key states BEFORE execution: an
            # upsert validDocIds bump mid-query would otherwise key
            # pre-invalidation rows under the POST-bump version — a
            # persistent lie every later identical query would hit.
            # Keying under the pre-bump version is safe: versions only
            # grow, so a probe can never construct the raced key again
            # (the entry is at worst dead weight until evicted).
            from pinot_tpu.server.result_cache import segment_cache_states
            pre_states = None if missing else segment_cache_states(segments)
            from pinot_tpu.query.plan import preprocess_request
            # FASTHLL derived rewrite happens HERE, once, before the
            # per-segment fan-out: this request instance is private to
            # this server query (deserialized per dispatch), and the
            # DataTable columns below must carry the rewritten names
            query = preprocess_request(segments, query)
            if query.join is not None:
                # join stage 2: fetch the (partition-filtered) dim
                # blocks and attach the probe context; StageCompileError
                # → typed reply, never a generic execution fault
                from pinot_tpu.query.stages.errors import (
                    StageCompileError, stage_error_datatable)
                try:
                    query = self._attach_join_context(request, query,
                                                      segments, deadline)
                except StageCompileError as e:
                    return stage_error_datatable(
                        request.request_id, "joinCompile", str(e))
                try:
                    with obs_profiler.active(profile, trace):
                        block = self._execute_segments(
                            query, segments, trace, deadline=deadline)
                except StageCompileError as e:
                    # raised from per-segment planning (e.g. the fact
                    # key column's type fails the integer contract)
                    return stage_error_datatable(
                        request.request_id, "joinCompile", str(e))
            else:
                with obs_profiler.active(profile, trace):
                    block = self._execute_segments(query, segments, trace,
                                                   deadline=deadline)
            if missing:
                block.exceptions.append(
                    f"{SEGMENT_MISSING_EXC_PREFIX} {sorted(missing)}")
            elapsed_ms = (time.perf_counter() - t_start) * 1e3
            if elapsed_ms > timeout_ms:
                block.exceptions.append(
                    f"QueryTimeoutError: {elapsed_ms:.0f}ms > "
                    f"{timeout_ms:.0f}ms")
            block.stats.time_used_ms = elapsed_ms
            self.metrics.timer(ServerQueryPhase.QUERY_PROCESSING).update(
                elapsed_ms)
            # per-table twin: the admission controller's rolling
            # service-time estimate (deadline-aware shedding) reads it
            self.metrics.timer(ServerQueryPhase.QUERY_PROCESSING,
                               table=query.table_name).update(elapsed_ms)
            trace.record(ServerQueryPhase.QUERY_PROCESSING, elapsed_ms)
            dt = DataTable.from_block(query, block)
            dt.metadata["requestId"] = str(request.request_id)
            # frozen (name, crc, validDocIds-version) states of the
            # segments this answer was computed over, captured at
            # acquisition time above — the instance layer keys the
            # result cache on them; None = uncacheable (mutable
            # segment, missing CRC, or missing segments)
            dt.cache_states = pre_states
            profile.finish_from_stats(block.stats)
            # the operator profile always travels (a handful of ints);
            # the broker folds it into rolling per-table stats
            dt.metadata["profileInfo"] = profile.to_json_str()
            if missing:
                dt.metadata[MISSING_SEGMENTS_KEY] = json.dumps(
                    sorted(missing))
            if request.enable_trace:
                dt.metadata["traceInfo"] = trace.to_json_str()
            return dt
        finally:
            self.residency.end_query(residency_token)
            for sdm in acquired:
                tdm.release_segment(sdm)

    def execute_batch(self, requests: List[InstanceRequest],
                      scheduler_wait_ms: List[float],
                      deadline: Optional[float]) -> List[DataTable]:
        """One sealed coalescer batch: N same-shape requests over one
        table + segment list, sharing device dispatches.

        The coalescer only seals groups whose members share a table,
        search-segment list, and plan-shape key, carry no trace, and
        are not staged (join/window/exchange) — the invariants this
        path leans on. Returns DataTables aligned with `requests`.
        """
        t_start = time.perf_counter()
        n = len(requests)
        for wait_ms in scheduler_wait_ms:
            self.metrics.meter(ServerMeter.QUERIES).mark()
            self.metrics.timer(ServerQueryPhase.SCHEDULER_WAIT).update(
                wait_ms)
        if deadline is not None and time.monotonic() >= deadline:
            out = []
            for request in requests:
                self.metrics.meter(
                    ServerMeter.DEADLINE_EXPIRED_QUERIES).mark()
                dt = DataTable()
                dt.metadata["requestId"] = str(request.request_id)
                dt.exceptions.append(
                    "DeadlineExceededError: query budget expired before "
                    "execution started; dropped without executing")
                out.append(dt)
            return out
        table = requests[0].query.table_name
        tdm = self.data_manager.table(table)
        if tdm is None:
            out = []
            for request in requests:
                dt = DataTable()
                dt.metadata["requestId"] = str(request.request_id)
                dt.exceptions.append(
                    f"TableDoesNotExistError: {table}")
                out.append(dt)
            return out

        trace = make_trace_context(False)
        profile = QueryProfile(table)
        acquired, missing = tdm.acquire_segments(
            requests[0].search_segments)
        residency_token = self.residency.begin_query(
            [s.segment for s in acquired])
        try:
            segments = [s.segment for s in acquired]
            from pinot_tpu.server.result_cache import segment_cache_states
            pre_states = None if missing else \
                segment_cache_states(segments)
            from pinot_tpu.query.plan import preprocess_request
            # preprocess HERE (not just inside the executor): the
            # DataTable columns must carry any FASTHLL-rewritten names
            queries = [preprocess_request(segments, r.query)
                       for r in requests]
            with obs_profiler.active(profile, trace):
                blocks = self.executor.execute_batch(
                    queries, segments, trace=trace, deadline=deadline)
            elapsed_ms = (time.perf_counter() - t_start) * 1e3
            out = []
            for request, query, block in zip(requests, queries, blocks):
                if missing:
                    block.exceptions.append(
                        f"{SEGMENT_MISSING_EXC_PREFIX} {sorted(missing)}")
                timeout_ms = query.query_options.timeout_ms or \
                    self.default_timeout_ms
                if request.deadline_budget_ms is not None:
                    timeout_ms = min(timeout_ms,
                                     request.deadline_budget_ms)
                if elapsed_ms > timeout_ms:
                    block.exceptions.append(
                        f"QueryTimeoutError: {elapsed_ms:.0f}ms > "
                        f"{timeout_ms:.0f}ms")
                block.stats.time_used_ms = elapsed_ms
                # every member pays (and reports) the batch wall time —
                # it really did wait for the shared dispatch
                self.metrics.timer(
                    ServerQueryPhase.QUERY_PROCESSING).update(elapsed_ms)
                self.metrics.timer(ServerQueryPhase.QUERY_PROCESSING,
                                   table=table).update(elapsed_ms)
                dt = DataTable.from_block(query, block)
                dt.metadata["requestId"] = str(request.request_id)
                dt.cache_states = pre_states
                # per-member profile: own result stats; the dispatch /
                # transfer / path numbers are the BATCH's (each member
                # honestly rode every shared dispatch), batchSize says so
                mp = QueryProfile(table)
                mp.dispatches = profile.dispatches
                mp.transfer_bytes = profile.transfer_bytes
                mp.kernel_ms = profile.kernel_ms
                mp.paths = dict(profile.paths)
                mp.batch_size = n
                mp.finish_from_stats(block.stats)
                dt.metadata["profileInfo"] = mp.to_json_str()
                if missing:
                    dt.metadata[MISSING_SEGMENTS_KEY] = json.dumps(
                        sorted(missing))
                out.append(dt)
            return out
        finally:
            self.residency.end_query(residency_token)
            for sdm in acquired:
                tdm.release_segment(sdm)

    def _attach_join_context(self, request: InstanceRequest, query,
                             segments: List, deadline: Optional[float]):
        """Build the JoinContext from the exchanged dim blocks and
        attach it to a server-local request copy."""
        import copy
        from pinot_tpu.query.stages import join as stages_join
        from pinot_tpu.query.stages.errors import StageCompileError
        if request.exchange_sources is None:
            raise StageCompileError(
                "join query dispatched without exchange sources (stage-1 "
                "dim scan missing)")
        fact_parts = stages_join.fact_partition_info(
            segments, query.join.fact_key)
        ctx = stages_join.build_context(query.join,
                                        request.exchange_sources,
                                        fact_parts, deadline_s=deadline)
        if segments:
            # fact-key contract check up front (exists, SV integer) —
            # an empty dim side must not mask a misspelled/mistyped key
            from pinot_tpu.query.plan import _join_key_source
            _join_key_source(ctx, segments[0])
        query = copy.copy(query)
        query._join_ctx = ctx
        return query

    def _execute_window_stage(self, request: InstanceRequest,
                              deadline: Optional[float]) -> DataTable:
        from pinot_tpu.query.stages.errors import (StageCompileError,
                                                   stage_error_datatable)
        from pinot_tpu.query.stages.window import execute_window_stage
        try:
            blk = execute_window_stage(
                request.query, request.exchange_sources,
                deadline_s=deadline,
                use_device=self.executor.use_device)
        except StageCompileError as e:
            return stage_error_datatable(request.request_id,
                                         "windowCompile", str(e))
        dt = DataTable.from_block(request.query, blk)
        dt.metadata["requestId"] = str(request.request_id)
        return dt

    def _execute_segments(self, query, segments: List, trace: TraceContext,
                          deadline: Optional[float] = None
                          ) -> IntermediateResultsBlock:
        # the sharded combine stacks ALL segments' lanes in HBM — it
        # only applies when every segment is device-tier (a demoted
        # segment must not be re-uploaded through the stack path)
        if self.sharded is not None and len(segments) > 1 and \
                all(self.residency.device_allowed(s) for s in segments):
            from pinot_tpu.parallel.sharded import NotShardable
            from pinot_tpu.query.plan import (GroupsLimitExceeded,
                                              UnsupportedOnDevice)
            try:
                with trace.span(ServerQueryPhase.SHARDED_EXECUTION):
                    blk = self.sharded.execute(query, segments)
                blk.execution_path = "sharded"
                obs_profiler.count_path("sharded", len(segments))
                return blk
            except (NotShardable, GroupsLimitExceeded, UnsupportedOnDevice):
                pass
        blk = self.executor.execute(query, segments, trace=trace,
                                    deadline=deadline)
        blk.execution_path = "sequential"
        return blk
