"""Server query executor: acquire → prune → execute → DataTable.

Parity: pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:100-267
— refcounted segment acquisition, pruning, per-segment execution (device
kernels, with the mesh-sharded combine when segments are homogeneous),
timeout accounting, execution-stats metadata on the DataTable.

`execute` (one request) and `execute_batch` (one sealed coalescer batch)
are one request frame round `query/executor.py` `ServerQueryExecutor`,
which also chooses sharded or sequential.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
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
from pinot_tpu.query.plan import preprocess_request
from pinot_tpu.query.stages.errors import (StageCompileError,
                                           stage_error_datatable)
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
        self.sharded = None
        if mesh is not None:
            from pinot_tpu.parallel.sharded import ShardedQueryExecutor
            self.sharded = ShardedQueryExecutor(mesh=mesh)
            data_manager.add_removal_listener(self.sharded.evict_segment)
        # segment_executor: the scheduler's query-worker pool — per-
        # segment plans fan out on it (CombineOperator parity); None
        # keeps the sequential per-segment loop
        self.executor = ServerQueryExecutor(
            use_device=use_device, segment_executor=segment_executor,
            sharded=self.sharded)
        # residency manager: heat accounting, tier routing (host/disk-
        # tier segments execute through host_exec, and keep a query off
        # the sharded combine), query pins so a concurrent demotion
        # never releases a lane mid-read. Defaults to the process-global
        # manager, which is unbudgeted (= the pre-manager behavior)
        # until someone configures a budget.
        from pinot_tpu.server import residency_manager
        self.residency = residency if residency is not None \
            else residency_manager.MANAGER
        self.executor.device_gate = self.residency.device_allowed
        self.executor.lane_stacks.fits = self.residency.admits_derived
        self.executor.mutable_gate = self.residency.mutable_device_allowed
        self.default_timeout_ms = default_timeout_ms
        self.metrics = metrics or MetricsRegistry("server")

    # -- the request frame, shared by execute and execute_batch -------------
    def _arrived(self, request: InstanceRequest, wait_ms: float) -> None:
        self.metrics.meter(ServerMeter.QUERIES).mark()
        vec = request.query.vector
        if vec is not None and int(getattr(vec, "nprobe", 0) or 0) > 0:
            self.metrics.meter(ServerMeter.IVF_NPROBE_QUERIES).mark()
        self.metrics.timer(ServerQueryPhase.SCHEDULER_WAIT).update(wait_ms)

    def _unserved(self, requests: List[InstanceRequest],
                  message: str) -> List[DataTable]:
        """Replies for work that touched no segment: the exception,
        and the requestId the broker matches replies by."""
        out = []
        for request in requests:
            dt = DataTable()
            dt.metadata["requestId"] = str(request.request_id)
            dt.exceptions.append(message)
            out.append(dt)
        return out

    def _drop_expired(self, requests: List[InstanceRequest],
                      deadline: Optional[float]
                      ) -> Optional[List[DataTable]]:
        if deadline is None or time.monotonic() < deadline:
            return None
        self.metrics.meter(
            ServerMeter.DEADLINE_EXPIRED_QUERIES).mark(len(requests))
        return self._unserved(
            requests, "DeadlineExceededError: query budget expired before "
            "execution started; dropped without executing")

    @contextmanager
    def _acquired(self, tdm, search_segments):
        """Yields (segments, missing, pre_states); the segments are
        refcounted and pinned until the block is left."""
        acquired, missing = tdm.acquire_segments(search_segments)
        segments = [s.segment for s in acquired]
        # residency entry: bump heat, reload disk-tier segments, pin
        # lane epochs so demotion drains us before releasing (paired
        # end_query in the finally below)
        residency_token = self.residency.begin_query(segments)
        try:
            # capture result-cache key states BEFORE execution: an
            # upsert validDocIds bump mid-query would otherwise key
            # pre-invalidation rows under the POST-bump version — a
            # persistent lie every later identical query would hit.
            # Keying under the pre-bump version is safe: versions only
            # grow, so a probe can never construct the raced key again
            # (the entry is at worst dead weight until evicted).
            from pinot_tpu.server.result_cache import segment_cache_states
            yield segments, missing, \
                None if missing else segment_cache_states(segments)
        finally:
            self.residency.end_query(residency_token)
            for sdm in acquired:
                tdm.release_segment(sdm)

    def _to_datatable(self, request: InstanceRequest, query,
                      block: IntermediateResultsBlock,
                      profile: QueryProfile, pre_states, missing,
                      elapsed_ms: float, trace: TraceContext) -> DataTable:
        if missing:
            block.exceptions.append(
                f"{SEGMENT_MISSING_EXC_PREFIX} {sorted(missing)}")
        timeout_ms = query.query_options.timeout_ms or self.default_timeout_ms
        if request.deadline_budget_ms is not None:
            # the broker's remaining budget caps the server-side timeout
            timeout_ms = min(timeout_ms, request.deadline_budget_ms)
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
        # acquisition time — the instance layer keys the result cache
        # on them; None = uncacheable (mutable segment, missing CRC,
        # or missing segments)
        dt.cache_states = pre_states
        profile.finish_from_stats(block.stats)
        # the operator profile always travels (a handful of ints);
        # the broker folds it into rolling per-table stats
        dt.metadata["profileInfo"] = profile.to_json_str()
        if missing:
            dt.metadata[MISSING_SEGMENTS_KEY] = json.dumps(sorted(missing))
        if request.enable_trace:
            # the collections and compiles that stopped the process
            # while this request was in it
            obs_profiler.record_stalls(trace)
            dt.metadata["traceInfo"] = trace.to_json_str()
        return dt

    def execute(self, request: InstanceRequest,
                scheduler_wait_ms: float = 0.0,
                deadline: Optional[float] = None,
                deser_ms: float = 0.0) -> DataTable:
        """`deadline`: absolute time.monotonic() instant from the
        broker-propagated budget; expired work is dropped or truncated
        instead of computing answers nobody will read."""
        t_start = time.perf_counter()
        self._arrived(request, scheduler_wait_ms)
        dropped = self._drop_expired([request], deadline)
        if dropped is not None:
            return dropped[0]
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
        tdm = self.data_manager.table(query.table_name)
        if tdm is None:
            return self._unserved(
                [request], f"TableDoesNotExistError: {query.table_name}")[0]

        profile = QueryProfile(query.table_name)
        with self._acquired(tdm, request.search_segments) as \
                (segments, missing, pre_states):
            # FASTHLL derived rewrite happens HERE, once, before the
            # per-segment fan-out: this request instance is private to
            # this server query (deserialized per dispatch), and the
            # DataTable columns below must carry the rewritten names
            query = preprocess_request(segments, query)
            try:
                if query.join is not None:
                    # join stage 2: fetch the (partition-filtered) dim
                    # blocks and attach the probe context
                    query = self._attach_join_context(request, query,
                                                      segments, deadline)
                with obs_profiler.active(profile, trace):
                    block = self.executor.execute(query, segments,
                                                  trace=trace,
                                                  deadline=deadline)
            except StageCompileError as e:
                # only a join raises it, from the context above or from
                # per-segment planning (e.g. the fact key column's type
                # fails the integer contract): a typed reply, never a
                # generic execution fault
                return stage_error_datatable(
                    request.request_id, "joinCompile", str(e))
            return self._to_datatable(
                request, query, block, profile, pre_states, missing,
                (time.perf_counter() - t_start) * 1e3, trace)

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
        for request, wait_ms in zip(requests, scheduler_wait_ms):
            self._arrived(request, wait_ms)
        dropped = self._drop_expired(requests, deadline)
        if dropped is not None:
            return dropped
        table = requests[0].query.table_name
        tdm = self.data_manager.table(table)
        if tdm is None:
            return self._unserved(requests,
                                  f"TableDoesNotExistError: {table}")

        trace = make_trace_context(False)
        profile = QueryProfile(table)
        with self._acquired(tdm, requests[0].search_segments) as \
                (segments, missing, pre_states):
            queries = [preprocess_request(segments, r.query)
                       for r in requests]
            with obs_profiler.active(profile, trace):
                blocks = self.executor.execute_batch(
                    queries, segments, trace=trace, deadline=deadline)
            # every member pays (and reports) the batch wall time — it
            # really did wait for the shared dispatch
            elapsed_ms = (time.perf_counter() - t_start) * 1e3
            out = []
            for request, query, block in zip(requests, queries, blocks):
                # per-member profile: own result stats; the dispatch /
                # transfer / path numbers are the BATCH's (each member
                # honestly rode every shared dispatch), batchSize says so
                mp = QueryProfile(table)
                mp.dispatches = profile.dispatches
                mp.transfer_bytes = profile.transfer_bytes
                mp.kernel_ms = profile.kernel_ms
                mp.paths = dict(profile.paths)
                mp.batch_size = len(requests)
                out.append(self._to_datatable(
                    request, query, block, mp, pre_states, missing,
                    elapsed_ms, trace))
            return out

    def _attach_join_context(self, request: InstanceRequest, query,
                             segments: List, deadline: Optional[float]):
        """Build the JoinContext from the exchanged dim blocks and
        attach it to a server-local request copy."""
        import copy
        from pinot_tpu.query.stages import join as stages_join
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
