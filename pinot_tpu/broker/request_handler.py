"""Broker request pipeline: compile → quota → route → scatter-gather →
reduce.

Parity: pinot-broker/.../requesthandler/BaseBrokerRequestHandler.java:127-346
(compile, ACL, table lookup offline/realtime/hybrid, QPS quota, optimizer,
time-boundary split, routing) and
SingleConnectionBrokerRequestHandler.java:54-111 + core/transport/
QueryRouter.java:43-57 (per-server InstanceRequests, gather with timeout,
partial-response tolerance, reduce via BrokerReduceService).
"""
from __future__ import annotations

import asyncio
import contextlib
import itertools
import json
import threading
import time
from typing import Dict, List, Optional, Tuple

from pinot_tpu.common.cluster_state import CONSUMING, ONLINE
from pinot_tpu.common.datatable import (DataTable, MISSING_SEGMENTS_KEY,
                                        RESULT_CACHE_HIT_KEY,
                                        RETRY_AFTER_MS_KEY,
                                        SEGMENT_MISSING_EXC_PREFIX,
                                        SERVER_BUSY_EXC_PREFIX,
                                        SERVER_BUSY_KEY, STAGE_ERROR_KEY)
from pinot_tpu.common.metrics import (BrokerGauge, BrokerMeter,
                                      BrokerQueryPhase, MetricsRegistry)
from pinot_tpu.transport import shm as _shm_mod
from pinot_tpu.common.request import BrokerRequest, InstanceRequest
from pinot_tpu.common.response import (BrokerResponse, classify_exception,
                                       exception_entry)
from pinot_tpu.common.serde import instance_request_to_bytes
from pinot_tpu.obs.slowlog import SlowQueryLog
from pinot_tpu.obs.profiler import TableStatsAggregator
from pinot_tpu.obs.tracing import (TraceContext, build_trace_tree,
                                   make_trace_context)
from pinot_tpu.common.table_name import (offline_table, raw_table,
                                         realtime_table)
from pinot_tpu.broker.fault_tolerance import FaultToleranceManager
from pinot_tpu.broker.quota import QueryQuotaManager
from pinot_tpu.broker.result_cache import BrokerResultCache
from pinot_tpu.broker.routing import RoutingError, RoutingManager
from pinot_tpu.broker.time_boundary import (TimeBoundaryService,
                                            attach_time_boundary)
from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
from pinot_tpu.pql.parser import compile_pql
from pinot_tpu.query.reduce import BrokerReduceService
from pinot_tpu.transport.tcp import EventLoopThread, ServerConnection


class ServerTransport:
    """Sends framed InstanceRequest bytes to a named server."""

    async def query(self, server: str, payload: bytes,
                    timeout: float) -> bytes:
        raise NotImplementedError

    async def close(self) -> None:
        pass


class InProcessTransport(ServerTransport):
    """Embedded-cluster transport: servers in this process (the reference's
    single-JVM ClusterTest pattern, full serde still exercised)."""

    def __init__(self, servers: Dict[str, object]):
        self.servers = servers        # name -> ServerInstance

    async def query(self, server: str, payload: bytes,
                    timeout: float) -> bytes:
        instance = self.servers[server]
        loop = asyncio.get_running_loop()
        return await asyncio.wait_for(
            loop.run_in_executor(None, instance.handle_request_bytes,
                                 payload),
            timeout)


class TcpTransport(ServerTransport):
    """One persistent MULTIPLEXED framed TCP connection per server:
    every concurrent query to a server shares its channel, correlated by
    requestId (ServerChannels parity), so in-flight requests are bounded
    by the server, not by a one-at-a-time connection lock."""

    def __init__(self, endpoints: Dict[str, Tuple[str, int]]):
        self.endpoints = dict(endpoints)
        self._conns: Dict[str, ServerConnection] = {}

    def set_endpoint(self, server: str, host: str, port: int) -> None:
        self.endpoints[server] = (host, port)
        stale = self._conns.pop(server, None)
        if stale is not None:
            # fail the old channel's in-flight requests promptly (they
            # were sent to the departed endpoint) instead of leaking a
            # reader task on a dead socket until its peers time out.
            # Callers are watcher threads, not the event loop — the
            # connection schedules close() onto ITS OWN loop.
            stale.close_threadsafe()

    async def query(self, server: str, payload: bytes,
                    timeout: float) -> bytes:
        conn = self._conns.get(server)
        if conn is None:
            host, port = self.endpoints[server]
            # concurrent first-queries race to create the channel;
            # setdefault keeps exactly one so they truly share it
            conn = self._conns.setdefault(server,
                                          ServerConnection(host, port))
        # the deadline covers connect + write + read: a black-holed
        # server (dropped SYNs) or a slow reply must still surface as a
        # timely partial response — and a timeout abandons only THIS
        # request's future, never the shared channel
        return await asyncio.wait_for(conn.request(payload, timeout),
                                      timeout)

    async def close(self) -> None:
        for conn in self._conns.values():
            # inline-HTTP brokers create connections on the API loop;
            # a close arriving from the handler's own loop must hop to
            # the connection's loop instead of awaiting cross-loop
            if conn._loop is None or \
                    conn._loop is asyncio.get_running_loop():
                await conn.close()
            else:
                conn.close_threadsafe()
        self._conns.clear()


def _dispatch_span(trace: Optional[TraceContext], dspan: Optional[dict],
                   name: str):
    """A span under one dispatch span (explicit parent: concurrent
    dispatches share the event-loop thread); nothing when untraced."""
    if dspan is None:
        return contextlib.nullcontext()
    return trace.span(name, parent_id=dspan["spanId"])


def _server_error(server: str, message: str) -> dict:
    """One per-server failure record; `recovered` flips to True when a
    replica re-dispatch later produced the data anyway."""
    return {"server": server, "message": message, "recovered": False}


class QueryRouter:
    """Budget-aware scatter engine: deadline propagation, breaker
    gating, hedged replica retries, per-server failure accounting.

    Each (sub-request, server, segments) dispatch unit runs through:
    1. breaker gate — an OPEN server is skipped outright,
    2. the primary call with the REMAINING deadline budget stamped into
       the InstanceRequest (deadline propagation),
    3. an optional hedge: if the primary is still pending past the
       server's p95-derived hedge threshold, the same segments go to
       another live replica and the first good answer wins,
    4. failover: on error / corrupt frame / timeout, the unit's
       segments are re-routed to other ONLINE/CONSUMING replicas from
       the current view (ranked by health score) while budget remains.

    Failures are never swallowed: every one is recorded (server +
    reason + whether a replica recovered it) and metered.
    """

    # primary + up to two failover waves per segment
    MAX_ATTEMPTS = 3

    def __init__(self, transport: ServerTransport, broker_id: str,
                 fault_tolerance: Optional[FaultToleranceManager] = None,
                 routing: Optional[RoutingManager] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 clock=time.monotonic):
        self.transport = transport
        self.broker_id = broker_id
        self.fault_tolerance = fault_tolerance
        self.routing = routing
        self.metrics = metrics or MetricsRegistry("broker")
        self._clock = clock

    async def submit(self, request_id: int,
                     routes: List[Tuple[BrokerRequest, Dict[str,
                                                            List[str]]]],
                     timeout: float, enable_trace: bool = False,
                     deadline: Optional[float] = None,
                     trace: Optional[TraceContext] = None,
                     parent_span_id: Optional[str] = None,
                     workload: Optional[str] = None,
                     exchange_sources: Optional[List[dict]] = None
                     ) -> Tuple[List[DataTable], int, int, List[dict]]:
        """routes: [(per-table request, {server: segments})] — returns
        (tables, num_queried, num_responded, errors). `deadline` is an
        absolute clock() instant shared by retries so re-dispatches
        never extend user-visible latency past the requested timeout.
        `trace`/`parent_span_id`: every dispatch (primary, hedge,
        failover) records a span under the scatter phase and stamps its
        own span id into the InstanceRequest as the server subtree's
        parent."""
        if deadline is None:
            deadline = self._clock() + timeout
        units = []
        for sub_request, routing in routes:
            for server, segments in routing.items():
                units.append((sub_request, server, segments))
        outcomes = await asyncio.gather(
            *(self._query_unit(request_id, sub, server, segments,
                               deadline, enable_trace, trace,
                               parent_span_id, workload,
                               exchange_sources)
              for sub, server, segments in units))
        tables: List[DataTable] = []
        errors: List[dict] = []
        responded = 0
        for unit_tables, unit_errors in outcomes:
            errors.extend(unit_errors)
            if unit_tables:
                tables.extend(unit_tables)
                responded += 1
        return tables, len(units), responded, errors

    # -- one dispatch unit --------------------------------------------------
    async def _query_unit(self, request_id: int, sub: BrokerRequest,
                          server: str, segments: List[str],
                          deadline: float, enable_trace: bool,
                          trace: Optional[TraceContext] = None,
                          parent_span_id: Optional[str] = None,
                          workload: Optional[str] = None,
                          exchange_sources: Optional[List[dict]] = None):
        errors: List[dict] = []
        tried = {server}
        tables: List[DataTable] = []
        # breaker gating happens inside _call_once (uniformly for the
        # primary, hedges and failovers); an OPEN primary just records
        # CircuitBreakerOpen there and falls through to failover
        dt = await self._dispatch_hedged(request_id, sub, server,
                                         segments, deadline,
                                         enable_trace, errors, tried,
                                         trace, parent_span_id, workload,
                                         exchange_sources)
        if dt is not None:
            for e in errors:         # e.g. primary failed, hedge won
                e["recovered"] = True
            return [dt], errors
        # failover: re-route this unit's segments to other live replicas
        # (waves, because the replacement can fail too) within budget.
        # EXCEPT a deadline-cause shed: the server judged the remaining
        # budget below the table's service-time estimate. The estimate
        # is the SHEDDING server's own rolling p75 — a transiently
        # degraded replica can shed what a healthy one would answer —
        # but under deadline pressure per-shed failover fan-out is the
        # worse failure mode (every doomed query multiplies RPCs right
        # at the overload knee), and each busy reply soft-dings the
        # shedder's health (on_busy), so routing steers subsequent
        # queries to healthier replicas within a few requests
        remaining_segs = list(segments)
        for _ in range(1, self.MAX_ATTEMPTS):
            if not remaining_segs or self._clock() >= deadline:
                break
            if any(e.get("busyCause") == "deadline" for e in errors):
                break
            groups = self._replica_groups(sub, remaining_segs, tried)
            if not groups:
                break
            self.metrics.meter(BrokerMeter.SEGMENT_RETRIES).mark(
                len(remaining_segs))
            items = sorted(groups.items())
            results = await asyncio.gather(
                *(self._call_once(request_id, sub, srv, segs, deadline,
                                  enable_trace, errors, trace,
                                  parent_span_id, workload,
                                  exchange_sources=exchange_sources)
                  for srv, segs in items))
            next_remaining: List[str] = []
            for (srv, segs), dt in zip(items, results):
                tried.add(srv)
                if dt is None:
                    next_remaining.extend(segs)
                else:
                    tables.append(dt)
            remaining_segs = next_remaining
        if not remaining_segs and tables:
            # every segment of the failed unit was recovered elsewhere:
            # the response is complete, demote the failures to telemetry
            for e in errors:
                e["recovered"] = True
        return tables, errors

    async def _dispatch_hedged(self, request_id, sub, server, segments,
                               deadline, enable_trace, errors, tried,
                               trace=None, parent_span_id=None,
                               workload=None, exchange_sources=None):
        """Primary call with a latency hedge to one replica."""
        ft = self.fault_tolerance
        primary = asyncio.ensure_future(self._call_once(
            request_id, sub, server, segments, deadline, enable_trace,
            errors, trace, parent_span_id, workload,
            exchange_sources=exchange_sources))
        hedge_after = ft.hedge_delay_s(server) if ft is not None else None
        if hedge_after is None:
            return await primary
        budget = deadline - self._clock()
        done, _pending = await asyncio.wait(
            {primary}, timeout=max(0.0, min(hedge_after, budget)))
        for t in done:
            # t came out of asyncio.wait's done set, so .result() is a
            # completed-future value read, not a loop-blocking wait —
            # the async-blocking rule VERIFIES this iteration pattern
            # (the audited `primary.result()` form was equivalent but
            # unverifiable statically)
            return t.result()
        hedge_server = self._hedge_candidate(sub, segments, tried)
        if hedge_server is None:
            return await primary
        tried.add(hedge_server)
        ft.on_hedge(server)
        # hedge=True travels in the request: under queue pressure the
        # server sheds hedged duplicates FIRST (deterministic order)
        hedge = asyncio.ensure_future(self._call_once(
            request_id, sub, hedge_server, segments, deadline,
            enable_trace, errors, trace, parent_span_id, workload,
            hedge=True, exchange_sources=exchange_sources))
        pending = {primary, hedge}
        winner = None
        while pending and winner is None:
            done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED)
            for t in done:
                dt = t.result()
                if dt is not None and winner is None:
                    winner = dt
        for t in pending:
            t.cancel()       # loser keeps running server-side; drop it
        if pending:
            # AWAIT the cancelled losers: their CancelledError handlers
            # patch the dispatch span (ms + attrs.cancelled), and those
            # dicts must be settled before _finish serializes the trace
            # tree on another thread
            await asyncio.wait(pending)
        return winner

    async def _call_once(self, request_id, sub, server, segments,
                         deadline, enable_trace, errors, trace=None,
                         parent_span_id=None, workload=None,
                         hedge=False, exchange_sources=None):
        """One dispatch to one server; stamps the remaining budget,
        classifies failures, feeds the health/breaker state."""
        ft = self.fault_tolerance
        if ft is not None and not ft.allow_request(server):
            # the one place every dispatch kind passes through, so the
            # breaker's single-probe half-open invariant holds for
            # hedges and failover waves too, not just primaries
            errors.append(_server_error(
                server, f"CircuitBreakerOpen: {server} is shedding load"))
            return None
        budget = deadline - self._clock()
        if budget <= 0:
            errors.append(_server_error(
                server, "DeadlineExceededError: no budget left to "
                f"dispatch to {server}"))
            return None
        # the dispatch span is created BEFORE the send so its id can
        # travel in the request as the server subtree's parent link;
        # concurrent dispatches of one query share an event-loop thread,
        # so parenting is explicit (parent_span_id), never stack-based.
        # ms is patched in when the reply lands (same dict object).
        dspan = None
        if trace is not None and trace.enabled:
            dspan = trace.record(f"dispatch:{server}", 0.0,
                                 parent_id=parent_span_id,
                                 segments=len(segments))
        with _dispatch_span(trace, dspan,
                            BrokerQueryPhase.REQUEST_SERIALIZATION):
            payload = instance_request_to_bytes(InstanceRequest(
                request_id=request_id, query=sub,
                search_segments=segments,
                broker_id=self.broker_id, enable_trace=enable_trace,
                deadline_budget_ms=budget * 1e3,
                trace_id=trace.trace_id if dspan is not None else None,
                parent_span_id=dspan["spanId"] if dspan is not None
                else None,
                workload=workload, hedge=hedge,
                exchange_sources=exchange_sources))
        self.metrics.meter(BrokerMeter.INSTANCE_REQUEST_BYTES).mark(
            len(payload))
        t0 = self._clock()
        try:
            raw = await asyncio.wait_for(
                self.transport.query(server, payload, budget), budget)
            # per-hop serde attribution: the decode share of the gather
            # is timed and its byte volume metered, so PROFILE
            # artifacts can split serde from transport+queueing
            self.metrics.meter(BrokerMeter.SERVER_RESPONSE_BYTES).mark(
                len(raw))
            with self.metrics.timer(
                    BrokerQueryPhase
                    .SERVER_RESPONSE_DESERIALIZATION).time(), \
                    _dispatch_span(
                        trace, dspan,
                        BrokerQueryPhase.RESPONSE_DESERIALIZATION):
                # colocated shared-memory replies decode straight from
                # the segment, then unlink (the decoder copies blocks
                # out of writable buffers by contract)
                dt = _shm_mod.datatable_from_reply(raw)
        except asyncio.CancelledError:
            # hedge loser / caller teardown: mark the span so the tree
            # shows an abandoned dispatch, not a 0ms "success"
            if dspan is not None:
                dspan["ms"] = round((self._clock() - t0) * 1e3, 3)
                dspan.setdefault("attrs", {})["cancelled"] = True
            raise
        except Exception as e:  # noqa: BLE001 — classified, never silent
            self.metrics.meter(BrokerMeter.SERVER_ERRORS).mark()
            self.metrics.meter(BrokerMeter.SERVER_ERRORS,
                               table=server).mark()
            if ft is not None:
                ft.on_failure(server)
            kind = "ServerTimeoutError" if \
                isinstance(e, asyncio.TimeoutError) else type(e).__name__
            errors.append(_server_error(server, f"{kind}: {e}"))
            if dspan is not None:
                dspan["ms"] = round((self._clock() - t0) * 1e3, 3)
                dspan.setdefault("attrs", {})["error"] = kind
            return None
        if dspan is not None:
            dspan["ms"] = round((self._clock() - t0) * 1e3, 3)
        busy_cause = dt.metadata.get(SERVER_BUSY_KEY)
        if busy_cause is not None:
            # typed server-busy: the server's admission control shed
            # this request. NON-RETRIABLE on the same server (it just
            # told us it is drowning) — record the failure so the unit
            # fails over to a replica; `tried` already excludes this
            # server from hedges and failover waves. Health takes a
            # soft ding, the breaker NEVER trips on honest shedding.
            self.metrics.meter(BrokerMeter.SERVER_BUSY_RESPONSES).mark()
            self.metrics.meter(BrokerMeter.SERVER_BUSY_RESPONSES,
                               table=busy_cause).mark()
            if ft is not None:
                ft.on_busy(server)
            retry_ms = dt.metadata.get(RETRY_AFTER_MS_KEY, "0")
            err = _server_error(
                server, f"{SERVER_BUSY_EXC_PREFIX} shed ({busy_cause}), "
                f"retryAfterMs={retry_ms}")
            # internal routing markers only — _finish surfaces just
            # server/message, so these never reach the client.
            # busyCause is ALSO the structured busy classifier _finish
            # keys 503-vs-425 on (never the message text, whose wording
            # is free to change); retryAfterMs feeds the whole-query-
            # shed Retry-After the HTTP layer returns with its 503
            err["busyCause"] = busy_cause
            try:
                err["retryAfterMs"] = float(retry_ms)
            except (TypeError, ValueError):
                err["retryAfterMs"] = 0.0
            errors.append(err)
            if dspan is not None:
                dspan.setdefault("attrs", {})["busy"] = busy_cause
            return None
        if ft is not None:
            ft.on_success(server, (self._clock() - t0) * 1e3)
        dt.metadata.setdefault("serverName", server)
        return dt

    # -- replica selection --------------------------------------------------
    def _view_for(self, sub: BrokerRequest):
        """Fetch the routing view ONCE per selection scan — view() deep-
        copies the table under the routing lock, so per-segment fetches
        would make failover O(segments × view size) in copies."""
        return self.routing.view(sub.table_name) \
            if self.routing is not None else None

    def _live_replicas(self, view, segment: str, tried: set) -> List[str]:
        if view is None:
            return []
        ft = self.fault_tolerance
        out = [srv for srv in view.servers_for(segment,
                                               states=(ONLINE, CONSUMING))
               if srv not in tried and (ft is None or ft.available(srv))]
        if ft is not None:
            out.sort(key=lambda s: -ft.health(s))
        return out

    def _replica_groups(self, sub: BrokerRequest, segments: List[str],
                        tried: set) -> Dict[str, List[str]]:
        """Healthiest untried live replica per segment, grouped into
        per-server dispatch lists."""
        view = self._view_for(sub)
        groups: Dict[str, List[str]] = {}
        for segment in segments:
            candidates = self._live_replicas(view, segment, tried)
            if candidates:
                groups.setdefault(candidates[0], []).append(segment)
        return groups

    def _hedge_candidate(self, sub: BrokerRequest, segments: List[str],
                         tried: set) -> Optional[str]:
        """A single untried replica serving EVERY segment of the unit
        (a hedge duplicates the whole unit, it does not split it)."""
        if not segments:
            return None
        view = self._view_for(sub)
        common: Optional[set] = None
        for segment in segments:
            servers = set(self._live_replicas(view, segment, tried))
            common = servers if common is None else common & servers
            if not common:
                return None
        ft = self.fault_tolerance
        if ft is not None:
            return max(common, key=ft.health)
        return sorted(common)[0]


class BrokerRequestHandler:
    """The broker's query entry point (PQL string → BrokerResponse)."""

    def __init__(self, routing: RoutingManager,
                 transport: ServerTransport,
                 time_boundary: Optional[TimeBoundaryService] = None,
                 quota: Optional[QueryQuotaManager] = None,
                 broker_id: str = "broker_0",
                 default_timeout_s: float = 15.0,
                 metrics: Optional[MetricsRegistry] = None,
                 access_control=None,
                 segment_pruner=None,
                 fault_tolerance: Optional[FaultToleranceManager] = None,
                 slow_log: Optional[SlowQueryLog] = None,
                 result_cache: Optional[BrokerResultCache] = None,
                 cache_freshness_ms: Optional[float] = None,
                 cache_offline: Optional[bool] = None):
        # optional broker-side segment pruner (PartitionZKMetadataPruner):
        # prune(request, table, segments) -> segments
        self.segment_pruner = segment_pruner
        self.routing = routing
        self.metrics = metrics or MetricsRegistry("broker")
        from pinot_tpu.obs import residency
        residency.bind_registry(self.metrics)
        # sampling JSONL slow-query log (obs/slowlog.py); default: the
        # PINOT_TPU_SLOWLOG* env config, None = disabled
        self.slow_log = slow_log if slow_log is not None else \
            SlowQueryLog.from_env()
        # rolling per-table operator stats folded from every query's
        # server-side profile (obs/profiler.py)
        self.table_stats = TableStatsAggregator()
        # pre-register the core series so /metrics serves a meaningful
        # exposition from boot (a counter that exists at 0 beats one
        # that appears after the first query) and export uptime
        self._t_boot = time.monotonic()
        self.metrics.meter(BrokerMeter.QUERIES)
        self.metrics.gauge(BrokerGauge.UPTIME_SECONDS).set_callable(
            lambda: time.monotonic() - self._t_boot)
        self.fault_tolerance = fault_tolerance or FaultToleranceManager(
            metrics=self.metrics)
        self.router = QueryRouter(transport, broker_id,
                                  fault_tolerance=self.fault_tolerance,
                                  routing=routing, metrics=self.metrics)
        self.time_boundary = time_boundary or TimeBoundaryService()
        self.quota = quota or QueryQuotaManager()
        # broker-level result cache for tables with a realtime part,
        # bounded by minConsumingFreshnessTimeMs: the query option opts
        # in per query; `cache_freshness_ms` sets a broker-wide default
        # bound (None = only explicitly-bounded queries are cached)
        self.result_cache = result_cache or BrokerResultCache()
        self.default_cache_freshness_ms = cache_freshness_ms
        # pure-OFFLINE tables: results change only on segment lifecycle
        # events, and the cluster watcher flushes this cache on exactly
        # those (register_result_cache) — so caching them is EXACT, not
        # freshness-bounded, keyed on the same canonical fingerprint.
        # Default off (opt in per deployment / via env for bench rigs).
        if cache_offline is None:
            import os
            cache_offline = os.environ.get(
                "PINOT_TPU_BROKER_CACHE_OFFLINE", "0") != "0"
        self.cache_offline = bool(cache_offline)
        # compiled-request cache: the serving plane replays a small set
        # of query STRINGS at high rate; re-lexing the same PQL per
        # request was ~0.4ms of the per-query CPU budget. Entries are
        # treated as immutable downstream (_retable/attach_time_boundary
        # copy; force_trace copies below). Fingerprints memoize beside
        # the compiled form since they hash the same canonical tree.
        self._compile_cache: Dict[str, list] = {}
        self._compile_cache_max = 512
        self.optimizer = BrokerRequestOptimizer()
        self.reducer = BrokerReduceService()
        if access_control is None:
            from pinot_tpu.broker.access_control import AllowAllAccessControl
            access_control = AllowAllAccessControl()
        self.access_control = access_control
        self.default_timeout_s = default_timeout_s
        self._request_ids = itertools.count(1)
        self._loop: Optional[EventLoopThread] = None
        self._loop_lock = threading.Lock()

    # -- sync facade -------------------------------------------------------
    def handle(self, pql: str, identity=None,
               force_trace: bool = False) -> BrokerResponse:
        """The CPU stages (compile, ACL, route, reduce) run HERE, on the
        caller's thread; only the scatter-gather await shares the event
        loop. One loop thread carries every concurrent query's network
        waits just fine — it cannot also carry every query's compile and
        reduce without becoming the serving plane's bottleneck."""
        with self._loop_lock:
            if self._loop is None:
                self._loop = EventLoopThread()
            loop = self._loop
        prepared = self._prepare(pql, identity, force_trace)
        if isinstance(prepared, BrokerResponse):
            return prepared
        request, trace, routes, timeout_s, deadline, t0, workload, \
            fingerprint = prepared
        tables, queried, responded, errors = loop.run(
            self._scatter(request, trace, routes, timeout_s, deadline,
                          workload))
        return self._finish(request, trace, t0, tables, queried,
                            responded, errors, pql=pql,
                            fingerprint=fingerprint)

    def close(self) -> None:
        if self._loop is not None:
            self._loop.run(self.router.transport.close())
            self._loop.stop()
            self._loop = None
        if self.slow_log is not None:
            self.slow_log.close()

    async def handle_async(self, pql: str, identity=None,
                           force_trace: bool = False) -> BrokerResponse:
        prepared = self._prepare(pql, identity, force_trace)
        if isinstance(prepared, BrokerResponse):
            return prepared
        request, trace, routes, timeout_s, deadline, t0, workload, \
            fingerprint = prepared
        tables, queried, responded, errors = await self._scatter(
            request, trace, routes, timeout_s, deadline, workload)
        return self._finish(request, trace, t0, tables, queried,
                            responded, errors, pql=pql,
                            fingerprint=fingerprint)

    # -- pipeline stages ---------------------------------------------------
    def _prepare(self, pql: str, identity, force_trace: bool):
        """Sync CPU stage: compile → ACL → quota → route. Returns a
        BrokerResponse on early exit, else the scatter inputs."""
        t0 = time.perf_counter()
        self.metrics.meter(BrokerMeter.QUERIES).mark()
        t = time.perf_counter()
        entry = self._compile_cache.get(pql)
        if entry is None:
            try:
                request = compile_pql(pql)
            except Exception as e:  # noqa: BLE001 — compile errors → resp
                self.metrics.meter(
                    BrokerMeter.REQUEST_COMPILATION_EXCEPTIONS).mark()
                return _error_response(150, f"PQLParsingError: {e}")
            if len(self._compile_cache) >= self._compile_cache_max:
                self._compile_cache.clear()    # rare: bounded, not LRU
            # [request, memoized fingerprint] — fp filled lazily below
            entry = self._compile_cache[pql] = [request, None]
        request = entry[0]
        if force_trace and "trace" not in request.query_options.options:
            # the HTTP client's JSON trace flag; an explicit OPTION(trace=…)
            # in the query wins. COPY before flipping: the cached
            # compiled request is shared across concurrent queries.
            import copy
            request = copy.copy(request)
            request.query_options = copy.copy(request.query_options)
            request.query_options.trace = True
        compile_ms = (time.perf_counter() - t) * 1e3
        self.metrics.timer(BrokerQueryPhase.REQUEST_COMPILATION).update(
            compile_ms)
        trace = make_trace_context(request.query_options.trace)
        trace.record(BrokerQueryPhase.REQUEST_COMPILATION, compile_ms)

        with self.metrics.timer(BrokerQueryPhase.AUTHORIZATION).time(), \
                trace.span(BrokerQueryPhase.AUTHORIZATION):
            allowed = self.access_control.has_access(identity, request)
        if not allowed:
            self.metrics.meter(
                BrokerMeter.REQUEST_DROPPED_DUE_TO_ACCESS_ERROR).mark()
            return _error_response(180, "AccessDeniedError: permission "
                                   f"denied for table {request.table_name}")

        raw = raw_table(request.table_name)
        # tenant/workload tag: OPTION(workload=...) in the query, else
        # a DIGEST of the authenticated identity's token — the key the
        # per-tenant quota buckets and the server's scheduler groups
        # isolate on. Never the raw token: the tag travels in plaintext
        # in every InstanceRequest and surfaces in scheduler-group
        # names and debug views, so a bearer credential must not be it.
        workload = request.query_options.options.get("workload")
        if workload:
            # an explicit tag spends THAT tenant's quota and joins its
            # scheduler group — give the ACL a chance to bind tags to
            # authenticated principals (default SPI: allow, tags are
            # cooperative; getattr tolerates duck-typed implementations)
            gate = getattr(self.access_control, "allow_workload", None)
            if gate is not None and not gate(identity, workload):
                self.metrics.meter(
                    BrokerMeter.REQUEST_DROPPED_DUE_TO_ACCESS_ERROR).mark()
                return _error_response(
                    180, "AccessDeniedError: identity may not use "
                    f"workload {workload}")
        else:
            token = getattr(identity, "token", None)
            if token:
                import hashlib
                workload = "id-" + hashlib.sha256(
                    token.encode("utf-8")).hexdigest()[:12]
        decision = self.quota.acquire(raw, workload)
        if not decision:
            self.metrics.meter(BrokerMeter.QUERY_QUOTA_EXCEEDED).mark()
            cause = decision.cause or "tableQuota"
            self.metrics.meter(BrokerMeter.QUERIES_DROPPED).mark()
            self.metrics.meter(BrokerMeter.QUERIES_DROPPED,
                               table=cause).mark()
            scope = f"tenant {workload} of table {raw}" \
                if cause == "tenantQuota" else f"table {raw}"
            resp = _error_response(
                429, f"QuotaExceededError: {scope} exceeded its QPS "
                f"quota; retry after {decision.retry_after_s:.2f}s")
            resp.exceptions[0]["retryAfterSeconds"] = round(
                decision.retry_after_s, 3)
            # the HTTP layer turns this into a 429 + Retry-After header
            resp.retry_after_s = decision.retry_after_s
            return resp

        # broker-level result cache: only tables with a realtime part
        # (the server-side CRC cache already covers pure-offline), only
        # under an explicit freshness bound. Probed BEFORE routing —
        # the hit path is the graceful-degradation valve under
        # overload, so it must not pay route computation + segment
        # pruning just to discard them (has_table on the realtime
        # variant also guarantees the table still exists)
        fingerprint = None
        opt_bound = request.query_options.options.get(
            "minConsumingFreshnessTimeMs")
        try:
            bound_ms = float(opt_bound) if opt_bound is not None \
                else self.default_cache_freshness_ms
        except (TypeError, ValueError):
            bound_ms = self.default_cache_freshness_ms
        # traced queries bypass the cache both ways: the client asked
        # to watch THIS execution, and a cached reply has no spans
        # (the put at _finish has the matching guard). Multi-stage
        # queries bypass too: the fingerprint keys on ONE table, but a
        # join answer also depends on the DIM table's segment state — a
        # cached join result would survive dim-table changes (the server
        # cache has the matching guard in ServerInstance._stage_request)
        cache_bound = None
        if not request.query_options.trace and request.join is None and \
                not request.windows:
            if bound_ms is not None and \
                    self.routing.has_table(realtime_table(raw)):
                cache_bound = bound_ms
            elif self.cache_offline and \
                    not self.routing.has_table(realtime_table(raw)) and \
                    self.routing.has_table(offline_table(raw)):
                # pure-offline: exact (not freshness-bounded) — every
                # segment lifecycle event flushes this cache, so age
                # never bounds validity
                cache_bound = float("inf")
        if cache_bound is not None:
            fp = entry[1]
            if fp is None:
                from pinot_tpu.query.fingerprint import query_fingerprint
                fp = entry[1] = query_fingerprint(request)
            # generation captured BEFORE execution: a view change that
            # clear()s the cache while this query is in flight (an
            # OFFLINE backfill) must not be undone by _finish's put
            # re-inserting the pre-backfill result
            fingerprint = (fp, self.result_cache.generation)
            cached = self.result_cache.get(fp, cache_bound)
            if cached is not None:
                self.metrics.meter(BrokerMeter.RESULT_CACHE_HITS).mark()
                cached.time_used_ms = (time.perf_counter() - t0) * 1e3
                return cached
            self.metrics.meter(BrokerMeter.RESULT_CACHE_MISSES).mark()

        with self.metrics.timer(BrokerQueryPhase.QUERY_ROUTING).time(), \
                trace.span(BrokerQueryPhase.QUERY_ROUTING):
            routes, error = self._resolve_routes(request, raw)
        if error is not None:
            self.metrics.meter(
                BrokerMeter.RESOURCE_MISSING_EXCEPTIONS).mark()
            return error

        timeout_s = (request.query_options.timeout_ms or
                     self.default_timeout_s * 1e3) / 1e3
        # ONE absolute deadline governs the scatter, every hedge and
        # every retry: re-dispatches spend the remaining budget, they
        # never extend user-visible latency past the requested timeout
        deadline = time.monotonic() + timeout_s
        return request, trace, routes, timeout_s, deadline, t0, \
            workload, fingerprint

    async def _scatter(self, request: BrokerRequest, trace: TraceContext,
                       routes, timeout_s: float, deadline: float,
                       workload: Optional[str] = None):
        """Async network stage: dispatch + gather + missing-segment
        retry. The only stage that runs on the shared event loop."""
        with self.metrics.timer(BrokerQueryPhase.SCATTER_GATHER).time(), \
                trace.span(BrokerQueryPhase.SCATTER_GATHER) as sg:
            sg_id = sg["spanId"] if sg is not None else None
            if request.join is not None or request.windows:
                # multi-stage plan: stage-1 exchange publish, then the
                # stage-2 scatter (query/stages/broker.py)
                from pinot_tpu.query.stages import broker as stages_broker
                return await stages_broker.scatter_stages(
                    self, request, routes, timeout_s, deadline, trace,
                    workload, next(self._request_ids))
            tables, queried, responded, errors = await self.router.submit(
                next(self._request_ids), routes, timeout_s,
                enable_trace=request.query_options.trace,
                deadline=deadline, trace=trace, parent_span_id=sg_id,
                workload=workload)
            tables, rq, rr, retry_errors = \
                await self._retry_missing_segments(
                    routes, tables, deadline,
                    enable_trace=request.query_options.trace,
                    trace=trace, parent_span_id=sg_id,
                    workload=workload)
            queried += rq
            responded += rr
            errors += retry_errors
        return tables, queried, responded, errors

    def _finish(self, request: BrokerRequest, trace: TraceContext,
                t0: float, tables: List[DataTable], queried: int,
                responded: int, errors: List[dict],
                pql: Optional[str] = None,
                fingerprint: Optional[str] = None) -> BrokerResponse:
        """Sync CPU stage: reduce + failure surfacing + trace merge."""
        if responded < queried:
            self.metrics.meter(
                BrokerMeter.BROKER_RESPONSES_WITH_PARTIAL_SERVERS).mark()
        # multi-stage compile errors come back as STAGE_ERROR_KEY-tagged
        # tables (deterministic query properties → 4xx, never reduced)
        stage_errs = [dt for dt in tables if STAGE_ERROR_KEY in dt.metadata]
        tables = [dt for dt in tables
                  if STAGE_ERROR_KEY not in dt.metadata]
        unrecovered = [e for e in errors if not e.get("recovered")]
        with self.metrics.timer(BrokerQueryPhase.REDUCE).time(), \
                trace.span(BrokerQueryPhase.REDUCE):
            blocks = [dt.to_block() for dt in tables]
            if blocks:
                resp = self.reducer.reduce(request, blocks)
            elif stage_errs:
                from pinot_tpu.query.stages.errors import \
                    STAGE_COMPILE_ERROR_CODE
                msg = stage_errs[0].exceptions[0] if \
                    stage_errs[0].exceptions else \
                    stage_errs[0].metadata[STAGE_ERROR_KEY]
                resp = _error_response(STAGE_COMPILE_ERROR_CODE, str(msg))
                stage_errs = stage_errs[1:]
            else:
                typed = next((e for e in unrecovered
                              if e.get("errorCode")), None)
                resp = _error_response(typed["errorCode"],
                                       typed["message"]) \
                    if typed is not None else \
                    _error_response(427, "ServerNotRespondedError: no "
                                    "server responded in time")
                if typed is not None:
                    unrecovered = [e for e in unrecovered
                                   if e is not typed]
        for dt in stage_errs:
            from pinot_tpu.query.stages.errors import \
                STAGE_COMPILE_ERROR_CODE
            resp.exceptions.append({
                "errorCode": STAGE_COMPILE_ERROR_CODE,
                "cause": "stageCompile",
                "message": str(dt.exceptions[0] if dt.exceptions
                               else dt.metadata[STAGE_ERROR_KEY])})
        # surface per-server failures a replica did NOT recover (the
        # old code silently `continue`d over them); recovered ones are
        # telemetry-only (meters/health), not client-facing noise
        for e in unrecovered:
            # the structured busyCause marker from _call_once is the
            # classifier — never the message text, whose wording is
            # free to change without turning sheds into 425 faults
            busy = e.get("busyCause") is not None
            # the machine cause ladder: a shed carries its admission
            # busyCause; otherwise classify the underlying message
            # prefix; otherwise it is a generic server fault
            inner = classify_exception(e.get("message") or "")
            resp.exceptions.append({
                # 503: typed server-busy (admission shed) — distinct
                # from 425 server errors so clients can back off
                # instead of treating overload as a fault; stage
                # orchestration errors carry their own code
                "errorCode": e.get("errorCode") or (503 if busy else 425),
                "cause": (e["busyCause"] if busy else
                          inner[1] if inner is not None else
                          "serverFault"),
                "message": f"ServerQueryError: server={e['server']}: "
                           f"{e['message']}"})
        if not tables and unrecovered and \
                all(e.get("busyCause") is not None for e in unrecovered):
            # the whole query was lost to shedding: a per-cause drop
            # meter mirrors the broker-side quota drops, and the reply
            # carries a real Retry-After (worst drain estimate across
            # the shedding servers) so the HTTP layer can answer 503 +
            # Retry-After instead of a 200 that invites instant retry
            self.metrics.meter(BrokerMeter.QUERIES_DROPPED).mark()
            self.metrics.meter(BrokerMeter.QUERIES_DROPPED,
                               table="serverBusy").mark()
            retry_s = max((e.get("retryAfterMs") or 0.0)
                          for e in unrecovered) / 1e3
            resp.retry_after_s = max(retry_s, 1.0)
        resp.partial_response = bool(
            responded < queried or unrecovered or
            any(dt.exceptions for dt in tables))
        resp.num_servers_queried = queried
        resp.num_servers_responded = responded
        resp.time_used_ms = (time.perf_counter() - t0) * 1e3
        if fingerprint is not None and not request.query_options.trace:
            # put() itself refuses partial/excepted/oversized responses
            # and drops inserts that lost a race with a clear()
            fp, gen = fingerprint
            self.result_cache.put(fp, resp, gen=gen)
        self.metrics.timer(BrokerQueryPhase.QUERY_TOTAL).update(
            resp.time_used_ms)
        self.metrics.meter(BrokerMeter.DOCUMENTS_SCANNED).mark(
            resp.num_docs_scanned)
        profile = self._fold_profiles(request, tables, resp.time_used_ms)
        if request.query_options.trace:
            # which path answered (paths.scan / cube / host / sharded),
            # dispatches, bytes pulled: beside the tree, traced only
            resp.profile_info = profile
            trace.finish_root()
            resp.trace_info = {"broker": trace.to_list()}
            merged = trace.to_list()
            for dt in tables:
                server_trace = dt.metadata.get("traceInfo")
                if not server_trace:
                    continue
                try:
                    spans = TraceContext.from_json_str(
                        server_trace).to_list()
                except Exception:  # noqa: BLE001 — skewed/corrupt metadata
                    continue       # a bad trace must not fail the query
                name = dt.metadata.get("serverName", "server")
                for s in spans:
                    s.setdefault("server", name)
                # hybrid tables: one server answers both the OFFLINE and
                # REALTIME sub-requests — merge, don't overwrite
                resp.trace_info.setdefault(name, []).extend(spans)
                merged.extend(spans)
            # ONE cross-process tree: each server subtree hangs off the
            # dispatch span whose id the broker stamped into its request
            resp.trace_tree = build_trace_tree(merged, trace.trace_id)
        if self.slow_log is not None:
            self.slow_log.maybe_log(resp.time_used_ms, {
                "table": raw_table(request.table_name),
                "pql": pql,
                "traceId": trace.trace_id,
                "numDocsScanned": resp.num_docs_scanned,
                "numSegmentsMatched": resp.num_segments_matched,
                "numServersQueried": queried,
                "numServersResponded": responded,
                "partialResponse": resp.partial_response,
                "exceptions": len(resp.exceptions)})
        return resp

    def _fold_profiles(self, request: BrokerRequest,
                       tables: List[DataTable],
                       time_used_ms: float) -> Optional[dict]:
        """Merge every server's per-query operator profile into one
        query-level record on the rolling per-table stats; returns it
        (None where no server sent one)."""
        merged: Optional[dict] = None
        for dt in tables:
            if dt.metadata.get(RESULT_CACHE_HIT_KEY):
                # a cache hit replays the ORIGINAL execution's profile
                # bytes; folding it again would add a phantom copy of
                # those operator timings per hit to the rolling stats
                # an operator sizes quotas from, for ~0 actual work
                continue
            raw = dt.metadata.get("profileInfo")
            if not raw:
                continue
            try:
                p = json.loads(raw)
            except ValueError:
                continue
            if not isinstance(p, dict):
                continue
            if merged is None:
                merged = p
                continue
            for k, v in p.items():
                if k == "paths":
                    paths = merged.setdefault("paths", {})
                    for path, n in (v or {}).items():
                        paths[path] = paths.get(path, 0) + int(n)
                elif isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0) + v
        if merged is not None:
            self.table_stats.record(raw_table(request.table_name),
                                    merged, time_used_ms)
        return merged

    async def _retry_missing_segments(self, routes, tables,
                                      deadline: float,
                                      enable_trace: bool = False,
                                      trace: Optional[TraceContext] = None,
                                      parent_span_id: Optional[str] = None,
                                      workload: Optional[str] = None,
                                      exchange_sources: Optional[
                                          List[dict]] = None):
        """One re-dispatch of segments a server reported missing.

        A routing table sampled just before a rebalance drop step / a
        reload bounce can point at a server that has already unloaded
        the segment (the server still answers for the rest and reports
        SegmentMissingError). The make-before-break invariant means
        another replica IS serving — re-resolve those segments against
        the CURRENT external view and dispatch once more; segments with
        no live replica keep their exception (an honest miss). Parity:
        the reference broker re-resolving routing on external-view
        change + tolerating partial responses.
        """
        if not any(MISSING_SEGMENTS_KEY in dt.metadata for dt in tables):
            return tables, 0, 0, []    # hot path: nothing to inspect
        if time.monotonic() >= deadline:
            # budget exhausted: keep the honest SegmentMissingError
            # exceptions rather than re-dispatching past the timeout
            # (the old code reused the FULL timeout here, so a retry
            # after a slow first wave could double user latency)
            return tables, 0, 0, []

        seg_home: Dict[str, tuple] = {}
        for sub, routing in routes:
            for server, segs in routing.items():
                for g in segs:
                    seg_home[g] = (sub, server)

        # grouped per sub-request: a retry route must pair each server's
        # segment list with the SAME request those segments belong to
        retry_groups: Dict[int, tuple] = {}
        for dt in tables:
            raw = dt.metadata.pop(MISSING_SEGMENTS_KEY, None)
            if raw is None:
                continue
            try:
                missing = json.loads(raw)
            except ValueError:
                continue
            if not isinstance(missing, list):
                continue        # skewed-version server: ignore, keep exc
            unresolved = []
            views: Dict[str, object] = {}
            for g in missing:
                sub, failed = seg_home.get(g, (None, None))
                view = None
                if sub is not None:
                    if sub.table_name not in views:
                        views[sub.table_name] = \
                            self.routing.view(sub.table_name)
                    view = views[sub.table_name]
                candidates = [srv for srv in
                              (view.servers_for(g, states=("ONLINE",
                                                           "CONSUMING"))
                               if view is not None else [])
                              if srv != failed]
                if sub is None or not candidates:
                    unresolved.append(g)
                    continue
                grp = retry_groups.setdefault(id(sub), (sub, {}))
                grp[1].setdefault(candidates[0], []).append(g)
            # the re-dispatch owns these segments now: drop the server's
            # human-facing exception and re-state only the honest misses
            dt.exceptions = [e for e in dt.exceptions if not
                             str(e).startswith(SEGMENT_MISSING_EXC_PREFIX)]
            if unresolved:
                dt.exceptions.append(
                    f"{SEGMENT_MISSING_EXC_PREFIX} {sorted(unresolved)}")
        retry_routes = list(retry_groups.values())

        if not retry_routes:
            return tables, 0, 0, []
        # the re-dispatch spends only the REMAINING budget (deadline is
        # absolute); a slow first wave leaves a short, honest retry
        remaining_s = max(deadline - time.monotonic(), 0.0)
        retry_tables, rq, rr, errors = await self.router.submit(
            next(self._request_ids), retry_routes, remaining_s,
            enable_trace=enable_trace, deadline=deadline, trace=trace,
            parent_span_id=parent_span_id, workload=workload,
            exchange_sources=exchange_sources)
        return tables + retry_tables, rq, rr, errors

    def _pruned_route(self, sub_request: BrokerRequest, table: str
                      ) -> Dict[str, List[str]]:
        routing = self.routing.route(table)
        if self.segment_pruner is None:
            return routing
        out = {}
        for server, segments in routing.items():
            kept = self.segment_pruner.prune(sub_request, table, segments)
            if kept:
                out[server] = kept
        # all segments pruned: keep one server with an empty segment list
        # so the response still carries the table's schema/zero counts
        if not out and routing:
            server = sorted(routing)[0]
            out[server] = []
        return out

    def _resolve_routes(self, request: BrokerRequest, raw: str):
        """Physical-table fan-out with hybrid time-boundary split."""
        off, rt = offline_table(raw), realtime_table(raw)
        has_off = self.routing.has_table(off)
        has_rt = self.routing.has_table(rt)
        if not has_off and not has_rt:
            return None, _error_response(
                190, f"TableDoesNotExistError: {raw}")
        routes = []
        boundary = self.time_boundary.get(off) if (has_off and has_rt) \
            else None
        try:
            if has_off:
                sub = self.optimizer.optimize(_retable(request, off))
                if boundary is not None:
                    sub = attach_time_boundary(sub, boundary, offline=True)
                routes.append((sub, self._pruned_route(sub, off)))
            if has_rt:
                sub = self.optimizer.optimize(_retable(request, rt))
                if boundary is not None:
                    sub = attach_time_boundary(sub, boundary, offline=False)
                routes.append((sub, self._pruned_route(sub, rt)))
        except RoutingError as e:
            # table removed between has_table and route (external-view race)
            return None, _error_response(190, f"RoutingError: {e}")
        return routes, None


def _retable(request: BrokerRequest, table: str) -> BrokerRequest:
    import copy
    out = copy.copy(request)
    out.table_name = table
    return out


def _error_response(code: int, message: str) -> BrokerResponse:
    resp = BrokerResponse()
    # exception_entry stamps the machine `cause` from the message
    # prefix; the explicit code always wins (e.g. stage compile 422)
    resp.exceptions.append(exception_entry(message, error_code=code))
    return resp
