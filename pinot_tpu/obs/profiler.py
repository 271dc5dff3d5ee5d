"""Per-query operator profiler + rolling per-table stats.

Answers VERDICT.md's "where does the time go" ask with attribution the
flat metrics cannot give: per query, how many docs were scanned, how
many segments were pruned vs matched, which execution path served each
segment (star-tree cube, device scan kernel, host fallback, mesh-
sharded), how many kernel dispatches ran and how many bytes crossed the
device→host boundary (the batched `jax.device_get` pulls the PR-1
transfer guard polices — `profiled_device_get` is the instrumented twin
of that guard's allowed explicit transfer).

The profile travels server→broker as a compact JSON blob in DataTable
metadata ("profileInfo"); the broker folds every query's profile into a
`TableStatsAggregator` — rolling per-table operator stats served from
the broker's debug API.

The ambient context is a per-thread slot: the server executor activates
(profile, trace) around a query, worker-pool threads re-activate the
captured context inside their closure, and the hot-path check when
nothing is active is a single threading.local attribute read.

Two process-level hooks of the chip's holder live here too, both with
jax imported inside the call (the broker imports this module):
`bind_compile_metrics` (XLA compile counters from `jax.monitoring`) and
`DeviceProfiler` (one `jax.profiler` session whose start is known on
the wall clock, behind the server's `/debug/profiler/*`).
"""
from __future__ import annotations

import json
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional, Tuple

from pinot_tpu.common.metrics import (ServerMeter, ServerQueryPhase,
                                      ServerTimer)

_tls = threading.local()


def current() -> Optional[Tuple["QueryProfile", object]]:
    """The (profile, trace) pair active on this thread, or None."""
    return getattr(_tls, "ctx", None)


@contextmanager
def active(profile: Optional["QueryProfile"], trace=None):
    """Activate a profile (+ trace) for this thread."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = (profile, trace) if profile is not None else None
    try:
        yield
    finally:
        _tls.ctx = prev


@contextmanager
def reactivate(ctx: Optional[tuple]):
    """Re-establish a captured ambient context on a worker thread."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield
    finally:
        _tls.ctx = prev


#: what `obs_span` hands back while no trace is on: one shared object
#: whose enter yields None and whose exit does nothing
_NO_SPAN = nullcontext()


def obs_span(name: str, **attrs):
    """A span on the ambient trace, for `with obs_span(...) as span`.
    With no trace on (or no `name`: a step that has no span of its own)
    it is one thread-local read and the shared no-op context (`span` is
    None): no generator, no clock, no allocation."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None or ctx[1] is None or not ctx[1].enabled or name is None:
        return _NO_SPAN
    return ctx[1].span(name, **attrs)


def profiled_device_get(x, programs: int = 1):
    """`jax.device_get` with dispatch/transfer accounting.

    Every driver funnels its one explicit batched device→host pull
    through here, with the number of `programs` whose outputs `x`
    holds (a rung of a query's ladders: one a segment): the ambient
    profile counts the programs as dispatches and the host-side bytes,
    the meters `devicePrograms` / `devicePulls` count programs and the
    one pull, and the ambient trace gets a `kernelDispatch` span (the
    host's wait for the device and the copy, not device time). With
    nothing active this is jax.device_get, the two marks and one
    threading.local read.
    """
    import jax
    for by_program, by_pull in _PULL_METERS:
        by_program.mark(programs)
        by_pull.mark()
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return jax.device_get(x)
    profile = ctx[0]
    # `ms` is device_get alone, also under a span: the profile's
    # kernelMs stays clear of the span's own bookkeeping
    with obs_span(ServerQueryPhase.KERNEL_DISPATCH) as span:
        t0 = time.perf_counter()
        outs = jax.device_get(x)
        ms = (time.perf_counter() - t0) * 1e3
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(outs):
        nbytes += int(getattr(leaf, "nbytes", 0))
    if profile is not None:
        profile.add_dispatch(nbytes, ms, programs)
    if span is not None:
        span["attrs"] = {"bytes": nbytes, "programs": programs}
    return outs


def count_path(path: str, n: int = 1) -> None:
    """Attribute n segments to an execution path on the ambient profile
    ("cube" star-tree, "scan" device kernel, "host" numpy fallback,
    "sharded" mesh combine)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None and ctx[0] is not None:
        ctx[0].count_path(path, n)


class QueryProfile:
    """One query's operator-level execution accounting (server side)."""

    __slots__ = ("table", "docs_scanned", "segments_processed",
                 "segments_matched", "segments_pruned", "paths",
                 "dispatches", "transfer_bytes", "kernel_ms",
                 "batch_size", "_lock")

    def __init__(self, table: str = ""):
        self.table = table
        self.docs_scanned = 0
        self.segments_processed = 0
        self.segments_matched = 0
        self.segments_pruned = 0
        self.paths: Dict[str, int] = {}
        self.dispatches = 0
        self.transfer_bytes = 0
        self.kernel_ms = 0.0
        # queries served by this query's batch window (1 == unbatched;
        # set by the coalescer runner when the query rode a batch)
        self.batch_size = 1
        self._lock = threading.Lock()

    def add_dispatch(self, nbytes: int, ms: float,
                     programs: int = 1) -> None:
        with self._lock:
            self.dispatches += programs
            self.transfer_bytes += nbytes
            self.kernel_ms += ms

    def count_path(self, path: str, n: int = 1) -> None:
        with self._lock:
            self.paths[path] = self.paths.get(path, 0) + n

    def finish_from_stats(self, stats) -> None:
        """Fold the combined block's ExecutionStats in at query end."""
        self.docs_scanned = stats.num_docs_scanned
        self.segments_processed = stats.num_segments_processed
        self.segments_matched = stats.num_segments_matched
        self.segments_pruned = stats.num_segments_pruned

    def to_json(self) -> dict:
        with self._lock:
            return {
                "docsScanned": self.docs_scanned,
                "segmentsProcessed": self.segments_processed,
                "segmentsMatched": self.segments_matched,
                "segmentsPruned": self.segments_pruned,
                "paths": dict(self.paths),
                "kernelDispatches": self.dispatches,
                "deviceTransferBytes": self.transfer_bytes,
                "kernelMs": round(self.kernel_ms, 3),
                "batchSize": self.batch_size,
            }

    def to_json_str(self) -> str:
        return json.dumps(self.to_json())


class TableStatsAggregator:
    """Rolling per-table operator stats at the broker.

    Each table keeps lifetime counters plus a bounded ring of the most
    recent per-query profiles, so the debug view can answer both "what
    does this table's traffic look like" and "what did the last N
    queries actually do".
    """

    RECENT = 64

    def __init__(self):
        self._tables: Dict[str, dict] = {}
        self._lock = threading.Lock()

    def record(self, table: str, profile: dict,
               time_used_ms: Optional[float] = None) -> None:
        with self._lock:
            t = self._tables.get(table)
            if t is None:
                t = self._tables[table] = {
                    "queries": 0, "docsScanned": 0, "segmentsProcessed": 0,
                    "segmentsMatched": 0, "segmentsPruned": 0,
                    "kernelDispatches": 0, "deviceTransferBytes": 0,
                    "kernelMs": 0.0, "paths": {}, "recent": []}
            t["queries"] += 1
            for k in ("docsScanned", "segmentsProcessed", "segmentsMatched",
                      "segmentsPruned", "kernelDispatches",
                      "deviceTransferBytes"):
                t[k] += int(profile.get(k, 0))
            t["kernelMs"] = round(t["kernelMs"] +
                                  float(profile.get("kernelMs", 0.0)), 3)
            for path, n in (profile.get("paths") or {}).items():
                t["paths"][path] = t["paths"].get(path, 0) + int(n)
            entry = dict(profile)
            if time_used_ms is not None:
                entry["timeUsedMs"] = round(time_used_ms, 3)
            recent = t["recent"]
            recent.append(entry)
            if len(recent) > self.RECENT:
                del recent[0]

    def table_names(self):
        with self._lock:
            return list(self._tables)

    def snapshot(self, table: Optional[str] = None) -> dict:
        """Isolated copy of the stats. Only the shallow copy happens
        under the lock — the JSON round-trip (which deep-copies the
        recent-profile rings) runs outside it so a debug scrape never
        stalls the query path's record() calls."""

        def copy_table(t: dict) -> dict:
            out = dict(t)
            out["paths"] = dict(t["paths"])
            out["recent"] = list(t["recent"])
            return out

        with self._lock:
            if table is not None:
                t = self._tables.get(table)
                shallow = copy_table(t) if t else None
            else:
                shallow = {name: copy_table(t)
                           for name, t in self._tables.items()}
        if shallow is None:
            return {}
        return json.loads(json.dumps(shallow))


# -- XLA compile counters ---------------------------------------------------

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_bound: "weakref.WeakSet" = weakref.WeakSet()
_compile_lock = threading.Lock()
_compile_listening = False


def _compile_registries() -> list:
    with _compile_lock:
        return list(_compile_bound)


def _on_compile_duration(event: str, duration_secs: float, **_kw) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    for metrics in _compile_registries():
        metrics.meter(ServerMeter.XLA_COMPILES).mark()
        metrics.timer(ServerTimer.XLA_COMPILE).update(duration_secs * 1e3)


def _on_compile_event(event: str, **_kw) -> None:
    if event != _CACHE_HIT_EVENT:
        return
    for metrics in _compile_registries():
        metrics.meter(ServerMeter.XLA_COMPILE_CACHE_HITS).mark()


def bind_compile_metrics(metrics) -> None:
    """Count on `metrics` every program this PROCESS meets for the
    first time: meter `xlaCompiles` and timer `xlaCompile` from JAX's
    backend-compile event (it fires whether XLA compiles the program or
    the persistent cache supplies it), meter `xlaCompileCacheHits` from
    the cache's hit event. All three exist at 0 from this call on. The
    listeners are process-global and registered once; registries are
    held weakly, like the residency ledger's."""
    global _compile_listening
    metrics.meter(ServerMeter.XLA_COMPILES)
    metrics.meter(ServerMeter.XLA_COMPILE_CACHE_HITS)
    metrics.timer(ServerTimer.XLA_COMPILE)
    with _compile_lock:
        _compile_bound.add(metrics)
        if _compile_listening:
            return
        _compile_listening = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)
    jax.monitoring.register_event_listener(_on_compile_event)


# -- cube descent counters --------------------------------------------------

#: (cubeDescentsNative, cubeDescentsNumpy) meters of every registry
#: bound, swapped whole by bind_cube_metrics so that a descent marks
#: without a lock of its own (Meter.mark takes the meter's), as the
#: residency ledger's lane-cache meters are
_cube_bound: "weakref.WeakSet" = weakref.WeakSet()
_CUBE_METERS: tuple = ()


def bind_cube_metrics(metrics) -> None:
    """Meters `cubeDescentsNative` / `cubeDescentsNumpy` on `metrics`,
    at 0 from this call on."""
    global _CUBE_METERS
    with _compile_lock:
        _cube_bound.add(metrics)
        _CUBE_METERS = tuple(
            (m.meter(ServerMeter.CUBE_DESCENTS_NATIVE),
             m.meter(ServerMeter.CUBE_DESCENTS_NUMPY)) for m in _cube_bound)


def mark_cube_descents(native: bool, segments: int) -> None:
    """`segments` segments answered from their cubes
    (startree/executor.py `_cube_execute`): by the one native
    select-and-gather call, or by the stepwise numpy twin."""
    for by_native, by_numpy in _CUBE_METERS:
        (by_native if native else by_numpy).mark(segments)


# -- group-by ladder counters ------------------------------------------------

#: {meter name: Meter} of every registry bound, swapped whole like
#: _CUBE_METERS
_group_bound: "weakref.WeakSet" = weakref.WeakSet()
_GROUP_METERS: tuple = ()
_GROUP_METER_NAMES = (
    ServerMeter.GROUP_SEGMENTS, ServerMeter.GROUP_SCOUT_DISPATCHES,
    ServerMeter.GROUP_HIST_DISPATCHES, ServerMeter.GROUP_TABLE_DISPATCHES,
    ServerMeter.GROUP_ESCALATIONS, ServerMeter.GROUP_EMPTY,
    *ServerMeter.GROUP_TABLES.values())


def bind_group_metrics(metrics) -> None:
    """The group-by ladder's meters (`groupSegments`, a dispatch meter
    a phase, `groupEscalations`, `groupEmpty`, `groupTables<Layout>`)
    on `metrics`, at 0 from this call on."""
    global _GROUP_METERS
    with _compile_lock:
        _group_bound.add(metrics)
        _GROUP_METERS = tuple({name: m.meter(name)
                               for name in _GROUP_METER_NAMES}
                              for m in _group_bound)


def mark_group_ladder(scout: int, hist: int, table: int,
                      layout: Optional[str]) -> None:
    """One segment went through the device group-by ladder
    (query/plan.py `SegmentLadder`): the launches of its scout,
    of its histogram rung and of its table (`table` - 1 of them kmax
    re-runs), and the layout of the final table; `layout` None where
    the filter matched nothing and no table ran. The three dispatch
    counts add up to the programs `profiled_device_get` counted on the
    query's profile for the segment."""
    counts = {ServerMeter.GROUP_SEGMENTS: 1,
              ServerMeter.GROUP_SCOUT_DISPATCHES: scout,
              ServerMeter.GROUP_HIST_DISPATCHES: hist,
              ServerMeter.GROUP_TABLE_DISPATCHES: table,
              ServerMeter.GROUP_ESCALATIONS: max(table - 1, 0),
              ServerMeter.GROUP_EMPTY if layout is None
              else ServerMeter.GROUP_TABLES[layout]: 1}
    for meters in _GROUP_METERS:
        for name, n in counts.items():
            if n:
                meters[name].mark(n)


# -- scan walk counters -------------------------------------------------------

#: (scanWalkSegments, scanPoolSegments) and (devicePrograms,
#: devicePulls) meters of every registry bound, swapped whole like
#: _CUBE_METERS
_walk_bound: "weakref.WeakSet" = weakref.WeakSet()
_WALK_METERS: tuple = ()
_PULL_METERS: tuple = ()


def bind_walk_metrics(metrics) -> None:
    """Meters `scanWalkSegments` / `scanPoolSegments` and
    `devicePrograms` / `devicePulls` on `metrics`, at 0 from this call
    on."""
    global _WALK_METERS, _PULL_METERS
    with _compile_lock:
        _walk_bound.add(metrics)
        _WALK_METERS = tuple(
            (m.meter(ServerMeter.SCAN_WALK_SEGMENTS),
             m.meter(ServerMeter.SCAN_POOL_SEGMENTS)) for m in _walk_bound)
        _PULL_METERS = tuple(
            (m.meter(ServerMeter.DEVICE_PROGRAMS),
             m.meter(ServerMeter.DEVICE_PULLS)) for m in _walk_bound)


def mark_scan_segments(walked: bool, segments: int = 1) -> None:
    """`segments` scan-route segments ran (query/executor.py): in the
    walk that queues a query's programs ahead of its pulls, or each as
    a pool task of its own (a consuming segment's frozen half, a
    batch's member)."""
    for by_walk, by_pool in _WALK_METERS:
        (by_walk if walked else by_pool).mark(segments)


# -- sum-lane counters -------------------------------------------------------

#: {lane kind: Meter} of every registry bound, swapped whole like
#: _CUBE_METERS
_sum_bound: "weakref.WeakSet" = weakref.WeakSet()
_SUM_METERS: tuple = ()
_SUM_STRATEGIES = {"parts": "parts", "psums": "parts", "vlane": "value",
                   "csums": "value", "hist": "hist", "vals": "hist"}


def bind_sum_lane_metrics(metrics) -> None:
    """Meters `sumLanesParts`, `sumLanesRaw`, `sumLanesValue` and
    `sumLanesHist` on `metrics`, at 0 from this call on."""
    global _SUM_METERS
    with _compile_lock:
        _sum_bound.add(metrics)
        _SUM_METERS = tuple({kind: m.meter(name) for kind, name
                             in ServerMeter.SUM_LANES.items()}
                            for m in _sum_bound)


def sum_lane_kind(agg_spec: tuple) -> Optional[str]:
    """The kind of lane a device aggregation spec (`query/plan.py`
    `_agg_device_spec`) sums over, by `ServerMeter.SUM_LANES`' keys;
    None for what is no SUM or AVG."""
    fname, _col, source, extra = agg_spec
    if fname not in ("sum", "avg"):
        return None
    if source == "raw":
        return "raw"
    if source == "mv":
        return "hist"
    return _SUM_STRATEGIES[extra[0]]


def mark_sum_lanes(agg_specs) -> None:
    """The device answered one segment's `agg_specs` (a scan's, or a
    group table's: `query/execution.py`, `query/plan.py`
    `SegmentLadder`): one mark a SUM or AVG among them, on the meter
    of the lanes it read. What a cube or the host answered, and a table
    that never ran, marks nothing."""
    for spec in agg_specs:
        kind = sum_lane_kind(spec)
        if kind is not None:
            for meters in _SUM_METERS:
                meters[kind].mark()


def sum_lane_attrs(agg_specs, segment) -> dict:
    """Span attributes of a launch that carries `agg_specs` over
    `segment`: `partLanes`, the one-byte slices of its integer sums
    (`int_part_info`: 4 for SSB's lo_revenue, 3 for lo_supplycost), and
    `valueLanes`, its raw and decoded value lanes."""
    kinds = [(sum_lane_kind(spec), spec[1]) for spec in agg_specs]
    return {"partLanes": sum(segment.data_source(col).int_part_info()[0]
                             for kind, col in kinds if kind == "parts"),
            "valueLanes": sum(kind in ("raw", "value")
                              for kind, _col in kinds)}


# -- the device profiler, one session at a time ------------------------------

PROFILER_ANCHOR = "pinot.profilerAnchor"


class ProfilerBusy(Exception):
    """A session is open (start), or none is (stop)."""


class DeviceProfiler:
    """One `jax.profiler` session of this process, with its start known
    on the wall clock.

    Every plane of a session's `.xplane.pb` shares one clock whose zero
    is the session's start, so a span (`startUs`, wall clock) can be
    laid beside a device op only if that zero is known on the wall
    clock. `start` reads `time.time_ns()` immediately before and after
    `jax.profiler.start_trace` (the zero lies between the two), then
    opens and closes one `TraceAnnotation(PROFILER_ANCHOR, wall_ns=...)`
    whose own start on the profiler's clock pins the offset to the
    width of one call: wall = anchorWallNs + (event.start_ns −
    anchor.start_ns)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open: Optional[dict] = None
        self._last: Optional[dict] = None

    def start(self, log_dir: str) -> dict:
        import jax
        with self._lock:
            if self._open is not None:
                raise ProfilerBusy("a profiler session is open since "
                                   f"{self._open['startedNs'][0]} ns")
            # the host's annotations and no Python call stacks: what
            # the spans need, at the cost PERF.md states
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1
            options.python_tracer_level = 0
            before = time.time_ns()
            jax.profiler.start_trace(log_dir, profiler_options=options)
            after = time.time_ns()
            anchor_ns = time.time_ns()
            with jax.profiler.TraceAnnotation(PROFILER_ANCHOR,
                                              wall_ns=anchor_ns):
                pass
            self._open = {"dir": log_dir, "startedNs": [before, after],
                          "anchorWallNs": anchor_ns}
            return dict(self._open)

    def stop(self) -> dict:
        import jax
        with self._lock:
            if self._open is None:
                raise ProfilerBusy("no profiler session is open")
            stopped = time.time_ns()
            try:
                jax.profiler.stop_trace()
            finally:
                session, self._open = self._open, None
            self._last = dict(session, stoppedNs=stopped)
            return dict(self._last)

    def status(self) -> dict:
        with self._lock:
            return {"open": dict(self._open) if self._open else None,
                    "last": dict(self._last) if self._last else None}


#: this process's one session (jax.profiler is process-global)
PROFILER = DeviceProfiler()
