"""Server admin/debug HTTP API.

Parity: pinot-server/.../api/resources/ — TablesResource (table list +
per-segment metadata), TableSizeResource (estimated bytes per segment),
HealthCheckResource, and MmapDebugResource. The reference's "native
memory" debug surface reports mmap/direct buffers; the TPU build's
native memory is HBM, so /debug/memory reports the DEVICE-RESIDENT lane
bytes per table/segment (what the reference's PinotDataBuffer global
accounting becomes on this architecture) next to the host-side column
footprint.
"""
from __future__ import annotations

from pinot_tpu.common.service_status import get_service_status
from pinot_tpu.transport.http import (ApiServer, HttpRequest, HttpResponse,
                                      metrics_response)


from pinot_tpu.segment.loader import segment_host_bytes as _host_bytes


def _device_bytes(seg) -> int:
    total = 0
    for name in seg.column_names:
        dev = getattr(seg.data_source(name), "_dev", None) or {}
        total += sum(int(a.nbytes) for a in dev.values()
                     if hasattr(a, "nbytes"))
    return total


class ServerApiServer(ApiServer):
    """Admin/debug surface for one ServerInstance."""

    def __init__(self, server):
        super().__init__()
        self.server = server
        self.router.add("GET", "/health", self._health)
        self.router.add("GET", "/metrics", self._metrics)
        self.router.add("GET", "/tables", self._tables)
        self.router.add("GET", "/tables/{table}/segments", self._segments)
        self.router.add("GET", "/tables/{table}/size", self._size)
        self.router.add("GET", "/debug/memory", self._memory)
        self.router.add("GET", "/debug/residency", self._residency)
        self.router.add("GET", "/debug/health", self._debug_health)
        self.router.add("GET", "/debug/profiler", self._profiler_status)
        self.router.add("POST", "/debug/profiler/start",
                        self._profiler_start)
        self.router.add("POST", "/debug/profiler/stop",
                        self._profiler_stop)

    async def _metrics(self, request: HttpRequest) -> HttpResponse:
        return metrics_response(self.server.metrics, request)

    async def _health(self, request: HttpRequest) -> HttpResponse:
        from pinot_tpu.common.service_status import Status
        status, desc = get_service_status(self.server.instance_id)
        if status in (Status.GOOD, Status.STARTING) and \
                "no status callback" in desc:
            # standalone servers (no participant) have no callback; they
            # are healthy iff they answer at all
            return HttpResponse(200, b"OK", content_type="text/plain")
        if status == Status.GOOD:
            return HttpResponse(200, b"OK", content_type="text/plain")
        return HttpResponse.error(503, f"{status.name}: {desc}")

    async def _tables(self, request: HttpRequest) -> HttpResponse:
        return HttpResponse.of_json(
            {"tables": self.server.data_manager.table_names()})

    async def _segments(self, request: HttpRequest) -> HttpResponse:
        table = request.path_params["table"]
        tdm = self.server.data_manager.table(table)
        if tdm is None:
            return HttpResponse.error(404, f"table {table} not found")
        sdms, _ = tdm.acquire_segments()
        try:
            out = {}
            for sdm in sdms:
                seg = sdm.segment
                meta = seg.metadata
                out[seg.segment_name] = {
                    "totalDocs": seg.num_docs,
                    "columns": len(seg.column_names),
                    "startTime": meta.start_time,
                    "endTime": meta.end_time,
                    "mutable": bool(getattr(seg, "is_mutable", False)),
                }
            return HttpResponse.of_json({"table": table, "segments": out})
        finally:
            for sdm in sdms:
                tdm.release_segment(sdm)

    async def _size(self, request: HttpRequest) -> HttpResponse:
        table = request.path_params["table"]
        tdm = self.server.data_manager.table(table)
        if tdm is None:
            return HttpResponse.error(404, f"table {table} not found")
        sdms, _ = tdm.acquire_segments()
        try:
            segs = {sdm.segment.segment_name:
                    {"hostBytes": _host_bytes(sdm.segment)}
                    for sdm in sdms}
            return HttpResponse.of_json({
                "table": table,
                "totalHostBytes": sum(v["hostBytes"]
                                      for v in segs.values()),
                "segments": segs})
        finally:
            for sdm in sdms:
                tdm.release_segment(sdm)

    async def _memory(self, request: HttpRequest) -> HttpResponse:
        out = {}
        dm = self.server.data_manager
        for table in dm.table_names():
            tdm = dm.table(table)
            if tdm is None:
                continue
            sdms, _ = tdm.acquire_segments()
            try:
                out[table] = {
                    sdm.segment.segment_name: {
                        "hbmResidentBytes": _device_bytes(sdm.segment),
                        "hostBytes": _host_bytes(sdm.segment),
                    } for sdm in sdms}
            finally:
                for sdm in sdms:
                    tdm.release_segment(sdm)
        total_hbm = sum(s["hbmResidentBytes"]
                        for t in out.values() for s in t.values())
        return HttpResponse.of_json({"totalHbmResidentBytes": total_hbm,
                                     "tables": out})

    async def _debug_health(self, request: HttpRequest) -> HttpResponse:
        """One-scrape leak-gate rollup (obs/health.py): RSS, residency
        ledger, exchange held-bytes, and the leak-sensitive gauges —
        the curated subset the soak's flatness detectors poll — plus
        the `device` block (utils/device.py)."""
        from pinot_tpu.obs.health import health_rollup
        from pinot_tpu.utils.device import device_report
        out = health_rollup(
            "server", self.server.metrics,
            extra={"instanceId": self.server.instance_id})
        # what the kernels run on, and the backend's own bytes-in-use
        # beside the ledger's total above (do the two agree on a chip?)
        out["device"] = device_report()
        return HttpResponse.of_json(out)

    # -- the device profiler (obs/profiler.py DeviceProfiler) ----------------
    # Only the process that holds the chip can trace it. start/stop run
    # off the API's event loop: opening a session and writing its
    # .xplane.pb each take seconds on a chip.
    async def _profiler_start(self, request: HttpRequest) -> HttpResponse:
        """Body {"dir"}. 409 while a session is open. Returns
        `startedNs` (the wall clock immediately before and after
        `jax.profiler.start_trace`) and `anchorWallNs` (see
        DeviceProfiler). The host's annotations are on and Python call
        stacks off (tracer levels 1 and 0): what the spans need."""
        from pinot_tpu.obs.profiler import PROFILER
        try:
            body = request.json() or {}
        except ValueError as e:
            return HttpResponse.error(400, f"bad JSON body: {e}")
        log_dir = body.get("dir")
        if not log_dir or not isinstance(log_dir, str):
            return HttpResponse.error(400, "`dir` (where the session's "
                                      ".xplane.pb is written) is required")
        return await self._profiler_call(PROFILER.start, log_dir)

    async def _profiler_stop(self, request: HttpRequest) -> HttpResponse:
        """Closes the session and writes its trace; returns {dir,
        startedNs: [before, after], anchorWallNs, stoppedNs}: the
        session's zero on the wall clock, to the width of one call.
        409 when none is open."""
        from pinot_tpu.obs.profiler import PROFILER
        return await self._profiler_call(PROFILER.stop)

    @staticmethod
    async def _profiler_call(fn, *args) -> HttpResponse:
        import asyncio
        from pinot_tpu.obs.profiler import ProfilerBusy
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, fn, *args)
        except ProfilerBusy as e:
            return HttpResponse.error(409, str(e))
        return HttpResponse.of_json(out)

    async def _profiler_status(self, request: HttpRequest) -> HttpResponse:
        from pinot_tpu.obs.profiler import PROFILER
        return HttpResponse.of_json(PROFILER.status())

    async def _residency(self, request: HttpRequest) -> HttpResponse:
        """The process-global residency ledger: every accounted device
        upload (scan/vdoc/vector/hll/stack/join/window lanes + exchange
        held bytes) by table and kind, with the largest owners — each
        entry annotated with the residency manager's `tier` and
        last-access `heat` when the segment is under management. The
        `manager` block adds the tier map (budget, per-tier totals,
        per-segment tier/heat/pins/coldHits, promotion backlog). This
        is the ledger view the `deviceBytesResident{table,kind}` gauges
        export — /debug/memory remains the per-segment lane walk."""
        from pinot_tpu.obs.residency import LEDGER
        snap = LEDGER.snapshot()
        residency = getattr(self.server, "residency", None)
        if residency is not None:
            snap["manager"] = residency.snapshot()
        return HttpResponse.of_json(snap)
