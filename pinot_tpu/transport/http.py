"""Minimal asyncio HTTP/1.1 server with pattern routing.

Parity: the role Jersey/Grizzly (controller, broker REST) plays in the
reference — an embedded HTTP layer hosting resource handlers
(pinot-controller/.../api/ControllerAdminApiApplication.java,
pinot-broker/.../BrokerAdminApiApplication.java). Implemented directly on
asyncio (stdlib only — no external HTTP framework in the image): request
parsing with Content-Length bodies, keep-alive, `{name}` path captures,
JSON and binary responses.
"""
from __future__ import annotations

import asyncio
import json
import re
import urllib.parse
from typing import Awaitable, Callable, Dict, List, Optional, Tuple


class HttpRequest:
    def __init__(self, method: str, path: str, query: Dict[str, str],
                 headers: Dict[str, str], body: bytes,
                 path_params: Optional[Dict[str, str]] = None,
                 client: str = ""):
        self.method = method
        self.path = path
        self.query = query
        self.headers = headers
        self.body = body
        self.path_params = path_params or {}
        self.client = client

    def json(self):
        return json.loads(self.body.decode("utf-8")) if self.body else None


class HttpResponse:
    def __init__(self, status: int = 200, body: bytes = b"",
                 content_type: str = "application/json",
                 headers: Optional[Dict[str, str]] = None):
        self.status = status
        self.body = body
        self.content_type = content_type
        # extra response headers (e.g. Retry-After on 429)
        self.headers = headers or {}

    @staticmethod
    def of_json(obj, status: int = 200,
                headers: Optional[Dict[str, str]] = None
                ) -> "HttpResponse":
        return HttpResponse(status, json.dumps(obj).encode("utf-8"),
                            headers=headers)

    @staticmethod
    def error(status: int, message: str) -> "HttpResponse":
        return HttpResponse.of_json({"error": message}, status)


Handler = Callable[[HttpRequest], Awaitable[HttpResponse]]


def metrics_response(registry, request: HttpRequest) -> HttpResponse:
    """The shared /metrics handler every component API mounts:
    Prometheus text exposition (obs/prometheus.py) by default, the
    legacy flat JSON snapshot behind ?format=json."""
    if request.query.get("format") == "json":
        return HttpResponse.of_json(registry.snapshot())
    from pinot_tpu.obs.prometheus import CONTENT_TYPE, render_prometheus
    return HttpResponse(200, render_prometheus(registry).encode("utf-8"),
                        content_type=CONTENT_TYPE)


class _PayloadTooLarge(Exception):
    pass

_REASONS = {200: "OK", 204: "No Content", 400: "Bad Request",
            403: "Forbidden", 404: "Not Found", 405: "Method Not Allowed",
            409: "Conflict", 413: "Payload Too Large", 429: "Too Many Requests",
            500: "Internal Server Error", 503: "Service Unavailable"}


class HttpRouter:
    """(METHOD, "/path/{with}/{captures}") → async handler."""

    def __init__(self) -> None:
        self._routes: List[Tuple[str, re.Pattern, Handler]] = []

    def add(self, method: str, pattern: str, handler: Handler) -> None:
        rx = re.compile(
            "^" + re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern) + "$")
        self._routes.append((method.upper(), rx, handler))

    def match(self, method: str, path: str
              ) -> Tuple[Optional[Handler], Dict[str, str], bool]:
        """→ (handler, path_params, path_exists)."""
        path_exists = False
        for m, rx, handler in self._routes:
            match = rx.match(path)
            if match:
                path_exists = True
                if m == method.upper():
                    return handler, {k: urllib.parse.unquote(v)
                                     for k, v in match.groupdict().items()
                                     }, True
        return None, {}, path_exists


class HttpServer:
    """Serves an HttpRouter on an asyncio event loop."""

    MAX_BODY = 512 * 1024 * 1024     # segments upload through this path

    def __init__(self, host: str, port: int, router: HttpRouter,
                 ssl_context=None):
        self.host = host
        self.port = port
        self.router = router
        self.ssl_context = ssl_context   # ssl.SSLContext → serve https
        self._server: Optional[asyncio.AbstractServer] = None
        self._conn_tasks: set = set()

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve, self.host, self.port, ssl=self.ssl_context)
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # wait_closed() (3.12) waits for every open connection; an
            # idle keep-alive client would park it forever — cancel the
            # per-connection tasks so shutdown is prompt, then WAIT for
            # them to unwind (an abandoned cancelled task is destroyed
            # pending once the loop halts)
            tasks = list(self._conn_tasks)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            try:
                await self._server.wait_closed()
            except asyncio.CancelledError:
                pass
            self._server = None

    async def _serve(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        peer = writer.get_extra_info("peername")
        client = f"{peer[0]}:{peer[1]}" if peer else ""
        task = asyncio.current_task()
        if task is not None:
            self._conn_tasks.add(task)
        try:
            while True:
                try:
                    request = await self._read_request(reader, client)
                except _PayloadTooLarge:
                    await self._write_response(
                        writer, HttpResponse.error(413, "payload too "
                                                   "large"), keep=False)
                    break
                if request is None:
                    break
                response = await self._dispatch(request)
                keep = request.headers.get("connection", "").lower() \
                    != "close"
                await self._write_response(writer, response, keep)
                if not keep:
                    break
        except (ConnectionError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, ValueError):
            pass       # malformed request / oversized header line
        except asyncio.CancelledError:
            pass       # server shutdown cancelled this connection
        finally:
            if task is not None:
                self._conn_tasks.discard(task)
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    async def _read_request(self, reader: asyncio.StreamReader,
                            client: str) -> Optional[HttpRequest]:
        try:
            line = await reader.readline()
        except (ValueError, ConnectionError):
            return None
        if not line or line in (b"\r\n", b"\n"):
            return None
        try:
            method, target, _version = line.decode("latin-1").split(None, 2)
        except ValueError:
            return None
        headers: Dict[str, str] = {}
        while True:
            hl = await reader.readline()
            if hl in (b"\r\n", b"\n", b""):
                break
            if b":" in hl:
                k, v = hl.decode("latin-1").split(":", 1)
                headers[k.strip().lower()] = v.strip()
        length = int(headers.get("content-length", "0") or "0")
        if length > self.MAX_BODY:
            raise _PayloadTooLarge
        body = await reader.readexactly(length) if length else b""
        parsed = urllib.parse.urlsplit(target)
        query = {k: v[0] for k, v in
                 urllib.parse.parse_qs(parsed.query).items()}
        return HttpRequest(method.upper(), parsed.path, query, headers,
                           body, client=client)

    async def _dispatch(self, request: HttpRequest) -> HttpResponse:
        if request.method == "OPTIONS":
            # CORS preflight: browser clients (e.g. the controller's
            # query console) fetch the broker cross-origin
            return HttpResponse(204, b"", content_type="text/plain")
        handler, params, path_exists = self.router.match(
            request.method, request.path)
        if handler is None:
            if path_exists:
                return HttpResponse.error(405, "method not allowed")
            return HttpResponse.error(404, f"no such path: {request.path}")
        request.path_params = params
        try:
            return await handler(request)
        except Exception as e:  # noqa: BLE001 — handler error → 500 JSON
            return HttpResponse.error(500, f"{type(e).__name__}: {e}")

    async def _write_response(self, writer: asyncio.StreamWriter,
                              response: HttpResponse, keep: bool) -> None:
        reason = _REASONS.get(response.status, "Unknown")
        extra = "".join(f"{k}: {v}\r\n"
                        for k, v in response.headers.items())
        head = (f"HTTP/1.1 {response.status} {reason}\r\n"
                f"Content-Type: {response.content_type}\r\n"
                f"Content-Length: {len(response.body)}\r\n"
                "Access-Control-Allow-Origin: *\r\n"
                "Access-Control-Allow-Methods: "
                "GET, POST, DELETE, OPTIONS\r\n"
                "Access-Control-Allow-Headers: Content-Type\r\n"
                f"{extra}"
                f"Connection: {'keep-alive' if keep else 'close'}\r\n"
                "\r\n")
        writer.write(head.encode("latin-1") + response.body)
        await writer.drain()


class ApiServer:
    """Base lifecycle for an HTTP API: router on an event-loop thread.

    Subclasses populate the router in __init__ via self.router.add(...).
    """

    def __init__(self) -> None:
        from pinot_tpu.transport.tcp import EventLoopThread
        self.router = HttpRouter()
        self._loop_cls = EventLoopThread
        self._loop = None
        self._server: Optional[HttpServer] = None
        self.port: Optional[int] = None
        self.tls_config = None           # TlsConfig → serve https

    def start(self, host: str = "127.0.0.1", port: int = 0,
              tls_config=None) -> int:
        if tls_config is not None:
            self.tls_config = tls_config
        ssl_ctx = self.tls_config.server_context() \
            if self.tls_config is not None else None
        self._loop = self._loop_cls()
        self._server = HttpServer(host, port, self.router, ssl_ctx)
        self._loop.run(self._server.start())
        self.port = self._server.port
        return self.port

    def stop(self) -> None:
        if self._server is not None and self._loop is not None:
            self._loop.run(self._server.stop())
            self._server = None
        if self._loop is not None:
            self._loop.stop()
            self._loop = None
