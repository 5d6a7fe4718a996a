"""Native epoll HTTP front-end: ctypes bridge to native/net_http.cpp.

The C++ server owns all sockets (non-blocking event loop, keep-alive,
pipelining, chunked request bodies, gzip both directions, idle timeouts,
header/body limits — parity with the reference's libevent net_http stack,
util/net_http/server/internal/evhttp_server.cc). Its worker threads call
back into Python with one plain (method, uri, body) triple per request,
plus an opaque request handle through which `tpuhttp_request_header`
exposes parsed request headers for the callback's duration (how the
`x-tpu-serving-trace` context adopts on this backend too); Python runs
the shared `/v1` router (`rest.route_request`) and replies via
`tpuhttp_send_response`. ctypes releases the GIL around foreign calls and
re-acquires it inside callbacks, so N native workers overlap wherever the
handler blocks in native code (device waits, protobuf C++ parsing).

Because the router is shared, the monitoring surfaces — the Prometheus
text endpoint, the `/monitoring/traces` Chrome-trace debug endpoint
(observability/tracing.py ring), and the health plane
(`/monitoring/healthz`, `/monitoring/readyz`, `/monitoring/slo`,
`/monitoring/runtime`, `/monitoring/flightrecorder`;
docs/OBSERVABILITY.md) — are served by BOTH backends identically.

Falls back to the pure-Python `http.server` backend when the toolchain is
unavailable (`start_best_rest_server`).
"""

from __future__ import annotations

import ctypes
import json
from typing import Callable, Optional

from min_tfs_client_tpu.observability.tracing import TRACE_HEADER
from min_tfs_client_tpu.server.handlers import Handlers
from min_tfs_client_tpu.server.rest import (
    prometheus_path_from,
    route_request,
)

_HANDLER_FN = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,   # user (unused; state captured in the closure)
    ctypes.c_void_p,   # request handle
    ctypes.c_char_p,   # method
    ctypes.c_char_p,   # uri
    ctypes.POINTER(ctypes.c_char),  # body (not NUL-terminated)
    ctypes.c_uint64,   # body length
)

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        from min_tfs_client_tpu.native.build import build_http

        so_path = build_http()
        if so_path is None:
            return None
        lib = ctypes.CDLL(str(so_path))
    except OSError:
        return None
    lib.tpuhttp_start.restype = ctypes.c_void_p
    lib.tpuhttp_start.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        _HANDLER_FN, ctypes.c_void_p,
    ]
    lib.tpuhttp_port.restype = ctypes.c_int
    lib.tpuhttp_port.argtypes = [ctypes.c_void_p]
    lib.tpuhttp_send_response.restype = None
    lib.tpuhttp_send_response.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.tpuhttp_stop.restype = None
    lib.tpuhttp_stop.argtypes = [ctypes.c_void_p]
    lib.tpuhttp_request_header.restype = ctypes.c_char_p
    lib.tpuhttp_request_header.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
    ]
    _lib = lib
    return _lib


def native_http_available() -> bool:
    return _load_lib() is not None


class NativeRestServer:
    """The /v1 REST surface served by the native event loop."""

    def __init__(
        self,
        handlers: Handlers,
        port: int,
        num_workers: int = 4,
        timeout_ms: int = 30000,
        prometheus_path: Optional[str] = None,
        route_fn: Optional[Callable] = None,
    ):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native HTTP library unavailable")
        self._lib = lib
        self._route = route_fn or route_request
        self._handlers = handlers
        self._prometheus_path = prometheus_path
        # Keep a reference: the C side holds this pointer for the server's
        # lifetime; letting it be collected would leave a dangling callback.
        self._cb = _HANDLER_FN(self._on_request)
        self._server = lib.tpuhttp_start(
            b"0.0.0.0", port, num_workers, timeout_ms, self._cb, None)
        if not self._server:
            raise RuntimeError(f"native HTTP server failed to bind port {port}")
        self.port = lib.tpuhttp_port(self._server)

    def _request_trace_id(self, req) -> str:
        """The x-tpu-serving-trace request header, fetched through the
        C side's header table while the Request is still alive (the
        returned pointer is only valid during the synchronous callback;
        ctypes' c_char_p restype copies it to Python bytes here)."""
        value = self._lib.tpuhttp_request_header(req, TRACE_HEADER.encode())
        if not value:
            return ""
        try:
            return value.decode("ascii")
        except UnicodeDecodeError:
            return ""

    def _on_request(self, _user, req, method, uri, body, body_len):
        try:
            raw = ctypes.string_at(body, body_len) if body_len else b""
            try:
                uri_str = uri.decode()
            except UnicodeDecodeError:
                status, ctype, payload = 400, "application/json", json.dumps(
                    {"error": "request URI is not valid UTF-8"}).encode()
            else:
                status, ctype, payload = self._route(
                    self._handlers, self._prometheus_path,
                    method.decode(), uri_str, raw,
                    trace_id=self._request_trace_id(req))
        except Exception as exc:  # noqa: BLE001 - must answer every request
            status, ctype, payload = (
                500, "application/json",
                json.dumps({"error": str(exc)}).encode())
        self._lib.tpuhttp_send_response(
            req, status, ctype.encode(), payload, len(payload))

    def shutdown(self) -> None:
        if self._server:
            self._lib.tpuhttp_stop(self._server)
            self._server = None

    # Context-manager and http.server-compatible aliases.
    close = shutdown
    server_close = shutdown

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()


def start_best_rest_server(
    handlers: Handlers,
    port: int,
    monitoring: Optional[object] = None,
    num_threads: int = 4,
    timeout_ms: int = 30000,
    impl: str = "auto",
) -> tuple[object, int]:
    """Native epoll front-end when buildable, http.server otherwise.

    impl: "auto" (native if the toolchain builds it), "native" (required,
    raises if unavailable), or "python" (force the http.server backend).
    """
    # Warm the native JSON codec now — building it lazily inside the
    # first predict request would stall that request on a g++ run.
    from min_tfs_client_tpu.server.json_fast import json_fast_available

    json_fast_available()

    prometheus_path = prometheus_path_from(monitoring)
    if impl == "native" and not native_http_available():
        raise RuntimeError("rest_api_impl=native but the native HTTP "
                           "library could not be built")
    if impl != "python" and native_http_available():
        server = NativeRestServer(
            handlers, port, num_workers=num_threads, timeout_ms=timeout_ms,
            prometheus_path=prometheus_path)
        return server, server.port
    from min_tfs_client_tpu.server.rest import start_rest_server

    return start_rest_server(handlers, port, monitoring)
