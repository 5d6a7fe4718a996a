"""On-demand profiling: JAX profiler server + TraceMe-style annotations.

Parity with the reference's profiler subsystem (SURVEY.md §5): it registers
a profiler RPC service on the main gRPC server (server.cc:324,339 ->
profiler/rpc/profiler_service_impl.cc) so external tooling can pull traces
from a production server, and wraps hot sections in `profiler::TraceMe`
annotations (shared_batch_scheduler.h:39).

TPU-native equivalents:
 * `start_profiler_server(port)` — jax.profiler.start_server: TensorBoard /
   xprof connect to this port and capture XPlane traces on demand (the
   Profile RPC parity path).
 * `trace(name)` — jax.profiler.TraceAnnotation context manager; a no-op
   fallback keeps the serving path alive if the profiler is unavailable.
 * `annotate(fn, name)` / @traced — decorator form for hot functions
   (batch formation, device execute, marshalling).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
from typing import Optional

_lock = threading.Lock()
_server = None                                     # guarded_by: _lock
_server_port: Optional[int] = None                 # guarded_by: _lock
_last_error: Optional[str] = None                  # guarded_by: _lock


def start_profiler_server(port: int) -> bool:
    """Start the in-process profiler gRPC server (idempotent). Returns True
    when the server is (already) running on `port`. A failure logs a
    structured warning (and is reported by `status()` /
    `/monitoring/runtime`) — never a silent False."""
    global _server, _server_port, _last_error
    with _lock:
        if _server is not None:
            return _server_port == port
        try:
            import jax

            _server = jax.profiler.start_server(port)
            _server_port = port
            _last_error = None
            return True
        except Exception as exc:  # pragma: no cover - profiler unavailable
            _server = None
            _server_port = None
            _last_error = f"{type(exc).__name__}: {exc}"
            logging.getLogger(__name__).warning(
                "profiler server failed to start on port %d: %s — "
                "on-demand trace capture will be unavailable",
                port, _last_error)
            return False


def profiler_port() -> Optional[int]:
    with _lock:
        return _server_port


def status() -> dict:
    """Profiler-server state for the `/monitoring/runtime` payload."""
    with _lock:
        return {"running": _server is not None, "port": _server_port,
                "last_error": _last_error}


def trace(name: str, **kwargs):
    """Context manager annotating a host-side region in profiler traces."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **kwargs)
    except Exception:  # pragma: no cover
        return contextlib.nullcontext()


def traced(name: Optional[str] = None):
    """Decorator: wrap a function in a trace annotation."""

    def deco(fn):
        label = name or f"{fn.__module__}.{fn.__qualname__}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with trace(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def start_trace_capture(log_dir: str) -> None:
    """Programmatic capture start (jax.profiler.start_trace): traces land
    in `log_dir` as XPlane/TensorBoard data."""
    import jax

    jax.profiler.start_trace(log_dir)


def stop_trace_capture() -> None:
    import jax

    jax.profiler.stop_trace()


# -- ProfilerService on the MAIN serving port --------------------------------


class ProfilerServiceImpl:
    """tensorflow.ProfilerService servicer backed by the JAX profiler.

    The reference registers this service on the main gRPC server
    (server.cc:324,339 -> profiler/rpc/profiler_service_impl.cc) so
    production tooling pulls traces without a side port. Profile() captures
    `duration_ms` of XPlane trace into a repository dir, with the stage
    spans mirrored into it and `host_clock.json` beside it
    (observability/profiling.py `traced_capture`), and returns every
    produced file as ProfileToolData; Monitor() returns a text snapshot of
    the serving metrics registry."""

    def Profile(self, request, context=None):  # noqa: N802 - gRPC API
        import pathlib
        import tempfile
        import time as time_mod

        from min_tfs_client_tpu.protos import tf_profiler_pb2 as pb

        response = pb.ProfileResponse()
        root = request.repository_root or tempfile.mkdtemp(prefix="tpu_prof_")
        duration_s = min(max(request.duration_ms, 1), 60_000) / 1e3
        # Snapshot what already exists so the response carries ONLY this
        # capture's files — never a prior run's traces or unrelated
        # contents of a caller-supplied repository_root.
        root_path = pathlib.Path(root)
        preexisting = ({f for f in root_path.rglob("*") if f.is_file()}
                       if root_path.exists() else set())
        try:
            from min_tfs_client_tpu.observability.profiling import (
                traced_capture,
            )

            with traced_capture(root):
                time_mod.sleep(duration_s)
        except Exception as exc:  # profiler unavailable: empty trace
            response.empty_trace = True
            if context is not None:
                context.set_details(f"profiler capture failed: {exc}")
            return response
        files = [f for f in root_path.rglob("*")
                 if f.is_file() and f not in preexisting]
        for f in sorted(files):
            data = f.read_bytes()
            tool = response.tool_data.add()
            tool.name = str(f.relative_to(root))
            tool.data = data
            if f.suffix == ".pb" and "xplane" in f.name:
                response.encoded_trace = data
        response.empty_trace = not files
        return response

    def Monitor(self, request, context=None):  # noqa: N802 - gRPC API
        from min_tfs_client_tpu.protos import tf_profiler_pb2 as pb
        from min_tfs_client_tpu.server.metrics import prometheus_text

        return pb.MonitorResponse(data=prometheus_text())
