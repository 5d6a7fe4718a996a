"""gRPC servicers: thin shims from transport to Handlers.

Parity with model_servers/prediction_service_impl.cc and
model_service_impl.cc — the servicers only translate deadline/metadata and
map ServingError codes onto the gRPC trailer (ToGRPCStatus,
grpc_status_util.cc:23).

The server they are registered on is a `grpc.aio` server on the
process's one event loop (server/server.py, utils/aio_loop.py). A plain
method here is a synchronous servicer, which that server runs on its
worker pool (`--grpc_max_threads`): everything but `Predict`. `Predict`
is a coroutine that chooses by what it can observe in the request: where
the request's signature has a form that awaits instead of blocking
(`Handlers.can_await`: a decode_step of a pooled backend, whose token is
parked already or comes with the tick loop's round), the handler runs
there and then on the loop thread, with no thread hand-off, and the loop
answers other requests while it awaits; every other request runs on the
same worker pool.
"""

from __future__ import annotations

import asyncio
import contextvars

from min_tfs_client_tpu.protos import grpc_service as gs
from min_tfs_client_tpu.server.handlers import Handlers
from min_tfs_client_tpu.utils import aio_loop
from min_tfs_client_tpu.utils.status import (
    error_from_exception,
    to_grpc_code,
)


def _incoming_trace_id(context):
    """The caller's x-tpu-serving-trace metadata value, if any — the
    router (or any upstream) propagating its fleet-scope trace id."""
    from min_tfs_client_tpu.observability import tracing

    for key, value in (context.invocation_metadata() or ()):
        if key == tracing.TRACE_HEADER:
            return value
    return None


def _guard(handler_fn, request, context):
    from min_tfs_client_tpu.observability import tracing

    aio_loop.note_request(inline=False)
    try:
        # Adopt the propagated trace id (None = mint locally): the
        # RequestTrace the handler opens then shares the caller's id, so
        # the router can stitch both processes' spans into one timeline.
        with tracing.transport("grpc"), \
                tracing.adopt(_incoming_trace_id(context)):
            return handler_fn(request)
    except Exception as exc:  # noqa: BLE001 - mapped onto the wire
        err = error_from_exception(exc)
        context.abort(to_grpc_code(err.code), err.message)


def _retrieve(task) -> None:
    """A shielded task's exception is looked at even where its RPC has
    gone (asyncio logs one that nobody retrieved)."""
    if not task.cancelled():
        task.exception()


class PredictionServiceImpl(gs.PredictionServiceServicer):
    def __init__(self, handlers: Handlers, pool):
        self._handlers = handlers
        # The server's worker pool: where a Predict that may wait runs.
        self._pool = pool

    async def Predict(self, request, context):
        from min_tfs_client_tpu.observability import tracing

        handlers = self._handlers
        loop = asyncio.get_running_loop()
        # The RPC runs in its own task, so the two context variables are
        # the task's (`_guard` has what they are for).
        with tracing.transport("grpc"), \
                tracing.adopt(_incoming_trace_id(context)):
            try:
                if handlers.can_await(request):
                    aio_loop.note_request(inline=True)
                    # A task of its own (under a copy of this context),
                    # shielded: a client that gives up cancels the RPC,
                    # not the step, which ends as it would on a pool
                    # thread, its answer kept for the resend.
                    work = loop.create_task(handlers.apredict(request))
                    work.add_done_callback(_retrieve)
                    return await asyncio.shield(work)
                aio_loop.note_request(inline=False)
                return await loop.run_in_executor(
                    self._pool, contextvars.copy_context().run,
                    handlers.predict, request)
            except Exception as exc:  # noqa: BLE001 - mapped onto the wire
                err = error_from_exception(exc)
                await context.abort(to_grpc_code(err.code), err.message)

    def Classify(self, request, context):
        return _guard(self._handlers.classify, request, context)

    def Regress(self, request, context):
        return _guard(self._handlers.regress, request, context)

    def MultiInference(self, request, context):
        return _guard(self._handlers.multi_inference, request, context)

    def GetModelMetadata(self, request, context):
        return _guard(self._handlers.get_model_metadata, request, context)


class SessionServiceImpl(gs.SessionServiceServicer):
    def __init__(self, handlers: Handlers):
        self._handlers = handlers

    def SessionRun(self, request, context):
        return _guard(self._handlers.session_run, request, context)


class ModelServiceImpl(gs.ModelServiceServicer):
    def __init__(self, handlers: Handlers):
        self._handlers = handlers

    def GetModelStatus(self, request, context):
        return _guard(self._handlers.get_model_status, request, context)

    def HandleReloadConfigRequest(self, request, context):
        return _guard(self._handlers.handle_reload_config, request, context)


def health_service_handler():
    """grpc.health.v1.Health on the serving port: the readiness verdict
    (observability/health.py) behind the standard probe protocol, so
    k8s / envoy / grpc-health-probe work against this server with zero
    extra deps (the wire format is hand-rolled — two one-field
    messages). Registered by server.py via add_generic_rpc_handlers."""
    from min_tfs_client_tpu.observability import health

    return health.grpc_health_handler()
