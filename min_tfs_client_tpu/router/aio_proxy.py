"""The router's asyncio data plane: the byte proxy off the thread pool.

PERF.md round-9 recorded the threaded plane's honest ceiling: ~74% of
direct qps at 8 callers, all of it the GIL — every proxied request
crossed a gRPC worker thread that held Python bytes while fifteen
siblings contended for the interpreter. This plane replaces the
thread-per-request model with ONE event loop: `grpc.aio` generic
handlers receive the client's raw bytes (`None` deserializer), the
routing key is lifted by the same wire scan the threaded plane uses
(proxy.routing_info — O(fields), byte-for-byte identical semantics),
and the forward is an `await` on a persistent per-backend aio channel.
The byte shuffling itself lives in gRPC's C++ event engine; Python
touches each request exactly once, so 8 concurrent callers cost 8
in-flight awaits instead of 8 GIL-contending threads.

Everything the threaded plane promised still holds, verbatim:

 * the forwarded request and the returned response are bit-identical
   to a direct connection (asserted in-bench and in integration);
 * client metadata propagates (hop-by-hop keys stripped), the client's
   deadline rides `context.time_remaining()`, and the fleet-scope
   trace id is echoed back as trailing metadata;
 * a fresh session pin rolls back on connection-level UNAVAILABLE only
   (a DEADLINE_EXCEEDED init may have succeeded server-side);
 * HandleReloadConfigRequest broadcasts — now CONCURRENTLY via
   asyncio.gather (one slow backend no longer serializes the fleet's
   config apply), first backend-reported error still wins the reply;
 * grpc.health.v1 on the router port answers for the SERVICE.

Trace handoff is task-based, not thread-based: each RPC runs in its own
asyncio task, `tracing.activate(trace)` binds the contextvar inside
that task, and coroutines fanned out with `asyncio.gather`/
`create_task` inherit a COPY of the context at task creation — the
sanctioned crossing servelint's span rule (SP002) recognizes. Handing a
live trace to a FOREIGN thread's loop via `run_coroutine_threadsafe`
remains a violation.

The loop's health is first-class telemetry: a sampled ticker measures
event-loop lag (scheduling overshoot of a fixed sleep), exports the
`router_event_loop_lag_ms` gauge, feeds `/monitoring/router`'s
`data_plane` block, and drops a flight-recorder event when lag crosses
the warn threshold — a wedged loop is this plane's analogue of a
saturated thread pool, and it must be visible BEFORE it becomes tail
latency.

The loop is not this plane's own: gRPC's completion queue takes ONE
asyncio loop a process (a second one races the C core's
PollerCompletionQueue and dies with BlockingIOError deep inside the
cython layer, long after construction and only under load), so the
plane's serve coroutine runs on the process's shared loop
(utils/aio_loop.py), beside any other plane and any in-process model
server's front end.

The threaded plane stays available behind `--data_plane=threads` for
one release (docs/MIGRATING.md).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import threading
import time
from typing import Optional

from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
from min_tfs_client_tpu.protos.grpc_service import SERVICE_SCHEMAS
from min_tfs_client_tpu.router.core import RouterCore
from min_tfs_client_tpu.router.membership import DEAD, Backend
from min_tfs_client_tpu.router.proxy import (
    _PKG,
    _SESSION_CLOSE_SIGNATURE,
    _forwardable_metadata,
    _recovery_verdict,
    routing_info,
    step_ordinal_guarded,
)
from min_tfs_client_tpu.utils import aio_loop
from min_tfs_client_tpu.utils.status import (
    ServingError,
    error_from_exception,
    to_grpc_code,
)

log = logging.getLogger(__name__)

# Event-loop lag sampling: the ticker sleeps this long and measures the
# overshoot. 100ms keeps the sampling tax at ~10 wakeups/s of pure
# asyncio bookkeeping (no syscalls beyond the timerfd) while catching
# any stall long enough to matter against a millisecond-scale forward.
LAG_TICK_S = 0.1

class AioChannelPool:
    """One persistent `grpc.aio` channel per backend. Created and used
    ONLY on the data-plane loop thread (aio channels bind to the running
    loop), so the dicts need no lock — the loop IS the serialization."""

    def __init__(self):
        self._channels: dict[str, object] = {}  # servelint: owns conns
        # Cached multicallables per (backend, method): building one per
        # request costs ~tens of us of cython setup on the loop.
        self._calls: dict[tuple, object] = {}

    def get(self, backend: Backend):
        import grpc

        channel = self._channels.get(backend.backend_id)
        if channel is None:
            channel = grpc.aio.insecure_channel(
                backend.grpc_target,
                options=[("grpc.max_send_message_length", -1),
                         ("grpc.max_receive_message_length", -1)])
            self._channels[backend.backend_id] = channel
        return channel

    def unary_unary(self, backend: Backend, full_method: str):
        cache_key = (backend.backend_id, full_method)
        call = self._calls.get(cache_key)
        if call is None:
            call = self.get(backend).unary_unary(full_method)
            self._calls[cache_key] = call
        return call

    async def close(self) -> None:
        channels, self._channels = list(self._channels.values()), {}
        self._calls = {}
        for channel in channels:
            await channel.close()


class AioDataPlane:
    """The asyncio byte proxy, on the process's ONE gRPC event loop
    (utils/aio_loop.py: a second loop in one process crashes grpc's
    completion queue under load, so every aio plane and every in-process
    ModelServer's front end share that one), started/stopped from the
    (threaded) control plane. The membership poller, REST surface, and
    flight recorder stay exactly where they were — only the gRPC data
    path runs on the loop."""

    def __init__(self, core: RouterCore, *,
                 default_timeout_s: float = 60.0,
                 loop_lag_warn_ms: float = 100.0,
                 grace_s: float = 2.0):
        self._core = core
        self._default_timeout_s = default_timeout_s
        self._loop_lag_warn_ms = loop_lag_warn_ms
        self._grace_s = grace_s
        self._channels = AioChannelPool()
        self._served = None  # Future of _serve, from aio_loop.submit
        self._started = threading.Event()
        self._boot_error: Optional[BaseException] = None
        self._bound_port: Optional[int] = None
        self._requested_port = 0
        self._stop_requested = False  # set via call_soon_threadsafe only

    # -- lifecycle -----------------------------------------------------------

    def start(self, port: int) -> int:
        """Serve on the process's loop, bind the port, return the bound
        port. Raises the boot error (e.g. port in use) in the caller."""
        # servelint: thread-ok written once HERE, before the serve
        # coroutine is submitted below; the loop thread only reads it
        self._requested_port = port
        self._served = aio_loop.submit(self._serve())
        if not self._started.wait(timeout=30.0):
            raise RuntimeError("aio data plane failed to start within 30s")
        if self._boot_error is not None:
            raise self._boot_error
        self._core.loop_health.set_mode("aio")
        return self._bound_port

    def stop(self, grace: float = 2.0) -> None:
        if self._served is None or self._served.done():
            return
        aio_loop.get().call_soon_threadsafe(self._request_stop, grace)
        try:
            # Bounded teardown: grace for in-flight RPCs + slack for the
            # channel closes; past that the serve task is left to the
            # loop (same discipline as the threaded plane's stop).
            self._served.result(timeout=grace + 10.0)
        except concurrent.futures.TimeoutError:
            pass

    def wait_for_termination(self) -> None:
        if self._served is not None:
            # servelint: blocks the router main thread parks here for
            # the process lifetime, exactly like grpc's own
            # wait_for_termination; SIGINT/stop() unblocks it
            self._served.result()

    def _request_stop(self, grace: float | None = None) -> None:
        # Runs ON the loop via call_soon_threadsafe: flip the flag the
        # serve coroutine polls through its asyncio.Event, carrying the
        # caller's grace so server.stop() honors it (the threaded plane
        # does; hard-cancelling in-flight RPCs after a fixed default
        # would break long-deadline drains).
        if grace is not None:
            # servelint: thread-ok only ever mutated on the loop thread
            # (call_soon_threadsafe marshals the stop() caller here)
            self._grace_s = grace
        # servelint: thread-ok same loop-thread-only discipline
        self._stop_requested = True
        event = getattr(self, "_stop_event", None)
        if event is not None:
            event.set()

    async def _serve(self) -> None:
        import grpc

        self._stop_event = asyncio.Event()
        server = grpc.aio.server(
            options=[("grpc.max_send_message_length", -1),
                     ("grpc.max_receive_message_length", -1)])
        server.add_generic_rpc_handlers(tuple(self._generic_handlers()))
        try:
            # servelint: thread-ok written before _started.set();
            # start() reads only after wait() — Event handoff
            self._bound_port = server.add_insecure_port(
                f"0.0.0.0:{self._requested_port}")
            await server.start()
        except BaseException as exc:
            # servelint: thread-ok same Event handoff as above
            self._boot_error = exc
            self._started.set()
            return
        ticker = asyncio.ensure_future(self._lag_ticker())
        self._started.set()
        if self._stop_requested:  # stop() raced the boot
            self._stop_event.set()
        # servelint: blocks the serve coroutine parks here for the
        # process lifetime; stop()/SIGINT sets the event (and the
        # ticker task keeps the loop demonstrably live meanwhile)
        await self._stop_event.wait()
        ticker.cancel()
        await server.stop(self._grace_s)
        await self._channels.close()

    # -- event-loop health ---------------------------------------------------

    async def _lag_ticker(self) -> None:
        """Sampled event-loop lag: sleep a fixed tick, measure the
        overshoot. Overshoot is exactly the scheduling delay every
        in-flight forward's completion is also paying."""
        from min_tfs_client_tpu.server import metrics

        while True:
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(LAG_TICK_S)
            except asyncio.CancelledError:
                return
            lag_ms = max(0.0,
                         (time.perf_counter() - t0 - LAG_TICK_S) * 1e3)
            over = lag_ms >= self._loop_lag_warn_ms
            self._core.loop_health.record_lag(lag_ms, over)
            metrics.safe_set(metrics.router_event_loop_lag_ms, lag_ms)
            if over:
                # A stalled loop is a fleet-wide latency event: put it
                # in the black box next to the forwards it delayed.
                try:
                    from min_tfs_client_tpu.observability import (
                        flight_recorder,
                    )

                    flight_recorder.record(
                        "event_loop_lag", lag_ms=round(lag_ms, 3),
                        warn_ms=self._loop_lag_warn_ms)
                except Exception:  # pragma: no cover - recorder must
                    pass           # not take down the ticker

    # -- forwarding ----------------------------------------------------------

    async def _forward(self, backend: Backend, full_method: str,
                       request_bytes: bytes, context,
                       on_rpc_error=None,
                       probing: bool = False,
                       retry_safe: bool = False) -> bytes:
        """One awaited unary forward over the backend's persistent aio
        channel. Same contract as the threaded plane's _forward: client
        deadline propagated, hop metadata stripped, trace id injected
        (metadata ONLY — the bytes stay untouched), `on_rpc_error`
        before the abort with the BACKEND'S status. `probing` (pin
        recovery) re-raises NOT_FOUND ("wrong backend") and
        connection-level UNAVAILABLE (candidate unreachable — says
        nothing about the session) instead of aborting, so the probe
        walk continues; DEADLINE_EXCEEDED aborts even while probing —
        the request may have EXECUTED on that backend. `retry_safe`
        (stateless, or ordinal-guarded decode step) enables the bounded
        in-forward UNAVAILABLE retry — the backoff is an awaited sleep,
        so a retrying forward never stalls the loop's other in-flight
        requests."""
        import grpc

        from min_tfs_client_tpu.robustness import faults
        from min_tfs_client_tpu.robustness.retry import (
            ROUTER_FORWARD_POLICY,
            next_forward_retry_delay_s,
        )
        from min_tfs_client_tpu.router.proxy import _record_forward_retry

        call = self._channels.unary_unary(backend, full_method)
        metadata = _forwardable_metadata(context)
        trace = tracing.current_trace()
        if trace is not None:
            metadata = [(k, v) for k, v in metadata
                        if k.lower() != tracing.TRACE_HEADER]
            metadata.append((tracing.TRACE_HEADER, trace.trace_id))
        policy = ROUTER_FORWARD_POLICY if retry_safe and not probing \
            else None
        self._core.note_forward_start(backend.backend_id)
        try:
            attempt = 0
            while True:
                # Deadline re-read per attempt: a retry must spend the
                # CLIENT'S remaining budget, not a fresh default.
                timeout = context.time_remaining()
                if timeout is None:
                    timeout = self._default_timeout_s
                try:
                    try:
                        fired = faults.point(
                            "router.forward.pre",
                            backend=backend.backend_id,
                            method=full_method,
                            probing=probing, attempt=attempt)
                    except ServingError as exc:
                        tracing.set_status(exc.code)
                        await context.abort(to_grpc_code(exc.code),
                                            exc.message)
                    if fired is not None and fired.deadline_ms:
                        timeout = fired.deadline_ms / 1e3
                    with tracing.span("router/forward",
                                      backend=backend.backend_id):
                        with tracing.span("router/backend_wait",
                                          backend=backend.backend_id):
                            response = await call(request_bytes,
                                                  timeout=timeout,
                                                  metadata=metadata)
                    break
                except grpc.RpcError as err:
                    code = err.code()
                    if probing and code in (grpc.StatusCode.NOT_FOUND,
                                            grpc.StatusCode.UNAVAILABLE):
                        raise
                    delay_s = next_forward_retry_delay_s(
                        policy, code.name, attempt)
                    if delay_s is not None:
                        _record_forward_retry(backend, full_method,
                                              attempt, trace)
                        await asyncio.sleep(delay_s)
                        attempt += 1
                        continue
                    unreachable = code in (
                        grpc.StatusCode.UNAVAILABLE,
                        grpc.StatusCode.DEADLINE_EXCEEDED)
                    self._core.note_result(backend, full_method,
                                           error_code=code.name,
                                           unreachable=unreachable)
                    tracing.set_status(code.name)
                    if on_rpc_error is not None:
                        on_rpc_error(code, err.details() or code.name)
                    await context.abort(code, err.details() or code.name)
        finally:
            self._core.note_forward_done(backend.backend_id)
        self._core.note_result(backend, full_method)
        return response

    async def _handle(self, service: str, method: str,
                      request_bytes: bytes, context) -> bytes:
        """Trace envelope around one routed request — the aio twin of
        the threaded plane's _handle. The RPC runs in its own asyncio
        task, so activate()'s contextvar binding is task-local: spans
        recorded across awaits land on this request's trace and no
        other."""
        if not tracing.enabled():
            return await self._handle_routed(service, method,
                                             request_bytes, context, None)
        incoming = None
        for key, value in (context.invocation_metadata() or ()):
            if key.lower() == tracing.TRACE_HEADER:
                incoming = value
                break
        trace = tracing.RequestTrace(
            f"route/{method}", transport="grpc",
            trace_id=tracing.valid_trace_id(incoming) if incoming else None)
        try:
            with tracing.activate(trace):
                context.set_trailing_metadata(
                    ((tracing.TRACE_HEADER, trace.trace_id),))
                return await self._handle_routed(service, method,
                                                 request_bytes, context,
                                                 trace)
        finally:
            # abort raises grpc's control-flow exception; the real
            # status was recorded via set_status before the raise.
            trace.finish(status=trace.status)

    async def _handle_routed(self, service: str, method: str,
                             request_bytes: bytes, context,
                             trace) -> bytes:
        from min_tfs_client_tpu.observability import flight_recorder  # noqa: F401 - hot path keeps the cached module ref local

        full_method = f"/{_PKG}.{service}/{method}"
        model = signature = ""
        session_id: Optional[bytes] = None
        try:
            with tracing.span("router/parse"):
                model, session_id, signature = routing_info(
                    service, method, request_bytes)
            with tracing.span("router/route"):
                decision = self._core.route(model, session_id,
                                            request_bytes, signature)
        except ServingError as exc:
            tracing.set_status(exc.code)
            await context.abort(to_grpc_code(exc.code), exc.message)
        except Exception as exc:  # noqa: BLE001 - mapped onto the wire
            err = error_from_exception(exc)
            tracing.set_status(err.code)
            flight_recorder.record_error(
                f"route/{method}", model, signature, err.code,
                str(exc), trace_id=trace.trace_id if trace else "")
            await context.abort(to_grpc_code(err.code), err.message)
        if trace is not None:
            trace.model = model
            trace.signature = signature
            trace.annotate(backend=decision.backend.backend_id,
                           sessioned=session_id is not None,
                           fresh_pin=decision.fresh_pin,
                           epoch=f"{decision.epoch:016x}")
        import grpc

        def on_rpc_error(code, details, backend_id=None):
            # `backend_id` names the backend that ACTUALLY failed —
            # recovery probes pass it explicitly, since the decision's
            # first choice may not be the candidate that errored.
            flight_recorder.record_error(
                f"route/{method}", model, signature, code.value[0],
                f"{backend_id or decision.backend.backend_id}: "
                f"{details}",
                trace_id=trace.trace_id if trace else "")
            # Fresh-pin rollback on proven non-delivery only, same as
            # the threaded plane: a DEADLINE_EXCEEDED init may have
            # succeeded server-side.
            if decision.fresh_pin and code == grpc.StatusCode.UNAVAILABLE:
                self._core.sessions.release(model, session_id)

        if decision.probe_candidates:
            response = await self._forward_recovering(
                decision, full_method, request_bytes, context,
                model, session_id, trace, on_rpc_error)
        else:
            # Provably-safe retry scope — the SHARED predicate
            # (robustness/retry.py), same as the threaded plane.
            from min_tfs_client_tpu.robustness.retry import (
                retry_safe_predict,
            )

            # Ordinal scan gated on decode_step, same as the threaded
            # plane: never a second wire walk for stateless payloads.
            retry_safe = retry_safe_predict(
                signature, session_id is not None,
                signature == "decode_step"
                and step_ordinal_guarded(request_bytes))
            response = await self._forward(decision.backend, full_method,
                                           request_bytes, context,
                                           on_rpc_error=on_rpc_error,
                                           retry_safe=retry_safe)
        if session_id is not None and \
                signature == _SESSION_CLOSE_SIGNATURE:
            self._core.session_closed(model, session_id)
        return response

    async def _forward_recovering(self, decision, full_method: str,
                                  request_bytes: bytes, context,
                                  model: str, session_id: bytes,
                                  trace, on_rpc_error) -> bytes:
        """PIN RECOVERY (docs/ROUTING.md "Replicated stickiness"): this
        replica holds no pin for an existing session, so the current
        view's argmax may be wrong — a join since the session's init
        moves exactly the joiner-won keys. Forward down the preference
        order; a NOT_FOUND is "wrong backend, next candidate"
        (forwarding a decode step to a backend without the session is
        side-effect-free by the decode-surface contract); the backend
        that answers gets the pin. Zero extra forwards when the view
        never churned — candidate #1 is the init-time placement."""
        import grpc

        first_not_found = None
        unreachable = 0
        for probes, backend in enumerate(decision.probe_candidates):
            def candidate_error(code, details, _bid=backend.backend_id):
                on_rpc_error(code, details, _bid)

            try:
                response = await self._forward(
                    backend, full_method, request_bytes, context,
                    on_rpc_error=candidate_error,
                    probing=True)
            except grpc.RpcError as err:
                # Only NOT_FOUND / UNAVAILABLE reach here (probing);
                # everything else aborted inside _forward.
                if err.code() == grpc.StatusCode.NOT_FOUND:
                    # Expected "wrong backend" from a healthy backend:
                    # count the request but NOT a backend error —
                    # router_session_recoveries is the recovery signal.
                    self._core.note_result(backend, full_method)
                    if first_not_found is None:
                        first_not_found = err
                else:
                    # Candidate unreachable (e.g. died post-join,
                    # pre-eject) — says nothing about the session;
                    # pulse ejection and keep walking. Aborting here
                    # would make a pinless replica answer divergently
                    # from one holding the pin.
                    self._core.note_result(backend, full_method,
                                           error_code=err.code().name,
                                           unreachable=True)
                    unreachable += 1
                continue
            self._core.session_recovered(
                model, session_id, backend.backend_id, probes)
            if trace is not None and probes:
                trace.annotate(backend=backend.backend_id,
                               recovered_probes=probes)
            return response
        code, details = _recovery_verdict(first_not_found, unreachable)
        tracing.set_status(code.name)
        await context.abort(code, details)

    async def _broadcast_reload(self, request_bytes: bytes,
                                context) -> bytes:
        """Fleet-wide config apply, now CONCURRENT: every non-DEAD
        backend gets the reload as its own task via asyncio.gather (the
        tasks inherit this request's trace through the context copy —
        the sanctioned task handoff), so one slow backend costs
        max(latency), not sum. Reply selection is unchanged: every
        backend is attempted, the first backend-REPORTED error (in
        stable backend order) wins the reply, else the last OK; an
        abort only when NO backend answered."""
        import grpc

        targets = [b for b in self._core.membership.backends()
                   if self._core.membership.state_of(b.backend_id) != DEAD]
        if not targets:
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                "no reachable backends for config reload")
        full_method = f"/{_PKG}.ModelService/HandleReloadConfigRequest"
        remaining = context.time_remaining()
        if remaining is None:
            remaining = self._default_timeout_s
        metadata = _forwardable_metadata(context)

        async def one(backend: Backend):
            call = self._channels.unary_unary(backend, full_method)
            try:
                response = await call(request_bytes, timeout=remaining,
                                      metadata=metadata)
            except grpc.RpcError as err:
                code = err.code()
                self._core.note_result(
                    backend, full_method, error_code=code.name,
                    unreachable=code in (
                        grpc.StatusCode.UNAVAILABLE,
                        grpc.StatusCode.DEADLINE_EXCEEDED))
                return ("unreachable", code, err.details() or code.name,
                        backend.backend_id)
            self._core.note_result(backend, full_method)
            return ("answered", response)

        with tracing.span("router/forward", backend="broadcast"):
            results = await asyncio.gather(*(one(b) for b in targets))
        last_ok: Optional[bytes] = None
        first_error: Optional[bytes] = None
        first_failure: Optional[tuple] = None
        for result in results:
            if result[0] == "unreachable":
                if first_failure is None:
                    first_failure = result[1:]
                continue
            response = result[1]
            try:
                parsed = apis.ReloadConfigResponse.FromString(response)
            except Exception:  # noqa: BLE001 - treat unparseable as OK-ish
                parsed = None
            if parsed is not None and parsed.status.error_code != 0:
                if first_error is None:
                    first_error = response
            else:
                last_ok = response
        if first_error is not None:
            return first_error  # first backend-REPORTED error wins
        if last_ok is None:
            code, details, backend_id = first_failure
            await context.abort(
                code, f"config reload failed against every backend "
                      f"(first: {backend_id}: {details})")
        return last_ok

    # -- registration --------------------------------------------------------

    def _generic_handlers(self):
        import grpc

        handlers = []
        for service, methods in SERVICE_SCHEMAS.items():
            method_handlers = {}
            for method in methods:
                if (service, method) == ("ModelService",
                                         "HandleReloadConfigRequest"):
                    fn = self._broadcast_reload
                else:
                    # Default-arg binding, same idiom as the threaded
                    # plane; the aio server awaits coroutine behaviors.
                    async def fn(request_bytes, context,
                                 _service=service, _method=method):
                        return await self._handle(_service, _method,
                                                  request_bytes, context)
                method_handlers[method] = \
                    grpc.unary_unary_rpc_method_handler(
                        fn, request_deserializer=None,  # raw bytes in
                        response_serializer=None)       # raw bytes out
            handlers.append(grpc.method_handlers_generic_handler(
                f"{_PKG}.{service}", method_handlers))
        handlers.append(self._health_handler())
        return handlers

    def _health_handler(self):
        """grpc.health.v1 for the SERVICE — same verdict logic as the
        threaded plane, async behavior."""
        import grpc

        from min_tfs_client_tpu.observability.health import (
            _NOT_SERVING,
            _SERVING,
            _encode_status,
            _parse_service,
        )

        async def check(request_bytes, context):
            service = _parse_service(request_bytes)
            if service is None:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    "malformed HealthCheckRequest")
            if not service:
                return _encode_status(
                    _SERVING if self._core.ready() else _NOT_SERVING)
            available = self._core.membership.model_available(service)
            if available is None:
                await context.abort(grpc.StatusCode.NOT_FOUND,
                                    "unknown service for health check")
            return _encode_status(_SERVING if available else _NOT_SERVING)

        return grpc.method_handlers_generic_handler(
            "grpc.health.v1.Health",
            {"Check": grpc.unary_unary_rpc_method_handler(
                check, request_deserializer=None,
                response_serializer=None)})
