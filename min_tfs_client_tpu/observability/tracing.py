"""Per-request tracing spine: Span / RequestTrace + four export sinks.

The reference stack threads `profiler::TraceMe` annotations and the
monitoring registry through every hot-path stage (shared_batch_scheduler.h:39,
util/prometheus_exporter.cc); this module is the cross-layer equivalent,
connecting them into ONE per-request timeline:

 * `request_trace(api, ...)` opens a RequestTrace at the transport entry
   point (server/handlers.py `_instrumented`) and publishes it in a
   contextvar;
 * `span(name)` wraps each hot-path stage (deserialize, queue-wait,
   batch-form, host->device, execute, device->host, serialize) and records
   a (name, start, end, args) tuple on the current trace;
 * the batching queue hands a request's trace across the caller->scheduler
   thread boundary explicitly (BatchTask.trace); the scheduler thread
   activates a `fanout` over every co-batched trace so one merged
   execution is accounted to each caller that rode in the batch;
 * asyncio TASKS (the router's aio data plane) need no explicit handoff
   at all: `_current` is a contextvar, every task created on the loop
   (`create_task`/`ensure_future`/`gather`) copies the spawning task's
   context, so the active trace rides into child coroutines and
   `activate()`'s set/reset stays task-local — concurrent requests on
   ONE loop thread cannot bleed spans into each other. Crossing into a
   foreign loop from another thread (`run_coroutine_threadsafe`) gets
   no such copy and is a span-rule violation (analysis/spans.py SP002).

Sinks, fed when a trace finishes:

 1. metrics registry — per-stage latency samplers, batch-occupancy gauge,
    padding-waste counter, queue-depth gauge (server/metrics.py; exported
    by the existing Prometheus text exporter);
 2. a bounded ring of recent traces, rendered as Chrome-trace/Perfetto
    JSON by the `/monitoring/traces` debug endpoint (server/rest.py);
 3. `jax.profiler.TraceAnnotation` bridging for the length of an
    in-process device capture (`profiler_annotations`, entered by
    observability/profiling.py `traced_capture`), so the capture shows
    the same stage names on the host threads. Off outside a capture: a
    TraceAnnotation object per span costs ~1us of pure Python even with
    no capture active, which is real money at toy-model latencies.
 4. the host track: a second bounded ring, of spans that belong to the
    PROCESS and to no request (`process_span`: a garbage collection,
    the metrics drain below, the event loop's ticker, the decode loop
    finding nothing due). Same clock, same rendering: `chrome_trace`
    puts them on one reserved `tid` as events of category `process`,
    beside the requests of `/monitoring/traces` and in a capture's
    `host_track.json` (observability/profiling.py).

Clocks: spans record `time.perf_counter()` (CLOCK_MONOTONIC — comparable
across threads); Chrome-trace `ts` values are microseconds relative to one
process-wide epoch so concurrent requests align on a single timeline.
Every trace also captures `time.time()` at open, so cross-process
stitching (the router's fleet view, docs/OBSERVABILITY.md "Fleet
tracing") can render all processes on the shared wall clock. A device
capture writes `clock_pair()` at its start and stop into the capture's
`host_clock.json`, which puts any `ts` of `/monitoring/traces` on the
profiler's clock by one addition.

Fleet scope: a trace carries a globally-unique `trace_id`. The router
mints one per routed request and propagates it as the
`x-tpu-serving-trace` gRPC metadata / HTTP header; server transports
ADOPT an incoming id (`adopt()`), so the backend's stage spans land in
the same logical trace as the router's routing/forward spans and
`/monitoring/traces?trace_id=` on the router can stitch both processes
into one timeline.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import os
import re
import threading
import time

_current: contextvars.ContextVar = contextvars.ContextVar(
    "request_trace", default=None)
_transport: contextvars.ContextVar = contextvars.ContextVar(
    "request_transport", default="")
_incoming_id: contextvars.ContextVar = contextvars.ContextVar(
    "incoming_trace_id", default=None)

_EPOCH = time.perf_counter()
_ids = itertools.count(1)

# The cross-process trace-context header: lowercase (gRPC metadata keys
# must be), carried as gRPC metadata on forwarded RPCs and as an HTTP
# request header on proxied REST calls. Metadata only — the proxied body
# stays byte-identical.
TRACE_HEADER = "x-tpu-serving-trace"

# Minted ids are <process-random 12 hex><per-process seq>: globally
# unique without paying os.urandom per request (~a string format, not a
# syscall, on the hot path).
_ID_PREFIX = os.urandom(6).hex()

# What an ADOPTED (wire-supplied) id may look like — anything else is
# dropped and a fresh id minted, so junk metadata can't inject into the
# monitoring JSON or grow unbounded keys.
_TRACE_ID_RE = re.compile(r"^[0-9a-zA-Z_.\-]{4,64}$")


def valid_trace_id(value) -> str | None:
    """Sanitized wire-supplied trace id, or None when unusable."""
    if isinstance(value, bytes):
        try:
            value = value.decode("ascii")
        except UnicodeDecodeError:
            return None
    if isinstance(value, str) and _TRACE_ID_RE.fullmatch(value):
        # fullmatch, not match: '$' alone still accepts a trailing
        # newline, which would defeat the sanitizer (URL injection into
        # the stitcher's backend fetch).
        return value
    return None

_enabled = True
_bridge = False  # on only inside profiler_annotations()
_ann_cls = None  # lazily resolved jax.profiler.TraceAnnotation; False = n/a

# The canonical stage names, in pipeline order. Anything recording a new
# stage should reuse these where they apply so dashboards/bench breakdowns
# aggregate across models (docs/OBSERVABILITY.md documents them).
STAGES = (
    # Router data plane (router/proxy.py), recorded in the ROUTER
    # process: routing-key wire scan, the routing decision (pin only on
    # a fresh sessioned request), the whole forward, and the inner
    # blocking RPC to the chosen backend.
    "router/parse",
    "router/route",
    "router/pin",
    "router/forward",
    "router/backend_wait",
    "serving/resolve",
    "serving/deserialize",
    "serving/parse_examples",
    "serving/validate",
    "batching/queue_wait",
    "batching/merge",
    "batching/execute",
    # Pipelined in-flight execution (window > 1): slot wait, async launch
    # (device dispatch + D2H copies issued), and the completion thread's
    # materialization of one batch (docs/OBSERVABILITY.md).
    "batching/in_flight_wait",
    "batching/dispatch",
    "batching/materialize",
    "serving/pad",
    "device/host_to_device",
    "device/execute",
    "device/device_to_host",
    "host/execute",
    "partition/pre",
    "partition/post",
    # Microbatched partition pipeline (multi-segment imports): per-chunk
    # host stage, device launch, and materialization — chunk j's host
    # stage overlaps chunk j-1's device segment.
    "pipeline/host",
    "pipeline/dispatch",
    "pipeline/materialize",
    # Pooled decode sessions (servables/decode_sessions.py). On the
    # request that opens a session: its prefill and pool write. On every
    # stepping request: the time until the round that computes its
    # token was snapshotted (nothing when the loop was ahead of it).
    # Recorded by the tick loop's own thread, on the trace of the first
    # request that collects a token of the round, the loop's phases:
    # from the previous round's fetch to this round's snapshot, the
    # host work before the first transfer (one chunked-prefill round
    # nested in it), the transfers and the ENQUEUE of the device program
    # (not its run), the wake-up of the riders of the round before and
    # the pool's bookkeeping while the device already runs, the wait for
    # the program's outputs, and from there to the riders' wake-up,
    # which comes after the next round's launch. The five that the loop
    # thread is inside (not `deliver`, which overlaps the next round)
    # carry `cpu_us`, the thread's CPU in them: a phase whose CPU is a
    # tenth of its length was waiting.
    "decode/init",
    "decode/wait",
    "decode/handoff",
    "decode/prepare",
    "decode/prefill_chunk",
    "decode/tick",
    "decode/wake",
    "decode/fetch",
    "decode/deliver",
    # A whole generation's expert-layer counts (models/mimo.py), on the
    # request's own trace after its batch was split: no duration, its
    # arguments are the numbers (prompt_tokens, pairs_* and held_* for
    # prefill and decode, max_load, load_total).
    "generate/route",
    # What a whole generation's cross-attention reads of the K and V it
    # holds (models/t5.py), on the request's own trace before it runs:
    # no duration, its arguments are the numbers (input_tokens,
    # blocks_read, blocks_held).
    "generate/cross",
    # What a whole generation's self-attention copies of the cache it
    # holds, over all its steps (models/t5.py), beside `generate/cross`:
    # no duration, its arguments are the numbers (rows_read, rows_held).
    "generate/self",
    # A whole generation's recurrent state (models/granite_hybrid.py), on
    # the request's own trace after its batch was split: no duration, its
    # arguments are the numbers (prompt_tokens, scan_rows, state_bytes,
    # steps).
    "generate/state",
    # What a whole generation's decode steps read of the latent cache it
    # holds (models/ling_hybrid.py), beside `generate/route` and
    # `generate/state`: no duration, its arguments are the numbers
    # (prompt_tokens, steps, latent_rows_read, latent_rows_held,
    # latent_rows_copied).
    "generate/latent",
    # What a whole generation's hyper-connected residual path mixed
    # (models/xing.py), beside `generate/route` and `generate/latent`: no
    # duration, its arguments are the numbers (prompt_tokens, steps,
    # stream_rows, sinkhorn_rounds).
    "generate/streams",
    "serving/serialize",
)


def enable(on: bool) -> None:
    """Process-wide switch. Disabled: request_trace/span become no-ops
    (used by the overhead smoke test and as the operator kill switch)."""
    global _enabled
    # servelint: thread-ok one atomic store of a bool that every reader
    # takes as it finds it: a span on either side of the flip is fine
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


@contextlib.contextmanager
def profiler_annotations():
    """For the block, mirror every `span` and request envelope into a
    jax.profiler.TraceAnnotation, so the capture that is running shows
    the serving stage names on the host threads over the XLA ops. The
    in-process captures enter this for their own length (the profiler
    takes one capture at a time, so blocks do not nest); a span that is
    open when it ends closes the annotation it opened."""
    global _bridge
    # servelint: thread-ok one atomic store; a span that reads it a
    # moment late or early has an annotation or has none, both fine
    _bridge = True
    try:
        yield
    finally:
        # servelint: thread-ok as above
        _bridge = False


def clock_pair() -> dict:
    """This instant on the three clocks a capture has to join, read back
    to back: `perf_counter` (what spans record), the Unix-epoch
    nanosecond (the profiler's clock), and `span_us`, the `ts` that
    `/monitoring/traces` would give it. `epoch_unix_ns` is the Unix
    nanosecond of `ts` 0: a span's `ts` maps to the profiler's clock as
    epoch_unix_ns + ts * 1000."""
    pc, ns = time.perf_counter(), time.time_ns()
    return {"perf_counter_s": pc, "unix_ns": ns, "span_us": _us(pc),
            "epoch_unix_ns": ns - round((pc - _EPOCH) * 1e9)}


def _annotation(name: str):
    global _ann_cls
    if _ann_cls is None:
        try:
            import jax

            _ann_cls = jax.profiler.TraceAnnotation
        except Exception:  # pragma: no cover - profiler lib unavailable
            _ann_cls = False
    return _ann_cls(name) if _ann_cls else None


# ---------------------------------------------------------------------------
# Sink 4: the host track. What the process does on nobody's behalf, on
# the spans' clock, in a ring of its own: a stall that idles the chip
# has to be visible where no request was open, or where the only open
# requests are waiting for it to end.

PROCESS_RING = 4096
# Its names, each written where the work happens: a collection's pause
# of 1 ms or more (observability/runtime.py `watch_gc`), one wake-up of
# the metrics drain that found work (`flush_metrics`), one tick of the
# gRPC event loop's 100 ms ticker with its overshoot and the loop
# thread's CPU (utils/aio_loop.py), and the decode loop's time with
# nothing due, from one loop thread's end to the next one's first
# snapshot (servables/decode_sessions.py `TickBatcher`).
PROCESS_SPANS = ("host/gc", "observe/drain", "loop/sample", "decode/idle")
# `_ids` counts requests from 1: no request's `tid` is 0.
PROCESS_TID = 0
# No lock, on purpose: a collector's callback writes here from whatever
# thread allocated last, also one that holds a lock of this module. A
# bounded deque's append, its `list()` copy and its clear are each one
# call under the interpreter lock.
_process: collections.deque = collections.deque(maxlen=PROCESS_RING)


def process_span(name: str, t0: float, t1: float, **args) -> None:
    """Record a span of the process itself, its ends stamped by hand
    with `time.perf_counter()`. Off with the kill switch, like the rest
    of the spine."""
    if _enabled:
        # servelint: thread-ok one atomic append (see `_process`)
        _process.append((name, t0, t1, args or None))


def process_snapshot(since: float | None = None,
                     until: float | None = None) -> list[tuple]:
    """The host track's spans `(name, t0, t1, args|None)`, oldest first;
    with bounds (perf_counter seconds), those that overlap them."""
    return [s for s in list(_process)
            if (since is None or s[2] >= since)
            and (until is None or s[1] <= until)]


def process_clear() -> None:
    # servelint: thread-ok one atomic call (see `_process`)
    _process.clear()


def open_annotation(name: str):
    """The profiler's side of a process span whose work runs on THIS
    thread: inside a capture, an entered TraceAnnotation that the caller
    closes with `close_annotation` where the work ends; None outside one.
    (A span no thread is inside, the event loop's sample or the decode
    loop's idle time, has no annotation, like the request spans that are
    stamped by hand.)"""
    ann = _annotation(name) if _bridge else None
    if ann is not None:
        ann.__enter__()
    return ann


def close_annotation(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


class RequestTrace:
    """One request's timeline: spans + metadata, filled as it flows.

    Deliberately lock-free on the recording path: `spans.append` of a
    pre-built tuple is atomic under the GIL, and every cross-thread
    writer finishes before the caller's `task.done.wait()` returns —
    the batch scheduler stops writing before handing the task off, and
    the in-flight window's completion thread closes its last span
    before `done.set()` (batching/session.py `_complete_batch`). Any
    new writer must keep that ordering: no span may be recorded after
    the task's `done` event fires. The same argument covers asyncio
    task writers (the aio router): gathered child tasks append on the
    one loop thread and are awaited before the request's `finish()`.
    Readers copy the list (`list(spans)`), which is likewise GIL-safe
    against a concurrent append.
    """

    __slots__ = ("id", "trace_id", "api", "model", "signature", "transport",
                 "status", "start", "wall_start", "end", "spans", "meta",
                 "costs")

    def __init__(self, api: str, model: str = "", signature: str = "",
                 transport: str = "", trace_id: str | None = None):
        self.id = next(_ids)
        # Adopt the caller-supplied id (the router's, propagated over the
        # wire) when one is active; otherwise mint — every trace is
        # fleet-addressable either way.
        self.trace_id = (trace_id or _incoming_id.get()
                         or f"{_ID_PREFIX}{self.id:06x}")
        self.api = api
        self.model = model
        self.signature = signature
        self.transport = transport
        self.status = "0"
        self.start = time.perf_counter()
        # Wall-clock anchor for cross-process stitching: perf_counter
        # epochs differ per process, time.time() is shared (modulo the
        # clock skew the stitcher annotates).
        self.wall_start = time.time()
        self.end: float | None = None
        self.spans: list[tuple] = []  # (name, t0, t1, args|None)
        self.meta: dict = {}
        # Accumulated cost events (observability/costs.py): compile wall
        # attributed to the triggering request, transfer bytes, KV
        # page-ticks. None until the first add_cost — most requests
        # never pay the dict.
        self.costs: dict | None = None

    def add_span(self, name: str, t0: float, t1: float,
                 args: dict | None = None) -> None:
        self.spans.append((name, t0, t1, args))

    def annotate(self, **kv) -> None:
        """Attach request metadata (batch size, padding bucket, queue...).
        Values are coerced to plain JSON-able scalars so the Chrome-trace
        encoder never chokes on a numpy int."""
        for k, v in kv.items():
            if isinstance(v, (int, float, str, bool, type(None))):
                self.meta[k] = v
            else:
                try:
                    self.meta[k] = float(v)
                except (TypeError, ValueError):
                    self.meta[k] = str(v)

    def add_cost(self, **kv) -> None:
        """Accumulate cost-event values (summed, not overwritten — a
        request can trigger several compiles or transfers). Fed into
        the per-request cost vector by observability/costs.py when the
        trace finishes."""
        costs = self.costs
        if costs is None:
            costs = self.costs = {}
        for k, v in kv.items():
            costs[k] = costs.get(k, 0.0) + float(v)

    def duration_s(self) -> float:
        return (self.end if self.end is not None
                else time.perf_counter()) - self.start

    def stage_durations(self) -> dict[str, float]:
        """name -> summed duration in seconds (a stage may repeat, e.g.
        per-chunk executes of an oversized request)."""
        out: dict[str, float] = {}
        for name, t0, t1, _ in list(self.spans):
            out[name] = out.get(name, 0.0) + (t1 - t0)
        return out

    def finish(self, status: str = "0") -> None:
        self.end = time.perf_counter()
        self.status = status
        _ring.record(self)
        # Metrics export (8+ histogram observations, gauge/counter updates)
        # is deferred to the drain thread — ~12us of registry bookkeeping
        # that should not ride the request's critical path. Readers get
        # read-your-writes through flush_metrics() (prometheus_text calls
        # it before serializing). The enqueue + liveness check share ONE
        # uncontended lock acquisition (~100ns): servelint's
        # lock-discipline rule flagged the old unlocked read of
        # _drain_thread, whose double-checked start could race a
        # just-died (post-fork) thread and drop the revival.
        with _pending_lock:
            _pending.append(self)
            if _drain_thread is None or not _drain_thread.is_alive():
                _start_drain_thread_locked()


class _Fanout:
    """Trace-like target multiplexing span/annotate onto every co-batched
    caller's trace (the scheduler thread runs ONE merged execution on
    behalf of N callers)."""

    __slots__ = ("traces",)

    def __init__(self, traces):
        self.traces = list(traces)

    def add_span(self, name, t0, t1, args=None):
        for tr in self.traces:
            tr.add_span(name, t0, t1, args)

    def annotate(self, **kv):
        for tr in self.traces:
            tr.annotate(**kv)

    def add_cost(self, **kv):
        """A cost event raised while executing a MERGED batch (e.g. the
        compile the batch triggered) is shared work: split it evenly
        across the riders so the fleet-wide sum stays conserved."""
        n = len(self.traces)
        if not n:
            return
        split = {k: float(v) / n for k, v in kv.items()}
        for tr in self.traces:
            tr.add_cost(**split)


def current_trace():
    """The RequestTrace (or batch fanout) active on this thread, or None."""
    return _current.get()


def annotate(**kv) -> None:
    tr = _current.get()
    if tr is not None:
        tr.annotate(**kv)


def add_span(name: str, t0: float, t1: float, **args) -> None:
    """Record on the current trace a stage whose ends were stamped by
    hand (`time.perf_counter()`): one that begins on one side of a lock
    or of a thread hand-off and ends on the other, where no `with` can
    hold it. No-op without a trace, as `span` is."""
    tr = _current.get()
    if tr is not None:
        tr.add_span(name, t0, t1, args or None)


def add_cost(**kv) -> None:
    """Accumulate cost events onto the current trace (no-op without
    one). A batch fanout splits the value across its riders."""
    tr = _current.get()
    if tr is not None and hasattr(tr, "add_cost"):
        tr.add_cost(**kv)


@contextlib.contextmanager
def activate(trace):
    """Make `trace` (a RequestTrace or _Fanout) current for the block —
    the explicit thread-handoff used by the batch scheduler."""
    token = _current.set(trace)
    try:
        yield trace
    finally:
        _current.reset(token)


def fanout(traces) -> _Fanout:
    return _Fanout(traces)


class transport:
    """Tag traces opened inside the block with the entry-point transport
    ("grpc", "rest", "tpu"). Class-based: this wraps every request."""

    __slots__ = ("_name", "_token")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._token = _transport.set(self._name)
        return self

    def __exit__(self, *exc):
        _transport.reset(self._token)
        return False


class adopt:
    """Make `trace_id` the incoming trace context for the block: any
    RequestTrace opened inside joins the caller's fleet-scope trace
    instead of minting its own id. The transports enter this with the
    sanitized `x-tpu-serving-trace` metadata/header value; a None or
    invalid id makes the block a no-op (fresh ids are minted as before).
    Class-based like `transport` — wraps every request."""

    __slots__ = ("_id", "_token")

    def __init__(self, trace_id):
        self._id = valid_trace_id(trace_id) if trace_id else None

    def __enter__(self):
        self._token = _incoming_id.set(self._id) if self._id else None
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            _incoming_id.reset(self._token)
        return False


def set_status(status) -> None:
    """Record the terminal status on the current trace without raising
    through it (the router data plane aborts via grpc context.abort,
    whose control-flow exception would otherwise mis-map to INTERNAL)."""
    tr = _current.get()
    if tr is not None and hasattr(tr, "status"):
        tr.status = str(status)


class request_trace:
    """Open a RequestTrace for one handler invocation (context manager).
    Enters yielding the trace (None when tracing is disabled); always
    finishes + exports it on exit, with the ServingError code as status
    when the handler raised. A plain class, not @contextmanager — this
    wraps every request and generator machinery costs ~1us per use."""

    __slots__ = ("_trace", "_token", "_ann")

    def __init__(self, api: str, model: str = "", signature: str = ""):
        if not _enabled:
            self._trace = None
            return
        self._trace = RequestTrace(api, model=model, signature=signature,
                                   transport=_transport.get())
        self._ann = _annotation(f"serving/{api}") if _bridge else None

    def __enter__(self):
        if self._trace is None:
            return None
        self._token = _current.set(self._trace)
        if self._ann is not None:
            self._ann.__enter__()
        return self._trace

    def __exit__(self, exc_type, exc, tb):
        if self._trace is None:
            return False
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _current.reset(self._token)
        if exc is None:
            # A handler may have recorded a terminal status explicitly
            # (set_status) on a non-raising path; keep it.
            status = self._trace.status
        else:
            # The SAME mapping the transports apply to the wire
            # (error_from_exception): a raw ValueError must record as
            # INVALID_ARGUMENT here too, or the SLO tracker would bill a
            # client-fault request to the server's error budget and a
            # malformed-request spray could shed readiness. Error path
            # only — the import never taxes a healthy request.
            from min_tfs_client_tpu.utils.status import (
                error_from_exception,
            )

            status = str(error_from_exception(exc).code)
        self._trace.finish(status=status)
        return False


class span:
    """Context manager recording one named stage on the current trace.

    Deliberately slim — this sits on the hot path of every request. The
    profiler bridge (TraceAnnotation) only engages inside
    profiler_annotations(), and the active-stage registry (the sampling
    profiler's sample→stage join) only when track_stages armed it — the
    common OFF path pays one module-bool check per side.
    """

    __slots__ = ("name", "args", "_t0", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args or None

    def __enter__(self):
        self._ann = _annotation(self.name) if _bridge else None
        if self._ann is not None:
            self._ann.__enter__()
        if _stage_tracking:
            ident = threading.get_ident()
            _stage_active[ident] = (self.name, _stage_active.get(ident))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if _stage_tracking:
            ident = threading.get_ident()
            entry = _stage_active.get(ident)
            # Pop whatever is on top; well-paired spans make that this
            # span's own entry. A toggle mid-span leaves entry None (armed
            # after enter) or a stale head (disarmed then re-armed) — both
            # self-heal because track_stages(False) clears the registry.
            if entry is not None:
                if entry[1] is None:
                    _stage_active.pop(ident, None)
                else:
                    _stage_active[ident] = entry[1]
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        tr = _current.get()
        if tr is not None:
            tr.add_span(self.name, self._t0, t1, self.args)
        return False


# ---------------------------------------------------------------------------
# Active-stage registry: ident -> (stage, prev) linked stack, armed only
# while the sampling profiler (observability/profiling.py) runs. The
# sampler thread reads it to join each stack sample to the serving stage
# the sampled thread was inside at that instant.

_stage_tracking = False
# servelint: lock-ok per-key store/delete where the key is the WRITING
# thread's own ident (no other thread writes that key) — single dict ops
# are GIL-atomic, and the sampler's cross-thread reads are best-effort
# point-in-time by design (a racy read misattributes one sample at most)
_stage_active: dict = {}


def track_stages(on: bool) -> None:
    """Arm/disarm the registry. OFF (the default): span enter/exit pays
    one module-bool check and nothing else, which keeps the tracing
    overhead smoke budgets intact when no profiler is running."""
    global _stage_tracking
    _stage_tracking = bool(on)
    if not on:
        _stage_active.clear()


def stage_tracking() -> bool:
    return _stage_tracking


def active_stage(ident) -> str | None:
    """The stage the thread with `ident` is inside right now, or None."""
    entry = _stage_active.get(ident)
    return entry[0] if entry is not None else None


def active_stages() -> dict:
    """Point-in-time ident -> stage snapshot (best-effort: retries the
    GIL-atomic copy if a concurrent resize lands mid-iteration)."""
    for _ in range(4):
        try:
            items = list(_stage_active.items())
        # servelint: retry-ok not an RPC — re-reads a local dict snapshot
        # after a concurrent-resize race; no side effects to repeat
        except RuntimeError:  # pragma: no cover - concurrent resize
            continue
        return {ident: entry[0] for ident, entry in items}
    return {}  # pragma: no cover - four consecutive resize collisions


# ---------------------------------------------------------------------------
# Sink 1: metrics registry (exported off the request path by a drain
# thread; flush_metrics() gives synchronous readers read-your-writes)

_pending_lock = threading.Lock()
_pending: collections.deque = collections.deque()  # guarded_by: _pending_lock
_drain_thread: threading.Thread | None = None      # guarded_by: _pending_lock


def _start_drain_thread_locked() -> None:
    """Start (or revive, after a fork — daemon threads do not survive
    into the child) the export thread. Caller holds _pending_lock."""
    global _drain_thread
    _drain_thread = threading.Thread(
        target=_drain_loop, name="trace-metrics-export", daemon=True)
    _drain_thread.start()


def _reset_after_fork() -> None:  # pragma: no cover - exercised via fork
    """A fork can land while another thread holds _pending_lock (the
    drain thread acquires it every 0.5s); the child would inherit a
    locked mutex with no owner and hang on its first finish(). Re-init
    the lock and let the next finish() restart the drain thread."""
    global _pending_lock, _drain_thread
    _pending_lock = threading.Lock()
    # servelint: lock-ok the child is single-threaded here and the
    # pre-fork lock may be held by a thread that no longer exists
    _drain_thread = None


if hasattr(os, "register_at_fork"):  # not on every platform
    os.register_at_fork(after_in_child=_reset_after_fork)


def _drain_loop() -> None:  # pragma: no cover - exercised via flush
    # Polled, NOT signalled per trace: waking a thread per request makes
    # it contend for the GIL mid-request, which costs the hot path far
    # more than the deferred bookkeeping saves. A scrape still sees fresh
    # samples — prometheus_text flushes synchronously.
    while True:
        time.sleep(0.5)
        flush_metrics()


def flush_metrics() -> None:
    """Drain every pending trace into the metrics registry. Called by the
    drain thread, and synchronously by the Prometheus exporter so a
    scrape right after a request still sees that request's samples.
    The registry export runs OUTSIDE the lock — holding _pending_lock
    across _export_metrics would stall every finishing request behind a
    scrape. A call that found work leaves one `observe/drain` on the host
    track (`traces`, and `cpu_us`, this thread's CPU inside it): the
    burst runs under the interpreter lock beside the request path."""
    ann = open_annotation("observe/drain")
    t0, cpu0, found = time.perf_counter(), time.thread_time(), 0
    while True:
        with _pending_lock:
            try:
                trace = _pending.popleft()
            except IndexError:
                break
        _export_metrics(trace)  # swallows what the planes raise
        found += 1
    close_annotation(ann)
    if found:
        process_span("observe/drain", t0, time.perf_counter(), traces=found,
                     cpu_us=int((time.thread_time() - cpu0) * 1e6))


def _export_metrics(trace: RequestTrace) -> None:
    try:
        # SLO windows ingest every finished trace here, on the drain
        # thread — the request path records spans and nothing else.
        from min_tfs_client_tpu.observability import slo

        slo.observe_trace(trace)
    except Exception:  # pragma: no cover - SLO must not break serving
        pass
    try:
        # Cost attribution ingests here too — same off-the-hot-path
        # discipline: the request path records spans/cost events, the
        # drain thread folds them into vectors, aggregates, and the
        # (sampled) JSONL wide-event log.
        from min_tfs_client_tpu.observability import costs

        costs.observe_trace(trace)
    except Exception:  # pragma: no cover - costs must not break serving
        pass
    try:
        # The watchdog only refreshes its recent-trace joins here (O(1)
        # dict writes) — detector evaluation stays on its own ticker.
        from min_tfs_client_tpu.observability import watchdog

        watchdog.observe_trace(trace)
    except Exception:  # pragma: no cover - watchdog must not break serving
        pass
    try:
        from min_tfs_client_tpu.server import metrics

        stages = trace.stage_durations()
        if stages:
            metrics.stage_latency.observe_many(
                {(stage,): dur * 1e6 for stage, dur in stages.items()})
        meta = trace.meta
        batch = meta.get("batch_size")
        bucket = meta.get("padding_bucket")
        # Occupancy/waste for requests that rode a batching queue are
        # recorded ONCE per formed batch by the scheduler (session.py);
        # exporting them again per rider would overcount the shared batch
        # N+1 times. Traces export them only for queue-less direct
        # execution, labeled by model (the "queue" of size 1).
        if batch and bucket and "queue" not in meta:
            label = trace.model or "unknown"
            metrics.safe_set(metrics.batch_occupancy,
                             float(batch) / float(bucket), label)
            waste = max(0, int(bucket) - int(batch))
            if waste:
                metrics.padding_wasted_examples.increment(label, by=waste)
            # Unbatched direct execution: the request saw no queue.
            metrics.safe_set(metrics.batch_queue_depth, 0.0, label)
    except Exception:  # pragma: no cover - metrics must not break serving
        pass


# ---------------------------------------------------------------------------
# Sink 2: bounded ring of recent traces + Chrome-trace rendering


class _Ring:
    def __init__(self, capacity: int):
        self._lock = threading.Lock()
        self._traces: collections.deque = collections.deque(
            maxlen=capacity)                       # guarded_by: self._lock

    def record(self, trace: RequestTrace) -> None:
        with self._lock:
            self._traces.append(trace)

    def snapshot(self, limit: int | None = None) -> list[RequestTrace]:
        with self._lock:
            traces = list(self._traces)
        return traces[-limit:] if limit else traces

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


def _ring_capacity() -> int:
    """TPU_SERVING_TRACE_RING, defaulting (not crashing the server at
    import) on malformed values; floor of 1."""
    try:
        return max(1, int(os.environ.get("TPU_SERVING_TRACE_RING", "256")))
    except ValueError:
        return 256


_ring = _Ring(_ring_capacity())


def configure_ring(capacity: int) -> None:
    """Resize the trace ring (the --trace_ring_size flag on server and
    router). Boot-time configuration: the ring is replaced, so traces
    recorded before the call are dropped. <= 0 keeps the env/default."""
    global _ring
    if capacity and int(capacity) > 0:
        _ring = _Ring(max(1, int(capacity)))


def ring_capacity() -> int:
    # servelint: lock-ok maxlen is set once at construction; the global
    # rebind in configure_ring is an atomic reference swap
    return _ring._traces.maxlen


def ring_snapshot(limit: int | None = None) -> list[RequestTrace]:
    return _ring.snapshot(limit)


def ring_clear() -> None:
    _ring.clear()


def find_traces(trace_id: str) -> list[RequestTrace]:
    """Every ring entry belonging to one fleet-scope trace id (a routed
    request yields one per process; within a process usually one)."""
    return [tr for tr in _ring.snapshot() if tr.trace_id == trace_id]


def _us(t: float) -> float:
    return round((t - _EPOCH) * 1e6, 3)


def chrome_trace(traces=None, limit: int | None = None, *, pid: int = 1,
                 process_name: str | None = None,
                 clock: str = "process", process_spans=()) -> dict:
    """Recent traces as a Chrome-trace (chrome://tracing / Perfetto
    "trace event") JSON object: one pid for the server, one tid per
    request, complete ("X") events for the request envelope and every
    stage span, plus thread_name metadata so the timeline is labelled.
    `process_spans` (what `process_snapshot` gives) go onto the reserved
    `tid` PROCESS_TID as events of category "process", on the process's
    own clock only: a reader that groups events by `tid` finds no
    request there.

    `pid`/`process_name` label the process lane (the fleet stitcher
    renders router and each backend as separate lanes); clock="wall"
    emits ts as wall-clock microseconds since the unix epoch — the only
    time base comparable ACROSS processes — instead of the process-local
    perf_counter epoch."""
    if traces is None:
        traces = _ring.snapshot(limit)
    events = []
    if process_name:
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": process_name}})
    for tr in traces:
        end = tr.end if tr.end is not None else tr.start
        if clock == "wall":
            def ts(t, _tr=tr):
                return round((_tr.wall_start + (t - _tr.start)) * 1e6, 3)
        else:
            ts = _us
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tr.id,
            "args": {"name": f"{tr.api} {tr.model} #{tr.id}".strip()},
        })
        args = dict(tr.meta)
        args.update(model=tr.model, signature=tr.signature,
                    transport=tr.transport, status=tr.status,
                    trace_id=tr.trace_id)
        events.append({
            "name": f"request/{tr.api}", "cat": "request", "ph": "X",
            "pid": pid, "tid": tr.id, "ts": ts(tr.start),
            "dur": round(max(0.0, end - tr.start) * 1e6, 3), "args": args,
        })
        for name, t0, t1, sargs in list(tr.spans):
            events.append({
                "name": name, "cat": "stage", "ph": "X", "pid": pid,
                "tid": tr.id, "ts": ts(t0),
                "dur": round(max(0.0, t1 - t0) * 1e6, 3),
                "args": dict(sargs or {}),
            })
    if process_spans and clock != "wall":
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": PROCESS_TID, "args": {"name": "host track"}})
        events.extend({
            "name": name, "cat": "process", "ph": "X", "pid": pid,
            "tid": PROCESS_TID, "ts": _us(t0),
            "dur": round(max(0.0, t1 - t0) * 1e6, 3),
            "args": dict(sargs or {}),
        } for name, t0, t1, sargs in process_spans)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"source": "min_tfs_client_tpu /monitoring/traces"}}


def stage_breakdown(traces=None) -> dict[str, dict]:
    """Aggregate per-stage p50/p99 (ms) over `traces` (default: the ring).
    The bench's --breakdown table and the debug endpoint's summary."""
    if traces is None:
        traces = _ring.snapshot()
    by_stage: dict[str, list[float]] = {}
    for tr in traces:
        for stage, dur in tr.stage_durations().items():
            by_stage.setdefault(stage, []).append(dur * 1e3)
    out: dict[str, dict] = {}
    for stage, xs in sorted(by_stage.items()):
        xs.sort()
        out[stage] = {
            "p50_ms": round(xs[len(xs) // 2], 4),
            "p99_ms": round(xs[min(len(xs) - 1, int(len(xs) * 0.99))], 4),
            "n": len(xs),
        }
    return out
