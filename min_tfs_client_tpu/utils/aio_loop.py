"""The process's ONE asyncio event loop for gRPC, and its health.

gRPC's completion queue takes one asyncio loop a process: a second loop
in one process races the C core's PollerCompletionQueue and dies with
BlockingIOError deep inside the cython layer, long after construction
and only under load. So the loop has one owner, this module: a daemon
thread started on first use, on which every `grpc.aio` server of the
process runs (each ModelServer's front end, server/server.py, and a
router's data plane, router/aio_proxy.py) — any number of them, side by
side. `submit(coro)` is the way in from another thread.

What runs ON the loop thread serves every request of the process, so it
must never block: what has to wait there awaits (a decode step awaits
the tick loop's round, `TickBatcher.astep`), and what cannot await runs
on a worker pool. `on_loop_thread()` says where a piece of code is.

The loop's health is first-class telemetry (a wedged loop is this
plane's analogue of a saturated thread pool): a ticker sleeps a fixed
interval and measures the overshoot, which is the scheduling delay
every request on the loop pays too. It feeds the
`grpc_event_loop_lag_ms` gauge, the `grpc` block of
`/monitoring/runtime` (`stats()`; with the front end's own counts of
requests answered on the loop and on the pool) and, over
`LAG_WARN_MS`, a flight-recorder event. The same tick reads the loop
THREAD's CPU clock: the one thread that serves every request is a
station, and its CPU a request is what the station costs. Each tick is
a `loop/sample` on the tracing spine's host track (`lag_us`, `cpu_us`),
and `event_loop_cpu_share` in `stats()` is the thread's CPU over the
window's wall time.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import os
import threading
import time

THREAD_NAME = "grpc-aio-loop"

# The ticker sleeps this long and measures the overshoot: ~10 wakeups a
# second of pure asyncio bookkeeping, and it catches any stall long
# enough to matter against a millisecond-scale request.
LAG_TICK_S = 0.1
LAG_WARN_MS = 100.0
# The percentiles of `stats()` are over the newest samples: the last
# minute.
LAG_WINDOW = 600


_lock = threading.Lock()
_loop = None    # guarded_by: _lock
_pid = None     # guarded_by: _lock
# Written once by the loop thread, before anything can run on it; the
# read in `on_loop_thread` is one atomic load on a hot path.
_ident = None

_stats_lock = threading.Lock()
_requests = {"inline": 0, "pooled": 0}           # guarded_by: _stats_lock
_lag = {"last": 0.0, "max": 0.0, "samples": 0, "over": 0,
        "recent": collections.deque(maxlen=LAG_WINDOW),
        # (wall s, the loop thread's CPU s) of the same samples
        "cpu": collections.deque(maxlen=LAG_WINDOW)}  # guarded_by: _stats_lock


def get() -> asyncio.AbstractEventLoop:
    """The loop, started on first use (and anew in a forked child, which
    inherits the parent's module state but not its thread)."""
    global _loop, _pid
    with _lock:
        if _loop is None or _pid != os.getpid():
            loop = asyncio.new_event_loop()
            ready = threading.Event()
            threading.Thread(target=_run, args=(loop, ready),
                             name=THREAD_NAME, daemon=True).start()
            # Timed + loop-on-predicate (servelint DL003).
            while not ready.wait(timeout=1.0):
                pass
            _loop, _pid = loop, os.getpid()
        return _loop


def submit(coro) -> concurrent.futures.Future:
    """Run `coro` on the loop, from any other thread."""
    return asyncio.run_coroutine_threadsafe(coro, get())


def on_loop_thread() -> bool:
    return threading.get_ident() == _ident


def _run(loop: asyncio.AbstractEventLoop, ready: threading.Event) -> None:
    global _ident
    asyncio.set_event_loop(loop)
    # servelint: thread-ok written once, before `ready` lets anything
    # onto the loop; `on_loop_thread` is one atomic load
    _ident = threading.get_ident()
    loop.create_task(_lag_ticker())
    ready.set()
    loop.run_forever()


async def _lag_ticker() -> None:
    from min_tfs_client_tpu.observability import tracing
    from min_tfs_client_tpu.server import metrics

    t1, cpu1 = time.perf_counter(), time.thread_time()
    while True:
        # A sample runs from the end of the one before, so the samples
        # tile the loop thread's time and their CPU adds up to its CPU;
        # the overshoot is the sleep's own.
        began, cpu0 = t1, cpu1
        t0 = time.perf_counter()
        await asyncio.sleep(LAG_TICK_S)
        t1, cpu1 = time.perf_counter(), time.thread_time()
        lag_ms = max(0.0, (t1 - t0 - LAG_TICK_S) * 1e3)
        over = lag_ms >= LAG_WARN_MS
        tracing.process_span("loop/sample", began, t1,
                             lag_us=int(lag_ms * 1e3),
                             cpu_us=int((cpu1 - cpu0) * 1e6))
        with _stats_lock:
            _lag["last"] = lag_ms
            _lag["max"] = max(_lag["max"], lag_ms)
            _lag["samples"] += 1
            _lag["over"] += over
            _lag["recent"].append(lag_ms)
            _lag["cpu"].append((t1 - began, cpu1 - cpu0))
        metrics.safe_set(metrics.grpc_event_loop_lag_ms, lag_ms)
        if over:
            # A stalled loop delays every request of the process: put it
            # in the black box next to them.
            try:
                from min_tfs_client_tpu.observability import flight_recorder

                flight_recorder.record("event_loop_lag", loop=THREAD_NAME,
                                       lag_ms=round(lag_ms, 3),
                                       warn_ms=LAG_WARN_MS)
            except Exception:  # servelint: fallback-ok the recorder must
                pass           # not take down the ticker


def note_request(inline: bool) -> None:
    """One gRPC request of a ModelServer went this way: answered on the
    loop thread, or on the worker pool."""
    with _stats_lock:
        _requests["inline" if inline else "pooled"] += 1


def stats() -> dict:
    """The `grpc` block of `/monitoring/runtime`."""
    with _stats_lock:
        out = {"grpc_requests_inline": _requests["inline"],
               "grpc_requests_pooled": _requests["pooled"]}
        recent = sorted(_lag["recent"])
        if recent:
            out.update(
                event_loop_lag_ms=round(_lag["last"], 3),
                event_loop_lag_p50_ms=round(recent[len(recent) // 2], 3),
                event_loop_lag_p99_ms=round(
                    recent[min(len(recent) - 1,
                               int(0.99 * len(recent)))], 3),
                event_loop_lag_max_ms=round(_lag["max"], 3),
                lag_samples=_lag["samples"],
                lag_over_threshold=_lag["over"],
                event_loop_cpu_share=round(
                    sum(cpu for _, cpu in _lag["cpu"])
                    / sum(wall for wall, _ in _lag["cpu"]), 4))
    return out
