"""Runtime telemetry: compile-event ledger, per-device HBM accounting,
and host<->device transfer counters — the `/monitoring/runtime` payload.

Full-program TPU serving makes compilation a FIRST-CLASS operational
event (arXiv:1810.09868): every new (batch bucket x seq bucket) shape
compiles a fresh executable whose wall time is user-visible latency on
whichever request triggered it, and whose HBM residency is permanent
until unload. The ledger makes that visible:

 * `record_compile(label, shape_bucket, wall_s, executables)` appends to
   a bounded ring + per-servable executable counts, increments the
   `:tpu/serving/compilation_count` counter, and ring-records a flight-
   recorder event. Callers detect misses cheaply: `jax.jit` callables
   expose `_cache_size()` (~0.04us), so the hot path pays two C-level
   calls per execution and builds the shape string only on an actual
   miss (servables/servable.py `_execute`, `run_union`;
   `instrument_jit` wraps the models/ decode jits the same way).
 * `device_memory()` reads PJRT `memory_stats()` per device (HBM in
   use / limit / peak) and falls back to the resource tracker's
   reservation ledger where the backend has no stats (CPU test meshes).
 * transfer counters: `count_transfer(direction, nbytes)` feeds the
   `:tpu/serving/transfer_bytes` counter from the explicit device_put /
   fetch paths, so link pressure is a scrapeable number.
"""

from __future__ import annotations

import collections
import gc
import threading
import time
import weakref
from typing import Mapping

from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.utils import aio_loop

_LEDGER_CAPACITY = 256

_lock = threading.Lock()
_events: collections.deque = collections.deque(
    maxlen=_LEDGER_CAPACITY)                       # guarded_by: _lock
_executables: dict[str, int] = {}                  # guarded_by: _lock
_tracker_ref = None  # weakref to the serving ResourceTracker, or None


def record_compile(label: str, shape_bucket: str, wall_s: float,
                   executables: int | None = None) -> None:
    """One jit cache miss. `label` is "model:version:signature" (or a
    models/-level jit name); `executables` is the callable's post-miss
    cache size — per-servable counts aggregate across its signatures."""
    servable = label.rsplit(":", 1)[0] if ":" in label else label
    with _lock:
        if executables is None:
            executables = _executables.get(label, 0) + 1
        _executables[label] = int(executables)
        _events.append((time.time(), label, shape_bucket,
                        round(wall_s * 1e3, 3)))
    try:
        from min_tfs_client_tpu.server import metrics

        metrics.compilation_count.increment(servable.split(":")[0])
        metrics.compile_wall_time.observe(wall_s * 1e6, servable.split(":")[0])
    except Exception:  # pragma: no cover - metrics must not break serving
        pass
    try:
        from min_tfs_client_tpu.observability import flight_recorder

        flight_recorder.record("compile", servable=label,
                               shape_bucket=shape_bucket,
                               wall_ms=round(wall_s * 1e3, 3))
    except Exception:  # pragma: no cover
        pass
    try:
        # Cost attribution: the compile's wall time bills the request
        # that triggered the miss (a merged batch's fanout splits it
        # across the riders) — observability/costs.py folds it into
        # that request's cost vector.
        from min_tfs_client_tpu.observability import tracing

        tracing.add_cost(compile_us=wall_s * 1e6)
    except Exception:  # pragma: no cover
        pass


def compile_ledger() -> dict:
    with _lock:
        events = [
            {"wall_time": round(ts, 6), "servable": label,
             "shape_bucket": bucket, "wall_ms": wall_ms}
            for ts, label, bucket, wall_ms in _events
        ]
        executables = dict(sorted(_executables.items()))
    return {"events": events, "executables": executables,
            "total_compiles": sum(executables.values())}


def reset_compile_ledger() -> None:
    with _lock:
        _events.clear()
        _executables.clear()


def shape_bucket(arrays) -> str:
    """Canonical shape-bucket string for a dict of arrays — only built
    on a detected miss, never per call."""
    parts = []
    for alias in sorted(arrays):
        arr = arrays[alias]
        shape = "x".join(str(d) for d in getattr(arr, "shape", ()))
        dtype = getattr(getattr(arr, "dtype", None), "name", "?")
        parts.append(f"{alias}:{dtype}[{shape}]")
    return ",".join(parts)


def ledgered_call(label: str, fn, call, bucket_source):
    """THE cache-miss detector: run `call()` (which invokes the jitted
    `fn`), recording a compile event when fn's jit cache grew across
    the call. `bucket_source` is the arrays dict (or a thunk returning
    the bucket string) — only consulted on a miss. Callables without
    `_cache_size` run unobserved. Two threads racing the same first
    shape may each attribute the one compile (the executable count uses
    the absolute cache size, so totals never drift)."""
    size_fn = getattr(fn, "_cache_size", None)
    if size_fn is None:  # pragma: no cover - older jax
        return call()
    before = size_fn()
    t0 = time.perf_counter()
    out = call()
    after = size_fn()
    if after > before:
        bucket = (bucket_source() if callable(bucket_source)
                  else shape_bucket(bucket_source))
        record_compile(label, bucket, time.perf_counter() - t0, after)
    return out


def instrument_jit(label: str, fn, bucket_fn=None):
    """Wrap a jitted callable so cache misses land in the ledger
    (same detection as ledgered_call, open-coded: this wrapper sits on
    per-request / per-token paths, so the hit path must not allocate
    thunks — `size_fn` is captured ONCE at wrap time and the call is
    direct). `bucket_fn(args)` overrides the shape-bucket rendering on
    a miss (Signature._execute passes the arrays-dict renderer; the
    default summarizes the whole arg pytree). Callables without cache
    introspection are returned unwrapped."""
    size_fn = getattr(fn, "_cache_size", None)
    if size_fn is None:  # pragma: no cover - older jax
        return fn
    bucket_fn = bucket_fn or _args_bucket

    def wrapper(*args, **kwargs):
        before = size_fn()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        after = size_fn()
        if after > before:
            record_compile(label, bucket_fn(args),
                           time.perf_counter() - t0, after)
        return out

    wrapper.__wrapped__ = fn
    return wrapper


def _args_bucket(args) -> str:
    """Shape summary of a jit call's arg pytree (miss path only — the
    tree walk is too dear per call, fine per compile). Shapes are
    grouped so a 500-leaf param tree reads as a few lines."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        shapes = collections.Counter(
            (getattr(getattr(leaf, "dtype", None), "name", "?"),
             "x".join(str(d) for d in getattr(leaf, "shape", ())))
            for leaf in leaves)
        parts = [f"{dtype}[{shape}]*{count}"
                 for (dtype, shape), count in sorted(shapes.items())[:8]]
        if len(shapes) > 8:
            parts.append(f"+{len(shapes) - 8} more")
        return ";".join(parts) or "()"
    except Exception:  # pragma: no cover
        return "unknown"


# -- HBM / device accounting -------------------------------------------------


def set_resource_tracker(tracker) -> None:
    """Register the serving ResourceTracker as the fallback accountant
    (weakly — telemetry must not extend the tracker's lifetime)."""
    global _tracker_ref
    _tracker_ref = weakref.ref(tracker) if tracker is not None else None


def device_memory() -> list[dict]:
    """Per-device HBM: PJRT memory_stats where the backend provides
    them, else the resource tracker's reservation estimates."""
    devices: list[dict] = []
    try:
        import jax

        for d in jax.local_devices():
            entry: dict = {"id": d.id, "platform": str(d.platform),
                           "kind": str(getattr(d, "device_kind", ""))}
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats:
                for key in ("bytes_in_use", "bytes_limit",
                            "peak_bytes_in_use", "bytes_reserved"):
                    if key in stats:
                        entry[key] = int(stats[key])
                entry["source"] = "pjrt"
            else:
                entry["source"] = "resource_tracker"
            devices.append(entry)
    except Exception:  # pragma: no cover - no jax backend at all
        pass
    tracker = _tracker_ref() if _tracker_ref is not None else None
    if tracker is not None:
        try:
            reserved = tracker.reserved_per_device()
            pools = tracker.device_pools()
            by_id = {d["id"]: d for d in devices}
            for device_id, limit in pools.items():
                entry = by_id.get(device_id)
                if entry is None:
                    entry = {"id": device_id, "source": "resource_tracker"}
                    devices.append(entry)
                entry["tracker_reserved_bytes"] = int(
                    reserved.get(device_id, 0))
                entry["tracker_pool_bytes"] = int(limit)
        except Exception:  # pragma: no cover - telemetry is best-effort
            pass
    return devices


def live_array_stats() -> dict:
    """Count + bytes of live jax.Arrays on this host (debug-endpoint
    granularity; walking the list is too dear for a scrape loop)."""
    try:
        import jax

        arrays = jax.live_arrays()
        return {"count": len(arrays),
                "bytes": int(sum(getattr(a, "nbytes", 0) for a in arrays))}
    except Exception:  # pragma: no cover
        return {"count": None, "bytes": None}


# -- paged KV pool accounting ------------------------------------------------

_kv_pools_lock = threading.Lock()
_kv_pools: list = []  # weakrefs to live PagedSlotPools  # guarded_by: _kv_pools_lock


def register_kv_pool(pool) -> None:
    """Weakly register a PagedSlotPool for the /monitoring/runtime
    `kv_pool` payload (telemetry must not extend a pool's lifetime)."""
    with _kv_pools_lock:
        _kv_pools[:] = [r for r in _kv_pools if r() is not None]
        _kv_pools.append(weakref.ref(pool))


def kv_pool_stats() -> list[dict]:
    """Per-pool occupancy/pressure snapshot, read at scrape time (the
    pools update their gauges on allocation events; this walks the pool
    state off the hot path per the deferred-export discipline). Each
    entry is the pool's published stats() snapshot: occupancy, table
    width, phase + pressure counters, byte accounting, and the
    step-contract fields (`step_contract`, `kv_gather_bytes_per_tick`,
    `prefill_chunk_size`, `chunking_sessions`, `prefill_chunks`) — see
    docs/OBSERVABILITY.md's reading guide."""
    with _kv_pools_lock:
        pools = [r() for r in _kv_pools]
    out = []
    for pool in pools:
        if pool is None:
            continue
        try:
            entry = {"model": pool.metric_label}
            entry.update(pool.stats())
            out.append(entry)
        except Exception:  # pragma: no cover - telemetry is best-effort
            pass
    return out


# -- transfer accounting -----------------------------------------------------


def count_transfer(direction: str, nbytes: int) -> None:
    """Accumulate host<->device link traffic ("host_to_device" /
    "device_to_host"). One counter bump per transfer batch, not per
    array — callers pre-sum."""
    if nbytes <= 0:
        return
    try:
        from min_tfs_client_tpu.server import metrics

        metrics.transfer_bytes.increment(direction, by=float(nbytes))
    except Exception:  # pragma: no cover - metrics must not break serving
        pass
    try:
        # Link bytes bill the request that moved them (batch fanout
        # splits across riders; no-op off the request path).
        from min_tfs_client_tpu.observability import tracing

        tracing.add_cost(transfer_bytes=float(nbytes))
    except Exception:  # pragma: no cover - costs must not break serving
        pass


# {section: {label: {"requests": n, count: total}}}
_generation_counts: dict[str, dict[str, dict]] = {}   # guarded_by: _lock


def count_generation(section: str, label: str,
                     counts: Mapping[str, int]) -> None:
    """Accumulate what a whole generation's program counted for one
    answered request, under the signature's label, in a section of
    /monitoring/runtime (`route`, `state`, or a model's own:
    servables/decode_signatures.CountTable)."""
    with _lock:
        totals = _generation_counts.setdefault(section, {}).setdefault(
            label, {"requests": 0})
        totals["requests"] += 1
        for name, value in counts.items():
            totals[name] = totals.get(name, 0) + int(value)


def generation_totals(section: str) -> dict:
    """{label: totals} of one section; {} where nothing wrote it."""
    with _lock:
        return {label: dict(totals) for label, totals
                in _generation_counts.get(section, {}).items()}


def transfer_totals() -> dict:
    try:
        from min_tfs_client_tpu.server import metrics

        return {
            "host_to_device_bytes": int(
                metrics.transfer_bytes.value("host_to_device")),
            "device_to_host_bytes": int(
                metrics.transfer_bytes.value("device_to_host")),
        }
    except Exception:  # pragma: no cover
        return {}


# -- garbage collections ------------------------------------------------------

# A collection of the whole heap holds the interpreter lock for as long
# as it runs (130-250 ms under the sessions cell, PERF.md): every thread
# of the process stops, the chip runs dry. The collector's own callbacks
# time each one.
GC_SPAN_MIN_S = 1e-3
_gc_pause_s = [0.0, 0.0, 0.0]  # by generation; written under the interpreter
_gc_open = None                # lock, by the one collection that can run


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        _gc_open = (tracing.open_annotation("host/gc"), time.perf_counter())
    elif _gc_open is not None:
        t1 = time.perf_counter()
        (ann, t0), _gc_open = _gc_open, None
        tracing.close_annotation(ann)
        _gc_pause_s[info["generation"]] += t1 - t0
        if t1 - t0 >= GC_SPAN_MIN_S:
            tracing.process_span("host/gc", t0, t1, gen=info["generation"],
                                 collected=info["collected"])


def watch_gc() -> None:
    """Time every collection from here on (idempotent): all of them into
    `gc_pause_seconds` of `/monitoring/runtime`, by generation, and a
    pause of GC_SPAN_MIN_S or more onto the tracing spine's host track
    as `host/gc` (`gen`, `collected`). Two clock reads a collection."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_pause_seconds() -> dict:
    return {str(gen): round(s, 6) for gen, s in enumerate(_gc_pause_s)}


# -- the /monitoring/runtime payload -----------------------------------------


def snapshot(include_live_arrays: bool = False) -> dict:
    from min_tfs_client_tpu.server import profiler

    with _lock:
        sections = ("route", "state", *_generation_counts)
    payload = {
        "compile": compile_ledger(),
        "devices": device_memory(),
        "transfer": transfer_totals(),
        "profiler": profiler.status(),
        "pipeline": pipeline_stats(),
        "kv_pool": kv_pool_stats(),
        # What whole generations counted, a section a count table.
        **{section: generation_totals(section) for section in sections},
        # The gRPC front end: requests answered on the event loop and on
        # the worker pool, and the loop's sampled lag (utils/aio_loop.py).
        "grpc": aio_loop.stats(),
        # Seconds the collector has held the interpreter, by generation,
        # since `watch_gc` (the server's boot).
        "gc_pause_seconds": gc_pause_seconds(),
    }
    if include_live_arrays:
        payload["live_arrays"] = live_array_stats()
    return payload


def pipeline_stats() -> dict:
    """Per-queue in-flight execution window stats (depth, dispatched,
    overlapped, overlap ratio) — the runtime view of the pipelined
    batching path (batching/session.py _InFlightWindow)."""
    try:
        from min_tfs_client_tpu.batching.session import pipeline_snapshot

        return pipeline_snapshot()
    except Exception:  # pragma: no cover - stats must not break the payload
        return {}
