"""Per-request cost attribution: "what did THIS request cost" answered
live — the `/monitoring/costs` payload and the `servecost` JSONL log.

The tracing spine records per-stage spans but stops at latency; the
learned cost model (ROADMAP item 4, arXiv:2008.01040) and multi-tenant
quotas (item 6) both need the DERIVED layer: each request's amortized
share of the merged batch's device time, the padding it wasted, the
compile it triggered, the bytes it moved, the KV pages its session
held. Three pieces:

 * `vector_from_trace` folds one finished RequestTrace into a cost
   vector. Attribution rules (docs/OBSERVABILITY.md "Cost attribution"):

     - device_execute_us: the merged batch's execute wall split across
       riders by their share of REAL examples
       (wall * own/total) — per-rider shares sum EXACTLY to the
       measured batch wall, the conservation law the unit suite
       asserts. Direct (unbatched) execution bills the request's own
       device/execute span.
     - padding_waste_us: the slice of that share burned on padding
       rows (share * (bucket - total)/bucket) — already included in
       device_execute_us, broken out for visibility, never
       double-counted.
     - queue_wait_us: batching queue + in-flight-window slot waits.
     - host_island_us: partition pre/post + pipeline host stages (the
       islands ROADMAP item 5 wants compiled away).
     - compile_us / transfer_bytes / kv_page_ticks: accumulated cost
       EVENTS (`tracing.add_cost`) — the runtime ledger attributes a
       jit-cache miss to the triggering request (a batch fanout splits
       it across riders), the transfer paths attribute link bytes, and
       the decode pools attribute pages-held-per-tick to the stepping
       session.

 * `CostTracker`: rolling per-(model, signature) windows of vector
   sums (the slo.py slice discipline — record touches one slice,
   queries merge), served at `/monitoring/costs` on BOTH REST backends
   and exported as `tpu_serving_cost_*` gauges at scrape time.

 * `CostLog`: a schema-versioned JSONL wide-event log
   (`--cost_log_dir`, `--cost_log_sample`), one record per sampled
   request, every record carrying `trace_id` so cost records JOIN
   stitched traces and flight-recorder digests. Sampling is
   DETERMINISTIC in the trace id (crc32 threshold), so every process
   that saw a trace makes the same keep/drop decision and a joined
   fleet log stays joinable. Size-bounded: past `max_log_bytes` the
   writer stops and counts drops — a long soak can never fill the
   disk.

Everything here runs on the tracing drain thread (`observe_trace`) or
at scrape time — the request path pays only the spans and cost events
it already records. Synchronous readers call `tracing.flush_metrics()`
first for read-your-writes (the /monitoring/costs route does).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import zlib

# Wide-event schema tag: every JSONL record and the /monitoring/costs
# payload carry it; `servecost` refuses to aggregate records from a
# schema it does not understand.
SCHEMA = "servecost/1"

# Vector fields aggregated per (model, signature). Means answer "what
# does one request of this shape cost"; totals answer "where did the
# window's device time / bytes actually go".
VECTOR_FIELDS = (
    "queue_wait_us",
    "device_execute_us",
    "padding_waste_us",
    "host_island_us",
    "decode_tick_us",
    "compile_us",
    "transfer_bytes",
    "kv_page_ticks",
    "total_us",
)

_QUEUE_STAGES = ("batching/queue_wait", "batching/in_flight_wait")
_HOST_ISLAND_STAGES = ("partition/pre", "partition/post", "pipeline/host")
_DECODE_STAGES = ("decode/prefill_chunk", "decode/tick", "decode/fetch")

# Hard cap on tracked (model, signature) keys — model names arrive from
# the wire (slo.py's cardinality argument); beyond it new keys drop and
# are counted.
_MAX_TRACKED_KEYS = 512


def vector_from_trace(trace) -> dict:
    """One finished RequestTrace -> its cost vector (plain floats)."""
    stages = trace.stage_durations()
    meta = trace.meta
    events = trace.costs or {}
    queue_wait_s = sum(stages.get(s, 0.0) for s in _QUEUE_STAGES)
    host_island_s = sum(stages.get(s, 0.0) for s in _HOST_ISLAND_STAGES)
    # A pooled decode round runs on the pool's loop thread, ahead of the
    # requests; the request that collects one of its tokens carries the
    # round's spans. It is billed the part of them inside its own
    # envelope (the device time it waited through), so that no vector
    # attributes more than its request's wall.
    end = trace.end if trace.end is not None else float("inf")
    decode_s = sum(max(0.0, min(t1, end) - max(t0, trace.start))
                   for name, t0, t1, _ in list(trace.spans)
                   if name in _DECODE_STAGES)

    total = meta.get("batch_size")
    bucket = meta.get("padding_bucket")
    own = meta.get("request_examples", total)
    # The merged batch's device wall: the synchronous execute span, or
    # dispatch + materialize on the pipelined (windowed) path.
    batch_wall_s = stages.get("batching/execute", 0.0) or (
        stages.get("batching/dispatch", 0.0)
        + stages.get("batching/materialize", 0.0))
    if batch_wall_s and total and own:
        # Amortized share: this rider's fraction of REAL examples. The
        # shares over a batch sum to the measured wall exactly (the
        # conservation law tests/unit/test_costs.py asserts).
        device_us = batch_wall_s * 1e6 * float(own) / float(total)
    else:
        # Direct execution (no batching queue): the request's own
        # device time.
        device_us = stages.get("device/execute", 0.0) * 1e6
    padding_us = 0.0
    if bucket and total and bucket > total:
        padding_us = device_us * (float(bucket) - float(total)) \
            / float(bucket)
    return {
        "queue_wait_us": round(queue_wait_s * 1e6, 3),
        "device_execute_us": round(device_us, 3),
        "padding_waste_us": round(padding_us, 3),
        "host_island_us": round(host_island_s * 1e6, 3),
        "decode_tick_us": round(decode_s * 1e6, 3),
        "compile_us": round(float(events.get("compile_us", 0.0)), 3),
        "transfer_bytes": float(events.get("transfer_bytes", 0.0)),
        "kv_page_ticks": float(events.get("kv_page_ticks", 0.0)),
        "total_us": round(trace.duration_s() * 1e6, 3),
    }


class _SumWindow:
    """Rolling window of vector SUMS for one (model, signature) key —
    the slo.py slice discipline (record touches the current slice,
    rotation zeroes the oldest in place). All methods run with the
    tracker lock held."""

    __slots__ = ("slices", "counts", "slice_s", "current",
                 "current_start")

    def __init__(self, window_s: float, num_slices: int = 6):
        self.slices = [collections.defaultdict(float)
                       for _ in range(num_slices)]
        self.counts = [0] * num_slices
        self.slice_s = max(0.5, window_s / num_slices)
        self.current = 0
        self.current_start = time.monotonic()

    def _advance(self, now: float) -> None:
        steps = int((now - self.current_start) / self.slice_s)
        if steps <= 0:
            return
        for _ in range(min(steps, len(self.slices))):
            self.current = (self.current + 1) % len(self.slices)
            self.slices[self.current].clear()
            self.counts[self.current] = 0
        self.current_start += steps * self.slice_s

    def record(self, now: float, vector: dict) -> None:
        self._advance(now)
        sl = self.slices[self.current]
        for field in VECTOR_FIELDS:
            sl[field] += vector.get(field, 0.0)
        self.counts[self.current] += 1

    def merged(self, now: float) -> tuple[dict, int]:
        self._advance(now)
        sums: dict[str, float] = {f: 0.0 for f in VECTOR_FIELDS}
        count = 0
        for sl, n in zip(self.slices, self.counts):
            for field, value in sl.items():
                sums[field] += value
            count += n
        return sums, count


class CostLog:
    """The schema-versioned JSONL wide-event writer. One file per
    process under `dir`; the first write emits a `meta` record carrying
    the knob context, then one `cost` record per sampled request. All
    calls run on the tracing drain thread; the lock only fences
    concurrent configure()/stats() readers."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dir: str | None = None          # guarded_by: self._lock
        self._sample = 1.0                    # guarded_by: self._lock
        self._context: dict = {}              # guarded_by: self._lock
        self._max_bytes = 256 * 1024 * 1024   # guarded_by: self._lock
        self._file = None                     # guarded_by: self._lock
        self._bytes = 0                       # guarded_by: self._lock
        self._written = 0                     # guarded_by: self._lock
        self._sampled_out = 0                 # guarded_by: self._lock
        self._dropped = 0                     # guarded_by: self._lock

    def configure(self, log_dir=None, sample=None, context=None,
                  max_bytes=None) -> None:
        with self._lock:
            if log_dir is not None:
                if self._file is not None:
                    try:
                        self._file.close()
                    except OSError:  # pragma: no cover - teardown
                        pass
                    self._file = None
                self._dir = log_dir or None
                self._bytes = 0
                self._written = 0
                self._sampled_out = 0
                self._dropped = 0
            if sample is not None:
                self._sample = max(0.0, min(1.0, float(sample)))
            if context is not None:
                self._context = dict(context)
            if max_bytes is not None:
                self._max_bytes = int(max_bytes)

    def _sampled(self, trace_id: str) -> bool:  # servelint: holds self._lock
        """Deterministic in the trace id: every process that saw this
        trace makes the SAME keep/drop decision, so a fleet's logs join
        on trace_id at any sample rate."""
        if self._sample >= 1.0:
            return True
        if self._sample <= 0.0:
            return False
        h = zlib.crc32(trace_id.encode("utf-8", "replace")) & 0xFFFFFFFF
        return h / 2.0 ** 32 < self._sample

    def write(self, record: dict) -> str:
        """Append one cost record; returns the outcome
        (logged | sampled_out | dropped | disabled)."""
        with self._lock:
            if self._dir is None:
                return "disabled"
            if not self._sampled(record.get("trace_id", "")):
                self._sampled_out += 1
                outcome = "sampled_out"
            elif self._bytes >= self._max_bytes:
                # Size bound: a soak must not fill the disk. Drops are
                # counted, never silent.
                self._dropped += 1
                outcome = "dropped"
            else:
                try:
                    if self._file is None:
                        os.makedirs(self._dir, exist_ok=True)
                        path = os.path.join(
                            self._dir, f"costs-{os.getpid()}.jsonl")
                        self._file = open(path, "a", encoding="utf-8")
                        header = json.dumps({
                            "schema": SCHEMA, "kind": "meta",
                            "t": round(time.time(), 6),
                            "pid": os.getpid(),
                            "context": self._context,
                        }, sort_keys=True)
                        self._file.write(header + "\n")
                        self._bytes += len(header) + 1
                    line = json.dumps(record, sort_keys=True)
                    self._file.write(line + "\n")
                    self._file.flush()
                    self._bytes += len(line) + 1
                    self._written += 1
                    outcome = "logged"
                except OSError:
                    self._dropped += 1
                    outcome = "dropped"
        try:
            from min_tfs_client_tpu.server import metrics

            metrics.cost_log_records.increment(outcome)
        except Exception:  # pragma: no cover - metrics must not break
            pass
        return outcome

    def stats(self) -> dict:
        with self._lock:
            return {
                "dir": self._dir,
                "sample": self._sample,
                "max_bytes": self._max_bytes,
                "bytes": self._bytes,
                "records_written": self._written,
                "sampled_out": self._sampled_out,
                "dropped": self._dropped,
            }

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:  # pragma: no cover - teardown
                    pass
                self._file = None


class CostTracker:
    """Per-(model, signature) registry of rolling cost windows plus the
    wide-event log. record() runs on the tracing drain thread;
    snapshot()/export_gauges() on monitoring readers — one uncontended
    lock covers the windows (the log has its own)."""

    def __init__(self, window_s: float = 60.0):
        self._lock = threading.Lock()
        self._window_s = window_s                # guarded_by: self._lock
        self._context: dict = {}                 # guarded_by: self._lock
        # (model, signature) -> _SumWindow
        self._windows: dict = {}                 # guarded_by: self._lock
        self._dropped_keys = 0                   # guarded_by: self._lock
        self.log = CostLog()

    def configure(self, window_s=None, log_dir=None, sample=None,
                  context=None, max_log_bytes=None) -> None:
        with self._lock:
            if window_s is not None:
                self._window_s = float(window_s)
                self._windows.clear()
                self._dropped_keys = 0
            if context is not None:
                self._context = dict(context)
        self.log.configure(log_dir=log_dir, sample=sample,
                           context=context, max_bytes=max_log_bytes)

    def record(self, model: str, signature: str, vector: dict) -> None:
        key = (model, signature)
        with self._lock:
            window = self._windows.get(key)
            if window is None:
                if len(self._windows) >= _MAX_TRACKED_KEYS:
                    self._dropped_keys += 1
                    return
                window = self._windows[key] = _SumWindow(self._window_s)
            window.record(time.monotonic(), vector)

    def snapshot(self) -> dict:
        """The /monitoring/costs payload: one entry per (model,
        signature) with window count, per-request means, and window
        totals, plus the tick duty-cycle registry and log stats."""
        now = time.monotonic()
        with self._lock:
            window_s = self._window_s
            context = dict(self._context)
            dropped = self._dropped_keys
            keyed = [(key, window.merged(now))
                     for key, window in sorted(self._windows.items())]
        entries = []
        for (model, signature), (sums, count) in keyed:
            entry = {"model": model, "signature": signature,
                     "count": count}
            if count:
                entry["mean"] = {f: round(sums[f] / count, 3)
                                 for f in VECTOR_FIELDS}
                entry["total"] = {f: round(sums[f], 3)
                                  for f in VECTOR_FIELDS}
            entries.append(entry)
        return {
            "schema": SCHEMA,
            "window_s": window_s,
            "context": context,
            "dropped_keys": dropped,
            "entries": entries,
            "tick_utilization": tick_utilization(),
            "log": self.log.stats(),
        }

    def export_gauges(self) -> None:
        """Mirror the window means into `tpu_serving_cost_*` gauges and
        the duty-cycle registry into `tpu_serving_tick_utilization` —
        called by the Prometheus exporter right before serialization
        (the slo.export_gauges discipline). Emptied windows export
        zeros: a cost gauge must clear when traffic stops, not freeze."""
        snap = self.snapshot()
        try:
            from min_tfs_client_tpu.server import metrics

            for entry in snap["entries"]:
                labels = (entry["model"], entry["signature"])
                mean = entry.get("mean", {})
                metrics.safe_set(metrics.cost_device_execute_us,
                                 mean.get("device_execute_us", 0.0),
                                 *labels)
                metrics.safe_set(metrics.cost_queue_wait_us,
                                 mean.get("queue_wait_us", 0.0), *labels)
                metrics.safe_set(metrics.cost_padding_waste_us,
                                 mean.get("padding_waste_us", 0.0),
                                 *labels)
                metrics.safe_set(metrics.cost_host_island_us,
                                 mean.get("host_island_us", 0.0), *labels)
                metrics.safe_set(metrics.cost_kv_page_ticks,
                                 mean.get("kv_page_ticks", 0.0), *labels)
            for label, value in snap["tick_utilization"].items():
                metrics.safe_set(metrics.tick_utilization, value, label)
        except Exception:  # pragma: no cover - metrics must not break
            pass

    def reset(self) -> None:
        with self._lock:
            self._windows.clear()
            self._dropped_keys = 0


tracker = CostTracker()


def configure(window_s=None, log_dir=None, sample=None, context=None,
              max_log_bytes=None) -> None:
    tracker.configure(window_s=window_s, log_dir=log_dir, sample=sample,
                      context=context, max_log_bytes=max_log_bytes)


def observe_trace(trace) -> None:
    """Feed one finished RequestTrace into the cost plane. Runs on the
    tracing drain thread (observability/tracing.py _export_metrics).
    Router-process traces (api "route/...") are skipped — the router's
    cost surface is the fleet view, not its own forwarding spans."""
    api = getattr(trace, "api", "")
    if api.startswith("route/"):
        return
    vector = vector_from_trace(trace)
    model = trace.model or "unknown"
    signature = trace.signature or ""
    tracker.record(model, signature, vector)
    record = {
        "schema": SCHEMA, "kind": "cost",
        "t": round(getattr(trace, "wall_start", time.time()), 6),
        "trace_id": trace.trace_id,
        "model": model, "signature": signature, "api": api,
        "transport": trace.transport, "status": trace.status,
    }
    record.update(vector)
    session = trace.meta.get("session_id")
    if session is not None:
        record["session_id"] = session
    tracker.log.write(record)


def snapshot() -> dict:
    return tracker.snapshot()


def export_gauges() -> None:
    tracker.export_gauges()


def reset() -> None:
    tracker.reset()


# -- tick-loop duty cycle -----------------------------------------------------
#
# The decode pools report each tick's busy interval here (one call per
# device round, off the per-token hot path by construction — the tick
# already amortizes K sessions). Utilization over the rolling window is
# the device-idle signal the cost model needs for decode legs: a pool
# at 0.3 utilization has head-room the autotuner can spend on bigger
# join windows; a pool at ~1.0 is device-bound.

_TICK_WINDOW_S = 30.0
_TICK_MAX_NOTES = 4096

_tick_lock = threading.Lock()
# label -> deque[(end_monotonic, busy_s)]
_ticks: dict = {}                                # guarded_by: _tick_lock
_tick_started: dict = {}                         # guarded_by: _tick_lock


def note_tick(label: str, busy_s: float) -> None:
    """Record one tick-loop device round for `label` (the pool's
    metric label). Bounded: per-label notes are a ring and entries
    older than the window are pruned on append."""
    now = time.monotonic()
    with _tick_lock:
        ring = _ticks.get(label)
        if ring is None:
            ring = _ticks[label] = collections.deque(
                maxlen=_TICK_MAX_NOTES)
            _tick_started[label] = now
        ring.append((now, float(busy_s)))
        while ring and now - ring[0][0] > _TICK_WINDOW_S:
            ring.popleft()


def tick_utilization() -> dict:
    """label -> busy fraction of the rolling window (the
    `tpu_serving_tick_utilization` gauge). The denominator is the
    elapsed window (or the pool's age while younger than one window),
    so a freshly-booted pool reads its true duty cycle, not a
    near-zero artifact."""
    now = time.monotonic()
    out: dict[str, float] = {}
    with _tick_lock:
        for label, ring in _ticks.items():
            busy = sum(b for t, b in ring
                       if now - t <= _TICK_WINDOW_S)
            span = min(_TICK_WINDOW_S,
                       max(1e-6, now - _tick_started[label]))
            out[label] = round(min(1.0, busy / span), 4)
    return out


def reset_ticks() -> None:
    with _tick_lock:
        _ticks.clear()
        _tick_started.clear()
