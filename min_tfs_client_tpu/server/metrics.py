"""Process-global metrics registry + Prometheus text exporter.

Parity with tensorflow/core/lib/monitoring (counter.h, gauge.h, sampler.h
exponential buckets, collection_registry.cc) and the exporter that walks the
registry into Prometheus text format (util/prometheus_exporter.cc:62-159).
Metric names keep the TF-Serving style (":tensorflow/serving/...") and are
sanitized for Prometheus exactly like the reference does (non-alphanumeric
-> '_').
"""

from __future__ import annotations

import bisect
import re
import threading
from typing import Sequence

_registry_lock = threading.Lock()
_registry: dict[str, "_Metric"] = {}         # guarded_by: _registry_lock


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str, label_names: Sequence[str],
                 extra: dict | None = None):
        self.name = name
        self.description = description
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        self._cells: dict[tuple, object] = {}    # guarded_by: self._lock
        if extra:
            # Subclass state (histogram buckets) must exist BEFORE the
            # metric publishes to the registry: with the old post-super()
            # assignment, a thread re-registering the same name could
            # alias a half-built instance and observe() into missing
            # buckets (servelint's lock audit surfaced this window).
            self.__dict__.update(extra)
        with _registry_lock:
            existing = _registry.get(name)
            if existing is not None:
                # Same-name re-creation returns the same metric (TF allows
                # only one registration; we tolerate idempotent re-use —
                # and keep the FIRST registration's state).
                self.__dict__ = existing.__dict__
                return
            _registry[name] = self


class Counter(_Metric):
    kind = "counter"

    def increment(self, *labels, by: float = 1.0) -> None:
        with self._lock:
            self._cells[labels] = self._cells.get(labels, 0.0) + by

    def value(self, *labels) -> float:
        with self._lock:
            return self._cells.get(labels, 0.0)


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, *labels) -> None:
        with self._lock:
            self._cells[labels] = value

    def value(self, *labels) -> float:
        with self._lock:
            return self._cells.get(labels, 0.0)


def exponential_buckets(scale: float, growth: float, count: int) -> list[float]:
    """Same shape as monitoring::Buckets::Exponential (sampler.h)."""
    out, value = [], scale
    for _ in range(count):
        out.append(value)
        value *= growth
    return out


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, description, label_names=(),
                 buckets: Sequence[float] | None = None):
        super().__init__(
            name, description, label_names,
            extra={"buckets":
                   list(buckets or exponential_buckets(10, 1.8, 33))})

    def observe(self, value: float, *labels) -> None:
        with self._lock:
            self._observe_locked(labels, value)

    def observe_many(self, samples: dict) -> None:
        """{label_tuple: value} under ONE lock acquisition — the per-stage
        export path records ~8 samples per request and sits on the hot
        path, so the lock round-trips matter."""
        with self._lock:
            for labels, value in samples.items():
                self._observe_locked(labels, value)

    def _observe_locked(self, labels: tuple, value: float) -> None:
        cell = self._cells.get(labels)
        if cell is None:
            cell = {"counts": [0] * (len(self.buckets) + 1),
                    "sum": 0.0, "count": 0}
            self._cells[labels] = cell
        idx = bisect.bisect_left(self.buckets, value)
        cell["counts"][idx] += 1
        cell["sum"] += value
        cell["count"] += 1


# ---------------------------------------------------------------------------
# Serving-path metrics (parity: servables/tensorflow/util.cc:36-71 +
# request latency; extended with TPU compile/padding visibility)

request_count = Counter(
    ":tensorflow/serving/request_count",
    "Number of requests, by API and status.", ("api", "status"))
request_latency = Histogram(
    ":tensorflow/serving/request_latency",
    "Request latency in microseconds, by API.", ("api",),
    buckets=exponential_buckets(10, 1.8, 33))
request_example_counts = Histogram(
    ":tensorflow/serving/request_example_counts",
    "Number of examples per request.", ("model",),
    buckets=exponential_buckets(1, 2, 20))
batch_padding_ratio = Histogram(
    ":tpu/serving/batch_padding_ratio",
    "Padded-to-real batch size ratio per executed batch.", ("model",),
    buckets=[1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0])
compilation_count = Counter(
    ":tpu/serving/compilation_count",
    "XLA compilations triggered by serving, by model.", ("model",))
model_load_latency = Histogram(
    ":tensorflow/serving/load_latency",
    "Servable load latency in microseconds.", ("model",),
    buckets=exponential_buckets(100, 2.0, 24))
batch_queue_depth = Gauge(
    ":tpu/serving/batch_queue_depth",
    "Batches in the queue (including the open tail), by queue.", ("queue",))
decode_session_count = Gauge(
    ":tpu/serving/decode_session_count",
    "Live incremental-decode sessions pinning HBM state.", ("model",))
kv_blocks_used = Gauge(
    ":tpu/serving/kv_blocks_used",
    "KV-cache pages allocated out of the paged decode pool, by model. "
    "Updated on page-allocation events (once per block_size tokens per "
    "session), never on the per-token tick.", ("model",))
kv_blocks_total = Gauge(
    ":tpu/serving/kv_blocks_total",
    "KV-cache page capacity of the paged decode pool, by model.",
    ("model",))
kv_gather_bytes_per_tick = Gauge(
    ":tpu/serving/kv_gather_bytes_per_tick",
    "KV bytes the most recent paged decode tick read: pages owned by the "
    "ticking sessions on the step-contract (direct) path, slots x table "
    "width on the dense-gather fallback. Updated once per tick under the "
    "pool lock (a dict write, no device sync).", ("model",))
kv_prefill_chunks = Counter(
    ":tpu/serving/kv_prefill_chunks",
    "Chunked-prefill rounds executed per session (one increment per "
    "session per chunk): forced decoder prefixes streaming through the "
    "paged step contract's multi-query path.", ("model",))
kv_evictions = Counter(
    ":tpu/serving/kv_evictions",
    "Paged-KV pressure events, by model and kind (swap = pages copied to "
    "host and freed; close = session dropped with RESOURCE_EXHAUSTED; "
    "restore = swapped session scattered back).", ("model", "kind"))

# -- request-tracing spine metrics (observability/tracing.py sinks) ---------
stage_latency = Histogram(
    ":tpu/serving/stage_latency",
    "Per-request stage latency in microseconds, by pipeline stage "
    "(deserialize, queue-wait, batch merge, pad, host->device, execute, "
    "device->host, serialize; see docs/OBSERVABILITY.md).", ("stage",),
    buckets=exponential_buckets(1, 1.8, 40))
batch_occupancy = Gauge(
    ":tpu/serving/batch_occupancy",
    "Real-examples / padded-bucket fraction of the most recently executed "
    "batch, by queue (or model for unbatched direct execution).", ("queue",))
padding_wasted_examples = Counter(
    ":tpu/serving/padding_wasted_examples",
    "Example-slots executed as padding (bucket size minus real examples), "
    "by queue.", ("queue",))
in_flight_batches = Gauge(
    ":tpu/serving/in_flight_batches",
    "Batches dispatched to the device whose outputs are not yet "
    "materialized (the pipelined execution window's current depth), "
    "by queue.", ("queue",))
pipeline_overlap_occupancy = Gauge(
    ":tpu/serving/pipeline_overlap_occupancy",
    "In-flight depth over the configured --max_in_flight_batches window "
    "at the most recent dispatch (1.0 = window fully used), by queue.",
    ("queue",))
partition_calibration_failures = Counter(
    ":tpu/serving/partition_calibration_failures",
    "Batch-1 calibration probes that failed; the dim-match heuristic "
    "stays in effect for the affected signature.", ("model",))

# -- health-plane metrics (observability/slo.py, health.py, runtime.py) ------
server_ready = Gauge(
    ":tpu/serving/ready",
    "Readiness verdict (1 = every configured model AVAILABLE and SLO "
    "burn below the shedding threshold) — the one signal load "
    "balancers and the adaptive scheduler consume.", ())
slo_latency_ms = Gauge(
    ":tpu/serving/slo_latency_ms",
    "Rolling-window latency quantile estimate in milliseconds, by "
    "model, signature, API, and quantile (log-histogram estimate, "
    "docs/OBSERVABILITY.md).", ("model", "signature", "api", "quantile"))
slo_error_ratio = Gauge(
    ":tpu/serving/slo_error_ratio",
    "Rolling-window server-fault error fraction, by model, signature, "
    "and API.", ("model", "signature", "api"))
slo_burn_rate = Gauge(
    ":tpu/serving/slo_burn_rate",
    "Observed burn over allowed burn for the window (1.0 = consuming "
    "exactly the budget), by model, signature, API, and kind "
    "(error|latency).", ("model", "signature", "api", "kind"))
compile_wall_time = Histogram(
    ":tpu/serving/compile_wall_time",
    "Wall time of one XLA compilation (jit cache miss) in "
    "microseconds, by model.", ("model",),
    buckets=exponential_buckets(1000, 2.0, 24))
transfer_bytes = Counter(
    ":tpu/serving/transfer_bytes",
    "Host<->device link traffic from the explicit transfer paths "
    "(device_put placement, overlapped output fetch), by direction.",
    ("direction",))
request_log_count = Counter(
    ":tensorflow/serving/request_log_count",
    "Request-log sampling outcomes, by model and outcome "
    "(logged | sampled_out | dropped).", ("model", "outcome"))

# -- cost-attribution metrics (observability/costs.py) -----------------------
cost_device_execute_us = Gauge(
    ":tpu/serving/cost_device_execute_us",
    "Rolling-window mean amortized device-execute share per request in "
    "microseconds (merged batch wall split across riders by real-"
    "example share; docs/OBSERVABILITY.md 'Cost attribution'), by "
    "model and signature.", ("model", "signature"))
cost_queue_wait_us = Gauge(
    ":tpu/serving/cost_queue_wait_us",
    "Rolling-window mean batching queue + in-flight-window wait per "
    "request in microseconds, by model and signature.",
    ("model", "signature"))
cost_padding_waste_us = Gauge(
    ":tpu/serving/cost_padding_waste_us",
    "Rolling-window mean slice of the per-request device share burned "
    "on padding rows, microseconds (already included in "
    "cost_device_execute_us; broken out for visibility), by model and "
    "signature.", ("model", "signature"))
cost_host_island_us = Gauge(
    ":tpu/serving/cost_host_island_us",
    "Rolling-window mean host-island time (partition pre/post + "
    "pipeline host stages) per request in microseconds, by model and "
    "signature.", ("model", "signature"))
cost_kv_page_ticks = Gauge(
    ":tpu/serving/cost_kv_page_ticks",
    "Rolling-window mean KV pages-held-per-tick attributed to each "
    "decode-step request (pages x ticks; the paged pool's HBM-"
    "residency cost unit), by model and signature.",
    ("model", "signature"))
cost_log_records = Counter(
    ":tpu/serving/cost_log_records",
    "servecost JSONL wide-event log outcomes "
    "(logged | sampled_out | dropped).", ("outcome",))
tick_utilization = Gauge(
    ":tpu/serving/tick_utilization",
    "Busy fraction of the decode tick loop over a rolling 30s window "
    "(device rounds' wall over elapsed wall), by pool metric label — "
    "the device-idle signal for decode legs.", ("model",))


# -- routing-tier metrics (min_tfs_client_tpu/router/; docs/ROUTING.md) ------
router_backend_requests = Counter(
    ":tpu/serving/router_backend_requests",
    "Requests the router forwarded, by backend and gRPC method (or "
    "'rest' for proxied HTTP).", ("backend", "method"))
router_backend_errors = Counter(
    ":tpu/serving/router_backend_errors",
    "Forwarded requests that came back as errors (or failed to reach "
    "the backend at all), by backend and status code.",
    ("backend", "code"))
router_backend_ejections = Counter(
    ":tpu/serving/router_backend_ejections",
    "Backend removals from the new-work rotation, by backend and kind "
    "(drain = health answered NOT_SERVING; dead = health plane "
    "unreachable).", ("backend", "kind"))
router_ring_occupancy = Gauge(
    ":tpu/serving/router_ring_occupancy",
    "Share of a fixed probe keyspace the hash ring currently assigns to "
    "each live backend (sums to ~1.0 across the fleet).", ("backend",))
router_sticky_sessions = Gauge(
    ":tpu/serving/router_sticky_sessions",
    "Sessions pinned to each backend in the router's stickiness table.",
    ("backend",))
router_live_backends = Gauge(
    ":tpu/serving/router_live_backends",
    "Backends currently in the new-work rotation (state LIVE).", ())
router_session_recoveries = Counter(
    ":tpu/serving/router_session_recoveries",
    "Sessions whose pin was RECOVERED by probing the preference order "
    "(a sessioned non-init request reached a replica holding no pin, "
    "and the current view's first choice answered NOT_FOUND), by the "
    "backend that actually held the session. Nonzero under a stable "
    "view means replicas disagree on placement.", ("backend",))
router_forward_retries = Counter(
    ":tpu/serving/router_forward_retries",
    "In-forward UNAVAILABLE retries the router performed for provably-"
    "safe requests (stateless, or decode steps carrying the at-most-"
    "once step_ordinal guard), by backend. A sustained nonzero rate "
    "means a backend's listener is flapping faster than the health "
    "poller ejects it (docs/ROBUSTNESS.md).", ("backend",))
router_event_loop_lag_ms = Gauge(
    ":tpu/serving/router_event_loop_lag_ms",
    "Sampled scheduling lag of the router's asyncio data-plane event "
    "loop (overshoot of a fixed-interval ticker, ms) — the aio "
    "analogue of thread-pool saturation; every in-flight forward's "
    "completion is late by about this much.", ())

grpc_event_loop_lag_ms = Gauge(
    ":tpu/serving/grpc_event_loop_lag_ms",
    "Sampled scheduling lag of the process's gRPC event loop "
    "(utils/aio_loop.py; overshoot of a fixed-interval ticker, ms): "
    "every request answered on the loop, and every hand-off to the "
    "worker pool, is late by about this much.", ())

# -- fleet-view re-exports (router/fleet.py; docs/OBSERVABILITY.md) ----------
fleet_backend_stale = Gauge(
    ":tpu/serving/fleet_backend_stale",
    "1 when the router's fleet scraper could not refresh this "
    "backend's monitoring payloads within the staleness window (dark "
    "backend), else 0.", ("backend",))
fleet_slo_max_burn_rate = Gauge(
    ":tpu/serving/fleet_slo_max_burn_rate",
    "Max SLO burn rate the backend last reported at /monitoring/slo, "
    "re-exported by the router's fleet scraper.", ("backend",))
fleet_kv_blocks_used = Gauge(
    ":tpu/serving/fleet_kv_blocks_used",
    "KV pages in use the backend last reported (summed over its paged "
    "pools), re-exported by the router's fleet scraper.", ("backend",))
fleet_kv_blocks_total = Gauge(
    ":tpu/serving/fleet_kv_blocks_total",
    "KV page capacity the backend last reported (summed over its "
    "paged pools), re-exported by the router's fleet scraper.",
    ("backend",))
fleet_tick_utilization = Gauge(
    ":tpu/serving/fleet_tick_utilization",
    "Max decode tick-loop duty cycle the backend last reported at "
    "/monitoring/costs, re-exported by the router's fleet scraper.",
    ("backend",))

# -- watchdog alerts (observability/watchdog.py; /monitoring/alerts) ---------
alerts_total = Counter(
    ":tpu/serving/alerts",
    "Watchdog alerts emitted, by detector signal and severity "
    "(edge-triggered with refire suppression — one persisting "
    "condition is one alert per refire window, not one per tick).",
    ("signal", "severity"))
alert_active = Gauge(
    ":tpu/serving/alert_active",
    "Number of series (models, pools, backends) a watchdog detector "
    "currently considers anomalous; 0 when the signal is quiet.",
    ("signal",))


def gauge_total(gauge: Gauge) -> float:
    """Sum of a gauge over all label combinations (e.g. live decode
    sessions across every model) — the drain loop's one read."""
    with gauge._lock:
        return float(sum(gauge._cells.values()))


def safe_set(gauge: Gauge, value: float, *labels) -> None:
    """Set a gauge without ever letting metrics break serving (the one
    place the swallow-everything policy lives)."""
    try:
        gauge.set(value, *labels)
    except Exception:  # pragma: no cover - metrics must not break serving
        pass


def _sanitize(name: str) -> str:
    return re.sub(r"[^a-zA-Z0-9_]", "_", name.lstrip(":"))


def prometheus_text() -> str:
    """Serialize every registered metric (prometheus_exporter.cc:153-159)."""
    try:
        # Request traces export their per-stage samples off the hot path;
        # drain them now so this scrape sees every finished request.
        from min_tfs_client_tpu.observability.tracing import flush_metrics

        flush_metrics()
    except Exception:  # pragma: no cover - exporter must always serialize
        pass
    try:
        # Derived health-plane gauges refresh at scrape time: SLO window
        # quantiles/burn and the readiness verdict. The SLO exporter
        # returns the shed-eligible burn from ITS window merge so the
        # readiness refresh doesn't repeat it.
        from min_tfs_client_tpu.observability import health, slo

        health.export_gauges(max_burn=slo.export_gauges())
    except Exception:  # pragma: no cover - exporter must always serialize
        pass
    try:
        # Cost-attribution gauges refresh at scrape time too (window
        # means + tick duty cycles), same deferred-export discipline.
        from min_tfs_client_tpu.observability import costs

        costs.export_gauges()
    except Exception:  # pragma: no cover - exporter must always serialize
        pass
    lines: list[str] = []
    with _registry_lock:
        metrics = list(_registry.values())
    for metric in metrics:
        pname = _sanitize(metric.name)
        lines.append(f"# TYPE {pname} {metric.kind}")
        with metric._lock:
            cells = dict(metric._cells)
        for labels, value in sorted(cells.items(), key=lambda kv: kv[0]):
            label_str = ""
            if metric.label_names:
                pairs = ",".join(
                    f'{k}="{v}"' for k, v in zip(metric.label_names, labels))
                label_str = "{" + pairs + "}"
            if metric.kind == "histogram":
                cum = 0
                for bound, count in zip(metric.buckets, value["counts"]):
                    cum += count
                    le = (f'{{le="{bound}"}}' if not metric.label_names else
                          label_str[:-1] + f',le="{bound}"}}')
                    lines.append(f"{pname}_bucket{le} {cum}")
                cum += value["counts"][-1]
                le_inf = ('{le="+Inf"}' if not metric.label_names else
                          label_str[:-1] + ',le="+Inf"}')
                lines.append(f"{pname}_bucket{le_inf} {cum}")
                lines.append(f"{pname}_sum{label_str} {value['sum']}")
                lines.append(f"{pname}_count{label_str} {value['count']}")
            else:
                lines.append(f"{pname}{label_str} {value}")
    return "\n".join(lines) + "\n"
