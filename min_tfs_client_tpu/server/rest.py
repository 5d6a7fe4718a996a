"""HTTP/REST front-end: the /v1/... JSON surface + Prometheus metrics.

Parity with model_servers/http_rest_api_handler.{h,cc} routes
(kPathRegex "/v1/.*", dispatch .cc:106-123) and util/json_tensor formats:
row ("instances") and columnar ("inputs") requests, "predictions"/"outputs"
responses, base64 {"b64": ...} bytes encoding. Backed by Python's threaded
http.server rather than a C++ libevent loop (util/net_http/) — the REST path
is a debug/ops surface; the performance path is gRPC and tpu://.
"""

from __future__ import annotations

import base64
import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from google.protobuf import json_format

from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
from min_tfs_client_tpu.server.handlers import Handlers
from min_tfs_client_tpu.tensor.codec import ndarray_to_tensor_proto
from min_tfs_client_tpu.utils.status import ServingError, error_from_exception

_MODEL_PATH = re.compile(
    r"(?i)^/v1/models/(?P<model>[^/:]+)"
    r"(?:/versions/(?P<version>\d+)|/labels/(?P<label>[^/:]+))?"
    r"(?::(?P<verb>classify|regress|predict))?$")
_METADATA_PATH = re.compile(
    r"(?i)^/v1/models/(?P<model>[^/:]+)"
    r"(?:/versions/(?P<version>\d+)|/labels/(?P<label>[^/:]+))?/metadata$")

PROMETHEUS_DEFAULT_PATH = "/monitoring/prometheus/metrics"
# Debug endpoint: recent request traces as Chrome-trace/Perfetto JSON
# (open the response in chrome://tracing or ui.perfetto.dev). Query params:
# ?limit=N (most recent N traces), ?summary=1 (per-stage p50/p99 table
# instead of the timeline).
TRACES_DEFAULT_PATH = "/monitoring/traces"
# Health-plane endpoints (observability/{health,slo,runtime,
# flight_recorder}.py; docs/OBSERVABILITY.md "Health plane"). Served by
# BOTH REST backends — the router below is shared with native_http.py.
HEALTHZ_PATH = "/monitoring/healthz"
READYZ_PATH = "/monitoring/readyz"
SLO_PATH = "/monitoring/slo"
RUNTIME_PATH = "/monitoring/runtime"
FLIGHT_RECORDER_PATH = "/monitoring/flightrecorder"
# Per-session decode timelines (servables/decode_sessions.py event
# logs): ?session=<id> for one session's full event list, bare for the
# fleet-debuggable summary. Cross-links with /monitoring/traces via the
# session_id annotation on decode-step traces.
SESSIONS_PATH = "/monitoring/sessions"
# Per-request cost attribution (observability/costs.py): rolling
# per-(model, signature) cost-vector aggregates, tick duty cycles, and
# the servecost JSONL log's stats. The router's fleet scraper reads
# this from every backend (docs/OBSERVABILITY.md "Cost attribution").
COSTS_PATH = "/monitoring/costs"
# Watchdog alert ring (observability/watchdog.py): streaming anomaly
# detectors over the slo/costs/runtime/tracing planes, evaluated on the
# watchdog's own ticker. The router serves the same path with the
# fleet-scope detectors and per-backend aggregation
# (docs/OBSERVABILITY.md "Alerting & trend gating").
ALERTS_PATH = "/monitoring/alerts"
# Sampling-profiler plane (observability/profiling.py): per-thread /
# per-stage CPU attribution from the continuous StackSampler, folded
# stacks for speedscope/flamegraph.pl, on-demand high-rate windows,
# differential views, and programmatic device capture
# (docs/OBSERVABILITY.md "Profiling plane"). Served by both REST
# backends and the router (router/proxy.py shares _profile_reply).
PROFILE_PATH = "/monitoring/profile"


def _fill_spec(spec: apis.ModelSpec, m: re.Match) -> None:
    spec.name = m.group("model")
    if m.group("version"):
        spec.version.value = int(m.group("version"))
    elif m.group("label"):
        spec.version_label = m.group("label")


def _json_value_to_array(value) -> np.ndarray:
    """JSON -> ndarray with b64 bytes handling (json_tensor semantics)."""
    def convert(v):
        if isinstance(v, dict) and set(v) == {"b64"}:
            return base64.b64decode(v["b64"])
        if isinstance(v, list):
            return [convert(x) for x in v]
        return v

    converted = convert(value)
    arr = np.asarray(converted)
    if arr.dtype.kind in ("U", "S"):
        arr = arr.astype(object)
        flat = arr.reshape(-1)
        flat[:] = [x.encode() if isinstance(x, str) else x for x in flat.tolist()]
    elif arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    elif arr.dtype == np.int64 and np.all(np.abs(arr) < 2**31):
        arr = arr.astype(np.int32)
    return arr


def _array_to_json(arr: np.ndarray):
    if arr.dtype == object or arr.dtype.kind in ("S", "U"):
        def enc(v):
            if isinstance(v, (bytes, np.bytes_)):
                try:
                    return bytes(v).decode("utf-8")
                except UnicodeDecodeError:
                    return {"b64": base64.b64encode(bytes(v)).decode()}
            return v
        return np.vectorize(enc, otypes=[object])(arr).tolist()
    if arr.dtype == np.dtype("float16") or str(arr.dtype) == "bfloat16":
        arr = arr.astype(np.float32)
    return arr.tolist()


def build_predict_request(
        body: dict, spec_match: re.Match) -> tuple[apis.PredictRequest, bool]:
    request = apis.PredictRequest()
    _fill_spec(request.model_spec, spec_match)
    if "signature_name" in body:
        request.model_spec.signature_name = body["signature_name"]
    if "instances" in body:
        instances = body["instances"]
        if not isinstance(instances, list) or not instances:
            raise ServingError.invalid_argument(
                "JSON 'instances' must be a non-empty list")
        if isinstance(instances[0], dict) and not set(instances[0]) == {"b64"}:
            names = set(instances[0])
            columns = {name: [] for name in names}
            for row in instances:
                if set(row) != names:
                    raise ServingError.invalid_argument(
                        "All instances must carry the same input names")
                for name in names:
                    columns[name].append(row[name])
            for name, col in columns.items():
                request.inputs[name].CopyFrom(
                    ndarray_to_tensor_proto(_json_value_to_array(col)))
        else:
            request.inputs["inputs"].CopyFrom(
                ndarray_to_tensor_proto(_json_value_to_array(instances)))
    elif "inputs" in body:
        inputs = body["inputs"]
        if isinstance(inputs, dict):
            for name, col in inputs.items():
                request.inputs[name].CopyFrom(
                    ndarray_to_tensor_proto(_json_value_to_array(col)))
        else:
            request.inputs["inputs"].CopyFrom(
                ndarray_to_tensor_proto(_json_value_to_array(inputs)))
    else:
        raise ServingError.invalid_argument(
            "Missing 'instances' or 'inputs' key in JSON body")
    return request, "instances" in body


def predict_response_to_json(response: apis.PredictResponse, row_format: bool):
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    outputs = {k: tensor_proto_to_ndarray(v)
               for k, v in response.outputs.items()}
    return outputs_to_json(outputs, row_format)


def outputs_to_json(outputs: dict, row_format: bool):
    if row_format:
        n = next(iter(outputs.values())).shape[0] if outputs else 0
        if len(outputs) == 1:
            arr = next(iter(outputs.values()))
            return {"predictions": _array_to_json(arr)}
        rows = []
        for i in range(n):
            rows.append({k: _array_to_json(v[i]) for k, v in outputs.items()})
        return {"predictions": rows}
    if len(outputs) == 1:
        return {"outputs": _array_to_json(next(iter(outputs.values())))}
    return {"outputs": {k: _array_to_json(v) for k, v in outputs.items()}}


def route_request(
    handlers: Handlers,
    prometheus_path: Optional[str],
    method: str,
    path: str,
    body_bytes: bytes,
    trace_id: str = "",
) -> tuple[int, str, bytes]:
    """Transport-independent /v1 router: (status, content_type, body).

    Shared by the Python `http.server` backend below and the native epoll
    front-end (`server/native_http.py`). Mirrors the reference's route
    dispatch (http_rest_api_handler.cc:106-123); transport concerns
    (gzip, keep-alive, limits) live in the respective servers.
    `trace_id` is the x-tpu-serving-trace request header — the Python
    backend reads it from the parsed request, the native front-end
    fetches it through `tpuhttp_request_header` during the callback.
    """
    from min_tfs_client_tpu.observability import tracing

    with tracing.transport("rest"), tracing.adopt(trace_id or None):
        return _route(handlers, prometheus_path, method, path, body_bytes)


def _route(
    handlers: Handlers,
    prometheus_path: Optional[str],
    method: str,
    path: str,
    body_bytes: bytes,
) -> tuple[int, str, bytes]:
    try:
        if method == "GET":
            if prometheus_path and path == prometheus_path:
                from min_tfs_client_tpu.server.metrics import prometheus_text

                return (200, "text/plain; version=0.0.4",
                        prometheus_text().encode())
            bare, _, query = path.partition("?")
            if bare == TRACES_DEFAULT_PATH:
                return _traces_reply(query)
            if bare in _MONITORING_ROUTES:
                return _MONITORING_ROUTES[bare](query)
            m = _METADATA_PATH.match(path)
            if m:
                request = apis.GetModelMetadataRequest()
                _fill_spec(request.model_spec, m)
                request.metadata_field.append("signature_def")
                response = handlers.get_model_metadata(request)
                return _json_reply(200, json_format.MessageToDict(
                    response, preserving_proto_field_name=True))
            m = _MODEL_PATH.match(path)
            if m and not m.group("verb"):
                request = apis.GetModelStatusRequest()
                _fill_spec(request.model_spec, m)
                response = handlers.get_model_status(request)
                return _json_reply(200, json_format.MessageToDict(
                    response, preserving_proto_field_name=True))
            return _json_reply(
                404, {"error": f"Malformed request: GET {path}"})
        if method == "POST":
            m = _MODEL_PATH.match(path)
            if not m or not m.group("verb"):
                return _json_reply(
                    404, {"error": f"Malformed request: POST {path}"})
            verb = m.group("verb").lower()
            if verb == "predict":
                # Native fast path: dense numeric bodies parse straight to
                # arrays (json_tensor.cpp); None -> general Python codec.
                request = row = None
                fast = _parse_predict_fast(body_bytes or b"{}")
                if fast is not None:
                    tensors, row, signature = fast
                    request = apis.PredictRequest()
                    _fill_spec(request.model_spec, m)
                    if signature:
                        request.model_spec.signature_name = signature
                    for name, arr in tensors.items():
                        request.inputs[name].CopyFrom(
                            ndarray_to_tensor_proto(arr))
                else:
                    body = json.loads(body_bytes or b"{}")
                    request, row = build_predict_request(body, m)
                response = handlers.predict(request)
                return _predict_reply(response, row)
            if verb in ("classify", "regress"):
                body = json.loads(body_bytes or b"{}")
                return _json_reply(
                    200, _classify_regress(handlers, verb, body, m))
            return _json_reply(400, {"error": f"unsupported verb {verb}"})
        return _json_reply(400, {"error": f"unsupported method {method}"})
    except Exception as exc:  # noqa: BLE001
        err = error_from_exception(exc)
        http_code = {3: 400, 5: 404, 12: 501, 14: 503, 4: 504}.get(
            err.code, 500)
        return _json_reply(http_code, {"error": err.message})


def _json_reply(code: int, payload: dict) -> tuple[int, str, bytes]:
    return code, "application/json", json.dumps(payload).encode()


def _traces_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/traces[?limit=N][&summary=1][&trace_id=ID] — the
    in-memory trace ring as Chrome-trace JSON (or the aggregated
    per-stage table), with the host track beside the requests (the
    process's own spans, from the oldest request shown on). `trace_id`
    filters to one fleet-scope trace and
    renders on the WALL clock (comparable across processes) — the form
    the router's stitcher fetches (docs/OBSERVABILITY.md "Fleet
    tracing")."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.observability import tracing

    params = parse_qs(query)
    limit = None
    if params.get("limit"):
        try:
            limit = max(1, int(params["limit"][0]))
        except ValueError:
            return _json_reply(400, {"error": "limit must be an integer"})
    trace_id = params.get("trace_id", [""])[0]
    if trace_id:
        traces = tracing.find_traces(trace_id)
        payload = tracing.chrome_trace(traces, clock="wall")
        payload["otherData"]["trace_id"] = trace_id
        payload["otherData"]["matches"] = len(traces)
        return _json_reply(200, payload)
    traces = tracing.ring_snapshot(limit)
    if params.get("summary", [""])[0] not in ("", "0"):
        payload: dict = {"traces": len(traces),
                         "stages": tracing.stage_breakdown(traces)}
    else:
        payload = tracing.chrome_trace(
            traces, process_spans=tracing.process_snapshot(
                since=min((tr.start for tr in traces), default=None)))
    return _json_reply(200, payload)


def _healthz_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/healthz — liveness. 200 while the process can
    serve at all; 503 when a load-bearing thread pool died."""
    from min_tfs_client_tpu.observability import health

    verdict = health.liveness()
    return _json_reply(200 if verdict["ok"] else 503, verdict)


def _readyz_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/readyz — readiness: all configured models
    AVAILABLE (warmup included) and SLO burn below the shedding
    threshold. 503 + reasons while not ready."""
    from min_tfs_client_tpu.observability import health

    verdict = health.readiness()
    return _json_reply(200 if verdict["ready"] else 503, verdict)


def _slo_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/slo — per-(model, signature, api) window
    quantiles, error ratios, and burn rates as JSON."""
    from min_tfs_client_tpu.observability import slo, tracing

    tracing.flush_metrics()  # read-your-writes for just-finished requests
    return _json_reply(200, slo.snapshot())


def _runtime_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/runtime[?live_arrays=1] — compile ledger, HBM
    accounting, transfer counters, profiler status."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.observability import runtime

    params = parse_qs(query)
    live = params.get("live_arrays", [""])[0] not in ("", "0")
    return _json_reply(200, runtime.snapshot(include_live_arrays=live))


def _flight_recorder_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/flightrecorder[?rearm=1] — the live event ring
    as JSON. `rearm=1` additionally re-arms the one-shot dump latch
    (multi-phase chaos runs latch one dump PER PHASE; the reply's
    `was_latched` says whether the latch had fired since the last
    re-arm). SIGUSR2 semantics are unchanged: it dumps on demand
    without consuming the latch."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.observability import flight_recorder

    payload = flight_recorder.to_json()
    params = parse_qs(query)
    if params.get("rearm", [""])[0] not in ("", "0"):
        payload["rearmed"] = True
        payload["was_latched"] = flight_recorder.rearm()
    return _json_reply(200, payload)


def _costs_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/costs — per-(model, signature) rolling cost
    aggregates (amortized device share, queue wait, padding waste,
    compile, transfer, KV page-ticks), tick-loop duty cycles, and the
    cost log's sampling stats."""
    from min_tfs_client_tpu.observability import costs, tracing

    tracing.flush_metrics()  # read-your-writes for just-finished requests
    return _json_reply(200, costs.snapshot())


def _sessions_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/sessions[?session=ID][&events=N] — per-session
    decode timelines from every live pool's event log: list view (one
    summary row per live/recently-closed session) or, with ?session=,
    that session's full event timeline (init -> prefill-chunk rounds ->
    ticks -> swap/restore -> close, pages held over time)."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.servables import decode_sessions

    params = parse_qs(query)
    session = params.get("session", [""])[0]  # parse_qs already unquotes
    events = None
    if params.get("events"):
        try:
            events = max(1, int(params["events"][0]))
        except ValueError:
            return _json_reply(400, {"error": "events must be an integer"})
    payload = decode_sessions.sessions_payload(
        session=session or None, max_events=events)
    return _json_reply(200, payload)


def _alerts_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/alerts[?tick=1][&limit=N] — the watchdog's alert
    ring: detector catalogue, currently-firing conditions, and recent
    structured alerts (each joined to a trace id and the latest
    flight-recorder error digest). `tick=1` forces one synchronous
    detector pass first, so tests and humans get a
    sampled-right-now verdict instead of waiting out the interval."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.observability import watchdog

    params = parse_qs(query)
    limit = None
    if params.get("limit"):
        try:
            limit = max(0, int(params["limit"][0]))
        except ValueError:
            return _json_reply(400, {"error": "limit must be an integer"})
    tick = params.get("tick", [""])[0] not in ("", "0")
    return _json_reply(200, watchdog.payload(limit=limit, tick=tick))


def _profile_reply(query: str) -> tuple[int, str, bytes]:
    """GET /monitoring/profile — the sampling-profiler plane.

    Bare: JSON summary (top self/total frames per thread and per stage,
    subsystem mix). `?format=collapsed`: folded stacks
    (`thread;frame;... count`) for speedscope / flamegraph.pl.
    `?seconds=N[&hz=H]`: on-demand high-rate window sampled in this
    worker thread (composes with format=collapsed). `?diff=1&seconds=N`:
    capture-window frame shares vs the rolling baseline ring.
    `?device=1&seconds=N`: programmatic jax.profiler.trace capture to
    --profile_dir — 501 where jax is absent (the router)."""
    from urllib.parse import parse_qs

    from min_tfs_client_tpu.observability import profiling

    params = parse_qs(query)
    seconds = None
    if params.get("seconds"):
        try:
            seconds = float(params["seconds"][0])
        except ValueError:
            return _json_reply(400, {"error": "seconds must be a number"})
    hz = None
    if params.get("hz"):
        try:
            hz = float(params["hz"][0])
        except ValueError:
            return _json_reply(400, {"error": "hz must be a number"})
    if params.get("device", [""])[0] not in ("", "0"):
        try:
            return _json_reply(
                200, profiling.device_capture(seconds or 3.0))
        except ValueError as exc:
            return _json_reply(400, {"error": str(exc)})
        except Exception as exc:  # noqa: BLE001 - jax absent/broken here
            return _json_reply(
                501, {"error": f"device capture unavailable: {exc}"})
    if params.get("diff", [""])[0] not in ("", "0"):
        return _json_reply(200, profiling.diff_payload(seconds or 2.0, hz))
    collapsed = params.get("format", [""])[0] == "collapsed"
    if seconds is not None:
        if collapsed:
            return (200, "text/plain; charset=utf-8",
                    profiling.capture_collapsed(seconds, hz).encode())
        return _json_reply(200, profiling.capture_payload(seconds, hz))
    if collapsed:
        return (200, "text/plain; charset=utf-8",
                profiling.collapsed().encode())
    return _json_reply(200, profiling.payload())


_MONITORING_ROUTES = {
    HEALTHZ_PATH: _healthz_reply,
    READYZ_PATH: _readyz_reply,
    SLO_PATH: _slo_reply,
    RUNTIME_PATH: _runtime_reply,
    FLIGHT_RECORDER_PATH: _flight_recorder_reply,
    SESSIONS_PATH: _sessions_reply,
    COSTS_PATH: _costs_reply,
    ALERTS_PATH: _alerts_reply,
    PROFILE_PATH: _profile_reply,
}


def _parse_predict_fast(body_bytes: bytes):
    from min_tfs_client_tpu.server.json_fast import parse_predict_fast

    return parse_predict_fast(body_bytes)


def _predict_reply(response, row_format: bool) -> tuple[int, str, bytes]:
    """Render a PredictResponse, preferring the native encoder for
    numeric outputs; falls back to the general Python path. The proto ->
    ndarray conversion happens exactly once either way."""
    from min_tfs_client_tpu.server.json_fast import (
        encode_predict_response_fast,
    )
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    outputs = {k: tensor_proto_to_ndarray(v)
               for k, v in response.outputs.items()}
    fast = encode_predict_response_fast(outputs, row_format)
    if fast is not None:
        return 200, "application/json", fast
    return _json_reply(200, outputs_to_json(outputs, row_format))


class _RestHandler(BaseHTTPRequestHandler):
    handlers: Handlers = None
    prometheus_path: Optional[str] = None
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):  # quiet
        pass

    def _send(self, code: int, content_type: str, body: bytes) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        # Response compression when the client accepts it (the reference's
        # net_http gzip support, evhttp_request.cc; worthwhile from ~1KB).
        if (len(body) >= 1024 and "gzip" in
                self.headers.get("Accept-Encoding", "").lower()):
            import gzip as _gzip

            body = _gzip.compress(body, compresslevel=5)
            self.send_header("Content-Encoding", "gzip")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, payload: dict) -> None:
        self._send(code, "application/json", json.dumps(payload).encode())

    def _read_body(self) -> bytes:
        length = int(self.headers.get("Content-Length", "0"))
        raw = self.rfile.read(length)
        if (self.headers.get("Content-Encoding", "").lower().strip()
                == "gzip"):
            import gzip as _gzip
            import zlib as _zlib

            try:
                raw = _gzip.decompress(raw)
            except (OSError, EOFError, _zlib.error):
                # corrupt deflate streams raise zlib.error / EOFError,
                # not OSError — all are the client's fault: 400.
                self._send_json(400, {
                    "error": "body declared Content-Encoding: gzip but "
                             "did not decompress"})
                return None
        return raw

    def _trace_header(self) -> str:
        from min_tfs_client_tpu.observability import tracing

        return self.headers.get(tracing.TRACE_HEADER, "")

    def do_GET(self):  # noqa: N802 - http.server API
        self._send(*route_request(
            self.handlers, self.prometheus_path, "GET", self.path, b"",
            trace_id=self._trace_header()))

    def do_POST(self):  # noqa: N802 - http.server API
        raw = self._read_body()
        if raw is None:
            return
        self._send(*route_request(
            self.handlers, self.prometheus_path, "POST", self.path, raw,
            trace_id=self._trace_header()))


def _classify_regress(handlers: Handlers, verb: str, body: dict, m: re.Match):
    from min_tfs_client_tpu.tensor.example_codec import build_input

    examples = body.get("examples")
    if not isinstance(examples, list) or not examples:
        raise ServingError.invalid_argument(
            "JSON body must carry a non-empty 'examples' list")
    context = body.get("context")
    decoded = []
    for ex in examples:
        decoded.append({
            k: (base64.b64decode(v["b64"])
                if isinstance(v, dict) and set(v) == {"b64"} else v)
            for k, v in ex.items()})
    inp = build_input(decoded, context=context)
    if verb == "classify":
        request = apis.ClassificationRequest()
        _fill_spec(request.model_spec, m)
        if "signature_name" in body:
            request.model_spec.signature_name = body["signature_name"]
        request.input.CopyFrom(inp)
        response = handlers.classify(request)
        return {"results": [
            [[c.label, c.score] for c in cl.classes]
            for cl in response.result.classifications]}
    request = apis.RegressionRequest()
    _fill_spec(request.model_spec, m)
    if "signature_name" in body:
        request.model_spec.signature_name = body["signature_name"]
    request.input.CopyFrom(inp)
    response = handlers.regress(request)
    return {"results": [r.value for r in response.result.regressions]}


def prometheus_path_from(monitoring: Optional[object]) -> Optional[str]:
    """MonitoringConfig -> metrics path, or None when disabled."""
    if monitoring is None or not monitoring.prometheus_config.enable:
        return None
    return monitoring.prometheus_config.path or PROMETHEUS_DEFAULT_PATH


def start_rest_server(
    handlers: Handlers,
    port: int,
    monitoring: Optional[object] = None,
) -> tuple[ThreadingHTTPServer, int]:
    handler_cls = type("BoundRestHandler", (_RestHandler,), {
        "handlers": handlers,
        "prometheus_path": prometheus_path_from(monitoring),
    })
    server = ThreadingHTTPServer(("0.0.0.0", port), handler_cls)
    thread = threading.Thread(
        target=server.serve_forever, name="rest-server", daemon=True)
    thread.start()
    return server, server.server_address[1]
