"""/monitoring/profile end-to-end: the sampling-profiler plane served
by BOTH REST backends and the router — JSON attribution summaries, the
folded-stack (speedscope/flamegraph.pl) rendering, on-demand capture
windows, diff-vs-baseline views, device capture gating — plus the
native front-end's x-tpu-serving-trace adoption (the header plumbing
that landed with this plane)."""

from __future__ import annotations

import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from min_tfs_client_tpu.observability import profiling, tracing
from min_tfs_client_tpu.server.server import Server, ServerOptions
from tests import fixtures

pytestmark = pytest.mark.integration

# thread;frame;frame;... count — flamegraph.pl / speedscope folded.
COLLAPSED_LINE = re.compile(r"^(?P<stack>\S.*) (?P<count>\d+)$")


@pytest.fixture(scope="module")
def model_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("profile_models")
    fixtures.write_jax_servable(root / "native")
    return root


@pytest.fixture(scope="module", params=["native", "python"])
def rest_server(model_root, request):
    """The profile plane, against BOTH HTTP backends (67 Hz so a short
    test window accumulates a meaningful sample count)."""
    if request.param == "native":
        from min_tfs_client_tpu.server.native_http import (
            native_http_available,
        )

        if not native_http_available():
            pytest.skip("native HTTP library not buildable here")
    # rest_api_port=0 alone leaves the REST front-end off; a monitoring
    # config forces it up on an ephemeral port (server.py boot).
    mon = model_root / f"monitoring-{request.param}.config"
    mon.write_text("prometheus_config { enable: true }\n")
    harness = fixtures.harness_threads()
    srv = Server(ServerOptions(
        grpc_port=0,
        rest_api_port=0,
        model_name="native",
        model_base_path=str(model_root / "native"),
        model_platform="jax",
        file_system_poll_wait_seconds=0,
        monitoring_config_file=str(mon),
        rest_api_impl=request.param,
        profile_sampler_hz=67.0,
    ))
    srv.harness_threads = harness
    srv.build_and_start()
    from min_tfs_client_tpu.client import TensorServingClient

    client = TensorServingClient("127.0.0.1", srv.grpc_port)
    for _ in range(3):
        client.predict_request(
            "native", {"x": np.arange(8, dtype=np.float32)})
    client.close()
    yield srv
    srv.stop()


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
            return resp.status, resp.headers.get_content_type(), resp.read()
    except urllib.error.HTTPError as err:
        return err.code, err.headers.get_content_type(), err.read()


def _get_json(port, path):
    code, ctype, body = _get(port, path)
    return code, json.loads(body)


def _wait_for_samples(port, minimum=20, deadline_s=20.0):
    """The payload once the ticker has accumulated `minimum` samples."""
    deadline = time.monotonic() + deadline_s
    while True:
        code, body = _get_json(port, "/monitoring/profile")
        assert code == 200, body
        if body["sampler"]["samples"] >= minimum:
            return body
        assert time.monotonic() < deadline, (
            f"sampler never reached {minimum} samples: {body['sampler']}")
        time.sleep(0.2)


class TestProfilePayload:
    def test_summary_attributes_samples_to_named_threads(self,
                                                         rest_server):
        body = _wait_for_samples(rest_server.rest_port)
        assert body["sampler"]["running"] is True
        assert body["sampler"]["hz"] == 67.0
        # The acceptance bar: >=95% of samples land on a thread the
        # subsystem map can name (TH002 forces name= on every spawn).
        samples, named, other = fixtures.own_attribution(
            body["threads"], rest_server.harness_threads)
        assert samples
        assert named / samples >= 0.95
        if not rest_server.harness_threads:
            assert body["sampler"]["attributed_pct"] >= 95.0
        assert body["threads"]
        for label, info in body["threads"].items():
            assert info["subsystem"], label
            assert info["samples"] > 0
        # A serving process always shows these planes under sampling.
        subsystems = set(body["subsystems"])
        assert "rest-frontend" in subsystems or "main" in subsystems
        assert other / samples < 0.05

    def test_collapsed_format_loads_as_folded_stacks(self, rest_server):
        _wait_for_samples(rest_server.rest_port)
        code, ctype, raw = _get(rest_server.rest_port,
                                "/monitoring/profile?format=collapsed")
        assert code == 200
        assert ctype == "text/plain"
        lines = raw.decode().splitlines()
        assert lines
        named = total = 0
        for line in lines:
            m = COLLAPSED_LINE.match(line)
            assert m, f"not a folded-stack line: {line!r}"
            count = int(m.group("count"))
            total += count
            thread = m.group("stack").split(";", 1)[0]
            if thread in rest_server.harness_threads:
                total -= count  # the test runner's own (see above)
            elif not thread.startswith("unnamed-"):
                named += count
        # The speedscope acceptance bar, measured on the wire format.
        assert named / total >= 0.95

    def test_capture_window_returns_fresh_high_rate_samples(
            self, rest_server):
        code, body = _get_json(
            rest_server.rest_port, "/monitoring/profile?seconds=0.3")
        assert code == 200, body
        assert body["capture"]["seconds"] == 0.3
        assert body["capture"]["hz"] == profiling.CAPTURE_HZ
        assert body["samples"] > 5
        code, ctype, raw = _get(
            rest_server.rest_port,
            "/monitoring/profile?seconds=0.3&format=collapsed")
        assert code == 200
        assert ctype == "text/plain"
        assert all(COLLAPSED_LINE.match(li)
                   for li in raw.decode().splitlines())

    def test_diff_view_compares_window_to_baseline(self, rest_server):
        _wait_for_samples(rest_server.rest_port)
        code, body = _get_json(
            rest_server.rest_port,
            "/monitoring/profile?diff=1&seconds=0.3")
        assert code == 200, body
        assert set(body) == {"window_samples", "baseline_samples",
                             "risers", "fallers"}
        assert body["window_samples"] > 0
        for entry in body["risers"] + body["fallers"]:
            assert set(entry) == {"frame", "window_pct", "baseline_pct",
                                  "delta_pct"}

    def test_malformed_seconds_is_a_400(self, rest_server):
        code, body = _get_json(
            rest_server.rest_port, "/monitoring/profile?seconds=banana")
        assert code == 400
        assert "seconds" in body["error"]

    def test_device_capture_without_profile_dir_is_a_400(
            self, rest_server):
        code, body = _get_json(
            rest_server.rest_port,
            "/monitoring/profile?device=1&seconds=0.1")
        assert code == 400
        assert "profile_dir" in body["error"]

    def test_device_capture_writes_a_trace_directory(self, rest_server,
                                                     tmp_path):
        # The fixture server booted with profile_dir="" — arm it for
        # this test only (the singleton keeps its running sampler).
        with profiling._singleton_lock:
            profiling._profile_dir = str(tmp_path)
        try:
            code, body = _get_json(
                rest_server.rest_port,
                "/monitoring/profile?device=1&seconds=0.2")
        finally:
            with profiling._singleton_lock:
                profiling._profile_dir = ""
        if code == 501:
            pytest.skip(f"device capture unavailable here: {body}")
        assert code == 200, body
        assert body["seconds"] == 0.2
        assert body["profile_dir"].startswith(str(tmp_path))
        assert body["files"], "device capture produced no trace files"
        # The clock that joins /monitoring/traces to the capture lies
        # beside it and is named with the capture's files.
        assert profiling.HOST_CLOCK_FILE in body["files"]
        with open(f"{body['profile_dir']}/{profiling.HOST_CLOCK_FILE}") as f:
            clock = json.load(f)
        held = (clock["stop"]["span_us"] - clock["start"]["span_us"]) / 1e6
        assert 0.2 <= held < 5.0


class TestNativeTraceAdoption:
    def test_propagated_trace_id_is_adopted_by_the_rest_backend(
            self, rest_server):
        """POST with x-tpu-serving-trace: the per-request trace in the
        ring must carry the caller's id — on the python backend via the
        handler's header dict, on the NATIVE backend via the
        tpuhttp_request_header bridge (new with this plane)."""
        trace_id = f"adopt-{rest_server.options.rest_api_impl}-0042"
        # Columnar format: the servable signature is rank-1, and the
        # row format would prepend a batch dimension.
        payload = json.dumps(
            {"inputs": {"x": list(range(8))}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{rest_server.rest_port}"
            "/v1/models/native:predict",
            data=payload,
            headers={"Content-Type": "application/json",
                     tracing.TRACE_HEADER: trace_id})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
        traces = tracing.find_traces(trace_id)
        assert traces, (
            f"{rest_server.options.rest_api_impl} backend dropped the "
            "propagated trace id")
        assert all(tr.trace_id == trace_id for tr in traces)


@pytest.fixture(scope="module")
def router(rest_server):
    """An in-process router in front of the module server (threads
    plane). Its build reconfigures the process-global sampler — the
    payload is process-wide either way."""
    from min_tfs_client_tpu.router.main import RouterOptions, RouterServer

    backend = f"127.0.0.1:{rest_server.grpc_port}:{rest_server.rest_port}"
    srv = RouterServer(RouterOptions(
        grpc_port=0, rest_api_port=0, backends=backend,
        health_poll_interval_s=0.25, data_plane="threads",
        profile_sampler_hz=67.0)).build_and_start()
    srv.harness_threads = rest_server.harness_threads
    yield srv
    srv.stop()


class TestRouterProfile:
    def test_router_serves_its_own_attribution(self, router):
        body = _wait_for_samples(router.rest_port)
        assert body["sampler"]["running"] is True
        samples, named, _ = fixtures.own_attribution(
            body["threads"], router.harness_threads)
        assert samples and named / samples >= 0.95

    def test_router_collapsed_and_diff_views(self, router):
        code, ctype, raw = _get(
            router.rest_port, "/monitoring/profile?format=collapsed")
        assert code == 200 and ctype == "text/plain"
        assert all(COLLAPSED_LINE.match(li)
                   for li in raw.decode().splitlines())
        code, body = _get_json(
            router.rest_port, "/monitoring/profile?diff=1&seconds=0.2")
        assert code == 200
        assert body["window_samples"] > 0

    def test_router_refuses_device_capture(self, router):
        """The router is jax-free by design: ?device=1 answers 400/501,
        never imports jax."""
        code, body = _get_json(
            router.rest_port,
            "/monitoring/profile?device=1&seconds=0.1")
        assert code in (400, 501), body
