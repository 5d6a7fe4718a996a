"""Monitoring-endpoint schema snapshots: every monitoring payload's
TOP-LEVEL key set is pinned against what docs/OBSERVABILITY.md
documents — on BOTH REST backends for the server endpoints, and on the
router for /monitoring/{router,fleet}. A payload key added or removed
without updating the doc (and this suite) fails loudly instead of
drifting silently."""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from min_tfs_client_tpu.server.server import Server, ServerOptions
from tests import fixtures

pytestmark = pytest.mark.integration

# The documented top-level keys, asserted EXACTLY (a superset means the
# doc is stale; a subset means the payload broke).
SERVER_SCHEMAS = {
    "/monitoring/slo": {"default_objective", "dropped_keys", "entries"},
    "/monitoring/runtime": {"compile", "devices", "transfer", "profiler",
                            "pipeline", "kv_pool", "route", "state",
                            "grpc", "gc_pause_seconds"},
    "/monitoring/sessions": {"pools"},
    "/monitoring/costs": {"schema", "window_s", "context", "dropped_keys",
                          "entries", "tick_utilization", "log"},
    "/monitoring/traces": {"traceEvents", "displayTimeUnit", "otherData"},
    "/monitoring/flightrecorder": {"capacity", "events"},
    "/monitoring/alerts": {"interval_s", "ticks", "detectors", "active",
                           "alerts"},
    "/monitoring/profile": {"sampler", "threads", "subsystems", "stages"},
}

ROUTER_SCHEMAS = {
    "/monitoring/router": {"backends", "poll_interval_s",
                           "eject_after_failures", "view", "ring",
                           "sessions", "data_plane", "inflight_forwards",
                           "sessions_recovered", "ready"},
    "/monitoring/fleet": {"scrape_interval_s", "stale_after_s", "sweeps",
                          "backends", "fleet"},
    # The router's alerts payload is the backend shape plus the scraped
    # per-backend alert summaries (the fleet-scope aggregation).
    "/monitoring/alerts": {"interval_s", "ticks", "detectors", "active",
                           "alerts", "backends"},
    # Same reply implementation as the backends — the sampler is
    # process-global, so the router serves its own attribution.
    "/monitoring/profile": {"sampler", "threads", "subsystems", "stages"},
}

# Second-level keys load-bearing enough to pin too: the fields the
# fleet scraper, the autotuner dataset, and the dashboards key on.
COSTS_ENTRY_KEYS = {"model", "signature", "count", "mean", "total"}
FLEET_BACKEND_KEYS = {"state", "rest_port", "stale", "unreachable",
                      "age_s", "error", "scrapes", "slo", "kv",
                      "compile", "transfer", "pipeline", "costs",
                      "tick_utilization", "cost_context", "cost_log",
                      "alerts"}


@pytest.fixture(scope="module")
def model_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("schema_models")
    fixtures.write_jax_servable(root / "native")
    return root


@pytest.fixture(scope="module", params=["native", "python"])
def rest_server(model_root, request):
    """The schema snapshots, against BOTH HTTP backends."""
    if request.param == "native":
        from min_tfs_client_tpu.server.native_http import (
            native_http_available,
        )

        if not native_http_available():
            pytest.skip("native HTTP library not buildable here")
    mon = model_root / f"monitoring-{request.param}.config"
    mon.write_text("prometheus_config { enable: true }\n")
    srv = Server(ServerOptions(
        grpc_port=0,
        rest_api_port=0,
        model_name="native",
        model_base_path=str(model_root / "native"),
        model_platform="jax",
        file_system_poll_wait_seconds=0,
        monitoring_config_file=str(mon),
        rest_api_impl=request.param,
    ))
    srv.build_and_start()
    # At least one served request so slo/costs/traces payloads carry
    # real entries, not just empty shells.
    from min_tfs_client_tpu.client import TensorServingClient

    client = TensorServingClient("127.0.0.1", srv.grpc_port)
    for _ in range(3):
        client.predict_request(
            "native", {"x": np.arange(8, dtype=np.float32)})
    client.close()
    yield srv
    srv.stop()


def _get_json(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


class TestServerEndpointSchemas:
    @pytest.mark.parametrize("path", sorted(SERVER_SCHEMAS))
    def test_top_level_keys_match_documented_schema(self, rest_server,
                                                    path):
        code, payload = _get_json(rest_server.rest_port, path)
        assert code == 200, payload
        assert set(payload) == SERVER_SCHEMAS[path], (
            f"{path} top-level keys drifted from the documented "
            f"schema: got {sorted(payload)}, documented "
            f"{sorted(SERVER_SCHEMAS[path])} — update "
            "docs/OBSERVABILITY.md and this snapshot together")

    def test_costs_entries_carry_documented_fields(self, rest_server):
        from min_tfs_client_tpu.observability.costs import (
            SCHEMA,
            VECTOR_FIELDS,
        )

        code, payload = _get_json(rest_server.rest_port,
                                  "/monitoring/costs")
        assert code == 200
        assert payload["schema"] == SCHEMA
        assert payload["entries"], "served requests produced no entries"
        for entry in payload["entries"]:
            assert set(entry) == COSTS_ENTRY_KEYS, entry
            assert set(entry["mean"]) == set(VECTOR_FIELDS)
            assert set(entry["total"]) == set(VECTOR_FIELDS)


@pytest.fixture(scope="module")
def router(rest_server):
    """An in-process router in front of the module server (threads
    plane: the schema under test is the payload, not the data plane,
    and the one-aio-loop-per-process guard stays out of play)."""
    from min_tfs_client_tpu.router.main import RouterOptions, RouterServer

    backend = f"127.0.0.1:{rest_server.grpc_port}:{rest_server.rest_port}"
    srv = RouterServer(RouterOptions(
        grpc_port=0, rest_api_port=0, backends=backend,
        health_poll_interval_s=0.25, data_plane="threads",
        fleet_scrape_interval_s=0.25)).build_and_start()
    yield srv
    srv.stop()


class TestRouterEndpointSchemas:
    @pytest.mark.parametrize("path", sorted(ROUTER_SCHEMAS))
    def test_top_level_keys_match_documented_schema(self, router, path):
        code, payload = _get_json(router.rest_port, path)
        assert code == 200, payload
        assert set(payload) == ROUTER_SCHEMAS[path], (
            f"{path} top-level keys drifted from the documented "
            f"schema: got {sorted(payload)}, documented "
            f"{sorted(ROUTER_SCHEMAS[path])} — update "
            "docs/OBSERVABILITY.md and this snapshot together")

    def test_fleet_backend_entries_carry_documented_fields(self, router):
        import time

        deadline = time.monotonic() + 20
        while True:
            code, payload = _get_json(router.rest_port,
                                      "/monitoring/fleet")
            assert code == 200
            entries = list(payload["backends"].values())
            if entries and all(not e.get("stale") and e.get("costs")
                               for e in entries):
                break
            assert time.monotonic() < deadline, (
                "fleet scrape never produced a fresh backend entry "
                f"with costs: {payload}")
            time.sleep(0.2)
        for entry in entries:
            assert set(entry) == FLEET_BACKEND_KEYS, sorted(entry)
        fleet = payload["fleet"]
        assert {"backends", "stale_backends", "live_backends",
                "max_slo_burn_rate", "kv_blocks_used", "kv_blocks_total",
                "max_tick_utilization", "cost_entries"} == set(fleet)
