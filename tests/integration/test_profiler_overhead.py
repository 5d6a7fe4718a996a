"""Sampling-profiler overhead smoke, in its own module (the
overhead-test convention: nothing else timed shares the process
window). The sampler is DEFAULT-ON in production at ~11 Hz — this A/B
pins what the ticker costs a REST request's p50 on BOTH HTTP
backends."""

from __future__ import annotations

import json
import time
import urllib.request

import pytest

from min_tfs_client_tpu.observability import profiling
from min_tfs_client_tpu.server.server import Server, ServerOptions
from tests import fixtures


@pytest.fixture(scope="module")
def model_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("prof_overhead_models")
    fixtures.write_jax_servable(root / "native")
    return root


@pytest.fixture(params=["native", "python"])
def rest_server(model_root, request):
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
        profile_sampler_hz=0.0,  # the test toggles the sampler itself
    ))
    srv.build_and_start()
    yield srv
    srv.stop()
    profiling.configure(hz=0.0)  # restore the process default (stopped)


class TestProfilerOverheadSmoke:
    def test_sampler_overhead_within_budget(self, rest_server):
        """Sampler ON (the production-default ~11 Hz) vs OFF over the
        REST predict path: the p50 delta must stay under 5% of the
        quiet p50 with the 60us floor (the tracing/health-plane
        overhead convention)."""
        import gc

        payload = json.dumps({"inputs": {"x": list(range(8))}}).encode()
        url = (f"http://127.0.0.1:{rest_server.rest_port}"
               "/v1/models/native:predict")

        def call():
            # A fresh connection per call, deliberately: the python
            # http.server backend's keep-alive path stalls ~40 ms per
            # request on Nagle x delayed-ACK (unbuffered small writes),
            # which would drown the measurement. Connect cost is paid
            # identically by both arms.
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
                assert resp.status == 200

        for _ in range(30):
            call()  # warm jit + allocator

        def chunk_p50(n=120):
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                call()
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[n // 2] * 1e6

        def measure() -> tuple[float, float]:
            """Best chunk of seven with the sampler on, and off."""
            on, off = [], []
            for _ in range(7):  # interleave so both see the same load
                profiling.start()
                on.append(chunk_p50())
                profiling.stop()
                off.append(chunk_p50())
            return min(on), min(off)

        profiling.configure(hz=profiling.DEFAULT_HZ)
        gc.collect()
        gc.disable()
        try:
            # What the sampler costs is a floor under the `on` arm;
            # what the machine's other work costs (this suite runs six
            # workers wide) lands on either arm, in either direction.
            # A sampler over its budget is over it in every attempt;
            # noise is not: the best of three attempts is held to the
            # same budget as one was.
            attempts = []
            for _ in range(3):
                sampling, quiet = measure()
                budget = max(0.05 * quiet, 60.0)
                attempts.append((sampling - quiet, budget, sampling, quiet))
                if sampling - quiet < budget:
                    break
        finally:
            gc.enable()
        overhead, budget, sampling, quiet = min(attempts)
        assert overhead < budget, (
            f"sampler overhead {overhead:.1f}us exceeds budget "
            f"{budget:.1f}us (on {sampling:.1f}us, off {quiet:.1f}us) "
            f"in all of {len(attempts)} attempts: {attempts}")

    def test_a_tick_s_own_cpu_time_is_under_half_a_percent_of_a_core(
            self, rest_server):
        """What the sampler controls, measured where no neighbour can
        reach it: the CPU seconds of this thread (not the wall) that one
        walk of every interpreter thread takes, driven directly. At the
        default rate that is the share of a core the module's header
        promises (<0.5% with tens of threads)."""
        import threading

        fold = profiling._Fold()
        exclude = frozenset((threading.get_ident(),))
        for _ in range(20):
            fold.sample_once(exclude)  # format every frame key once
        threads = fold.samples // fold.ticks
        assert threads >= 5, "a serving process has its planes' threads"
        best = float("inf")
        for _ in range(5):
            t0 = time.thread_time()
            for _ in range(50):
                fold.sample_once(exclude)
            best = min(best, (time.thread_time() - t0) / 50)
        share = best * profiling.DEFAULT_HZ
        assert share < 0.005, (
            f"one tick over {threads} threads takes {best * 1e6:.0f}us of "
            f"CPU: {100 * share:.2f}% of a core at {profiling.DEFAULT_HZ} Hz")
