"""Request-tracing spine (observability/tracing.py): span recording,
thread-handoff across the batching queue, the three export sinks
(Prometheus samplers/gauges, the Chrome-trace ring + endpoint, the
optional profiler bridge), and the overhead kill switch."""

import json
import threading

import numpy as np
import pytest

from min_tfs_client_tpu.batching.scheduler import SharedBatchScheduler
from min_tfs_client_tpu.batching.session import BatchedSignatureRunner
from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.servables.servable import Signature, TensorSpec


@pytest.fixture()
def scheduler():
    s = SharedBatchScheduler(num_threads=2)
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def _schedule_witness(schedule_witness):
    """Runtime schedule witness (docs/STATIC_ANALYSIS.md): the tracing
    spine's deferred-export locking is verified live."""
    yield


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.ring_clear()
    yield
    tracing.ring_clear()


def _host_sig(executed=None):
    def fn(inputs):
        if executed is not None:
            executed.append(int(np.shape(inputs["x"])[0]))
        return {"y": np.asarray(inputs["x"], np.float32) * 2.0}

    return Signature(
        fn=fn,
        inputs={"x": TensorSpec(np.float32, (None,))},
        outputs={"y": TensorSpec(np.float32, (None,))},
        on_host=True,
    )


class TestSpanRecording:
    def test_spans_nest_on_current_trace(self):
        with tracing.request_trace("predict", model="m") as tr:
            with tracing.span("outer"):
                with tracing.span("inner", detail=1):
                    pass
        names = [s[0] for s in tr.spans]
        assert names == ["inner", "outer"]  # exit order: inner closes first
        inner = next(s for s in tr.spans if s[0] == "inner")
        outer = next(s for s in tr.spans if s[0] == "outer")
        # Nesting: inner's interval lies within outer's.
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert inner[3] == {"detail": 1}
        assert tr.end is not None and tr.status == "0"

    def test_span_without_trace_is_silent(self):
        assert tracing.current_trace() is None
        with tracing.span("orphan"):
            pass  # no error, nothing recorded anywhere

    def test_disabled_tracing_records_nothing(self):
        tracing.enable(False)
        try:
            with tracing.request_trace("predict") as tr:
                with tracing.span("stage"):
                    pass
            assert tr is None
            assert tracing.ring_snapshot() == []
        finally:
            tracing.enable(True)

    def test_error_status_recorded(self):
        with pytest.raises(ValueError):
            with tracing.request_trace("predict", model="m"):
                raise ValueError("boom")
        (tr,) = tracing.ring_snapshot()
        assert tr.status != "0"

    def test_annotate_coerces_to_json_scalars(self):
        with tracing.request_trace("predict") as tr:
            tracing.annotate(batch_size=np.int64(4), frac=np.float32(0.5),
                             name="q", flag=True)
        json.dumps(tr.meta)  # must not choke on numpy scalars
        assert tr.meta["batch_size"] == 4.0


class TestExplicitSpansAndCaptureClock:
    def test_add_span_records_hand_stamped_times_on_the_current_trace(self):
        with tracing.request_trace("decode_step") as tr:
            tracing.add_span("decode/wait", 10.0, 10.5, round=3, led=False)
            tracing.add_span("decode/deliver", 10.5, 10.6)
        assert tr.spans == [
            ("decode/wait", 10.0, 10.5, {"round": 3, "led": False}),
            ("decode/deliver", 10.5, 10.6, None)]
        tracing.add_span("decode/wait", 1.0, 2.0)  # no trace: silent

    def test_kill_switch_stops_hand_stamped_spans_too(self):
        tracing.enable(False)
        try:
            with tracing.request_trace("decode_step") as tr:
                tracing.add_span("decode/wait", 1.0, 2.0, round=1)
            assert tr is None and tracing.current_trace() is None
        finally:
            tracing.enable(True)

    def test_the_decode_loop_s_stages_are_canonical_and_documented(self):
        import pathlib

        new = {"decode/init", "decode/wait", "decode/handoff",
               "decode/prepare", "decode/deliver"}
        assert new <= set(tracing.STAGES)
        assert len(set(tracing.STAGES)) == len(tracing.STAGES)
        doc = (pathlib.Path(__file__).resolve().parents[2]
               / "docs" / "OBSERVABILITY.md").read_text()
        for stage in tracing.STAGES:
            if stage.startswith("decode/"):
                assert f"| `{stage}` |" in doc, stage

    def test_a_clock_pair_is_one_instant_on_three_clocks(self):
        import time

        before = time.time_ns()
        pair = tracing.clock_pair()
        after = time.time_ns()
        assert before <= pair["unix_ns"] <= after
        assert pair["span_us"] == pytest.approx(
            (pair["perf_counter_s"] - tracing._EPOCH) * 1e6, abs=1e-2)
        # ts 0 of /monitoring/traces on the Unix clock, whenever read.
        again = tracing.clock_pair()
        assert abs(again["epoch_unix_ns"] - pair["epoch_unix_ns"]) < 1e6
        assert pair["epoch_unix_ns"] + pair["span_us"] * 1e3 \
            == pytest.approx(pair["unix_ns"], abs=1e3)

    def test_the_bridge_is_scoped_and_there_is_no_switch_to_leave_it_on(
            self, monkeypatch):
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                entered.append("end " + self.name)

        monkeypatch.setattr(tracing, "_ann_cls", Annotation)
        assert not hasattr(tracing, "bridge_profiler")
        with tracing.request_trace("predict"):
            with tracing.span("device/execute"):
                pass
        assert entered == []
        with pytest.raises(RuntimeError):
            with tracing.profiler_annotations():
                with tracing.request_trace("predict"):
                    with tracing.span("device/execute"):
                        pass
                raise RuntimeError("the capture failed")
        assert entered == ["serving/predict", "device/execute",
                           "end device/execute", "end serving/predict"]
        assert not tracing._bridge


class TestBatchingHandoff:
    def test_traces_cross_the_queue_and_fan_out(self, scheduler):
        executed = []
        runner = BatchedSignatureRunner(
            _host_sig(executed), scheduler, name="q0",
            max_batch_size=4, batch_timeout_s=0.2)
        traces, results = {}, {}

        def call(key, value):
            with tracing.request_trace("predict", model="m") as tr:
                traces[key] = tr
                results[key] = runner.run({"x": np.asarray([value],
                                                           np.float32)})

        threads = [threading.Thread(target=call, args=(k, float(i)))
                   for i, k in enumerate(["a", "b"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        runner.close()

        np.testing.assert_allclose(results["a"]["y"], [0.0])
        np.testing.assert_allclose(results["b"]["y"], [2.0])
        assert executed == [2]  # one merged execution served both callers
        for tr in traces.values():
            stages = tr.stage_durations()
            # The scheduler thread accounted the shared batch work back to
            # EACH rider: queue wait, merge, execute, and the inner
            # signature stages.
            for stage in ("batching/queue_wait", "batching/merge",
                          "batching/execute", "serving/validate",
                          "host/execute"):
                assert stage in stages, (tr.model, sorted(stages))
            assert tr.meta["batch_size"] == 2
            assert tr.meta["queue"] == "q0"
            assert "queue_depth" in tr.meta
            assert tr.meta["padding_bucket"] >= 2

    def test_queue_wait_span_uses_span_clock(self, scheduler):
        runner = BatchedSignatureRunner(
            _host_sig(), scheduler, name="q1",
            max_batch_size=8, batch_timeout_s=0.05)
        with tracing.request_trace("predict") as tr:
            runner.run({"x": np.zeros((1,), np.float32)})
        runner.close()
        (qw,) = [s for s in tr.spans if s[0] == "batching/queue_wait"]
        # Start/end must be ordered and inside the request envelope
        # (catches a monotonic-vs-perf_counter epoch mix-up).
        assert tr.start <= qw[1] <= qw[2] <= tr.end


class TestMetricsSink:
    def test_prometheus_exports_stage_samplers_and_gauges(self, scheduler):
        from min_tfs_client_tpu.server import metrics

        runner = BatchedSignatureRunner(
            _host_sig(), scheduler, name="prom_q",
            max_batch_size=4, batch_timeout_s=0.0)
        waste_before = metrics.padding_wasted_examples.value("prom_q")
        with tracing.request_trace("predict", model="prom_m"):
            runner.run({"x": np.asarray([1.0, 2.0, 3.0], np.float32)})
        runner.close()

        from min_tfs_client_tpu.server.metrics import prometheus_text

        text = prometheus_text()
        # Padding waste counted ONCE per formed batch (3 -> bucket 4 =
        # one wasted slot), not again per rider trace.
        assert metrics.padding_wasted_examples.value("prom_q") \
            == waste_before + 1
        assert ('tpu_serving_stage_latency_bucket{stage='
                '"batching/queue_wait"' in text)
        assert 'tpu_serving_stage_latency_count{stage="host/execute"}' in text
        assert 'tpu_serving_batch_occupancy{queue="prom_q"} 0.75' in text
        # 3 real examples rounded up to the bucket of 4: one wasted slot.
        assert 'tpu_serving_padding_wasted_examples{queue="prom_q"}' in text
        assert 'tpu_serving_batch_queue_depth{queue="prom_q"}' in text

    def test_direct_path_reports_occupancy_by_model(self):
        sig = Signature(
            fn=lambda inputs: {"y": inputs["x"] * 1.0},
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"y": TensorSpec(np.float32, (None,))},
            batch_buckets=(4, 8),
        )
        with tracing.request_trace("predict", model="direct_m") as tr:
            sig.run({"x": np.asarray([1.0, 2.0, 3.0], np.float32)})
        assert tr.meta["batch_size"] == 3
        assert tr.meta["padding_bucket"] == 4

        from min_tfs_client_tpu.server.metrics import prometheus_text

        text = prometheus_text()
        assert 'tpu_serving_batch_occupancy{queue="direct_m"} 0.75' in text
        assert 'tpu_serving_batch_queue_depth{queue="direct_m"} 0.0' in text


class TestRingAndChromeTrace:
    def test_ring_is_bounded(self):
        for i in range(300):
            with tracing.request_trace("predict", model=f"m{i}"):
                pass
        traces = tracing.ring_snapshot()
        assert len(traces) == 256  # default capacity
        assert traces[-1].model == "m299"
        assert tracing.ring_snapshot(limit=5)[0].model == "m295"

    def test_chrome_trace_shape(self):
        with tracing.request_trace("predict", model="m"):
            with tracing.span("serving/validate"):
                pass
        blob = tracing.chrome_trace()
        payload = json.loads(json.dumps(blob))  # strictly JSON-serializable
        events = payload["traceEvents"]
        phases = {e["ph"] for e in events}
        assert phases == {"M", "X"}
        xs = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"request/predict",
                                           "serving/validate"}
        for e in xs:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert e["pid"] == 1 and e["tid"] > 0

    def test_stage_breakdown_aggregates(self):
        for _ in range(4):
            with tracing.request_trace("predict"):
                with tracing.span("device/execute"):
                    pass
        table = tracing.stage_breakdown()
        assert table["device/execute"]["n"] == 4
        assert table["device/execute"]["p50_ms"] >= 0


class TestTracesEndpoint:
    def test_endpoint_returns_chrome_trace_json(self):
        from min_tfs_client_tpu.server import rest

        with tracing.request_trace("predict", model="m"):
            with tracing.span("serving/validate"):
                pass
        code, ctype, body = rest.route_request(
            None, None, "GET", "/monitoring/traces", b"")
        assert code == 200 and ctype == "application/json"
        payload = json.loads(body)
        assert any(e["name"] == "request/predict"
                   for e in payload["traceEvents"])

        code, _, body = rest.route_request(
            None, None, "GET", "/monitoring/traces?limit=1&summary=1", b"")
        assert code == 200
        summary = json.loads(body)
        assert summary["traces"] == 1
        assert "serving/validate" in summary["stages"]

    def test_endpoint_rejects_bad_limit(self):
        from min_tfs_client_tpu.server import rest

        code, _, body = rest.route_request(
            None, None, "GET", "/monitoring/traces?limit=nope", b"")
        assert code == 400
        assert "limit" in json.loads(body)["error"]


class TestPartitionedStageAttribution:
    def test_partitioned_signature_skips_host_execute_envelope(self):
        """A partitioned on_host signature emits the partition's own
        stage spans; an enveloping host/execute span would double-count
        them in stage sums and file device time under a host stage."""
        sig = Signature(
            fn=lambda inputs: {"y": np.asarray(inputs["x"]) * 2.0},
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"y": TensorSpec(np.float32, (None,))},
            on_host=True,
        )
        sig.partition = object()  # marker: fn routes through partition.run
        with tracing.request_trace("predict", model="m") as tr:
            sig.run({"x": np.asarray([1.0], np.float32)})
        assert "host/execute" not in tr.stage_durations()

        sig.partition = None
        with tracing.request_trace("predict", model="m") as tr:
            sig.run({"x": np.asarray([1.0], np.float32)})
        assert "host/execute" in tr.stage_durations()


class TestFleetTraceContext:
    """Fleet-scope trace ids (docs/OBSERVABILITY.md "Fleet tracing"):
    minting, wire adoption, sanitization, and the multi-process
    Chrome-trace rendering the router's stitcher builds on."""

    def test_every_trace_gets_a_unique_id(self):
        ids = {tracing.RequestTrace("predict").trace_id
               for _ in range(64)}
        assert len(ids) == 64
        assert all(tracing.valid_trace_id(i) for i in ids)

    def test_request_trace_adopts_incoming_id(self):
        with tracing.adopt("router-abc-123"):
            with tracing.request_trace("predict") as tr:
                pass
        assert tr.trace_id == "router-abc-123"
        # Outside the adopt block a fresh id is minted again.
        with tracing.request_trace("predict") as tr2:
            pass
        assert tr2.trace_id != "router-abc-123"

    def test_adoption_sanitizes_wire_junk(self):
        for junk in ("", "a" * 65, "bad id", "a\nb", "x" * 3, None):
            with tracing.adopt(junk):
                with tracing.request_trace("predict") as tr:
                    pass
            assert tr.trace_id != junk, junk
            assert tracing.valid_trace_id(tr.trace_id)
        # bytes-valued gRPC metadata adopts after decode
        with tracing.adopt(b"deadbeef01"):
            with tracing.request_trace("predict") as tr:
                pass
        assert tr.trace_id == "deadbeef01"

    def test_find_traces_by_id(self):
        with tracing.adopt("fleet-id-7"):
            with tracing.request_trace("predict"):
                pass
        with tracing.request_trace("predict"):
            pass
        found = tracing.find_traces("fleet-id-7")
        assert [t.trace_id for t in found] == ["fleet-id-7"]

    def test_chrome_trace_process_lanes_and_wall_clock(self):
        import time as _time

        with tracing.adopt("lane-id-1"):
            with tracing.request_trace("predict") as tr:
                with tracing.span("serving/serialize"):
                    pass
        payload = tracing.chrome_trace([tr], pid=2,
                                       process_name="backend b1",
                                       clock="wall")
        meta = [e for e in payload["traceEvents"]
                if e.get("name") == "process_name"]
        assert meta and meta[0]["args"]["name"] == "backend b1"
        envelope = [e for e in payload["traceEvents"]
                    if e.get("cat") == "request"][0]
        assert envelope["pid"] == 2
        assert envelope["args"]["trace_id"] == "lane-id-1"
        # wall clock: microseconds since the unix epoch, ~now
        assert abs(envelope["ts"] / 1e6 - _time.time()) < 60
        # default clock stays process-relative (backward compatible)
        legacy = tracing.chrome_trace([tr])
        legacy_env = [e for e in legacy["traceEvents"]
                      if e.get("cat") == "request"][0]
        assert legacy_env["ts"] < 1e14 and legacy_env["pid"] == 1

    def test_set_status_records_on_current_trace(self):
        with tracing.request_trace("predict") as tr:
            tracing.set_status("UNAVAILABLE")
        assert tr.status == "UNAVAILABLE"

    def test_configure_ring_resizes(self):
        original = tracing.ring_capacity()
        try:
            tracing.configure_ring(3)
            assert tracing.ring_capacity() == 3
            for _ in range(5):
                with tracing.request_trace("predict"):
                    pass
            assert len(tracing.ring_snapshot()) == 3
            tracing.configure_ring(0)  # 0 = keep current
            assert tracing.ring_capacity() == 3
        finally:
            tracing.configure_ring(original)

    def test_traces_endpoint_trace_id_filter(self):
        from min_tfs_client_tpu.server import rest

        with tracing.adopt("endpoint-id-9"):
            with tracing.request_trace("predict"):
                pass
        with tracing.request_trace("predict"):
            pass
        status, _, body = rest._traces_reply("trace_id=endpoint-id-9")
        assert status == 200
        payload = json.loads(body)
        assert payload["otherData"]["trace_id"] == "endpoint-id-9"
        envelopes = [e for e in payload["traceEvents"]
                     if e.get("cat") == "request"]
        assert len(envelopes) == 1
        assert envelopes[0]["args"]["trace_id"] == "endpoint-id-9"
        assert envelopes[0]["ts"] > 1e14  # wall clock for stitching

    def test_rest_route_adopts_header(self):
        from min_tfs_client_tpu.server import rest

        sig = Signature(
            fn=lambda inputs: {
                "y": np.asarray(inputs["x"], np.float32) * 2.0},
            inputs={"x": TensorSpec(np.float32, (None, 2))},
            outputs={"y": TensorSpec(np.float32, (None, 2))},
            on_host=True,
        )
        handlers = _FakeHandlers(sig)
        status, _, _ = rest.route_request(
            handlers, None, "POST", "/v1/models/m:predict",
            json.dumps({"instances": [{"x": [1.0, 2.0]}]}).encode(),
            trace_id="rest-adopted-1")
        assert status == 200
        assert tracing.find_traces("rest-adopted-1")


class _FakeHandlers:
    """Just enough of server.handlers.Handlers for the REST route: a
    predict() that opens the standard request trace."""

    def __init__(self, sig):
        self._sig = sig

    def predict(self, request):
        from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
        from min_tfs_client_tpu.tensor.codec import (
            ndarray_to_tensor_proto,
            tensor_proto_to_ndarray,
        )

        with tracing.request_trace("predict", model="m"):
            inputs = {k: tensor_proto_to_ndarray(v)
                      for k, v in request.inputs.items()}
            outputs = self._sig.run(inputs)
            response = apis.PredictResponse()
            for alias, arr in outputs.items():
                response.outputs[alias].CopyFrom(
                    ndarray_to_tensor_proto(arr))
            return response
