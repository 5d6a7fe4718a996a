"""Cost-attribution unit suite (observability/costs.py): the
conservation law (per-rider amortized device shares sum to the measured
batch execute wall), vector construction, fanout cost splitting, the
rolling windows, the JSONL wide-event log (sampling determinism + size
bound), the tick duty-cycle registry, and the servecost aggregator."""

import json
import threading
import time

import numpy as np
import pytest

from min_tfs_client_tpu.batching.scheduler import SharedBatchScheduler
from min_tfs_client_tpu.batching.session import BatchedSignatureRunner
from min_tfs_client_tpu.observability import costs, tracing
from min_tfs_client_tpu.observability.servecost import (
    DATASET_SCHEMA,
    aggregate,
)
from min_tfs_client_tpu.observability.servecost import main as servecost_main
from min_tfs_client_tpu.servables.servable import Signature, TensorSpec


@pytest.fixture(autouse=True)
def _clean_cost_state():
    def scrub():
        costs.tracker.log.close()
        costs.reset()
        costs.reset_ticks()
        costs.configure(log_dir="", sample=1.0, context={},
                        max_log_bytes=256 * 1024 * 1024)

    scrub()
    yield
    scrub()


def _finished_trace(model="m", signature="s", *, spans=(), meta=None,
                    cost_events=None, duration_s=0.01):
    trace = tracing.RequestTrace("predict", model=model,
                                 signature=signature)
    t0 = trace.start
    for name, start_s, end_s in spans:
        trace.add_span(name, t0 + start_s, t0 + end_s)
    if meta:
        trace.annotate(**meta)
    if cost_events:
        trace.add_cost(**cost_events)
    trace.end = t0 + duration_s
    return trace


class TestVectorFromTrace:
    def test_batched_share_and_padding(self):
        # Merged batch: 4 real examples padded to bucket 8, this rider
        # brought 2 of them, the batch's execute wall was 4ms.
        trace = _finished_trace(
            spans=[("batching/queue_wait", 0.0, 0.001),
                   ("batching/execute", 0.001, 0.005)],
            meta={"queue": "q", "batch_size": 4, "padding_bucket": 8,
                  "request_examples": 2})
        v = costs.vector_from_trace(trace)
        assert v["queue_wait_us"] == pytest.approx(1000.0, rel=1e-6)
        # share = wall * own/total = 4000 * 2/4
        assert v["device_execute_us"] == pytest.approx(2000.0, rel=1e-6)
        # padding slice = share * (bucket-total)/bucket = 2000 * 0.5
        assert v["padding_waste_us"] == pytest.approx(1000.0, rel=1e-6)

    def test_windowed_path_uses_dispatch_plus_materialize(self):
        trace = _finished_trace(
            spans=[("batching/dispatch", 0.0, 0.002),
                   ("batching/materialize", 0.004, 0.006)],
            meta={"queue": "q", "batch_size": 2, "padding_bucket": 2,
                  "request_examples": 1})
        v = costs.vector_from_trace(trace)
        assert v["device_execute_us"] == pytest.approx(2000.0, rel=1e-6)
        assert v["padding_waste_us"] == 0.0

    def test_direct_execution_bills_own_device_span(self):
        trace = _finished_trace(
            spans=[("device/execute", 0.0, 0.003)],
            meta={"batch_size": 2, "padding_bucket": 4})
        v = costs.vector_from_trace(trace)
        assert v["device_execute_us"] == pytest.approx(3000.0, rel=1e-6)
        assert v["padding_waste_us"] == pytest.approx(1500.0, rel=1e-6)

    def test_cost_events_and_host_islands(self):
        trace = _finished_trace(
            spans=[("partition/pre", 0.0, 0.001),
                   ("pipeline/host", 0.001, 0.002),
                   ("decode/tick", 0.002, 0.003)],
            cost_events={"compile_us": 1500.0, "transfer_bytes": 4096,
                         "kv_page_ticks": 3})
        v = costs.vector_from_trace(trace)
        assert v["host_island_us"] == pytest.approx(2000.0, rel=1e-6)
        assert v["decode_tick_us"] == pytest.approx(1000.0, rel=1e-6)
        assert v["compile_us"] == pytest.approx(1500.0)
        assert v["transfer_bytes"] == 4096
        assert v["kv_page_ticks"] == 3


    def test_a_decode_round_is_billed_inside_the_request_only(self):
        """The pool's loop runs ahead of the requests: the request that
        collects a token carries its round's spans, whose tick and fetch
        may lie before it began. It is billed what it waited through."""
        trace = _finished_trace(
            spans=[("decode/tick", -0.004, -0.003),    # before the request
                   ("decode/fetch", -0.003, 0.002),    # 2 ms of 5 inside
                   ("decode/prefill_chunk", 0.002, 0.003),
                   ("decode/deliver", 0.002, 0.004)],  # not a device stage
            duration_s=0.005)
        v = costs.vector_from_trace(trace)
        assert v["decode_tick_us"] == pytest.approx(3000.0, rel=1e-6)
        assert v["decode_tick_us"] <= v["total_us"]


class TestFanoutCostSplit:
    def test_add_cost_splits_across_riders(self):
        a = tracing.RequestTrace("predict")
        b = tracing.RequestTrace("predict")
        fan = tracing.fanout([a, b])
        fan.add_cost(compile_us=1000.0, transfer_bytes=512)
        assert a.costs["compile_us"] == pytest.approx(500.0)
        assert b.costs["transfer_bytes"] == pytest.approx(256.0)

    def test_compile_attribution_through_runtime_ledger(self):
        from min_tfs_client_tpu.observability import runtime

        trace = tracing.RequestTrace("predict", model="m")
        with tracing.activate(trace):
            runtime.record_compile("m:1:sig", "f32[4]", 0.002)
        assert trace.costs["compile_us"] == pytest.approx(2000.0)

    def test_add_cost_accumulates(self):
        trace = tracing.RequestTrace("predict")
        trace.add_cost(compile_us=100.0)
        trace.add_cost(compile_us=50.0)
        assert trace.costs["compile_us"] == pytest.approx(150.0)


class TestConservation:
    def test_amortized_shares_sum_to_measured_batch_wall(self):
        """The acceptance law: for one merged batch, the riders'
        amortized device-execute shares sum to the MEASURED batch
        execute wall within +-5%."""
        def fn(inputs):
            time.sleep(0.02)  # a wall the shares must reconstruct
            return {"y": np.asarray(inputs["x"]) * 2.0}

        sig = Signature(
            fn=fn,
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"y": TensorSpec(np.float32, (None,))},
            on_host=True)
        scheduler = SharedBatchScheduler(num_threads=1)
        runner = BatchedSignatureRunner(
            sig, scheduler, name="cost-conservation", max_batch_size=8,
            batch_timeout_s=0.25)
        sizes = [1, 2, 1, 3]
        traces: list = [None] * len(sizes)
        barrier = threading.Barrier(len(sizes))

        def caller(i, n):
            barrier.wait()
            with tracing.request_trace("predict", model="m",
                                       signature="s") as trace:
                traces[i] = trace
                runner.run({"x": np.ones((n,), np.float32)})

        threads = [threading.Thread(target=caller, args=(i, n),
                                    name=f"cost-rider-{i}", daemon=True)
                   for i, n in enumerate(sizes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
            assert not t.is_alive()
        scheduler.stop()
        # All riders merged into ONE batch (the law is per-batch).
        totals = {t.meta.get("batch_size") for t in traces}
        assert totals == {sum(sizes)}, \
            f"riders did not co-batch: batch sizes {totals}"
        measured_wall_us = traces[0].stage_durations()[
            "batching/execute"] * 1e6
        vectors = [costs.vector_from_trace(t) for t in traces]
        share_sum = sum(v["device_execute_us"] for v in vectors)
        assert share_sum == pytest.approx(measured_wall_us, rel=0.05), (
            f"amortized shares sum {share_sum:.1f}us vs measured batch "
            f"wall {measured_wall_us:.1f}us")
        # Each rider's share is proportional to its real examples.
        for v, n in zip(vectors, sizes):
            assert v["device_execute_us"] == pytest.approx(
                measured_wall_us * n / sum(sizes), rel=0.05)
        # request_examples rode each trace (the numerator).
        assert [t.meta["request_examples"] for t in traces] == sizes


class TestTrackerWindows:
    def test_snapshot_means_and_totals(self):
        for n in range(4):
            costs.observe_trace(_finished_trace(
                spans=[("device/execute", 0.0, 0.001 * (n + 1))]))
        snap = costs.snapshot()
        assert snap["schema"] == costs.SCHEMA
        (entry,) = snap["entries"]
        assert entry["model"] == "m" and entry["signature"] == "s"
        assert entry["count"] == 4
        assert entry["mean"]["device_execute_us"] == pytest.approx(
            2500.0, rel=1e-3)
        assert entry["total"]["device_execute_us"] == pytest.approx(
            10000.0, rel=1e-3)

    def test_router_traces_are_skipped(self):
        trace = tracing.RequestTrace("route/grpc", model="m")
        trace.end = trace.start + 0.001
        costs.observe_trace(trace)
        assert costs.snapshot()["entries"] == []

    def test_key_cap_counts_drops(self):
        for i in range(costs._MAX_TRACKED_KEYS + 5):
            costs.tracker.record(f"m{i}", "s",
                                 {f: 0.0 for f in costs.VECTOR_FIELDS})
        assert costs.snapshot()["dropped_keys"] == 5

    def test_export_gauges_sets_cost_metrics(self):
        from min_tfs_client_tpu.server import metrics

        costs.observe_trace(_finished_trace(
            spans=[("device/execute", 0.0, 0.002)],
            cost_events={"kv_page_ticks": 4}))
        costs.note_tick("poolX", 0.01)
        costs.export_gauges()
        assert metrics.cost_device_execute_us.value("m", "s") == \
            pytest.approx(2000.0, rel=1e-3)
        assert metrics.cost_kv_page_ticks.value("m", "s") == \
            pytest.approx(4.0)
        assert metrics.tick_utilization.value("poolX") > 0.0


class TestCostLog:
    def test_records_carry_trace_id_and_schema(self, tmp_path):
        costs.configure(log_dir=str(tmp_path), sample=1.0,
                        context={"kv_block_size": 4})
        trace = _finished_trace()
        costs.observe_trace(trace)
        (path,) = sorted(tmp_path.glob("*.jsonl"))
        lines = [json.loads(line)
                 for line in path.read_text().splitlines()]
        assert lines[0]["kind"] == "meta"
        assert lines[0]["schema"] == costs.SCHEMA
        assert lines[0]["context"] == {"kv_block_size": 4}
        (record,) = lines[1:]
        assert record["kind"] == "cost"
        assert record["trace_id"] == trace.trace_id
        assert record["model"] == "m"
        for field in costs.VECTOR_FIELDS:
            assert field in record

    def test_sample_zero_writes_nothing(self, tmp_path):
        costs.configure(log_dir=str(tmp_path), sample=0.0)
        costs.observe_trace(_finished_trace())
        assert list(tmp_path.glob("*.jsonl")) == []
        assert costs.snapshot()["log"]["sampled_out"] == 1
        # The aggregates still ran — sampling only gates the file.
        assert costs.snapshot()["entries"][0]["count"] == 1

    def test_sampling_is_deterministic_in_trace_id(self, tmp_path):
        costs.configure(log_dir=str(tmp_path), sample=0.5)
        log = costs.tracker.log
        for trace_id in ("abcd1234", "ffff0000", "1234beef"):
            assert log._sampled(trace_id) == log._sampled(trace_id)

    def test_size_bound_drops_and_counts(self, tmp_path):
        costs.configure(log_dir=str(tmp_path), sample=1.0,
                        max_log_bytes=400)
        for _ in range(10):
            costs.observe_trace(_finished_trace())
        stats = costs.snapshot()["log"]
        assert stats["dropped"] > 0
        assert stats["bytes"] <= 400 + 600  # header + one record overshoot
        # Every line actually on disk is still well-formed JSON.
        (path,) = sorted(tmp_path.glob("*.jsonl"))
        for line in path.read_text().splitlines():
            json.loads(line)


class TestTickUtilization:
    def test_busy_fraction_over_window(self):
        costs.note_tick("p", 0.2)
        util = costs.tick_utilization()
        # Pool age ~0 => utilization clamps to 1.0; it must never
        # exceed 1.
        assert 0.0 < util["p"] <= 1.0

    def test_prunes_outside_window_entries(self):
        costs.note_tick("p", 0.1)
        with costs._tick_lock:
            ring = costs._ticks["p"]
            t, b = ring[0]
            ring[0] = (t - costs._TICK_WINDOW_S - 5.0, b)
            costs._tick_started["p"] = t - costs._TICK_WINDOW_S - 5.0
        assert costs.tick_utilization()["p"] == 0.0


class TestServecost:
    def _write_log(self, tmp_path):
        costs.configure(log_dir=str(tmp_path), sample=1.0,
                        context={"max_in_flight_batches": 4})
        for n in range(3):
            costs.observe_trace(_finished_trace(
                spans=[("device/execute", 0.0, 0.001 * (n + 1))]))
        costs.tracker.log.close()

    def test_aggregate_produces_schema_versioned_dataset(self, tmp_path):
        self._write_log(tmp_path)
        dataset = aggregate([str(tmp_path)])
        assert dataset["schema"] == DATASET_SCHEMA
        assert dataset["records"] == 3
        assert dataset["malformed"] == 0
        assert dataset["contexts"] == [{"max_in_flight_batches": 4}]
        agg = dataset["models"]["m"]["s"]
        assert agg["count"] == 3
        assert agg["mean"]["device_execute_us"] == pytest.approx(
            2000.0, rel=1e-3)
        assert "device_execute_us_p50" in agg
        assert "total_us_p99" in agg

    def test_malformed_lines_counted_not_hidden(self, tmp_path):
        self._write_log(tmp_path)
        (path,) = sorted(tmp_path.glob("*.jsonl"))
        with open(path, "a") as f:
            f.write("{not json\n")
        dataset = aggregate([str(tmp_path)])
        assert dataset["records"] == 3
        assert dataset["malformed"] == 1

    def test_unknown_schema_refused(self, tmp_path):
        (tmp_path / "bad.jsonl").write_text(
            json.dumps({"schema": "servecost/999", "kind": "cost"}) + "\n")
        with pytest.raises(ValueError, match="servecost/999"):
            aggregate([str(tmp_path)])

    def test_cli_writes_artifact(self, tmp_path):
        self._write_log(tmp_path / "logs")
        out = tmp_path / "dataset.json"
        rc = servecost_main([str(tmp_path / "logs"), "--out", str(out)])
        assert rc == 0
        dataset = json.loads(out.read_text())
        assert dataset["schema"] == DATASET_SCHEMA
        assert dataset["records"] == 3

    def test_cli_empty_is_an_error(self, tmp_path):
        (tmp_path / "empty.jsonl").write_text("")
        out = tmp_path / "dataset.json"
        rc = servecost_main([str(tmp_path), "--out", str(out)])
        assert rc == 1
