"""The sampling-profiler plane (observability/profiling.py): frame
trees, subsystem/stage attribution joins, sampler lifecycle, the folded
(speedscope/flamegraph.pl) rendering, diff views, and the watchdog's
hot-frame alert join."""

import json
import pathlib
import re
import threading
import time

import pytest

from min_tfs_client_tpu.observability import profiling, tracing
from tests import fixtures

COLLAPSED_LINE = re.compile(r"^(?P<stack>\S.*) (?P<count>\d+)$")


@pytest.fixture(autouse=True)
def _fresh_module_state():
    """Each test gets a stopped, unconfigured module singleton and a
    disarmed stage registry."""
    profiling.stop()
    with profiling._singleton_lock:
        profiling._sampler = None
        profiling._profile_dir = ""
    tracing.track_stages(False)
    yield
    profiling.stop()
    with profiling._singleton_lock:
        profiling._sampler = None
        profiling._profile_dir = ""
    tracing.track_stages(False)


def _busy_thread(name: str, stage: str | None = None,
                 delay: float = 0.0):
    """A named thread spinning CPU (optionally inside a tracing span)
    until the returned event is set. `delay` postpones the span entry:
    stage registration is edge-triggered at span __enter__, so the
    span must open AFTER the sampler arms stage tracking."""
    stop = threading.Event()

    def spin():
        if delay:
            time.sleep(delay)
        if stage is not None:
            with tracing.span(stage):
                while not stop.is_set():
                    sum(i * i for i in range(500))
        else:
            while not stop.is_set():
                sum(i * i for i in range(500))

    t = threading.Thread(target=spin, name=name, daemon=True)
    t.start()
    return stop, t


class TestSubsystemAttribution:
    @pytest.mark.parametrize("name,expected", [
        ("batch-worker-3", "batch-workers"),
        ("adaptive-batch-0", "batch-workers"),
        ("serial-device-batch-1", "tick-batcher"),
        ("inflight-native", "completion"),
        ("trace-metrics-export", "tracing-drain"),
        ("router-aio-data-plane", "router-event-loop"),
        ("grpc-aio-loop", "grpc-event-loop"),
        ("Thread-2 (_poll_wrapper)", "grpc-server"),
        ("router-membership-poll", "membership-poller"),
        ("router-grpc_0", "router-data-plane"),
        ("watchdog-ticker", "watchdog"),
        ("profile-sampler", "profiler"),
        ("rest-server", "rest-frontend"),
        ("router-rest-server", "rest-frontend"),
        ("ThreadPoolExecutor-0_3", "grpc-handlers"),
        ("Thread-1 (_serve)", "grpc-server"),
        ("MainThread", "main"),
        ("Dummy-7", "foreign"),
        ("something-unheard-of", "other"),
    ])
    def test_thread_name_maps_to_subsystem(self, name, expected):
        assert profiling.subsystem_for(name) == expected


class TestFrameTree:
    def test_fold_tracks_self_total_and_samples(self):
        tree = profiling.FrameTree()
        tree.fold(["a", "b", "c"])
        tree.fold(["a", "b"])
        assert tree.samples == 2
        assert tree.key_self == {"c": 1, "b": 1}
        assert tree.key_total == {"a": 2, "b": 2, "c": 1}

    def test_recursion_counts_total_once_per_sample(self):
        tree = profiling.FrameTree()
        tree.fold(["f", "f", "f"])
        assert tree.key_total["f"] == 1
        assert tree.key_self["f"] == 1

    def test_collapsed_lines_carry_full_paths_and_counts(self):
        tree = profiling.FrameTree()
        tree.fold(["a", "b"])
        tree.fold(["a", "b"])
        tree.fold(["a"])
        out: dict = {}
        tree.collapsed_into(out, "worker")
        assert out == {"worker;a;b": 2, "worker;a": 1}

    def test_node_budget_overflows_into_truncation_leaf(self):
        tree = profiling.FrameTree(max_nodes=2)
        tree.fold(["a", "b"])        # fills the budget
        tree.fold(["a", "x", "y"])   # x would be node 3 -> overflow sink
        assert tree.truncated == 1
        out: dict = {}
        tree.collapsed_into(out, "t")
        assert out["t;a;(tree-truncated)"] == 1
        # The flat counters stay exact even for overflowed samples.
        assert tree.key_self["y"] == 1
        assert tree.samples == 2

    def test_summary_reports_top_frames_with_shares(self):
        tree = profiling.FrameTree()
        for _ in range(3):
            tree.fold(["a", "hot"])
        tree.fold(["a", "cold"])
        body = tree.summary(limit=1)
        assert body["samples"] == 4
        assert body["top_self"] == [
            {"frame": "hot", "samples": 3, "pct": 75.0}]
        assert body["top_total"][0] == {
            "frame": "a", "samples": 4, "pct": 100.0}


class TestStageRegistry:
    def test_disarmed_spans_leave_no_registry_entries(self):
        with tracing.span("serving/deserialize"):
            assert tracing.active_stage(threading.get_ident()) is None
        assert tracing.active_stages() == {}

    def test_armed_spans_push_and_pop_nested(self):
        ident = threading.get_ident()
        tracing.track_stages(True)
        try:
            with tracing.span("serving/deserialize"):
                assert tracing.active_stage(ident) == "serving/deserialize"
                with tracing.span("device/execute"):
                    assert tracing.active_stage(ident) == "device/execute"
                assert tracing.active_stage(ident) == "serving/deserialize"
            assert tracing.active_stage(ident) is None
        finally:
            tracing.track_stages(False)

    def test_disarm_clears_stale_entries(self):
        tracing.track_stages(True)
        span = tracing.span("host/execute")
        span.__enter__()
        assert tracing.active_stages()
        tracing.track_stages(False)
        assert tracing.active_stages() == {}
        span.__exit__(None, None, None)  # stale pop is a harmless no-op


class TestStackSampler:
    def test_samples_named_threads_with_stage_join(self):
        harness = fixtures.harness_threads()
        sampler = profiling.StackSampler(hz=250.0)
        sampler.start()  # arms stage tracking BEFORE the span opens
        stop, t = _busy_thread("batch-worker-0",
                               stage="serving/deserialize")
        try:
            time.sleep(0.4)
        finally:
            stop.set()
            t.join()
            sampler.stop()
        body = sampler.summary()
        assert body["samples"] > 10
        # Over the threads this process started (under pytest-xdist the
        # runner's own, which no one can name, are sampled too).
        samples, named, _ = fixtures.own_attribution(body["threads"],
                                                     harness)
        assert samples > 10 and named / samples >= 0.95
        if not harness:
            assert body["attributed_pct"] >= 95.0
        assert "batch-worker-0" in body["threads"]
        worker = body["threads"]["batch-worker-0"]
        assert worker["subsystem"] == "batch-workers"
        assert worker["samples"] > 0
        assert body["subsystems"]["batch-workers"] == worker["samples"]
        assert "serving/deserialize" in body["stages"]
        # The sampler never samples itself.
        assert "profile-sampler" not in body["threads"]

    def test_stop_joins_ticker_and_disarms_stage_tracking(self):
        sampler = profiling.StackSampler(hz=100.0)
        sampler.start()
        assert sampler.running()
        assert tracing.stage_tracking()
        sampler.stop()
        assert not sampler.running()
        assert not tracing.stage_tracking()
        assert not any(th.name == "profile-sampler"
                       for th in threading.enumerate())

    def test_zero_hz_never_starts_a_ticker(self):
        sampler = profiling.StackSampler(hz=0.0)
        sampler.start()
        assert not sampler.running()
        sampler.stop()

    def test_collapsed_output_is_speedscope_folded_format(self):
        stop, t = _busy_thread("batch-worker-1")
        sampler = profiling.StackSampler(hz=250.0)
        sampler.start()
        try:
            time.sleep(0.3)
        finally:
            stop.set()
            t.join()
            sampler.stop()
        text = sampler.collapsed()
        lines = text.splitlines()
        assert lines
        total = 0
        for line in lines:
            m = COLLAPSED_LINE.match(line)
            assert m, f"not a folded-stack line: {line!r}"
            frames = m.group("stack").split(";")
            assert len(frames) >= 1 and all(frames)
            total += int(m.group("count"))
        assert total == sampler.summary()["samples"]

    def test_capture_window_works_without_running_ticker(self):
        # The span opens ~50ms INTO the capture window: capture's
        # temporary stage arming must catch it.
        stop, t = _busy_thread("batch-worker-2", stage="host/execute",
                               delay=0.05)
        sampler = profiling.StackSampler(hz=0.0)
        try:
            body = sampler.capture_summary(seconds=0.3, hz=400.0)
        finally:
            stop.set()
            t.join()
        assert body["samples"] > 5
        assert "batch-worker-2" in body["threads"]
        assert "host/execute" in body["stages"]
        assert body["capture"]["hz"] == 400.0
        # The temporary arming was undone (no ticker running).
        assert not tracing.stage_tracking()

    def test_diff_reports_risers_against_baseline(self):
        sampler = profiling.StackSampler(hz=200.0, baseline_bucket_s=0.1,
                                         baseline_buckets=4)
        sampler.start()
        try:
            time.sleep(0.35)  # idle baseline buckets accumulate
            stop, t = _busy_thread("batch-worker-3")
            try:
                diff = sampler.diff(seconds=0.25, hz=400.0)
            finally:
                stop.set()
                t.join()
        finally:
            sampler.stop()
        assert diff["baseline_samples"] > 0
        assert diff["window_samples"] > 0
        assert diff["risers"], diff
        assert all(d["delta_pct"] > 0 for d in diff["risers"])
        assert all(d["delta_pct"] < 0 for d in diff["fallers"])


class TestModuleFacade:
    def test_payload_pins_top_level_keys(self):
        profiling.configure(hz=0.0)
        body = profiling.payload()
        assert set(body) == {"sampler", "threads", "subsystems", "stages"}
        assert body["sampler"]["running"] is False

    def test_configure_start_stop_roundtrip(self):
        profiling.configure(hz=150.0)
        profiling.start()
        assert profiling.running()
        time.sleep(0.1)
        profiling.configure(hz=0.0)  # reconfigure stops the old ticker
        assert not profiling.running()
        assert not any(th.name == "profile-sampler"
                       for th in threading.enumerate())

    def test_top_hot_frames_empty_without_data(self):
        assert profiling.top_hot_frames() == []

    def test_top_hot_frames_excludes_profiler_itself(self):
        stop, t = _busy_thread("batch-worker-4")
        profiling.configure(hz=250.0)
        profiling.start()
        try:
            time.sleep(0.3)
        finally:
            stop.set()
            t.join()
        frames = profiling.top_hot_frames(3)
        profiling.stop()
        assert frames
        assert all(set(f) == {"frame", "samples", "pct"} for f in frames)

    def test_device_capture_requires_profile_dir(self):
        profiling.configure(hz=0.0, profile_dir="")
        with pytest.raises(ValueError, match="profile_dir"):
            profiling.device_capture(0.1)


class TestTracedCapture:
    """One clock with the device capture (docs/OBSERVABILITY.md
    "Profiling plane"): both in-process captures write host_clock.json
    and mirror the stage spans into the capture for its length only."""

    @staticmethod
    def _span_every_10ms(stop, seen):
        while not stop.wait(0.01):
            with tracing.request_trace("predict") as trace:
                with tracing.span("device/execute"):
                    pass
            name, t0, _, _ = trace.spans[0]
            seen.append((tracing._bridge, tracing._us(t0)))

    def _capture(self, take):
        """Run `take()` (one capture) beside a thread that records a
        span every 10 ms; returns what the thread saw."""
        stop, seen = threading.Event(), []
        worker = threading.Thread(target=self._span_every_10ms,
                                  args=(stop, seen), name="batch-worker-7")
        worker.start()
        try:
            time.sleep(0.05)
            took = take()
            time.sleep(0.05)
        finally:
            stop.set()
            worker.join()
        return took, seen

    def _check(self, clock_path, seen):
        clock = json.loads(clock_path.read_text())
        assert clock["schema"] == "host_clock/1"
        zero, start, stop = clock["zero"], clock["start"], clock["stop"]
        assert zero["span_us"] <= start["span_us"] < stop["span_us"]
        for pair in (zero, start, stop):
            # One pair is one instant on all three clocks.
            assert pair["span_us"] == pytest.approx(
                (pair["unix_ns"] - pair["epoch_unix_ns"]) / 1e3, abs=1.0)
        assert clock["epoch_unix_ns"] == zero["epoch_unix_ns"]
        assert abs(clock["drift_ns"]) < 50_000_000
        # The bridge was on for the capture's length and for no longer.
        assert not tracing._bridge
        inside = [ts for bridged, ts in seen if bridged]
        outside = [ts for bridged, ts in seen if not bridged]
        assert inside and outside
        assert all(start["span_us"] <= ts <= stop["span_us"]
                   for ts in inside)
        assert all(ts < start["span_us"] + 1e4 or ts > stop["span_us"] - 1e4
                   for ts in outside)
        # A span recorded inside the capture maps onto the capture's
        # clock (ns from its zero) between the capture's own two ends.
        for ts in inside:
            mapped = (ts - zero["span_us"]) * 1e3
            assert (start["unix_ns"] - zero["unix_ns"] - 1e6 <= mapped
                    <= stop["unix_ns"] - zero["unix_ns"] + 1e6)

    def test_device_capture_writes_the_clock_and_scopes_the_bridge(
            self, tmp_path):
        took, seen = self._capture(
            lambda: profiling.device_capture(0.2, str(tmp_path)))
        assert profiling.HOST_CLOCK_FILE in took["files"]
        assert any(f.endswith(".xplane.pb") for f in took["files"])
        self._check(pathlib.Path(took["profile_dir"])
                    / profiling.HOST_CLOCK_FILE, seen)

    def test_profiler_service_profile_does_the_same(self, tmp_path):
        from min_tfs_client_tpu.protos import tf_profiler_pb2 as pb
        from min_tfs_client_tpu.server.profiler import ProfilerServiceImpl

        request = pb.ProfileRequest(duration_ms=200,
                                    repository_root=str(tmp_path))
        response, seen = self._capture(
            lambda: ProfilerServiceImpl().Profile(request))
        assert not response.empty_trace
        assert profiling.HOST_CLOCK_FILE in [
            tool.name for tool in response.tool_data]
        self._check(tmp_path / profiling.HOST_CLOCK_FILE, seen)

    def test_a_capture_that_fails_to_start_leaves_the_bridge_off(
            self, tmp_path, monkeypatch):
        import jax

        def refuse(log_dir):
            raise RuntimeError("one capture at a time")

        monkeypatch.setattr(jax.profiler, "trace", refuse)
        with pytest.raises(RuntimeError, match="one capture"):
            profiling.device_capture(0.1, str(tmp_path))
        assert not tracing._bridge


class TestWatchdogHotFrameJoin:
    def _emit_with(self, det_cls):
        from min_tfs_client_tpu.observability.watchdog import (
            WARN,
            Finding,
            Watchdog,
        )

        det = det_cls()
        w = Watchdog(detectors=[])
        return w._emit(det, Finding(WARN, 1.0, 0.5, "planted"), {})

    @pytest.mark.parametrize("signal", ["tick_collapse", "ticker_lag",
                                        "fleet_straggler"])
    def test_cpu_shaped_alerts_join_top_hot_frames(self, signal):
        from min_tfs_client_tpu.observability import watchdog

        det_cls = {"tick_collapse": watchdog.TickCollapseDetector,
                   "ticker_lag": watchdog.TickerLagDetector,
                   "fleet_straggler": watchdog.StragglerDetector}[signal]
        assert det_cls.join_frames is True
        stop, t = _busy_thread("batch-worker-5")
        profiling.configure(hz=250.0)
        profiling.start()
        try:
            time.sleep(0.3)
        finally:
            stop.set()
            t.join()
        alert = self._emit_with(det_cls)
        profiling.stop()
        assert alert["signal"] == signal
        assert alert["hot_frames"], alert
        assert len(alert["hot_frames"]) <= 3

    def test_alert_omits_join_when_sampler_never_ran(self):
        from min_tfs_client_tpu.observability.watchdog import (
            TickerLagDetector,
        )

        alert = self._emit_with(TickerLagDetector)
        assert "hot_frames" not in alert

    def test_non_cpu_detectors_do_not_join(self):
        from min_tfs_client_tpu.observability.watchdog import (
            KVLeakDetector,
            SLOBurnDetector,
        )

        assert SLOBurnDetector.join_frames is False
        assert KVLeakDetector.join_frames is False
