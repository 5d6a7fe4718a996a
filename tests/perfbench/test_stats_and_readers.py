"""Percentile, lateness and window-edge arithmetic, and the metric
readers, on hand-made records."""

import json
import pathlib
import types

import pytest

from perfbench import metrics, spans, stats

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("values, q, want", [
    ([5.0], 95, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50], 95, 48.0),
    (list(range(1, 101)), 99, 99.01),
    ([3, 1, 2], 0, 1.0),
    ([3, 1, 2], 100, 3.0),
])
def test_percentile_interpolates_between_closest_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None


def test_window_is_half_open():
    assert stats.in_window(0.0, 0.0, 10.0)
    assert stats.in_window(9.999, 0.0, 10.0)
    assert not stats.in_window(10.0, 0.0, 10.0)
    assert not stats.in_window(-0.001, 0.0, 10.0)


def request(due, sent, done, ok=True, outputs=1):
    return {"id": 0, "due": due, "sent": sent, "done": done, "ok": ok,
            "outputs": outputs}


def test_latency_runs_from_due_time_and_lateness_is_send_minus_due():
    r = request(due=1.000, sent=1.004, done=1.050)
    assert stats.first_output_ms(r) == pytest.approx(50.0)
    assert stats.lateness_ms(r) == pytest.approx(4.0)


def test_requests_belong_to_the_window_they_were_due_in():
    rows = [request(-0.5, -0.5, 0.2), request(0.0, 0.0, 0.1),
            request(9.9, 9.9, 10.4), request(10.0, 10.0, 10.1)]
    assert [r["due"] for r in stats.due_in_window(rows, 0.0, 10.0)] \
        == [0.0, 9.9]


def test_rate_counts_completions_inside_the_window_over_its_length():
    events = [(-0.1, 32), (0.0, 32), (5.0, 32), (9.99, 32), (10.0, 32)]
    assert stats.completed_in_window(events, 0.0, 10.0) == 96
    assert stats.rate_per_s(events, 0.0, 10.0) == pytest.approx(9.6)


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics

    values = [100, 101, 102, 103, 104, 105]
    q1, q2, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / q2)


def run_of(**kw):
    base = dict(records={"requests": [], "sessions": []}, seconds=10.0,
                setup_s=42.5, requests=[], trace=None, capture=None,
                memory_peak_bytes=0, config={}, traffic={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_open_loop_readers_on_hand_made_records():
    rows = [request(0.1 * k, 0.1 * k + 0.001 * k, 0.1 * k + 0.010 + 0.001 * k)
            for k in range(100)]                    # latencies 10..109 ms
    rows.append(request(-1.0, -1.0, 5.0))           # lead-in: not counted
    rows.append(request(5.0, 5.0, 5.5, ok=False))   # failed: no latency
    run = run_of(records={"requests": rows, "sessions": []})
    assert metrics.load("first_output_p50_ms").read(run) == pytest.approx(59.5)
    assert metrics.load("generate_first_output_p95_ms").read(run) \
        == pytest.approx(104.05)
    late = metrics.load("generator_late_p99_ms").read(run)
    assert 97.0 < late < 99.0
    assert metrics.load("setup_s").read(run) == 42.5


def test_outputs_per_s_counts_examples_and_tokens_completed_in_window():
    rows = [request(0.0, 0.0, 0.5, outputs=32),
            request(9.0, 9.0, 10.5, outputs=32),       # ends after the window
            request(1.0, 1.0, 2.0, ok=False, outputs=32)]
    sessions = [{"due": -2.0, "done": 3.0, "ok": True,
                 "steps": [-0.5, 0.5, 1.5, 2.5]}]
    run = run_of(records={"requests": rows, "sessions": sessions})
    assert metrics.load("outputs_per_s").read(run) == pytest.approx(3.5)


def test_session_readers_on_hand_made_records():
    sessions = [
        {"due": 1.0, "done": 2.0, "ok": True, "steps": [1.2, 1.3, 1.5]},
        {"due": -1.0, "done": 0.5, "ok": True, "steps": [-0.9, -0.1, 0.3]},
        {"due": 9.0, "done": 11.0, "ok": True, "steps": [9.4, 10.2]},
    ]
    run = run_of(records={"requests": [], "sessions": sessions})
    # gaps whose second answer is in the window: 100, 200 and 400 ms
    assert metrics.load("token_gap_p50_ms").read(run) == pytest.approx(200.0)
    # sessions started in the window: 200 and 400 ms to the first token
    assert metrics.load("session_first_output_p50_ms").read(run) \
        == pytest.approx(300.0)


def chrome(requests):
    events = []
    for tid, (args, stage_spans) in enumerate(requests, 1):
        events.append({"name": "request/predict", "cat": "request",
                       "ph": "X", "tid": tid, "ts": 1000.0 * tid,
                       "dur": 900.0, "args": args})
        for name, offset, dur, sargs in stage_spans:
            events.append({"name": name, "cat": "stage", "ph": "X",
                           "tid": tid, "ts": 1000.0 * tid + offset,
                           "dur": dur, "args": sargs})
    return {"traceEvents": events}


def test_span_readers_on_a_hand_made_trace():
    batch = [("batching/execute", 5000.0, 600.0, {})]
    rider = lambda wait, size: (  # noqa: E731
        {"signature": "", "status": "0", "batch_size": size},
        [("serving/validate", 50.0, 20.0, {}),
         ("batching/queue_wait", 80.0, wait, {}),
         ("serving/pad", 300.0, 30.0, {}),
         ("device/host_to_device", 330.0, 50.0, {}),
         ("device/execute", 380.0, 100.0, {}),
         ("device/device_to_host", 480.0, 300.0, {}),
         ("serving/serialize", 800.0, 40.0, {})])
    served = []
    for k, wait in enumerate((100.0, 200.0, 300.0)):
        args, stage = rider(wait, 3)
        # the three ride ONE batch: the same execute span on each trace
        stage = stage + [("batching/execute", 5000.0 - 1000.0 * (k + 1),
                          600.0, {})]
        served.append((args, stage))
    served.append(({"signature": "decode_step", "status": "0"}, []))
    served.append(({"signature": "", "status": "13"}, rider(900.0, 1)[1]))
    requests = spans.of_signature(
        spans.requests_from_chrome(chrome(served)), "serving_default")
    assert len(requests) == 3
    run = run_of(requests=requests, config={
        "serve": {"batching": {"max_batch_size": 32}}})
    assert metrics.load("queue_wait_p50_ms").read(run) == pytest.approx(0.2)
    assert metrics.load("dispatch_host_p50_ms").read(run) \
        == pytest.approx(0.1)
    assert metrics.load("fetch_wait_p50_ms").read(run) == pytest.approx(0.4)
    assert metrics.load("codec_p50_ms").read(run) == pytest.approx(0.09)
    # one batch of 3 examples against a max_batch_size of 32
    assert metrics.load("batch_occupancy").read(run) \
        == pytest.approx(100 * 3 / 32)


def test_tick_readers_count_each_tick_once():
    tick = lambda at, slots: ("decode/tick", at, 80.0, {"slots": slots})  # noqa: E731
    served = [({"signature": "decode_step", "status": "0"},
               [tick(100.0, 24)]),
              ({"signature": "decode_step", "status": "0"},
               [tick(100.0 - 1000.0 + 150000.0, 20)]),
              ({"signature": "decode_step", "status": "0"},
               [tick(100.0 - 2000.0 + 250000.0, 22)])]
    requests = spans.of_signature(
        spans.requests_from_chrome(chrome(served)), "decode_step")
    run = run_of(requests=requests)
    assert metrics.load("tick_slots_mean").read(run) == pytest.approx(22.0)
    assert metrics.load("tick_period_p50_ms").read(run) \
        == pytest.approx(125.0)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_a_reader_that_finds_nothing_returns_nothing(name):
    assert metrics.load(name).read(run_of()) is None


def test_read_all_leaves_out_what_was_not_read():
    run = run_of()
    got = metrics.read_all(["setup_s", "queue_wait_p50_ms"], run, BENCH)
    assert got == {"setup_s": {"value": 42.5, "unit": "s"}}
