"""`tick_ahead_share` on hand-written request traces."""

import json
import pathlib
import types

import pytest

from perfbench import metrics, spans

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def chrome(waits):
    """One decode_step request per entry: the args of its `decode/wait`
    (None: the request has no such span)."""
    events = []
    for tid, wait in enumerate(waits, 1):
        events.append({"name": "request/predict", "cat": "request",
                       "ph": "X", "tid": tid, "ts": 1000.0 * tid,
                       "dur": 900.0,
                       "args": {"signature": "decode_step", "status": "0"}})
        events.append({"name": "host/execute", "cat": "stage", "ph": "X",
                       "tid": tid, "ts": 1000.0 * tid + 10.0, "dur": 800.0,
                       "args": {}})
        if wait is not None:
            events.append({"name": "decode/wait", "cat": "stage", "ph": "X",
                           "tid": tid, "ts": 1000.0 * tid + 20.0,
                           "dur": 0.0 if wait.get("ahead") else 500.0,
                           "args": wait})
    return {"traceEvents": events}


def run_of(waits):
    requests = spans.of_signature(
        spans.requests_from_chrome(chrome(waits)), "decode_step")
    return types.SimpleNamespace(requests=requests)


@pytest.mark.parametrize("waits, want", [
    # three of four steps found their token parked or under way
    ([{"round": 1, "ahead": 1}, {"round": 2, "ahead": 1},
      {"round": 3, "ahead": 0}, {"round": 3, "ahead": 1}], 75.0),
    ([{"round": 1, "ahead": 0}, {"round": 1, "ahead": 0}], 0.0),
    ([{"round": 7, "ahead": 1}], 100.0),
    # a request without the span (it failed before the pool) is no step
    ([{"round": 1, "ahead": 1}, None, {"round": 2, "ahead": 0}], 50.0),
    # a program whose loop does not run ahead says `led`, not `ahead`
    ([{"round": 1, "led": True}, {"round": 1, "led": False}], None),
    ([], None),
])
def test_share_of_the_steps_the_loop_was_ahead_of(waits, want):
    got = metrics.load("tick_ahead_share").read(run_of(waits))
    assert got == (None if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_for_the_sessions_cell_only():
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "tick_ahead_share"]
    assert entry == {"name": "tick_ahead_share", "unit": "%",
                     "better": "higher", "source": "program_span",
                     "layer": "decode pool", "moves": "outputs_per_s",
                     "workloads": ["t5-large.sessions"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there when it came, nothing moved
    assert names.index("tick_ahead_share") == names.index("idle_named") + 1


def test_a_line_leaves_it_out_where_there_is_nothing_to_read():
    run = run_of([{"round": 1, "led": True}])
    assert metrics.read_all(["tick_ahead_share"], run, BENCH) == {}
    run = run_of([{"round": 1, "ahead": 1}])
    assert metrics.read_all(["tick_ahead_share"], run, BENCH) == {
        "tick_ahead_share": {"value": 100.0, "unit": "%"}}
