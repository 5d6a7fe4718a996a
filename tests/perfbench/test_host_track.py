"""The capture's host track (perfbench/host_track.py) and the readers
over it, on hand-written events whose answers are known; and where each
new metric stands in BENCHMARK.json."""

import json
import pathlib
import types

import pytest

from perfbench import host_timeline, host_track, metrics, spans

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1_000_000          # ns
ZERO_US = 5_000_000.0   # the server's clock read 5 s when the capture began
OFFSET_NS = -1_300_000  # and the device plane runs 1.3 ms early


def us(device_ms: float) -> float:
    """A time on the device plane (ms) as the server's clock has it."""
    return ZERO_US + (device_ms * MS - OFFSET_NS) / 1e3


def device(modules, ops):
    names: dict = {}

    def rows(found):
        return [[names.setdefault(n, len(names)), int(s * MS), int(d * MS)]
                for n, s, d in found]

    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": rows(modules)},
        {"name": "XLA Ops", "events": rows(ops)}]}],
        "names": list(names)}


# Five programs, busy for all of their runs; times in ms on the device
# plane. Between them: 10-30, 40-60, 70-90, 100-110.
RUNS = [("jit_tick(7)", 0, 10), ("jit_tick(7)", 30, 10),
        ("jit_tick(7)", 60, 10), ("jit_tick(7)", 90, 10),
        ("jit_other(9)", 110, 5)]
EVENTS = device(RUNS, [("fusion.1", s, d) for _, s, d in RUNS])

# The decode loop's rounds: (handoff, prepare, tick, wake, fetch), ms.
ROUNDS = {
    1: ((-2, -1.5), (-1.5, -1), (-1, -0.5), (-0.5, 1), (1, 11)),
    2: ((11, 12), (12, 28), (28, 29.5), (29.5, 31), (31, 42)),
    # a collection stopped the loop inside its launch
    3: ((42, 42.5), (42.5, 43), (43, 59), (59, 59.5), (59.5, 71)),
    4: ((88, 88.5), (88.5, 89), (89, 89.6), (89.6, 90.4), (90.4, 101)),
}
TICK_CPU_US = {1: 100, 2: 300, 3: 200, 4: 400}


def chrome(with_process=True, with_wake=True, lead=True):
    """A `host_track.json`: four stepping requests that each carry a
    round's spans, a fifth that only rode, a `decode_init` whose session
    opening holds the device's third gap, and the process's own spans."""
    events = []

    def request(tid, signature, begin, end, found):
        events.append({"name": "request/predict", "cat": "request",
                       "ph": "X", "pid": 1, "tid": tid, "ts": us(begin),
                       "dur": (end - begin) * 1e3,
                       "args": {"signature": signature, "status": "0"}})
        events.extend({"name": name, "cat": "stage", "ph": "X", "pid": 1,
                       "tid": tid, "ts": us(a), "dur": (b - a) * 1e3,
                       "args": args} for name, (a, b), args in found)

    envelopes = {1: (-3, 12), 2: (5, 43), 3: (41, 72), 4: (85, 102)}
    for r, times in ROUNDS.items():
        found = [(name, at, {"round": r}) for name, at in
                 zip(host_track.LOOP_PHASES, times)
                 if with_wake or name != "decode/wake"]
        if with_wake:
            found[2][2]["cpu_us"] = TICK_CPU_US[r]
        request(r, "decode_step", *envelopes[r], found)
    request(5, "decode_step", 95, 99,
            [("decode/wait", (95, 95), {"round": 4, "ahead": 1})])
    request(6, "decode_init", 71, 89,
            [("decode/init", (72, 88), {"tokens": 300})])
    if with_process:
        events.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": 0,
                       "args": {"name": "host track"}})
        events.extend(
            {"name": name, "cat": "process", "ph": "X", "pid": 1, "tid": 0,
             "ts": us(a), "dur": (b - a) * 1e3, "args": args}
            for name, a, b, args in IN_CAPTURE + (LEAD if lead else []))
    ends = {"zero_us": us(-5), "stop_us": us(105)}
    if lead:
        ends["lead_us"] = us(-115)
    return {"traceEvents": events,
            "otherData": {"schema": "host_track/1", "capture": ends}}


# The process's own spans inside the capture (-5 to 105 ms): they name
# its gaps. What they cost is read in the lead before it.
IN_CAPTURE = [
    ("host/gc", 43, 58, {"gen": 2, "collected": 11}),
    ("observe/drain", 20, 22, {"traces": 500, "cpu_us": 1900}),
    ("observe/drain", 62, 65, {"traces": 520, "cpu_us": 2800}),
    ("decode/idle", 71, 80, {"restarted": 1}),
    ("loop/sample", 0, 50, {"lag_us": 9000, "cpu_us": 49000}),
    ("loop/sample", 50, 100, {"lag_us": 9000, "cpu_us": 49000}),
]
# The 110 ms before it, undisturbed by the profiler.
LEAD = [
    ("host/gc", -100, -85, {"gen": 2, "collected": 11}),
    ("observe/drain", -80, -78, {"traces": 500, "cpu_us": 1900}),
    ("observe/drain", -40, -37, {"traces": 520, "cpu_us": 2800}),
    # began before the lead: not whole inside it
    ("loop/sample", -160, -110, {"lag_us": 0, "cpu_us": 9000}),
    ("loop/sample", -110, -60, {"lag_us": 500, "cpu_us": 20000}),
    ("loop/sample", -60, -10, {"lag_us": 1500, "cpu_us": 30000}),
    # runs into the capture: not whole inside the lead either
    ("loop/sample", -10, 0, {"lag_us": 0, "cpu_us": 7000}),
]
# When the load generator saw its steps answered, in seconds from the
# window's opening; the capture began 18 s into the window.
CAPTURE_START_S = 18.0
STEPS = [[17.880, 17.890, 17.930], [17.950, 17.989, 17.991, 18.050]]


RECORDED_OFFSET = {"zero": {"span_us": ZERO_US},
                   "device_offset_ns": OFFSET_NS}


def run_of(tmp_path, monkeypatch, *, track: dict | None,
           clock: dict = RECORDED_OFFSET):
    """A run as run.py leaves it: events.json and the one capture
    directory under <run>/profile, host_track.json in it or not (the
    parent commit writes none)."""
    monkeypatch.setattr(host_timeline, "RUN_DIR", tmp_path)
    monkeypatch.setattr(host_timeline, "_cached", {})
    monkeypatch.setattr(host_track, "_cached", {})
    capture_dir = tmp_path / "profile" / "servespy-1"
    capture_dir.mkdir(parents=True)
    (tmp_path / "events.json").write_text(json.dumps(EVENTS))
    (capture_dir / "host_clock.json").write_text(json.dumps(clock))
    files = ["plugins/profile/x/vm.xplane.pb", "host_clock.json"]
    if track is not None:
        (capture_dir / "host_track.json").write_text(json.dumps(track))
        files.append("host_track.json")
    every = spans.requests_from_chrome(track or chrome())
    return types.SimpleNamespace(
        requests=spans.of_signature(every, "decode_step"),
        records={"requests": [], "sessions": [
            {"steps": steps} for steps in STEPS]},
        trace={"window_s": 0.11, "busy_s": 0.045},
        capture={"files": files, "seconds": 0.11,
                 "start": CAPTURE_START_S},
        config={"main_program": {"decode_step": "jit_tick"}},
        traffic={"signature": "decode_step"})


NEW = {  # name -> what it reads on the hand-made run
    "tick_wake_p50_ms": 1.15,            # 1.5, 1.5, 0.5, 0.8
    "tick_phase_cover": 100.0,           # 100, 100, 82.6 (a hole: 80-88)
    "tick_cpu_launch_mean_ms": 0.25,      # 0.1, 0.3, 0.2, 0.4
    "tick_tail_p50_ms": 1.0,             # 1, 2, 1, 1
    # in the lead: 50 ms of CPU between 110 and 10 ms before the capture
    # began, which the generator's clock has at 17.895 to 17.995 s: 4 steps
    "loop_cpu_per_step_ms": 12.5,
    "event_loop_late_share": 2.0,        # 2 ms late in those 100
    "observe_drain_share": 100 * 5 / 110,   # of the lead's 110 ms
    "gc_pause_share": 100 * 15 / 110,
    "tput_gc_pause_share": 100 * 15 / 110,
    "host_idle_named": 100 * 60 / 70,    # the last gap has no name
    "tput_host_idle_named": 100 * 60 / 70,
}


def test_the_file_s_two_halves():
    track = host_track.load(chrome())
    assert len(track["requests"]) == 6
    assert track["requests"] == spans.requests_from_chrome(
        chrome(with_process=False))
    assert [s[0] for s in track["process"] if s[1] >= us(-5)] == [
        "loop/sample", "observe/drain", "host/gc", "loop/sample",
        "observe/drain", "decode/idle"]
    assert track["capture_us"] == (us(-5), us(105))
    assert host_track.quiet(track) == (us(-115), us(-5))
    assert [s[3]["cpu_us"] for s in host_track.samples(track)] \
        == [20000, 30000]


def test_a_file_with_no_lead_is_priced_inside_its_capture():
    track = host_track.load(chrome(lead=False))
    assert host_track.quiet(track) == track["capture_us"]
    assert [s[3]["cpu_us"] for s in host_track.samples(track)] \
        == [49000, 49000]
    assert host_track.process_share(track, "host/gc") \
        == pytest.approx(100 * 15 / 110)


def test_every_gap_between_programs_gets_the_name_of_its_cause():
    found = host_track.timeline(chrome(), EVENTS, RECORDED_OFFSET,
                                "jit_tick")
    assert [(name, round(start * 1e3, 6), round(seconds * 1e3, 6))
            for name, start, seconds in found["gaps"]] == [
        # the loop waited for a lock through most of its prepare
        ("decode/prepare", 10, 20),
        # `decode/tick` was open for 16 ms of it, the collection for 15:
        # the process's own spans are asked first
        ("host/gc", 40, 20),
        # a session's opening, on a request of another signature
        ("decode/init", 70, 20),
        ("unattributed", 100, 10)]
    assert found["idle_s"] == pytest.approx(
        {"between": 0.070, "named": 0.060})
    # What the cell's own requests alone can say of the same gaps
    # (`idle_named`): the third has no name, the second the victim's.
    old = host_timeline.timeline(
        EVENTS, spans.of_signature(found["requests"], "decode_step"),
        RECORDED_OFFSET, "jit_tick")
    assert [g[0] for g in old["gaps"]] == [
        "decode/prepare", "decode/tick", "unattributed", "unattributed"]


def test_the_loop_s_sample_is_never_a_gap_s_name():
    track = chrome()
    track["traceEvents"] = [e for e in track["traceEvents"]
                            if e.get("name") not in ("host/gc",
                                                     "decode/init")]
    found = host_track.timeline(track, EVENTS, RECORDED_OFFSET, "jit_tick")
    # (The third: no loop thread for 9 ms, then the next round's first
    # phases: half of it together, and the idle time the most of that.)
    assert [g[0] for g in found["gaps"]] == [
        "decode/prepare", "decode/tick", "decode/idle", "unattributed"]


def test_a_round_s_cover_and_its_fetch_tail():
    found = host_track.timeline(chrome(), EVENTS, RECORDED_OFFSET,
                                "jit_tick")
    assert host_track.phase_cover(found) == pytest.approx(
        [1.0, 1.0, 38 / 46])
    assert [t / MS for t in found["fetch_tail_ns"]] \
        == pytest.approx([1.0, 2.0, 1.0, 1.0])
    # Without a recorded offset the capture's quickest launch (1 ms)
    # is taken as nothing, and every tail reads that much longer.
    found = host_track.timeline(chrome(), EVENTS,
                                {"zero": {"span_us": ZERO_US}}, "jit_tick")
    assert [t / MS for t in found["fetch_tail_ns"]] \
        == pytest.approx([2.0, 3.0, 2.0, 2.0])


@pytest.mark.parametrize("name, want", sorted(NEW.items()))
def test_each_reader_on_the_hand_made_run(tmp_path, monkeypatch, name, want):
    run = run_of(tmp_path, monkeypatch, track=chrome())
    assert metrics.load(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_parent_commit_wrote_no_host_track_and_reads_nothing(
        tmp_path, monkeypatch, name):
    """No file, no `decode/wake`, no `cpu_us`: every reader gives None
    and raises nothing; so does an untraced run."""
    run = run_of(tmp_path, monkeypatch, track=None)
    run.requests = spans.of_signature(spans.requests_from_chrome(
        chrome(with_process=False, with_wake=False)), "decode_step")
    assert metrics.load(name).read(run) is None
    assert metrics.read_all([name], run, BENCH) == {}
    run.trace = run.capture = None
    assert metrics.load(name).read(run) is None


def test_a_quiet_capture_reads_zero_and_not_nothing(tmp_path, monkeypatch):
    track = chrome()
    track["traceEvents"] = [e for e in track["traceEvents"]
                            if e.get("cat") != "process"]
    run = run_of(tmp_path, monkeypatch, track=track)
    for name in ("gc_pause_share", "observe_drain_share"):
        assert metrics.load(name).read(run) == 0.0
    for name in ("loop_cpu_per_step_ms", "event_loop_late_share"):
        assert metrics.load(name).read(run) is None  # no sample to read


def test_the_capture_is_read_once_per_run(tmp_path, monkeypatch):
    run = run_of(tmp_path, monkeypatch, track=chrome())
    first = host_track.of_run(run)
    (tmp_path / "events.json").write_text("not json any more")
    assert host_track.of_run(run) is first


S = ["t5-large.sessions"]
G = ["t5-large.generate", "mimo-v2.5.mixed-generate"]
ENTRIES = [  # in the order they were appended; the first stands behind
    ("prefill_packed_share", None, None, None, None, None, None),
    ("tick_wake_p50_ms", "ms", "lower", "program_span", "decode pool",
     "outputs_per_s", S),
    ("tick_phase_cover", "%", "higher", "program_span", "decode pool",
     "outputs_per_s", S),
    ("tick_cpu_launch_mean_ms", "ms", "lower", "program_span", "decode pool",
     "outputs_per_s", S),
    ("tick_tail_p50_ms", "ms", "lower", "device_trace", "decode pool",
     "outputs_per_s", S),
    ("loop_cpu_per_step_ms", "ms", "lower", "program_counter", "transport",
     "outputs_per_s", S),
    ("event_loop_late_share", "%", "lower", "program_counter", "transport",
     "outputs_per_s", S),
    ("observe_drain_share", "%", "lower", "program_span", "host process",
     "outputs_per_s", S),
    ("gc_pause_share", "%", "lower", "program_span", "host process",
     "first_output_p50_ms", G),
    ("tput_gc_pause_share", "%", "lower", "program_span", "host process",
     "outputs_per_s", S),
    ("host_idle_named", "%", "higher", "device_trace", "device",
     "first_output_p50_ms", G),
    ("tput_host_idle_named", "%", "higher", "device_trace", "device",
     "outputs_per_s", S),
]


@pytest.mark.parametrize("before, entry", list(zip(ENTRIES, ENTRIES[1:])),
                         ids=[e[0] for e in ENTRIES[1:]])
def test_the_benchmark_lists_it_behind_its_neighbour(before, entry):
    name, unit, better, source, layer, moves, cells = entry
    (found,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert found == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there, nothing moved
    assert names.index(name) == names.index(before[0]) + 1
    assert (ROOT / "perfbench" / "metrics" / f"{name}.py").exists()
    assert name in NEW
