"""The device's idle gaps, cut at the programs' edges and named from the
server's spans (perfbench/host_timeline.py): on hand-written events whose
answers are known, and on a few ticks recorded on the v5e (data/, see
data/README_timeline.txt)."""

import gzip
import json
import pathlib
import types

import pytest

from perfbench import host_timeline, metrics

DATA = pathlib.Path(__file__).parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1_000_000         # ns
ZERO_US = 5_000_000.0  # the server's clock read 5 s when the capture began
OFFSET_NS = -1_300_000  # and the device plane runs 1.3 ms early


def events(modules, ops):
    names: dict = {}

    def rows(found):
        return [[names.setdefault(n, len(names)), int(s * MS), int(d * MS)]
                for n, s, d in found]

    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": rows(modules)},
        {"name": "XLA Ops", "events": rows(ops)}]}],
        "names": list(names)}


def us(device_ms: float) -> float:
    """A time on the device plane (ms) as the server's clock has it."""
    return ZERO_US + (device_ms * MS - OFFSET_NS) / 1e3


def request(begin_ms, end_ms, *spans):
    return {"api": "request/predict", "ts": us(begin_ms),
            "dur": (end_ms - begin_ms) * 1e3, "args": {},
            "spans": [(name, us(a), (b - a) * 1e3, args[0] if args else {})
                      for name, a, b, *args in spans]}


# Five programs; times in ms on the device plane.
HAND = events(
    modules=[("jit_tick(7)", 0, 10), ("jit_tick(7)", 30, 10),
             ("jit_tick(7)", 55, 5), ("jit_tick(7)", 70, 5),
             ("jit_other(9)", 80, 5)],
    ops=[("fusion.1", 0, 4), ("fusion.2", 6, 4),  # 2 ms idle in a program
         ("fusion.1", 31, 9),    # 10 -> 31: 20 ms between, 1 ms inside
         ("fusion.1", 55, 5),    # 40 -> 55: waits only
         ("fusion.1", 70, 5),    # 60 -> 70: nobody asked for anything
         ("copy.3", 80, 5)])     # 75 -> 80: a request, and no span
REQUESTS = [
    request(-1, 42,
            ("decode/tick", -0.4, -0.2, {"round": 1}),
            ("decode/fetch", -0.2, 10.1, {"round": 1}),
            ("decode/deliver", 10.1, 12),
            ("decode/prepare", 12, 28, {"lock_wait_us": 15000}),
            ("decode/tick", 29.5, 29.8, {"round": 2})),
    # The same round's spans on a second rider's trace: counted once.
    request(5, 42, ("decode/prepare", 12, 28, {"lock_wait_us": 15000}),
            ("decode/wait", 5, 29)),
    request(38, 58, ("decode/wait", 39, 56)),
    request(50, 59, ("decode/wait", 50, 54), ("decode/tick", 54.7, 54.9)),
    request(75, 90),
]
RECORDED_OFFSET = {"zero": {"span_us": ZERO_US},
                   "device_offset_ns": OFFSET_NS}


def test_gaps_are_cut_at_program_edges_and_named_longest_first():
    found = host_timeline.timeline(HAND, REQUESTS, RECORDED_OFFSET,
                                   "jit_tick")
    assert [(name, round(start * 1e3, 6), round(seconds * 1e3, 6))
            for name, start, seconds in found["gaps"]] == [
        ("decode/prepare", 10, 20), ("decode/wait", 40, 15),
        ("no request in flight", 60, 10), ("unattributed", 75, 5),
        ("in:jit_tick", 4, 2), ("in:jit_tick", 30, 1)]
    assert found["idle_s"] == pytest.approx(
        {"inside": 0.003, "between": 0.050, "named": 0.045})


def test_a_recorded_device_offset_maps_a_nonzero_epoch_onto_the_capture():
    clock = host_timeline.Clock(RECORDED_OFFSET, OFFSET_NS)
    assert clock.ns(us(30.0)) == pytest.approx(30 * MS)
    assert clock.ns(ZERO_US) == pytest.approx(OFFSET_NS)
    found = host_timeline.timeline(HAND, REQUESTS, RECORDED_OFFSET,
                                   "jit_tick")
    assert found["device_offset_ns"] == OFFSET_NS
    assert [v / MS for v in found["launch_to_device_ns"]] \
        == pytest.approx([0.4, 0.5, 0.3])


def test_without_one_the_quickest_launch_of_the_capture_is_zero():
    found = host_timeline.timeline(
        HAND, REQUESTS, {"zero": {"span_us": ZERO_US}}, "jit_tick")
    assert found["device_offset_ns"] == pytest.approx(OFFSET_NS + 0.3 * MS)
    assert [v / MS for v in found["launch_to_device_ns"]] \
        == pytest.approx([0.1, 0.2, 0.0])
    # 0.3 ms is nothing against these gaps: the names stay.
    assert [g[0] for g in found["gaps"][:4]] == [
        "decode/prepare", "decode/wait", "no request in flight",
        "unattributed"]


def test_a_program_that_no_launch_span_started_is_paired_with_none():
    """`jit_other` is not the cell's main program, and the fourth
    `jit_tick` has no launch span near it."""
    found = host_timeline.timeline(HAND, REQUESTS, RECORDED_OFFSET,
                                   "jit_tick")
    assert len(found["launch_to_device_ns"]) == 3


def run_of(tmp_path, monkeypatch, *, clock: dict | None, requests=REQUESTS,
           hand=HAND, main_program="jit_tick", window_s=0.1):
    """A run as run.py leaves it: events.json and the one capture
    directory under <run>/profile, host_clock.json in it or not."""
    monkeypatch.setattr(host_timeline, "RUN_DIR", tmp_path)
    monkeypatch.setattr(host_timeline, "_cached", {})
    capture_dir = tmp_path / "profile" / "servespy-1"
    capture_dir.mkdir(parents=True)
    (tmp_path / "events.json").write_text(json.dumps(hand))
    files = ["plugins/profile/x/vm.xplane.pb"]
    if clock is not None:
        (capture_dir / "host_clock.json").write_text(json.dumps(clock))
        files.append("host_clock.json")
    return types.SimpleNamespace(
        requests=requests, trace={"window_s": window_s, "busy_s": 0.0},
        capture={"files": files, "seconds": window_s},
        config={"main_program": {"decode_step": main_program}},
        traffic={"signature": "decode_step"})


NEW_READERS = ["tick_launch_to_device_p50_ms", "tput_idle_between_programs",
               "tput_idle_named", "idle_between_programs", "idle_named"]


def test_the_readers_on_the_hand_made_run(tmp_path, monkeypatch):
    run = run_of(tmp_path, monkeypatch, clock=RECORDED_OFFSET)
    read = {name: metrics.load(name).read(run) for name in NEW_READERS}
    assert read["tick_launch_to_device_p50_ms"] == pytest.approx(0.4)
    assert read["tput_idle_between_programs"] == pytest.approx(50.0)
    assert read["idle_between_programs"] == pytest.approx(50.0)
    assert read["tput_idle_named"] == pytest.approx(90.0)
    assert read["idle_named"] == pytest.approx(90.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_an_older_program_s_capture_has_no_clock_and_reads_nothing(
        tmp_path, monkeypatch, name):
    run = run_of(tmp_path, monkeypatch, clock=None)
    assert metrics.load(name).read(run) is None
    run.trace = None  # an untraced run
    assert metrics.load(name).read(run) is None


def test_the_capture_is_read_once_per_run(tmp_path, monkeypatch):
    run = run_of(tmp_path, monkeypatch, clock=RECORDED_OFFSET)
    first = host_timeline.of_run(run)
    (tmp_path / "events.json").write_text("not json any more")
    assert host_timeline.of_run(run) is first


SPAN_READERS = {
    "tick_wait_p50_ms": 17.0,       # waits of 24, 17 and 4 ms a request
    "tick_handoff_p50_ms": None,    # no such span in the hand-made run
    "tick_prepare_p50_ms": 16.0,
    "tick_launch_p50_ms": 0.2,      # 0.2, 0.3, 0.2
    "tick_fetch_p50_ms": 10.3,
    "tick_deliver_p50_ms": 1.9,
    "tick_table_width_mean": None,  # no `width=` on these ticks
}


@pytest.mark.parametrize("name, want", sorted(SPAN_READERS.items()))
def test_the_span_readers_count_a_round_s_span_once(name, want):
    run = types.SimpleNamespace(requests=REQUESTS)
    got = metrics.load(name).read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_table_width_is_the_mean_over_distinct_ticks():
    ticks = [request(0, 1, ("decode/tick", 0, 1, {"width": 16, "slots": 24})),
             request(0, 1, ("decode/tick", 0, 1, {"width": 16, "slots": 24})),
             request(2, 3, ("decode/tick", 2, 3, {"width": 8, "slots": 24}))]
    run = types.SimpleNamespace(requests=ticks)
    assert metrics.load("tick_table_width_mean").read(run) == 12.0


# ---------------------------------------------------------------------------
# A few ticks recorded on the v5e

RECORDED = DATA / "v5e_sessions_timeline.json.gz"


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.skip("no recorded timeline")
    return (json.loads(gzip.decompress(RECORDED.read_bytes())),
            json.loads((DATA / "v5e_sessions_timeline.expected.json")
                       .read_text()))


def test_the_timeline_of_ticks_recorded_on_the_v5e(recorded):
    cut, want = recorded
    found = host_timeline.timeline(cut["events"], cut["requests"],
                                   cut["host_clock"], "jit_direct_tick_fn")
    assert found["idle_s"]["inside"] == pytest.approx(want["inside_s"])
    assert found["idle_s"]["between"] == pytest.approx(want["between_s"])
    assert found["idle_s"]["named"] == pytest.approx(want["named_s"])
    assert found["device_offset_ns"] == pytest.approx(
        want["device_offset_ns"])
    assert sorted(found["launch_to_device_ns"]) == pytest.approx(
        want["launch_to_device_ns"])
    between = [g for g in found["gaps"] if not g[0].startswith("in:")]
    assert [g[0] for g in between[:len(want["longest_between"])]] \
        == [g[0] for g in want["longest_between"]]
    for got, (_, start, seconds) in zip(between, want["longest_between"]):
        assert got[1:] == pytest.approx((start, seconds))
    assert sum(1 for g in found["gaps"] if g[0].startswith("in:")) \
        == want["inside_gaps"]


# By name, not by prefix: a later metric named `tick_launch_...` has no
# reading in the recorded file and is none of this test's.
RECORDED_READERS = (
    "tick_wait_p50_ms", "tick_handoff_p50_ms", "tick_prepare_p50_ms",
    "tick_launch_p50_ms", "tick_fetch_p50_ms", "tick_deliver_p50_ms",
    "tick_table_width_mean", "tick_launch_to_device_p50_ms",
    "tput_idle_between_programs", "tput_idle_named")


def test_the_benchmark_still_lists_every_recorded_reader():
    assert set(RECORDED_READERS) <= {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("name", RECORDED_READERS)
def test_each_new_reader_on_the_recorded_ticks(recorded, tmp_path,
                                               monkeypatch, name):
    cut, want = recorded
    run = run_of(tmp_path, monkeypatch, clock=cut["host_clock"],
                 requests=cut["requests"], hand=cut["events"],
                 main_program="jit_direct_tick_fn",
                 window_s=want["window_s"])
    assert metrics.load(name).read(run) == pytest.approx(
        want["metrics"][name])
