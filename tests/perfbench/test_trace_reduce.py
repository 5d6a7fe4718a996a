"""The device trace -> {idle share, per-program time, per-kernel time,
roofline share} reduction: on hand-made events whose answers are known,
and on a small trace recorded on the v5e (data/, cut from a traced run
of t5-large.sessions by this benchmark; see data/README.txt)."""

import gzip
import json
import pathlib
import types

import pytest

from perfbench import metrics, trace_reduce

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000  # ns


def trace(modules, ops, plane="/device:TPU:0"):
    names: dict = {}

    def rows(events):
        return [[names.setdefault(n, len(names)), s, d] for n, s, d in events]

    return {"planes": [
        {"name": plane, "lines": [
            {"name": "XLA Modules", "events": rows(modules)},
            {"name": "XLA Ops", "events": rows(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": rows([("main", 0, 100 * MS)])}]}],
        "names": list(names)}


HAND = trace(
    modules=[("jit_tick(111)", 0, 10 * MS), ("jit_tick(111)", 20 * MS, 10 * MS),
             ("jit_tick(222)", 40 * MS, 20 * MS), ("jit_other(9)", 70 * MS, 5 * MS)],
    ops=[("while.3", 0, 10 * MS),               # encloses the next two
         ("_paged_kernel.1", 1 * MS, 4 * MS), ("fusion.7", 5 * MS, 5 * MS),
         ("_paged_kernel.2", 20 * MS, 6 * MS), ("fusion.8", 26 * MS, 4 * MS),
         ("_paged_kernel.1", 40 * MS, 14 * MS), ("fusion.7", 54 * MS, 6 * MS),
         ("copy.1", 70 * MS, 5 * MS)])


def test_busy_is_the_union_of_operations_not_their_sum():
    reduced = trace_reduce.reduce(HAND)
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(0.045)     # 10+10+20+5 ms
    assert reduced["window_s"] == pytest.approx(0.075)   # first start..last end
    assert trace_reduce.idle_share(reduced) == pytest.approx(0.4)
    assert reduced["idle_gaps"] == pytest.approx([0.010, 0.010, 0.010])


def test_a_longer_traced_window_counts_as_idle():
    reduced = trace_reduce.reduce(HAND, window_s=0.09)
    assert trace_reduce.idle_share(reduced) == pytest.approx(0.5)


def test_program_time_is_the_median_run_over_all_compiled_shapes():
    reduced = trace_reduce.reduce(HAND)
    assert sorted(trace_reduce.program_runs(reduced, "jit_tick")) \
        == ["jit_tick(111)", "jit_tick(222)"]
    assert trace_reduce.program_ms(reduced, "jit_tick") == pytest.approx(10.0)
    assert trace_reduce.program_ms(reduced, "jit_other") == pytest.approx(5.0)
    assert trace_reduce.program_ms(reduced, "jit_absent") is None


def test_runs_cut_by_the_captures_edges_are_left_out():
    cut = trace(modules=[("jit_decode_fn(1)", 0, 1111 * MS),
                         ("jit_decode_fn(1)", 1200 * MS, 1683 * MS),
                         ("jit_decode_fn(1)", 2900 * MS, 1683 * MS),
                         ("jit_decode_fn(1)", 4600 * MS, 570 * MS)],
                ops=[("while.3", 0, 5170 * MS)])
    reduced = trace_reduce.reduce(cut)
    assert trace_reduce.program_ms(reduced, "jit_decode_fn") \
        == pytest.approx(1683.0)


def test_kernel_time_by_name_and_its_share_of_the_programs_that_call_it():
    reduced = trace_reduce.reduce(HAND)
    assert sorted(trace_reduce.kernel_times(reduced, "_paged_kernel")) \
        == pytest.approx([0.004, 0.006, 0.014])
    assert trace_reduce.kernel_share(reduced, "_paged_kernel", "jit_tick") \
        == pytest.approx(24 / 40)
    assert trace_reduce.kernel_share(reduced, "_flash_kernel", "jit_tick") \
        is None


def test_breakdown_sums_the_instances_of_a_name_and_names_no_gap():
    got = trace_reduce.breakdown(trace_reduce.reduce(HAND))
    assert got["device_ops"][0] == ["_paged_kernel", pytest.approx(0.024)]
    assert ["fusion", pytest.approx(0.015)] in got["device_ops"]
    assert all(name == "unattributed" for name, _ in got["idle_gaps"])
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_breakdown_takes_the_names_of_the_gaps_it_is_given():
    gaps = [("observe/drain", 1.5, 0.126), ("host/gc", 0.2, 0.051)] \
        + [("decode/fetch", float(k), 0.01) for k in range(12)]
    got = trace_reduce.breakdown(trace_reduce.reduce(HAND), gaps)
    assert got["idle_gaps"][:2] == [["observe/drain", 0.126],
                                    ["host/gc", 0.051]]
    assert len(got["idle_gaps"]) == 10


def test_breakdown_names_the_gaps_of_the_ticks_recorded_on_the_v5e():
    """The recorded capture (data/README_timeline.txt) through
    host_track's rule, as run.py feeds it: each of the longest gaps
    between two programs has the name that was worked out by hand."""
    from perfbench import host_timeline, host_track

    cut = json.loads(gzip.decompress(
        (DATA / "v5e_sessions_timeline.json.gz").read_bytes()))
    want = json.loads(
        (DATA / "v5e_sessions_timeline.expected.json").read_text())
    track = {"requests": cut["requests"], "process": [],
             "spans": host_timeline.distinct(cut["requests"])}
    found = host_track.timeline_of(track, cut["events"], cut["host_clock"],
                                   "jit_direct_tick_fn")
    got = trace_reduce.breakdown(trace_reduce.reduce(cut["events"]),
                                 found["gaps"])
    longest = want["longest_between"]
    assert got["idle_gaps"][:len(longest)] == [
        [name, pytest.approx(seconds)] for name, _, seconds in longest]
    assert all(name != "unattributed" for name, _ in got["idle_gaps"])


def test_no_operation_on_a_device_reduces_to_nothing():
    assert trace_reduce.reduce(trace([], [])) is None
    host_only = {"names": ["x"], "planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [[0, 0, 5]]}]}]}
    assert trace_reduce.reduce(host_only) is None


def test_two_chips_are_averaged():
    both = trace(modules=[], ops=[("a", 0, 10 * MS), ("a", 30 * MS, 10 * MS)])
    second = trace(modules=[], ops=[("a", 0, 40 * MS)],
                   plane="/device:TPU:1")["planes"][0]
    both["planes"].append(second)
    reduced = trace_reduce.reduce(both)
    assert reduced["chips"] == 2
    assert reduced["busy_s"] == pytest.approx(0.030)     # mean of 20 and 40 ms
    assert reduced["window_s"] == pytest.approx(0.040)


def test_roofline_readers_from_a_hand_made_trace():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    paged = trace(modules=[("jit_direct_tick_fn(1)", 0, 80 * MS)],
                  ops=[("_paged_kernel.5", 0, 2 * MS)])
    sessions = [{"init_done": -5.0, "done": 50.0,
                 "steps": [0.1 * k for k in range(-40, 400)]}] * 10
    run = types.SimpleNamespace(
        trace=trace_reduce.reduce(paged), peak=peak,
        capture={"start": 2.0, "end": 2.0 + 1e-9},
        records={"sessions": sessions, "requests": []},
        traffic={"signature": "decode_step"},
        config={"num_heads": 16, "main_program": {
            "decode_step": "jit_direct_tick_fn"}, "kernels": {
            "_paged_kernel": {"heads": "num_heads", "d_head": 64,
                              "page_tokens": 16}}},
        kernel=lambda name: metrics.load_file(
            pathlib.Path(metrics.HERE).parent / "kernels" / f"{name}.py"))
    # at t=2.0 each session has 61 answers (+1 in flight): 4 pages, 40 in all
    need_bytes = 40 * 16 * 16 * 64 * 2 * 2
    assert metrics.load("paged_roofline").read(run) == pytest.approx(
        100 * (need_bytes / 819e9) / 2e-3)
    assert metrics.load("kernel_share").read(run) == pytest.approx(2.5)
    assert metrics.load("tput_program_ms").read(run) == pytest.approx(80.0)
    assert metrics.load("tput_device_idle").read(run) == pytest.approx(0.0)


RECORDED = DATA / "v5e_sessions_trace.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_the_reduction_of_a_trace_recorded_on_the_v5e():
    events = json.loads(gzip.decompress(RECORDED.read_bytes()))
    want = json.loads((DATA / "v5e_sessions_trace.expected.json").read_text())
    reduced = trace_reduce.reduce(events)
    assert reduced["chips"] == want["chips"]
    assert reduced["busy_s"] == pytest.approx(want["busy_s"])
    assert reduced["window_s"] == pytest.approx(want["window_s"])
    assert trace_reduce.idle_share(reduced) == pytest.approx(want["idle_share"])
    assert trace_reduce.program_ms(reduced, "jit_direct_tick_fn") \
        == pytest.approx(want["tick_program_ms"])
    calls = trace_reduce.kernel_times(reduced, "_paged_kernel")
    assert len(calls) == want["paged_kernel_calls"]
    assert sum(calls) == pytest.approx(want["paged_kernel_s"])
    assert trace_reduce.kernel_share(
        reduced, "_paged_kernel", "jit_direct_tick_fn") \
        == pytest.approx(want["kernel_share"])
    # One tick calls the kernel once per decoder layer.
    ticks = sum(len(r) for r in trace_reduce.program_runs(
        reduced, "jit_direct_tick_fn").values())
    assert want["paged_kernel_calls"] == pytest.approx(24 * ticks, abs=24)
