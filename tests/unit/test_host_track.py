"""The host track: the tracing spine's sink for what the process does on
nobody's behalf, what writes to it, and the capture's `host_track.json`."""

import gc
import json
import threading
import time

import numpy as np
import pytest

from min_tfs_client_tpu.observability import profiling, runtime, tracing
from min_tfs_client_tpu.servables.decode_sessions import SlotPool, TickBatcher
from min_tfs_client_tpu.utils import aio_loop
from perfbench import host_track, spans


def until(done, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not done():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.005)


def tick_loop_threads():
    return [t for t in threading.enumerate() if t.name == "decode-tick-loop"]


@pytest.fixture(autouse=True)
def _clean_spine():
    tracing.enable(True)
    tracing.flush_metrics()
    tracing.process_clear()
    yield
    tracing.enable(True)
    tracing.process_clear()


def named(name):
    return [s for s in tracing.process_snapshot() if s[0] == name]


class TestTheRing:
    def test_a_process_span_is_kept_with_its_hand_stamped_ends(self):
        tracing.process_span("host/gc", 10.0, 10.5, gen=2, collected=7)
        tracing.process_span("decode/idle", 11.0, 12.0)
        assert tracing.process_snapshot() == [
            ("host/gc", 10.0, 10.5, {"gen": 2, "collected": 7}),
            ("decode/idle", 11.0, 12.0, None)]

    def test_the_ring_is_bounded_by_a_constant(self):
        """The ring is the process's: a collector's callback, the aio
        loop's ticker or a drain thread that an earlier test of this
        worker left may write into it meanwhile. So the ring's size is
        read off all of it, and what it dropped off this test's own
        spans (a name nobody else writes): the newest, in order, and as
        many of the oldest gone as the others' spans took room."""
        written = tracing.PROCESS_RING + 10
        for k in range(written):
            tracing.process_span("test/ring", float(k), k + 0.1, lag_us=k)
        kept = tracing.process_snapshot()
        assert len(kept) == tracing.PROCESS_RING == 4096
        mine = [s for s in kept if s[0] == "test/ring"]
        others = len(kept) - len(mine)
        assert mine[0][3] == {"lag_us": 10 + others}
        assert [s[1] for s in mine] == [
            float(k) for k in range(10 + others, written)]

    def test_the_kill_switch_stops_it_with_the_rest_of_the_spine(self):
        tracing.enable(False)
        tracing.process_span("host/gc", 1.0, 2.0, gen=0, collected=0)
        assert tracing.process_snapshot() == []

    @pytest.mark.parametrize("since, until_, want", [
        (None, None, ["a", "b", "c"]),
        (2.5, None, ["b", "c"]),      # b ends at 3: it overlaps
        (None, 2.0, ["a", "b"]),      # b starts at 2
        (3.5, 4.5, []),
        (4.5, 9.0, ["c"]),
    ])
    def test_a_snapshot_with_bounds_gives_what_overlaps_them(
            self, since, until_, want):
        for name, t0, t1 in (("a", 0.0, 1.0), ("b", 2.0, 3.0),
                             ("c", 5.0, 6.0)):
            tracing.process_span(name, t0, t1)
        assert [s[0] for s in tracing.process_snapshot(since, until_)] == want

    def test_writers_on_many_threads_and_a_reader_need_no_lock(self):
        """The ring takes a collector's callback from any thread, so it
        has no lock: appends and snapshots race, the interpreter
        switching every 10 us, and nothing is lost or torn."""
        import sys

        writers, each = 8, 3000
        stop, torn = threading.Event(), []

        def write(k):
            for n in range(each):
                tracing.process_span("loop/sample", float(n), n + 1.0,
                                     writer=k, n=n)

        def read():
            while not stop.is_set():
                for name, t0, t1, args in tracing.process_snapshot():
                    if "writer" in args and (t1 - t0 != 1.0
                                             or args["n"] != t0):
                        torn.append((name, t0, t1, args))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            reader = threading.Thread(target=read, name="ring-reader")
            threads = [threading.Thread(target=write, args=(k,),
                                        name=f"ring-writer-{k}")
                       for k in range(writers)]
            reader.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            stop.set()
            reader.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not reader.is_alive()
        assert not any(t.is_alive() for t in threads) and not torn
        kept = [s for s in tracing.process_snapshot()
                if s[3] and "writer" in s[3]]
        # 24,000 written; an event loop that another test started may
        # have ticked into the ring since.
        assert tracing.PROCESS_RING - 5 <= len(kept) <= tracing.PROCESS_RING
        # Each writer's spans are in its own order.
        for k in range(writers):
            mine = [s[3]["n"] for s in kept if s[3]["writer"] == k]
            assert mine == sorted(mine)

    def test_every_name_the_program_writes_is_declared(self):
        assert tracing.PROCESS_SPANS == (
            "host/gc", "observe/drain", "loop/sample", "decode/idle")
        assert not set(tracing.PROCESS_SPANS) & set(tracing.STAGES)
        assert "decode/wake" in tracing.STAGES


class TestRendering:
    def _requests(self):
        traces = []
        for api in ("decode_step", "decode_init"):
            with tracing.request_trace(api, model="m",
                                       signature=api) as tr:
                with tracing.span("host/execute"):
                    pass
            traces.append(tr)
        return traces

    def test_process_spans_lie_on_one_tid_that_no_request_has(self):
        traces = self._requests()
        t0 = traces[0].start
        tracing.process_span("host/gc", t0, t0 + 0.25, gen=2, collected=3)
        payload = tracing.chrome_trace(
            traces, process_spans=tracing.process_snapshot())
        mine = [e for e in payload["traceEvents"]
                if e.get("cat") == "process"]
        assert mine == [{
            "name": "host/gc", "cat": "process", "ph": "X", "pid": 1,
            "tid": tracing.PROCESS_TID, "ts": tracing._us(t0),
            "dur": 250000.0, "args": {"gen": 2, "collected": 3}}]
        assert tracing.PROCESS_TID not in {tr.id for tr in traces}
        assert tracing.PROCESS_TID == 0 and min(tr.id for tr in traces) >= 1
        json.dumps(payload)

    def test_the_benchmark_s_reader_returns_the_requests_it_returned_before(
            self):
        traces = self._requests()
        tracing.process_span("observe/drain", traces[0].start,
                             traces[1].end, traces=2, cpu_us=40)
        tracing.process_span("decode/idle", traces[0].start - 1.0,
                             traces[0].start, restarted=1)
        without = spans.requests_from_chrome(tracing.chrome_trace(traces))
        with_track = spans.requests_from_chrome(tracing.chrome_trace(
            traces, process_spans=tracing.process_snapshot()))
        assert with_track == without and len(without) == 2

    def test_the_wall_clock_rendering_leaves_the_track_out(self):
        traces = self._requests()
        tracing.process_span("host/gc", 1.0, 2.0, gen=0, collected=0)
        payload = tracing.chrome_trace(
            traces, clock="wall", process_spans=tracing.process_snapshot())
        assert not [e for e in payload["traceEvents"]
                    if e.get("cat") == "process"]

    def test_the_endpoint_shows_the_track_from_its_oldest_request_on(self):
        from min_tfs_client_tpu.server import rest

        tracing.ring_clear()
        tracing.process_span("host/gc", 1.0, 2.0, gen=0, collected=0)
        traces = self._requests()
        assert traces[0].end < traces[1].start
        tracing.process_span("observe/drain", traces[0].start,
                             traces[0].end, traces=1, cpu_us=9)
        status, _, body = rest._traces_reply("")
        events = json.loads(body)["traceEvents"]
        assert status == 200
        assert [e["name"] for e in events if e.get("cat") == "process"] \
            == ["observe/drain"]
        # ?limit=1 shows the newest request, and the track since it began.
        _, _, body = rest._traces_reply("limit=1")
        events = json.loads(body)["traceEvents"]
        assert len([e for e in events if e.get("cat") == "request"]) == 1
        assert not [e for e in events if e.get("cat") == "process"]


class TestWhatWritesToIt:
    def test_a_collection_of_a_millisecond_is_a_span_and_all_are_counted(
            self, monkeypatch):
        runtime.watch_gc()
        runtime.watch_gc()  # once, however often it is asked for
        assert gc.callbacks.count(runtime._on_gc) == 1
        before = runtime.gc_pause_seconds()
        monkeypatch.setattr(runtime, "GC_SPAN_MIN_S", 0.0)
        gc.collect()
        (span,) = [s for s in named("host/gc") if s[3]["gen"] == 2][-1:]
        assert span[2] > span[1] and span[3]["collected"] >= 0
        after = runtime.gc_pause_seconds()
        assert sorted(after) == ["0", "1", "2"]
        assert after["2"] >= before["2"] + (span[2] - span[1]) - 1e-6
        assert runtime.snapshot()["gc_pause_seconds"] == \
            runtime.gc_pause_seconds()
        # A pause under the floor is counted and leaves no span.
        monkeypatch.setattr(runtime, "GC_SPAN_MIN_S", 3600.0)
        tracing.process_clear()
        gc.collect()
        assert named("host/gc") == []
        assert runtime.gc_pause_seconds()["2"] > after["2"]

    def test_a_drain_that_found_work_is_one_span(self):
        tracing.flush_metrics()
        assert named("observe/drain") == []  # nothing was pending
        for _ in range(3):
            with tracing.request_trace("predict", model="m"):
                pass
        tracing.flush_metrics()
        (span,) = named("observe/drain")
        assert span[3]["traces"] == 3
        assert 0 <= span[3]["cpu_us"] <= (span[2] - span[1]) * 1e6 + 1000

    def test_the_event_loop_s_ticker_writes_a_sample_a_tick(self):
        aio_loop.get()
        until(lambda: len(named("loop/sample")) >= 3, timeout_s=5.0)
        first, second = named("loop/sample")[-2:]
        # The samples tile the loop thread's time.
        assert second[1] == first[2]
        for _, t0, t1, args in (first, second):
            assert aio_loop.LAG_TICK_S <= t1 - t0 < 2.0
            assert args["lag_us"] == pytest.approx(
                (t1 - t0 - aio_loop.LAG_TICK_S) * 1e6, abs=5000)
            assert 0 <= args["cpu_us"] <= (t1 - t0) * 1e6
        stats = aio_loop.stats()
        assert 0.0 <= stats["event_loop_cpu_share"] <= 1.0
        assert runtime.snapshot()["grpc"]["event_loop_cpu_share"] >= 0.0


class TestTheDecodeLoop:
    """`decode/idle` and `decode/wake` from a TickBatcher driven by hand,
    over the dense pool and a one-line model."""

    @pytest.fixture(autouse=True)
    def _no_thread_outlives_its_work(self):
        until(lambda: not tick_loop_threads())
        yield
        until(lambda: not tick_loop_threads())

    def _drive(self):
        from min_tfs_client_tpu.robustness import faults

        state = {"n": np.zeros((1,), np.int32)}
        pool = SlotPool(
            state, lambda s: ({"n": s["n"] + 1}, {"token": s["n"] + 1}),
            max_slots=4)
        batcher = TickBatcher(pool.tick)
        traces, tokens = [], []

        def session(room):
            slot = pool.acquire_slot()
            pool.write(state, slot)
            batcher.admit(slot, room=room)
            for _ in range(room):
                with tracing.request_trace("decode_step",
                                           signature="decode_step") as tr:
                    tokens.append(int(batcher.step(slot)["token"][0]))
                traces.append(tr)
            batcher.release(slot)
            pool.release_slot(slot)

        # Rounds of 40 ms and more: the slivers between two phases (a
        # clock read, a function call; after a loop's last fetch, its
        # way to the snapshot that finds nothing) are nothing against
        # them.
        session(1)       # round 1 compiles the tick and imports its planes
        del traces[:], tokens[:]
        faults.arm({"rules": [{"point": "backend.tick.pre",
                               "action": "delay", "delay_ms": 40}]})
        try:
            session(3)   # rounds 2-4; then nothing is due, the loop ends
            until(lambda: not tick_loop_threads())
            time.sleep(0.03)
            session(2)   # rounds 5-6
        finally:
            faults.disarm()
        assert tokens == [1, 2, 3, 1, 2]
        return traces

    def test_the_time_with_nothing_due_is_a_process_span(self):
        traces = self._drive()
        found = {name: {args["round"]: (t0, t1) for tr in traces
                        for n, t0, t1, args in tr.spans if n == name}
                 for name in ("decode/handoff", "decode/fetch")}
        idles = named("decode/idle")
        assert all(args == {"restarted": 1} for *_, args in idles)
        # One session, one token ahead: the loop also ends between two
        # of its steps, whenever the token it parked is not yet
        # collected. Every such stretch ends at a round's snapshot, and
        # begins after the fetch of the round before.
        for _, t0, t1, _ in idles:
            (r,) = [r for r, (_, taken) in found["decode/handoff"].items()
                    if taken == t1]
            if r - 1 in found["decode/fetch"]:  # not the warm-up's round
                assert found["decode/fetch"][r - 1][1] <= t0 < t1
        # The longest is the time between the two sessions.
        longest = max(idles, key=lambda s: s[2] - s[1])
        assert longest[2] - longest[1] >= 0.03
        assert longest[2] == found["decode/handoff"][5][1]

    def test_the_wake_phase_lies_between_launch_and_fetch(self):
        traces = self._drive()
        by_round: dict = {}
        for tr in traces:
            for name, t0, t1, args in tr.spans:
                if name.startswith("decode/") and name != "decode/wait":
                    by_round.setdefault(args["round"], {})[name] = (
                        t0, t1, args)
        assert sorted(by_round) == [2, 3, 4, 5, 6]
        for r, found in by_round.items():
            tick, wake, fetch = (found[n] for n in (
                "decode/tick", "decode/wake", "decode/fetch"))
            assert tick[1] <= wake[0] <= wake[1] <= fetch[0], r
            assert wake[0] - tick[1] < 1e-3 and fetch[0] - wake[1] < 1e-3
            assert wake[2]["under_pool_lock"] == 1
            assert wake[2]["round"] == r and wake[2]["cpu_us"] >= 0
        # One session at a time: a round wakes the one rider of the
        # round before, if that rider was waiting for it.
        assert {found["decode/wake"][2]["woken"]
                for found in by_round.values()} <= {0, 1}

    def test_the_loop_s_phases_add_up_to_its_period(self):
        traces = self._drive()
        payload = tracing.chrome_trace(
            traces, process_spans=tracing.process_snapshot())
        payload["otherData"]["capture"] = {"zero_us": 0.0, "stop_us": 1e15}
        cover = host_track.phase_cover(host_track.load(payload))
        # Rounds 2-5 have a next round; 4 -> 5 runs over the idle time.
        assert len(cover) == 4
        assert all(0.99 <= c <= 1.0 for c in cover), cover
        # Without the idle span the round before it is mostly uncovered.
        bare = tracing.chrome_trace(traces)
        bare["otherData"]["capture"] = payload["otherData"]["capture"]
        assert min(host_track.phase_cover(host_track.load(bare))) < 0.75


class TestTheCaptureIsWholeByItself:
    def test_a_capture_writes_the_track_beside_the_clock(self, tmp_path):
        tracing.ring_clear()
        with tracing.request_trace("predict", signature="early"):
            pass
        # Of the process's own spans, the lead before the capture too:
        # inside one the profiler slows every thread, so what they cost
        # is read before it. Not what is older than the lead.
        now = time.perf_counter()
        tracing.process_span("host/gc", now - 1.0, now - 0.9, gen=1,
                             collected=0)
        tracing.process_span("host/gc", now - 2 * profiling.TRACK_LEAD_S,
                             now - profiling.TRACK_LEAD_S - 1.0, gen=2,
                             collected=0)
        time.sleep(0.01)
        with profiling.traced_capture(str(tmp_path)):
            with tracing.request_trace("predict", signature="decode_init"):
                with tracing.span("decode/init", tokens=4):
                    time.sleep(0.01)
            with tracing.request_trace("predict", signature="decode_step"):
                pass
            now = time.perf_counter()
            tracing.process_span("observe/drain", now - 0.005, now,
                                 traces=2, cpu_us=100)
        clock = json.loads((tmp_path / profiling.HOST_CLOCK_FILE).read_text())
        payload = json.loads(
            (tmp_path / profiling.HOST_TRACK_FILE).read_text())
        assert payload["otherData"]["schema"] == "host_track/1"
        assert payload["otherData"]["capture"] == {
            "zero_us": clock["zero"]["span_us"],
            "stop_us": clock["stop"]["span_us"],
            "lead_us": pytest.approx(clock["zero"]["span_us"]
                                     - profiling.TRACK_LEAD_S * 1e6)}
        track = host_track.load(payload)
        # Requests of ANY signature that overlap the capture; not the
        # one that ended before it.
        assert sorted(r["args"]["signature"] for r in track["requests"]) \
            == ["decode_init", "decode_step"]
        (init,) = [r for r in track["requests"]
                   if r["args"]["signature"] == "decode_init"]
        assert [s[0] for s in init["spans"]] == ["decode/init"]
        # (An event loop that another test started goes on ticking.)
        assert [(name, args) for name, _, _, args in track["process"]
                if name != "loop/sample"] \
            == [("host/gc", {"gen": 1, "collected": 0}),
                ("observe/drain", {"traces": 2, "cpu_us": 100})]

    def test_the_endpoint_s_reply_lists_it(self, tmp_path):
        reply = profiling.device_capture(0.1, str(tmp_path))
        assert {profiling.HOST_CLOCK_FILE, profiling.HOST_TRACK_FILE} \
            <= set(reply["files"])
