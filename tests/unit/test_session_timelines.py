"""Per-session decode timelines (decode_sessions.SessionTimelines) and
the cross-process flight-recorder correlation: the pure-Python halves of
the fleet-observability issue — ring bounds, slot-reuse isolation, the
/monitoring/sessions payload/endpoint, and trace ids in request digests
joining the router's and a backend's latched dumps."""

from __future__ import annotations

import json

import pytest

from min_tfs_client_tpu.observability import flight_recorder
from min_tfs_client_tpu.servables import decode_sessions
from min_tfs_client_tpu.servables.decode_sessions import SessionTimelines


class TestTimelineRings:
    def test_events_per_session_is_a_ring(self):
        tl = SessionTimelines(label="t", events_per_session=16)
        tl.begin(0, b"s0")
        for i in range(40):
            tl.event(0, "tick", tokens=i)
        detail = tl.find("s0")
        assert len(detail) == 1
        events = detail[0]["events"]
        assert len(events) == 16  # bounded, newest kept
        assert events[-1]["tokens"] == 39
        assert events[0]["tokens"] == 24  # oldest 24 rolled out ("init" too)

    def test_list_view_caps_events_and_counts_drops(self):
        tl = SessionTimelines(label="t", events_per_session=64)
        tl.begin(1, b"s1")
        for i in range(20):
            tl.event(1, "tick", tokens=i)
        snap = tl.snapshot(max_events=4)
        row = snap["live"][0]
        assert len(row["events"]) == 4
        assert row["events_dropped"] == 17  # init + 20 ticks - 4 shown

    def test_closed_archive_is_a_ring(self):
        tl = SessionTimelines(label="t", closed_capacity=3)
        for i in range(5):
            tl.begin(0, f"s{i}".encode())
            tl.close(0)
        snap = tl.snapshot()
        assert snap["live"] == []
        assert [t["session_id"] for t in snap["closed"]] == \
            ["s2", "s3", "s4"]
        assert all(t["state"] == "closed" for t in snap["closed"])

    def test_slot_reuse_archives_never_splices(self):
        tl = SessionTimelines(label="t")
        tl.begin(2, b"first")
        tl.event(2, "tick", tokens=1)
        tl.begin(2, b"second")  # no observed close: supersede
        tl.event(2, "tick", tokens=1)
        first = tl.find("first")[0]
        second = tl.find("second")[0]
        assert first["state"] == "superseded"
        assert len([e for e in first["events"] if e["kind"] == "tick"]) == 1
        assert second["state"] == "live"

    def test_events_on_unknown_slot_are_dropped(self):
        tl = SessionTimelines(label="t")
        tl.event(7, "tick")  # never began: no crash, no ghost session
        tl.close(7)
        assert tl.snapshot()["live"] == []
        assert tl.snapshot()["closed"] == []


class TestRoundEvents:
    """What a round does to all its sessions is written once a round
    (`round_event`) and joined to a session when its timeline is read."""

    @staticmethod
    def _kinds(row):
        return [(e["kind"], e.get("tokens")) for e in row["events"]]

    def test_a_round_is_one_entry_whatever_it_carries(self):
        tl = SessionTimelines(label="t")
        for slot in range(3):
            tl.begin(slot, f"s{slot}".encode())
        tl.round_event("tick", {0: (1, 1), 2: (5, 2)}, ("tokens", "pages"),
                       tick_ms=31.5)
        assert len(tl._rounds) == 1
        rode = tl.find("s2")[0]["events"][-1]
        assert {k: rode[k] for k in ("kind", "tokens", "pages", "tick_ms")} \
            == {"kind": "tick", "tokens": 5, "pages": 2, "tick_ms": 31.5}
        assert self._kinds(tl.find("s1")[0]) == [("init", None)]
        # The dense pool's round has no per-slot field.
        tl.round_event("tick", dict.fromkeys([1], ()), tick_ms=2.0)
        assert tl.find("s1")[0]["events"][-1]["tick_ms"] == 2.0

    def test_a_session_s_own_events_and_its_rounds_are_in_time_order(self):
        tl = SessionTimelines(label="t")
        tl.begin(0, b"s0")
        tl.round_event("tick", {0: (1, 1)}, ("tokens", "pages"))
        tl.event(0, "swap_out", pages=1)
        tl.round_event("tick", {0: (2, 1)}, ("tokens", "pages"))
        tl.close(0)
        (row,) = tl.snapshot()["closed"]
        assert self._kinds(row) == [("init", None), ("tick", 1),
                                    ("swap_out", None), ("tick", 2),
                                    ("close", None)]

    @pytest.mark.parametrize("how", ["close", "supersede"])
    def test_a_slot_s_next_session_takes_no_round_of_the_one_before(
            self, how):
        tl = SessionTimelines(label="t")
        tl.begin(4, b"first")
        tl.round_event("tick", {4: (1, 1)}, ("tokens", "pages"))
        if how == "close":
            tl.close(4)
        tl.begin(4, b"second")
        tl.round_event("tick", {4: (7, 3)}, ("tokens", "pages"))
        assert self._kinds(tl.find("first")[0])[:2] \
            == [("init", None), ("tick", 1)]
        assert ("tick", 7) not in self._kinds(tl.find("first")[0])
        assert self._kinds(tl.find("second")[0]) \
            == [("init", None), ("tick", 7)]

    def test_both_rings_bound_what_a_view_shows(self):
        tl = SessionTimelines(label="t", events_per_session=16)
        tl.begin(0, b"s0")
        for k in range(decode_sessions.ROUNDS_KEPT + 40):
            tl.round_event("tick", {0: (k, 1)}, ("tokens", "pages"))
        assert len(tl._rounds) == decode_sessions.ROUNDS_KEPT == 1024
        events = tl.find("s0")[0]["events"]
        assert len(events) == 16  # the session's ring, newest kept
        assert events[-1]["tokens"] == decode_sessions.ROUNDS_KEPT + 39
        row = tl.snapshot(max_events=4)["live"][0]
        assert len(row["events"]) == 4 and row["events_dropped"] == 12


class TestSessionsPayload:
    def test_payload_lists_registered_pools_weakly(self):
        tl = SessionTimelines(label="payload-pool")
        tl.begin(0, b"alive")
        pools = {p["pool"]: p
                 for p in decode_sessions.sessions_payload()["pools"]}
        assert "payload-pool" in pools
        assert pools["payload-pool"]["live"][0]["session_id"] == "alive"
        del tl, pools
        import gc

        gc.collect()
        remaining = [p["pool"] for p in
                     decode_sessions.sessions_payload()["pools"]]
        assert "payload-pool" not in remaining  # registry is weak

    def test_session_detail_spans_pools_and_archives(self):
        a = SessionTimelines(label="pool-a")
        b = SessionTimelines(label="pool-b")
        a.begin(0, b"shared-id")
        a.close(0)
        b.begin(3, b"shared-id")
        detail = decode_sessions.sessions_payload(session="shared-id")
        assert detail["found"] is True
        states = {(t["pool"], t["state"]) for t in detail["timelines"]}
        assert states == {("pool-a", "closed"), ("pool-b", "live")}
        missing = decode_sessions.sessions_payload(session="ghost")
        assert missing["found"] is False and missing["timelines"] == []

    def test_rest_endpoint_routes_and_validates(self):
        from min_tfs_client_tpu.server import rest

        tl = SessionTimelines(label="rest-pool")
        tl.begin(1, b"rest-session")
        status, ctype, body = rest._sessions_reply("")
        assert status == 200 and ctype == "application/json"
        payload = json.loads(body)
        assert any(p["pool"] == "rest-pool" for p in payload["pools"])
        status, _, body = rest._sessions_reply("session=rest-session")
        assert status == 200
        assert json.loads(body)["found"] is True
        status, _, _ = rest._sessions_reply("events=zero")
        assert status == 400


class TestRecorderTraceCorrelation:
    def test_error_digest_carries_trace_id(self):
        rec = flight_recorder.FlightRecorder(capacity=16)
        rec.dump = lambda reason="manual": None  # no files from unit tests
        rec.record_error("predict", "m", "sig", 3, "boom 17",
                         trace_id="trace-77")
        event = rec.to_json()["events"][-1]
        assert event["trace_id"] == "trace-77"
        assert event["error_digest"]

    def test_router_and_backend_digests_join_on_trace_id(self):
        """The cross-process join the issue demands: one request's
        failure shows up in BOTH processes' rings under one trace id,
        with per-process digests (different failure-mode scope)."""
        router = flight_recorder.FlightRecorder(capacity=16)
        backend = flight_recorder.FlightRecorder(capacity=16)
        for rec in (router, backend):
            rec.dump = lambda reason="manual": None
        trace_id = "fleet-trace-42"
        backend.record_error("predict", "t5", "decode_step", 13,
                             "buffer donated twice", trace_id=trace_id)
        router.record_error("route/Predict", "t5", "decode_step", 13,
                            "127.0.0.1:8500: buffer donated twice",
                            trace_id=trace_id)
        join = {
            name: [e for e in rec.to_json()["events"]
                   if e.get("trace_id") == trace_id]
            for name, rec in (("router", router), ("backend", backend))
        }
        assert len(join["router"]) == 1 and len(join["backend"]) == 1
        assert join["router"][0]["error_digest"]
        assert join["backend"][0]["error_digest"]

    def test_latch_dump_is_one_shot_shared_with_internal(self):
        rec = flight_recorder.FlightRecorder(capacity=16)
        dumps = []
        rec.dump = lambda reason="manual": dumps.append(reason)
        rec.latch_dump("UNAVAILABLE from every backend")
        rec.latch_dump("UNAVAILABLE from every backend")
        rec.record_error("predict", "m", "s", 13, "internal boom")
        assert dumps == ["UNAVAILABLE from every backend"]
        rec.reset()
        rec.record_error("predict", "m", "s", 13, "internal boom")
        assert dumps[-1] == "first INTERNAL error"

    def test_rearm_reopens_the_latch_without_clearing_the_ring(self):
        """Multi-phase storms latch ONE dump per phase: rearm() resets
        the latch, keeps the events, and reports whether the latch had
        fired — the /monitoring/flightrecorder?rearm=1 contract."""
        rec = flight_recorder.FlightRecorder(capacity=16)
        dumps = []
        rec.dump = lambda reason="manual": dumps.append(reason)
        rec.record_error("predict", "m", "s", 13, "phase-1 internal")
        assert dumps == ["first INTERNAL error"]
        assert rec.rearm() is True        # latch HAD fired
        assert rec.rearm() is False       # idempotent re-arm
        assert len(rec.snapshot()) == 1   # ring untouched
        rec.record_error("predict", "m", "s", 13, "phase-2 internal")
        assert dumps == ["first INTERNAL error", "first INTERNAL error"]

    def test_rearm_endpoint_query(self):
        """The REST reply honors ?rearm=1 against the process-global
        recorder (shared by a backend's two REST front-ends and the
        router's monitoring surface alike)."""
        import json as _json

        from min_tfs_client_tpu.server import rest as rest_mod

        flight_recorder.reset()
        dumps = []
        original_dump = flight_recorder.recorder.dump
        flight_recorder.recorder.dump = \
            lambda reason="manual": dumps.append(reason)
        try:
            flight_recorder.record_error("predict", "m", "s", 13, "boom")
            code, _, body = rest_mod._flight_recorder_reply("rearm=1")
            payload = _json.loads(body)
            assert code == 200
            assert payload["rearmed"] is True
            assert payload["was_latched"] is True
            assert payload["events"], "ring must not be cleared"
            # plain GET: no rearm key at all
            code, _, body = rest_mod._flight_recorder_reply("")
            assert "rearmed" not in _json.loads(body)
            # the latch is genuinely open again
            flight_recorder.record_error("predict", "m", "s", 13, "boom2")
            assert len(dumps) == 2
        finally:
            flight_recorder.recorder.dump = original_dump
            flight_recorder.reset()


class TestNoLiveBackendsLatch:
    def test_router_core_records_and_latches(self):
        from min_tfs_client_tpu.router.core import RouterCore
        from min_tfs_client_tpu.router.membership import (
            UNREACHABLE,
            Backend,
        )
        from min_tfs_client_tpu.utils.status import ServingError

        flight_recorder.reset()
        dumps = []
        original_dump = flight_recorder.recorder.dump
        flight_recorder.recorder.dump = \
            lambda reason="manual": dumps.append(reason)
        try:
            backends = [Backend("127.0.0.1", 18700)]
            core = RouterCore(
                backends, poll_interval_s=0.05, probe_timeout_s=0.05,
                poller=lambda b: (UNREACHABLE, None))
            core.membership.poll_once()  # -> DEAD
            for _ in range(2):
                with pytest.raises(ServingError) as err:
                    core.route("m", None, b"req")
                assert "no live backends" in err.value.message
            kinds = [e["kind"] for e in flight_recorder.to_json()["events"]]
            assert "no_live_backends" in kinds
            # DEAD transition context rides the same ring.
            assert "backend_state" in kinds
            # One dump for N consecutive failures (latched).
            assert dumps == ["UNAVAILABLE from every backend"]
        finally:
            flight_recorder.recorder.dump = original_dump
            flight_recorder.reset()
