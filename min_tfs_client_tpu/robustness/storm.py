"""fleet_storm: a seeded, replayable OPEN-LOOP storm with invariants
asserted while the fleet burns.

Every closed-loop bench leg self-throttles: when the fleet degrades,
the callers slow down, and the degradation hides. A storm is open-loop
— arrivals happen when the SCHEDULE says, not when the last reply came
back — and it mixes the traffic shapes that found every post-PR-9 bug
class only after review: short and long decode sessions, stateless
floods, burst arrivals, and mid-run chaos (SIGKILL, drain, join,
KV-pressure phases). The schedule is a pure function of the seed, so a
storm that caught a race replays bit-for-bit.

Invariants are checked DURING the run, per event, not by a final sweep:

 * no lost non-pinned request — every stateless request (bounded-retry
   client) must succeed while the fleet has live capacity;
 * every session stream is bit-exact (fixture: base+n counters; t5:
   the pre-storm reference token stream) or terminated with a TYPED
   retryable error, and ONLY when its backend was killed — a session
   pinned to a DRAINING backend must finish untouched (the drain-race
   detector) and a typed capacity refusal is backpressure, not loss;
 * open-loop p99 stays within a budget of the quiet-phase baseline;
 * the flight recorders (router + backends) stay silent: no INTERNAL,
   no UNAVAILABLE-from-all latch, and no fault events beyond the armed
   plan's.

The harness (tests/integration/test_fleet_storm.py) owns the
subprocess fleet; this module owns the
schedule, the workers, and the verdict.
"""

from __future__ import annotations

import json
import random
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np


@dataclass(frozen=True)
class StormConfig:
    """One replayable storm. Everything the schedule derives from is
    here; two runs with equal configs generate identical schedules."""

    seed: int = 0
    quiet_s: float = 3.0            # baseline phase (no chaos/sessions)
    duration_s: float = 12.0        # storm phase length
    model: str = "sess"
    # Open-loop arrival processes (storm phase).
    stateless_rate_hz: float = 15.0
    session_rate_hz: float = 1.2
    session_steps_choices: tuple = (3, 6, 12)
    session_step_interval_s: float = 0.08
    burst_every_s: float = 0.0      # 0 = no bursts
    burst_size: int = 16
    # Chaos schedule: (at_s, op) with op in {"kill:<i>", "drain:<i>",
    # "join"} — executed via the harness-supplied callbacks.
    chaos: tuple = ()
    # p99 budget: storm-phase open-loop p99 <= quiet p99 * ratio + floor.
    # Generous by design — a ONE-core CI host serializes everything; the
    # invariant catches order-of-magnitude thrash, not microseconds.
    p99_budget_ratio: float = 25.0
    p99_floor_ms: float = 500.0
    max_workers: int = 12
    recorder_poll_s: float = 1.0
    # Client retry policy for storm traffic (the typed-UNAVAILABLE
    # contract is what makes these retries honest).
    client_retries: int = 6
    client_backoff_s: float = 0.05


@dataclass(frozen=True)
class T5StormSpec:
    """Optional KV-pressure leg: sessions against a paged t5 model.
    `references[i]` is prompt i's full greedy token stream, computed
    on a QUIET fleet before the storm — bit-exactness under pressure
    (swap/restore, chunked scheduling) is asserted against it."""

    model: str
    prompts: tuple            # tuple of (1, seq) int32 ndarrays
    references: tuple         # tuple of token lists (ints)
    session_rate_hz: float = 0.8
    step_interval_s: float = 0.05


@dataclass
class Violation:
    at_s: float
    kind: str
    detail: str


@dataclass
class StormReport:
    seed: int
    violations: list = field(default_factory=list)
    stateless_sent: int = 0
    stateless_ok: int = 0
    stateless_retried: int = 0
    sessions_started: int = 0
    sessions_completed: int = 0
    sessions_killed: int = 0          # terminated by a SIGKILL, typed
    sessions_refused: int = 0         # typed capacity backpressure
    t5_sessions_completed: int = 0
    quiet_p50_ms: float = 0.0
    quiet_p99_ms: float = 0.0
    storm_p50_ms: float = 0.0
    storm_p99_ms: float = 0.0
    fault_events_seen: int = 0
    recorder_internal_errors: int = 0
    chaos_executed: list = field(default_factory=list)

    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        out = dict(self.__dict__)
        out["violations"] = [v.__dict__ for v in self.violations]
        out["ok"] = self.ok()
        return out


# -- schedule ----------------------------------------------------------------


@dataclass(frozen=True)
class StormEvent:
    at_s: float          # relative to storm-phase start
    kind: str            # stateless | session | t5_session | chaos
    payload: tuple = ()


def generate_schedule(cfg: StormConfig,
                      t5: Optional[T5StormSpec] = None
                      ) -> list[StormEvent]:
    """The storm-phase schedule, a pure function of (cfg, t5 spec).
    Arrivals are jittered-uniform around each process's period (open
    loop: times are fixed BEFORE the run), bursts drop `burst_size`
    stateless arrivals at one instant, chaos ops land verbatim."""
    rng = random.Random(cfg.seed)
    events: list[StormEvent] = []

    def arrivals(rate_hz: float):
        if rate_hz <= 0:
            return
        t = 0.0
        while True:
            t += rng.uniform(0.4, 1.6) / rate_hz
            if t >= cfg.duration_s:
                return
            yield t

    for t in arrivals(cfg.stateless_rate_hz) or ():
        events.append(StormEvent(t, "stateless",
                                 (rng.uniform(-8.0, 8.0),)))
    session_n = 0
    for t in arrivals(cfg.session_rate_hz) or ():
        steps = rng.choice(cfg.session_steps_choices)
        base = rng.randrange(10_000, 1_000_000)
        events.append(StormEvent(t, "session",
                                 (session_n, base, steps)))
        session_n += 1
    if t5 is not None:
        t5_n = 0
        for t in arrivals(t5.session_rate_hz) or ():
            prompt_idx = rng.randrange(len(t5.prompts))
            events.append(StormEvent(t, "t5_session",
                                     (t5_n, prompt_idx)))
            t5_n += 1
    if cfg.burst_every_s > 0:
        t = cfg.burst_every_s
        while t < cfg.duration_s:
            for _ in range(cfg.burst_size):
                events.append(StormEvent(t, "stateless",
                                         (rng.uniform(-8.0, 8.0),)))
            t += cfg.burst_every_s
    for at_s, op in cfg.chaos:
        events.append(StormEvent(float(at_s), "chaos", (op,)))
    events.sort(key=lambda e: (e.at_s, e.kind, e.payload))
    return events


# -- the runner --------------------------------------------------------------


class _RecorderMonitor:
    """Polls every process's /monitoring/flightrecorder DURING the run
    and turns INTERNAL errors / no-live-backends latches into
    violations the moment they appear. Watermarked by event seq so one
    bad event is one violation."""

    def __init__(self, rest_ports: list[int], report: StormReport,
                 violations, started_at: float, poll_s: float):
        self._ports = rest_ports
        self._report = report
        self._violations = violations
        self._started_at = started_at
        self._poll_s = poll_s
        self._seq: dict[int, int] = {p: 0 for p in rest_ports}
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="storm-recorder-monitor",
            daemon=True)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=self._poll_s + 15.0)

    def sweep(self) -> None:
        for port in self._ports:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}"
                        "/monitoring/flightrecorder",
                        timeout=5) as resp:
                    events = json.loads(resp.read())["events"]
            except Exception:  # noqa: BLE001 - a killed backend's port
                continue       # legitimately stops answering
            for event in events:
                if event.get("seq", 0) <= self._seq[port]:
                    continue
                self._seq[port] = event["seq"]
                kind = event.get("kind")
                if kind == "fault":
                    self._report.fault_events_seen += 1
                elif kind == "error" and event.get("code") == 13:
                    self._report.recorder_internal_errors += 1
                    self._violations(Violation(
                        time.monotonic() - self._started_at,
                        "flight_recorder_internal",
                        f"port {port}: INTERNAL in the ring: "
                        f"{event.get('message', '')[:160]}"))
                elif kind == "no_live_backends":
                    self._violations(Violation(
                        time.monotonic() - self._started_at,
                        "no_live_backends",
                        f"port {port}: router saw zero live backends "
                        "during a storm that never killed the whole "
                        "fleet"))

    def _loop(self) -> None:
        while not self._stop.wait(timeout=self._poll_s):
            try:
                self.sweep()
            except Exception:  # pragma: no cover - monitor must survive
                pass
        self.sweep()  # final watermarked pass before the verdict


class FleetStorm:
    """One storm run against a harness-owned fleet.

    `chaos_ops` maps "kill:<i>"/"drain:<i>"/"join" to callables; kill
    callbacks MUST return the dying backend's serving pid (the runner
    marks it so that pinned sessions' typed terminations are allowed —
    and ONLY those)."""

    def __init__(self, cfg: StormConfig, *,
                 router_grpc_ports: list[int],
                 monitor_rest_ports: list[int],
                 chaos_ops: dict[str, Callable],
                 t5: Optional[T5StormSpec] = None):
        from min_tfs_client_tpu.client import TensorServingClient

        self.cfg = cfg
        self.t5 = t5
        self._chaos_ops = chaos_ops
        self._monitor_ports = monitor_rest_ports
        self.report = StormReport(seed=cfg.seed)
        self._lock = threading.Lock()
        self._killed_pids: set[int] = set()   # guarded_by: self._lock
        self._rr = 0                          # guarded_by: self._lock
        # servelint: thread-ok written once in run() before any worker
        # thread spawns; workers only read it (violation timestamps)
        self._t0 = 0.0
        self._clients = [
            TensorServingClient("127.0.0.1", port,
                                retry_unavailable=True,
                                max_retries=cfg.client_retries,
                                retry_backoff_s=cfg.client_backoff_s)
            for port in router_grpc_ports]

    # -- plumbing ------------------------------------------------------------

    def _client(self):
        with self._lock:
            self._rr += 1
            return self._clients[self._rr % len(self._clients)]

    def _violate(self, violation: Violation) -> None:
        with self._lock:
            self.report.violations.append(violation)

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def _allowed_termination(self, owner_pid: Optional[int]) -> bool:
        with self._lock:
            return owner_pid is not None and owner_pid in self._killed_pids

    # -- workers -------------------------------------------------------------

    def _stateless_once(self, scheduled_at: float, x_value: float,
                        sink: list) -> None:
        from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

        x = np.asarray([np.float32(x_value)], np.float32)
        with self._lock:
            self.report.stateless_sent += 1
        try:
            resp = self._client().predict_request(
                self.cfg.model, {"x": x}, timeout=30)
        except Exception as exc:  # noqa: BLE001 - ANY terminal failure
            self._violate(Violation(
                self._now(), "lost_stateless_request",
                f"stateless request failed terminally after bounded "
                f"retry: {exc}"))
            return
        got = tensor_proto_to_ndarray(resp.outputs["y"])
        want = x * np.float32(3.0) + np.float32(1.0)
        # One-ulp tolerance, not bytes: XLA legitimately fuses x*3+1
        # into an FMA whose f32 rounding differs from two host ops.
        # (Routed-vs-direct BYTE identity is asserted separately —
        # bench's routed leg — against the same backend bytes.)
        if not np.allclose(got, want, rtol=1e-6, atol=1e-6):
            self._violate(Violation(
                self._now(), "stateless_value",
                f"y != 3x+1 for x={x_value}: got {got!r}"))
            return
        latency_ms = (self._now() - scheduled_at) * 1e3
        with self._lock:
            self.report.stateless_ok += 1
            sink.append(latency_ms)

    def _session_worker(self, index: int, base: int, steps: int) -> None:
        from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray
        from min_tfs_client_tpu.utils.status import Code

        sid = np.asarray(b"storm-%d-%d" % (self.cfg.seed, index), object)
        client = self._client()
        with self._lock:
            self.report.sessions_started += 1
        try:
            resp = client.predict_request(
                self.cfg.model,
                {"session_id": sid, "base": np.asarray(base, np.int32)},
                signature_name="decode_init", timeout=30)
        except Exception as exc:  # noqa: BLE001 - init may hit capacity
            if _grpc_code_value(exc) == Code.RESOURCE_EXHAUSTED:
                with self._lock:
                    self.report.sessions_refused += 1
            else:
                self._violate(Violation(
                    self._now(), "session_init_failed",
                    f"session {index}: init died: {exc}"))
            return
        owner_pid = int(tensor_proto_to_ndarray(resp.outputs["pid"])[0])
        for step in range(1, steps + 1):
            time.sleep(self.cfg.session_step_interval_s)
            try:
                resp = client.predict_request(
                    self.cfg.model,
                    {"session_id": sid,
                     "step_ordinal": np.asarray(step, np.int64)},
                    signature_name="decode_step", timeout=30)
            except Exception as exc:  # noqa: BLE001 - classified below
                code = _grpc_code_value(exc)
                typed_retryable = code in (Code.UNAVAILABLE,
                                           Code.NOT_FOUND)
                if typed_retryable and \
                        self._allowed_termination(owner_pid):
                    with self._lock:
                        self.report.sessions_killed += 1
                    return  # state died with its SIGKILLed process
                self._violate(Violation(
                    self._now(), "session_stream_broken",
                    f"session {index} (pid {owner_pid}) step {step} "
                    f"failed ({'typed' if typed_retryable else 'UNTYPED'}"
                    f") while its backend was never killed: {exc}"))
                return
            token = int(tensor_proto_to_ndarray(resp.outputs["token"])[0])
            pid = int(tensor_proto_to_ndarray(resp.outputs["pid"])[0])
            if token != base + step or pid != owner_pid:
                self._violate(Violation(
                    self._now(), "session_not_bit_exact",
                    f"session {index}: step {step} returned token "
                    f"{token} from pid {pid}; expected {base + step} "
                    f"from {owner_pid}"))
                return
        try:
            client.predict_request(
                self.cfg.model, {"session_id": sid},
                signature_name="decode_close", timeout=30)
        except Exception:  # noqa: BLE001 - close is best-effort
            pass
        with self._lock:
            self.report.sessions_completed += 1

    def _t5_session_worker(self, index: int, prompt_idx: int) -> None:
        from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray
        from min_tfs_client_tpu.utils.status import Code

        spec = self.t5
        sid = np.asarray(b"storm-t5-%d-%d" % (self.cfg.seed, index),
                         object)
        client = self._client()
        reference = spec.references[prompt_idx]
        try:
            client.predict_request(
                spec.model,
                {"session_id": sid,
                 "input_ids": spec.prompts[prompt_idx]},
                signature_name="decode_init", timeout=60)
        except Exception as exc:  # noqa: BLE001 - capacity is typed
            if _grpc_code_value(exc) == Code.RESOURCE_EXHAUSTED:
                with self._lock:
                    self.report.sessions_refused += 1
            else:
                self._violate(Violation(
                    self._now(), "t5_init_failed",
                    f"t5 session {index}: init died: {exc}"))
            return
        for step in range(1, len(reference) + 1):
            time.sleep(spec.step_interval_s)
            try:
                resp = client.predict_request(
                    spec.model,
                    {"session_id": sid,
                     "step_ordinal": np.asarray(step, np.int64)},
                    signature_name="decode_step", timeout=60)
            except Exception as exc:  # noqa: BLE001 - classified below
                code = _grpc_code_value(exc)
                if code == Code.RESOURCE_EXHAUSTED:
                    # refuse/close eviction under KV pressure is typed
                    # backpressure, not corruption; close so the
                    # refused session's pages return to the arena
                    with self._lock:
                        self.report.sessions_refused += 1
                    try:
                        client.predict_request(
                            spec.model, {"session_id": sid},
                            signature_name="decode_close", timeout=60)
                    except Exception:  # noqa: BLE001 - best-effort
                        pass
                    return
                self._violate(Violation(
                    self._now(), "t5_stream_broken",
                    f"t5 session {index} step {step}: {exc}"))
                return
            token = int(tensor_proto_to_ndarray(resp.outputs["token"])[0])
            if token != reference[step - 1]:
                self._violate(Violation(
                    self._now(), "t5_not_bit_exact",
                    f"t5 session {index} step {step}: token {token} != "
                    f"reference {reference[step - 1]} — KV pressure "
                    "(swap/restore) corrupted a stream"))
                return
        try:
            client.predict_request(
                spec.model, {"session_id": sid},
                signature_name="decode_close", timeout=60)
        except Exception:  # noqa: BLE001 - close is best-effort
            pass
        with self._lock:
            self.report.t5_sessions_completed += 1

    def _run_chaos(self, op: str) -> None:
        fn = self._chaos_ops.get(op)
        if fn is None:
            self._violate(Violation(
                self._now(), "bad_chaos_op",
                f"schedule names chaos op {op!r} the harness did not "
                "provide"))
            return
        try:
            result = fn()
        except Exception as exc:  # noqa: BLE001 - harness failure
            self._violate(Violation(
                self._now(), "chaos_op_failed", f"{op}: {exc}"))
            return
        if op.startswith("kill:") and result is not None:
            # Mark the dying pid BEFORE its sessions can observe the
            # kill (fn returns after the SIGKILL is sent).
            with self._lock:
                self._killed_pids.add(int(result))
        with self._lock:
            self.report.chaos_executed.append(op)

    # -- phases --------------------------------------------------------------

    def run(self) -> StormReport:
        cfg = self.cfg
        # servelint: thread-ok written once HERE, before the monitor or
        # any worker thread spawns; all threads only read it
        self._t0 = time.monotonic()
        monitor = _RecorderMonitor(
            self._monitor_ports, self.report, self._violate,
            self._t0, cfg.recorder_poll_s).start()
        quiet_lat: list = []
        storm_lat: list = []
        try:
            # Phase 1 — QUIET baseline: stateless only, no chaos.
            rng = random.Random(cfg.seed ^ 0x5EED)
            pool = ThreadPoolExecutor(
                max_workers=cfg.max_workers,
                thread_name_prefix="storm-worker")
            quiet_events = []
            t = 0.0
            while True:
                t += rng.uniform(0.4, 1.6) / max(cfg.stateless_rate_hz,
                                                 1.0)
                if t >= cfg.quiet_s:
                    break
                quiet_events.append(
                    StormEvent(t, "stateless", (rng.uniform(-8, 8),)))
            self._play(quiet_events, pool, quiet_lat,
                       session_threads=[])
            # Phase 2 — the STORM. (_t0 stays the run origin: all
            # violation timestamps and latency math are span-relative,
            # so one base serves both phases.)
            session_threads: list[threading.Thread] = []
            self._play(generate_schedule(cfg, self.t5), pool, storm_lat,
                       session_threads=session_threads)
            # Drain: session workers are the long tail (steps *
            # interval, plus retry backoff against a dying fleet).
            deadline = time.monotonic() + 60.0
            for thread in session_threads:
                thread.join(timeout=max(0.5,
                                        deadline - time.monotonic()))
                if thread.is_alive():
                    self._violate(Violation(
                        self._now(), "session_worker_hung",
                        f"{thread.name} never finished"))
            pool.shutdown(wait=True)
        finally:
            monitor.stop()
        self._finish(quiet_lat, storm_lat)
        return self.report

    def _play(self, events, pool, latency_sink, session_threads) -> None:
        start = time.monotonic()
        for event in events:
            delay = event.at_s - (time.monotonic() - start)
            if delay > 0:
                time.sleep(delay)
            scheduled_at = self._now()
            if event.kind == "stateless":
                pool.submit(self._stateless_once, scheduled_at,
                            event.payload[0], latency_sink)
            elif event.kind == "session":
                index, base, steps = event.payload
                thread = threading.Thread(
                    target=self._session_worker,
                    args=(index, base, steps),
                    name=f"storm-session-{index}", daemon=True)
                thread.start()
                session_threads.append(thread)
            elif event.kind == "t5_session":
                index, prompt_idx = event.payload
                thread = threading.Thread(
                    target=self._t5_session_worker,
                    args=(index, prompt_idx),
                    name=f"storm-t5-session-{index}", daemon=True)
                thread.start()
                session_threads.append(thread)
            elif event.kind == "chaos":
                # join boots a process (seconds): its own thread so the
                # schedule's arrivals keep landing on time.
                op = event.payload[0]
                thread = threading.Thread(
                    target=self._run_chaos, args=(op,),
                    name=f"storm-chaos-{op.replace(':', '-')}",
                    daemon=True)
                thread.start()
                session_threads.append(thread)

    def _finish(self, quiet_lat: list, storm_lat: list) -> None:
        report = self.report
        if quiet_lat:
            report.quiet_p50_ms = round(_pct(quiet_lat, 50), 3)
            report.quiet_p99_ms = round(_pct(quiet_lat, 99), 3)
        if storm_lat:
            report.storm_p50_ms = round(_pct(storm_lat, 50), 3)
            report.storm_p99_ms = round(_pct(storm_lat, 99), 3)
        if quiet_lat and storm_lat:
            budget = (report.quiet_p99_ms * self.cfg.p99_budget_ratio
                      + self.cfg.p99_floor_ms)
            if report.storm_p99_ms > budget:
                self._violate(Violation(
                    self._now(), "p99_unbounded",
                    f"storm open-loop p99 {report.storm_p99_ms}ms "
                    f"exceeded budget {budget:.1f}ms "
                    f"(quiet p99 {report.quiet_p99_ms}ms * "
                    f"{self.cfg.p99_budget_ratio} + "
                    f"{self.cfg.p99_floor_ms}ms)"))
        for client in self._clients:
            try:
                client.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


def load_cost_records(log_dir) -> tuple[list, int]:
    """Read every servecost JSONL record under `log_dir` (the fleet's
    shared --cost_log_dir): (cost records, malformed line count). Meta
    records are schema-checked and skipped; a malformed line counts,
    never hides."""
    import pathlib

    records: list = []
    malformed = 0
    for path in sorted(pathlib.Path(log_dir).glob("*.jsonl")):
        data = path.read_text(encoding="utf-8")
        lines = data.split("\n")
        # A SIGKILLed backend can leave ONE unterminated tail line in
        # its own file; that is the kill's signature, not a malformed
        # record. Anything unparseable on a COMPLETE line counts.
        unterminated_tail = bool(lines and lines[-1] != "")
        for index, line in enumerate(lines):
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
                if not isinstance(record, dict):
                    raise ValueError("record is not an object")
            except ValueError:
                if not (unterminated_tail and index == len(lines) - 1):
                    malformed += 1
                continue
            if record.get("kind") == "cost":
                records.append(record)
    return records, malformed


def ring_trace_ids(rest_port: int, timeout_s: float = 10.0) -> set:
    """The fleet-scope trace ids currently in one process's trace ring
    (GET /monitoring/traces request envelopes) — what a run's cost log
    must JOIN against."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{rest_port}/monitoring/traces",
            timeout=timeout_s) as resp:
        payload = json.loads(resp.read())
    return {event["args"]["trace_id"]
            for event in payload.get("traceEvents", ())
            if event.get("cat") == "request"
            and (event.get("args") or {}).get("trace_id")}


def fetch_alert_payload(rest_port: int, *, tick: bool = False,
                        limit: Optional[int] = None,
                        timeout_s: float = 10.0) -> dict:
    """GET one process's /monitoring/alerts body. `tick=True` forces a
    synchronous detector pass first (a backend watchdog tick, or a full
    fleet sweep on a router port) so the reply reflects now, not the
    last scheduled tick."""
    query = []
    if tick:
        query.append("tick=1")
    if limit is not None:
        query.append(f"limit={int(limit)}")
    suffix = ("?" + "&".join(query)) if query else ""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{rest_port}/monitoring/alerts{suffix}",
            timeout=timeout_s) as resp:
        return json.loads(resp.read())


def collect_alerts(rest_ports, *, tick: bool = True,
                   timeout_s: float = 10.0) -> dict:
    """Alert payloads from every port that still answers, keyed by
    port. A killed process's port legitimately refuses — the storm's
    alert verdict is over the survivors."""
    payloads: dict = {}
    for port in rest_ports:
        try:
            payloads[port] = fetch_alert_payload(
                port, tick=tick, timeout_s=timeout_s)
        except Exception:  # noqa: BLE001 - dead port is data, not error
            continue
    return payloads


def alerts_at_or_above(payloads: dict, severity: str) -> list:
    """Every alert at or above `severity` across a collect_alerts()
    result — the ring, the active set, and (on router payloads) each
    backend's condensed summary. This is the storm's quiet-above-WARN
    assertion surface: a clean run must return [] for CRITICAL."""
    from min_tfs_client_tpu.observability.watchdog import severity_rank

    floor = severity_rank(severity)
    found = []
    for port, payload in sorted(payloads.items()):
        sources = [("ring", payload.get("alerts") or ()),
                   ("active", payload.get("active") or ())]
        for bid, summary in sorted(
                (payload.get("backends") or {}).items()):
            if isinstance(summary, dict):
                sources.append((f"backend[{bid}].active",
                                summary.get("active") or ()))
                sources.append((f"backend[{bid}].recent",
                                summary.get("recent") or ()))
        for source, alerts in sources:
            for alert in alerts:
                if not isinstance(alert, dict):
                    continue
                if severity_rank(alert.get("severity", "")) >= floor:
                    found.append({"port": port, "source": source,
                                  **alert})
    return found


def verify_cost_log_join(log_dir, backend_rest_ports,
                         min_join_fraction: float = 0.95,
                         settle_s: float = 6.0) -> dict:
    """The storm's cost-attribution verdict (ROADMAP item 7's
    adversarial-training-mix increment): every record parses, every
    record carries a wire-valid trace id, and the run's ring traces
    JOIN the cost log by trace_id. Polls up to `settle_s` for the
    tracing drain thread to flush the tail (records land ~0.5s after a
    trace finishes). Returns the verdict dict; raises AssertionError on
    violation."""
    from min_tfs_client_tpu.observability import tracing

    ring_ids: set = set()
    for port in backend_rest_ports:
        try:
            ring_ids |= ring_trace_ids(port)
        except Exception:  # noqa: BLE001 - a killed backend's port
            continue       # legitimately stops answering
    deadline = time.monotonic() + settle_s
    while True:
        records, malformed = load_cost_records(log_dir)
        logged_ids = {r.get("trace_id") for r in records}
        joined = ring_ids & logged_ids
        fraction = len(joined) / len(ring_ids) if ring_ids else 0.0
        if fraction >= min_join_fraction or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    assert malformed == 0, \
        f"{malformed} malformed cost-log line(s) under {log_dir}"
    assert records, f"no cost records under {log_dir}"
    invalid = [r.get("trace_id") for r in records
               if not tracing.valid_trace_id(r.get("trace_id") or "")]
    assert not invalid, \
        f"cost records with invalid trace ids: {invalid[:5]}"
    assert ring_ids, "no request traces found in any backend ring"
    assert fraction >= min_join_fraction, (
        f"only {len(joined)}/{len(ring_ids)} ring traces joined the "
        f"cost log (want >= {min_join_fraction:.0%})")
    return {"records": len(records), "malformed": malformed,
            "ring_ids": len(ring_ids), "joined": len(joined),
            "join_fraction": round(fraction, 4)}


def _pct(values: list, pct: float) -> float:
    ordered = sorted(values)
    index = min(len(ordered) - 1,
                max(0, int(round(pct / 100.0 * (len(ordered) - 1)))))
    return ordered[index]


def _grpc_code_value(exc) -> Optional[int]:
    """Canonical-code value of a client-side failure: grpc.RpcError ->
    its status code's canonical value; ServingError -> its code;
    anything else None (untyped)."""
    code = getattr(exc, "code", None)
    if callable(code):
        try:
            return code().value[0]
        except Exception:  # noqa: BLE001 - foreign error shape
            return None
    if isinstance(code, int):
        return code
    return None
