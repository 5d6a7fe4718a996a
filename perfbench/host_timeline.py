"""Names for the device's idle time: the capture's gaps, each with its
start, split at the programs' edges and joined to the server's spans.

The device trace (`.perfbench/run/events.json`, what children.child_trace
wrote) counts nanoseconds from the start of the profiler's session. The
server's spans (`/monitoring/traces`) count microseconds on its own
clock. `host_clock.json`, which the server writes beside its capture,
holds that clock's reading just before the session began (`zero`), so a
span's `ts` lies at (ts - zero.span_us) * 1000 ns on the capture's HOST
planes. The DEVICE plane is not on the host planes' clock: measured on a
v5e it ran 1.3 ms early in a process's first capture and 0.25 ms late in
its later ones (PERF.md section 7). So unless the file records a
`device_offset_ns`, the offset is taken from the capture itself: each
launch span (`decode/tick`, else `device/execute`) is paired with the
run of the cell's main program that it started, and the smallest
distance from a launch to its run is taken as zero.

A gap is a stretch of the "XLA Ops" line in which no operation ran. The
part of it inside a run on "XLA Modules" is the program's own, and no
host span can explain it: `in:<program>`. The part between two runs
takes the name of what the host was doing (`name_gap`). Pure Python,
never imports jax; the tests run it on hand-written events.
"""

from __future__ import annotations

import json
import pathlib
import statistics

from perfbench import trace_reduce

RUN_DIR = pathlib.Path(__file__).resolve().parents[1] / ".perfbench" / "run"
HOST_CLOCK = "host_clock.json"

# What a thread does to move a round or a batch on, against what it does
# while it waits for one.
PHASES = ("batching/merge", "batching/execute", "batching/dispatch",
          "serving/pad", "serving/validate")
PHASE_PREFIXES = ("decode/", "device/")
WAITS = ("decode/wait", "batching/queue_wait")
LAUNCHES = ("decode/tick", "device/execute")
NO_REQUEST = "no request in flight"
UNATTRIBUTED = trace_reduce.UNATTRIBUTED
# A launch is looked for from this long before its span, which is more
# than the device plane has been seen to run early.
SLACK_NS = 5_000_000


def program_of(module_name: str) -> str:
    """`jit_direct_tick_fn(1234)` -> `jit_direct_tick_fn`."""
    return module_name.split("(", 1)[0]


def device_gaps(events: dict) -> tuple[list, list]:
    """(gaps inside programs, gaps between programs), each a list of
    (start ns, end ns, program or None), over every chip that ran
    something. A gap that crosses a program's edge is cut there."""
    inside, between = [], []
    for plane in trace_reduce.device_planes(events):
        busy = trace_reduce.union(
            (s, s + d) for _, s, d in
            trace_reduce._events(events, plane, trace_reduce.OPS_LINE))
        runs = sorted(
            (s, s + d, program_of(name)) for name, s, d in
            trace_reduce._events(events, plane, trace_reduce.MODULES_LINE))
        k = 0
        for (_, g0), (g1, _) in zip(busy, busy[1:]):
            at = g0
            while k < len(runs) and runs[k][1] <= at:
                k += 1
            j = k
            while at < g1:
                if j < len(runs) and runs[j][0] <= at:
                    end = min(g1, runs[j][1])
                    inside.append((at, end, runs[j][2]))
                    j += 1
                else:
                    end = min(g1, runs[j][0]) if j < len(runs) else g1
                    between.append((at, end, None))
                at = end
    return inside, between


def distinct(requests: list[dict]) -> list[tuple]:
    """Every span once, as (name, ts us, dur us, args): a batch's spans
    are written onto each rider's trace."""
    seen = {(name, ts, dur): args for r in requests
            for name, ts, dur, args in r["spans"]}
    return sorted((name, ts, dur, args)
                  for (name, ts, dur), args in seen.items())


def launches(spans: list[tuple]) -> list[float]:
    """Starts (us) of the spans that enqueue the cell's main program."""
    for name in LAUNCHES:
        starts = sorted(ts for n, ts, _, _ in spans if n == name)
        if starts:
            return starts
    return []


class Clock:
    """From a span's `ts` (us, server clock) to the device plane's ns."""

    def __init__(self, host_clock: dict, offset_ns: float = 0.0):
        self.zero_us = host_clock["zero"]["span_us"]
        self.offset_ns = offset_ns

    def ns(self, ts_us: float) -> float:
        return (ts_us - self.zero_us) * 1e3 + self.offset_ns


def launch_to_device(events: dict, spans: list[tuple], clock: Clock,
                     main_program: str) -> list[float]:
    """For each launch span, ns on `clock` from its start to the start
    of the run of the main program it began: the first run that starts
    after it (less the slack) and before the next launch does."""
    runs = sorted(
        s for plane in trace_reduce.device_planes(events)
        for name, s, _ in
        trace_reduce._events(events, plane, trace_reduce.MODULES_LINE)
        if name.startswith(main_program))
    starts = [clock.ns(ts) for ts in launches(spans)]
    out, j = [], 0
    for k, t in enumerate(starts):
        until = starts[k + 1] if k + 1 < len(starts) else float("inf")
        while j < len(runs) and runs[j] < t - SLACK_NS:
            j += 1
        if j < len(runs) and runs[j] < until - SLACK_NS:
            out.append(runs[j] - t)
    return out


def _is_phase(name: str) -> bool:
    return name not in WAITS and (
        name in PHASES or name.startswith(PHASE_PREFIXES))


def _covered(intervals, g0: float, g1: float) -> float:
    return sum(b - a for a, b in trace_reduce.union(
        (max(a, g0), min(b, g1)) for a, b in intervals
        if a < g1 and b > g0))


def name_gap(g0: float, g1: float, spans_ns: list[tuple],
             requests_ns: list[tuple]) -> str:
    """What the host was doing in a gap between two programs. The spans
    that move work on (PHASES) have it if together they cover half of it
    or more: the one that covers most names it. Else the waits, by the
    same rule: work was there and no thread was moving it. Else, if no
    request was open, nobody asked for anything. Else it has no name."""
    over: dict[str, list] = {}
    for name, a, b in spans_ns:
        if a < g1 and b > g0:
            over.setdefault(name, []).append((a, b))
    for wanted in (_is_phase, WAITS.__contains__):
        mine = {n: iv for n, iv in over.items() if wanted(n)}
        together = [i for iv in mine.values() for i in iv]
        if mine and _covered(together, g0, g1) >= 0.5 * (g1 - g0):
            return max(sorted(mine),
                       key=lambda n: _covered(mine[n], g0, g1))
    if not any(a < g1 and b > g0 for a, b in requests_ns):
        return NO_REQUEST
    return UNATTRIBUTED


def timeline(events: dict, requests: list[dict], host_clock: dict,
             main_program: str) -> dict:
    """The named gaps `[(name, start s, seconds)]`, longest first, with
    what the metrics read: the idle seconds inside and between programs,
    those between that got a name, and each launch's ns to its run."""
    spans = distinct(requests)
    # The recorded device offset if the file has one; else the one that
    # makes the quickest launch of this capture take no time.
    waits = launch_to_device(events, spans, Clock(host_clock), main_program)
    clock = Clock(host_clock, host_clock.get(
        "device_offset_ns", min(waits, default=0.0)))
    spans_ns = [(name, clock.ns(ts), clock.ns(ts + dur))
                for name, ts, dur, _ in spans]
    requests_ns = [(clock.ns(r["ts"]), clock.ns(r["ts"] + r["dur"]))
                   for r in requests]
    inside, between = device_gaps(events)
    gaps = [(f"in:{program}", a, b) for a, b, program in inside]
    gaps += [(name_gap(a, b, spans_ns, requests_ns), a, b)
             for a, b, _ in between]
    chips = len(trace_reduce.device_planes(events)) or 1
    seconds = {"inside": 0.0, "between": 0.0, "named": 0.0}
    for name, a, b in gaps:
        kind = "inside" if name.startswith("in:") else "between"
        seconds[kind] += (b - a) / 1e9 / chips
        if kind == "between" and name != UNATTRIBUTED:
            seconds["named"] += (b - a) / 1e9 / chips
    return {
        "gaps": sorted(((name, a / 1e9, (b - a) / 1e9)
                        for name, a, b in gaps),
                       key=lambda g: g[2], reverse=True),
        "idle_s": seconds,
        "device_offset_ns": clock.offset_ns,
        "launch_to_device_ns": [w - clock.offset_ns for w in waits],
    }


_cached: dict = {}


def of_run(run) -> dict | None:
    """The timeline of the run that has just ended, read once per
    process (the capture's events are half a million). None where the
    run took no capture, or the program wrote no host_clock.json."""
    if run.trace is None or not run.capture:
        return None
    named = [f for f in run.capture["files"]
             if pathlib.PurePath(f).name == HOST_CLOCK]
    found = sorted((RUN_DIR / "profile").glob(f"*/{HOST_CLOCK}"))
    events = RUN_DIR / "events.json"
    if not (named and found and events.exists()):
        return None
    key = (str(found[-1]), found[-1].stat().st_mtime_ns)
    if key not in _cached:
        _cached.clear()
        _cached[key] = timeline(
            json.loads(events.read_text()), run.requests,
            json.loads(found[-1].read_text()),
            run.config["main_program"][run.traffic["signature"]])
    return _cached[key]


def share_of_window(run, kind: str) -> float | None:
    """Idle seconds of one kind over the traced window, in percent."""
    found = of_run(run)
    if found is None:
        return None
    return 100.0 * found["idle_s"][kind] / run.trace["window_s"]


def median_launch_ms(run) -> float | None:
    found = of_run(run)
    if not found or not found["launch_to_device_ns"]:
        return None
    return statistics.median(found["launch_to_device_ns"]) / 1e6
