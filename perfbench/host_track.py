"""What the host did during the capture, from the capture's own file.

`host_track.json` (the server writes it beside `host_clock.json` at the
end of a capture: observability/profiling.py) holds, in the format of
`/monitoring/traces`, every request trace of ANY signature that overlaps
the capture and the process's own spans (category `process`, on a `tid`
no request has): `host/gc`, `observe/drain`, `loop/sample`,
`decode/idle`, the latter from ten seconds BEFORE the capture on
(`otherData.capture.lead_us`). Inside a capture the profiler slows every
Python thread two- to threefold, so what the process's own work costs is
read from that lead (`quiet`), and the capture's spans name the device's
gaps. `run.requests` holds the cell's own signature only, and
no process span, so a gap of the device that lies under a `decode_init`,
under a collection or under no request at all has no name there
(`idle_named`); here it has one (`host_idle_named`).

The clocks are joined as perfbench/host_timeline.py joins them, with its
own `Clock`, `device_gaps` and `name_gap`, imported and not edited.
`name_gap` takes a span for a phase by its NAME (`decode/`, `device/`,
a few more). A process span is a phase too: it is offered under a name
that passes (`AS_PHASE` in front), and the gap gets the span's own name
back. `loop/sample` tiles the whole capture and is not offered. The
process spans are asked first (`named_gaps`): a stall is named by its
cause.

A program that wrote no `host_track.json` (the parent of the PR that
brought it) gives every reader here nothing to read: None. Pure Python,
never imports jax; the tests run it on hand-written events.
"""

from __future__ import annotations

import json
import pathlib
import statistics

from perfbench import host_timeline, spans, trace_reduce
from perfbench.host_timeline import Clock, device_gaps, name_gap

HOST_TRACK = "host_track.json"
PROCESS = "process"
SAMPLE = "loop/sample"
IDLE = "decode/idle"
# What the decode loop's thread is inside, in a round's order; with
# `decode/idle` (no loop thread exists) they tile the loop's time.
LOOP_PHASES = ("decode/handoff", "decode/prepare", "decode/tick",
               "decode/wake", "decode/fetch")
AS_PHASE = "device/~"


def load(payload: dict) -> dict:
    """The file's two halves: `requests` as perfbench/spans.py gives
    them (the process events fall out there: their `tid` has no
    envelope) with `spans`, each of theirs once, and `process`, both as
    (name, ts us, dur us, args); and the capture's two ends on the same
    clock."""
    process = sorted(
        ((e["name"], e["ts"], e["dur"], e.get("args", {}))
         for e in payload.get("traceEvents", [])
         if e.get("ph") == "X" and e.get("cat") == PROCESS),
        key=lambda s: s[1])
    ends = payload["otherData"]["capture"]
    requests = spans.requests_from_chrome(payload)
    return {"requests": requests,
            "spans": host_timeline.distinct(requests), "process": process,
            "capture_us": (ends["zero_us"], ends["stop_us"]),
            "lead_us": ends.get("lead_us", ends["zero_us"])}


def quiet(track: dict) -> tuple[float, float]:
    """The stretch in which the process's own work is priced: the lead
    before the capture, which no profiler disturbs; the capture itself
    where the file has no lead."""
    zero, stop = track["capture_us"]
    return (track["lead_us"], zero) if track["lead_us"] < zero \
        else (zero, stop)


def clipped_us(found, a: float, b: float) -> float:
    """Microseconds of [a, b] that the (ts, dur) spans cover together."""
    return sum(e - s for s, e in trace_reduce.union(
        (max(ts, a), min(ts + dur, b)) for ts, dur in found
        if ts < b and ts + dur > a))


def process_share(track: dict, name: str) -> float:
    """The quiet stretch's share, in percent, under the process spans
    `name`."""
    a, b = quiet(track)
    return 100.0 * clipped_us(
        [(ts, dur) for n, ts, dur, _ in track["process"] if n == name],
        a, b) / (b - a)


def samples(track: dict) -> list[tuple]:
    """The event loop's samples that lie whole inside the quiet stretch."""
    a, b = quiet(track)
    return [s for s in track["process"]
            if s[0] == SAMPLE and s[1] >= a and s[1] + s[2] <= b]


def answered(run, track: dict, begin_us: float, end_us: float) -> int:
    """The steps or requests the load generator saw answered between two
    instants of the server's clock. The clocks meet at the capture's
    start: `zero` on the server's, `run.capture["start"]` seconds from
    the window's opening on the generator's (the call that began the
    capture crossed in between: a millisecond or two)."""
    zero = track["capture_us"][0]
    t0, t1 = (run.capture["start"] + (ts - zero) / 1e6
              for ts in (begin_us, end_us))
    done = [t for s in run.records["sessions"] for t in s["steps"]]
    done += [r["done"] for r in run.records["requests"] if r["ok"]]
    return sum(t0 <= t < t1 for t in done)


def phase_cover(track: dict) -> list[float]:
    """Per round of the decode loop, the share of [its `decode/handoff`
    start, the next round's) that the loop's spans cover together."""
    loop = [s for s in track["spans"] if s[0] in LOOP_PHASES]
    begins = {args["round"]: ts for name, ts, _, args in loop
              if name == LOOP_PHASES[0] and "round" in args}
    tiles = [(ts, dur) for _, ts, dur, _ in loop]
    tiles += [(ts, dur) for name, ts, dur, _ in track["process"]
              if name == IDLE]
    return [clipped_us(tiles, a, begins[r + 1]) / (begins[r + 1] - a)
            for r, a in sorted(begins.items())
            if begins.get(r + 1, a) > a]


def fetch_tails(events: dict, track: dict, clock: Clock,
                main_program: str) -> list[float]:
    """For each round, ns on `clock` from the end of the run of the main
    program that its `decode/tick` began to the end of its
    `decode/fetch`: the run paired with the launch as
    `host_timeline.launch_to_device` pairs them."""
    runs = sorted(
        (s, s + d) for plane in trace_reduce.device_planes(events)
        for name, s, d in
        trace_reduce._events(events, plane, trace_reduce.MODULES_LINE)
        if name.startswith(main_program))
    by_round: dict = {}
    for name, ts, dur, args in track["spans"]:
        if name in ("decode/tick", "decode/fetch") and "round" in args:
            by_round.setdefault(args["round"], {})[name] = (ts, dur)
    rounds = sorted((r for r in by_round.values() if len(r) == 2),
                    key=lambda r: r["decode/tick"][0])
    starts = [clock.ns(r["decode/tick"][0]) for r in rounds]
    out, j = [], 0
    for k, (t, found) in enumerate(zip(starts, rounds)):
        until = starts[k + 1] if k + 1 < len(starts) else float("inf")
        while j < len(runs) and runs[j][0] < t - host_timeline.SLACK_NS:
            j += 1
        if j < len(runs) and runs[j][0] < until - host_timeline.SLACK_NS:
            out.append(clock.ns(sum(found["decode/fetch"])) - runs[j][1])
    return out


def named_gaps(events: dict, track: dict, clock: Clock) -> list[tuple]:
    """The device's gaps between two programs, each (name, start ns,
    end ns). `name_gap`'s rule, asked twice: first of the process spans
    alone, for a stall has the name of its cause (the collection), not
    of the phase in which it caught the thread that suffered it (the
    span that was open across it is longer, and would win); then of
    every request's spans and the process spans together."""
    own = [(AS_PHASE + name, clock.ns(ts), clock.ns(ts + dur))
           for name, ts, dur, _ in track["process"] if name != SAMPLE]
    found = own + [(name, clock.ns(ts), clock.ns(ts + dur))
                   for name, ts, dur, _ in track["spans"]]
    open_ns = [(clock.ns(r["ts"]), clock.ns(r["ts"] + r["dur"]))
               for r in track["requests"]]

    def name(a: float, b: float) -> str:
        cause = name_gap(a, b, own, ())
        if not cause.startswith(AS_PHASE):
            cause = name_gap(a, b, found, open_ns)
        return cause.removeprefix(AS_PHASE)

    _, between = device_gaps(events)
    return [(name(a, b), a, b) for a, b, _ in between]


def timeline(payload: dict, events: dict, host_clock: dict,
             main_program: str) -> dict:
    """The track with what needs the device trace beside it: the gaps
    between programs by name, longest first, as (name, start s,
    seconds); the idle seconds between programs and those of them with
    a name; each round's fetch tail in ns."""
    return timeline_of(load(payload), events, host_clock, main_program)


def timeline_of(track: dict, events: dict, host_clock: dict,
                main_program: str) -> dict:
    """`timeline` of a track that is loaded already (`requests`, `spans`
    and `process`, as `load` gives them)."""
    # The device plane's offset, by host_timeline's rule: the recorded
    # one, else the capture's quickest launch takes no time.
    waits = host_timeline.launch_to_device(
        events, track["spans"], Clock(host_clock), main_program)
    clock = Clock(host_clock, host_clock.get(
        "device_offset_ns", min(waits, default=0.0)))
    gaps = named_gaps(events, track, clock)
    chips = len(trace_reduce.device_planes(events)) or 1
    return dict(
        track,
        gaps=sorted(((name, a / 1e9, (b - a) / 1e9) for name, a, b in gaps),
                    key=lambda g: g[2], reverse=True),
        idle_s={"between": sum(b - a for _, a, b in gaps) / 1e9 / chips,
                "named": sum(b - a for name, a, b in gaps
                             if name != host_timeline.UNATTRIBUTED)
                / 1e9 / chips},
        fetch_tail_ns=fetch_tails(events, track, clock, main_program))


_cached: dict = {}


def of_run(run) -> dict | None:
    """The timeline of the run that has just ended, read once per
    process. None where the run took no capture, or the program wrote no
    host_track.json beside it."""
    if run.trace is None or not run.capture:
        return None
    run_dir = host_timeline.RUN_DIR
    named = [f for f in run.capture["files"]
             if pathlib.PurePath(f).name == HOST_TRACK]
    found = sorted((run_dir / "profile").glob(f"*/{HOST_TRACK}"))
    events = run_dir / "events.json"
    if not (named and found and events.exists()):
        return None
    clock = found[-1].with_name(host_timeline.HOST_CLOCK)
    key = (str(found[-1]), found[-1].stat().st_mtime_ns)
    if key not in _cached:
        _cached.clear()
        _cached[key] = timeline(
            json.loads(found[-1].read_text()),
            json.loads(events.read_text()), json.loads(clock.read_text()),
            run.config["main_program"][run.traffic["signature"]])
    return _cached[key]


def share(run, name: str) -> float | None:
    found = of_run(run)
    return None if found is None else process_share(found, name)


def idle_named(run) -> float | None:
    """Of the idle seconds between programs, the share with a name."""
    found = of_run(run)
    if not found or not found["idle_s"]["between"]:
        return None
    return 100.0 * found["idle_s"]["named"] / found["idle_s"]["between"]


def median_or_none(values) -> float | None:
    values = list(values)
    return statistics.median(values) if values else None
