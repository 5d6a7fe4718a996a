"""From the device trace to numbers. Input: the plain events that
children.child_trace wrote from the profiler's .xplane.pb. Pure Python,
so the tests run it on a small recorded trace (tests/perfbench/data).

A TPU device plane has a line "XLA Modules" (one event per run of a
compiled program, named `jit_<fn>(<fingerprint>)`) and a line "XLA Ops"
(one event per operation; a `while` spans the operations of its body, so
events nest and times by name may overlap, but the union does not).
"""

from __future__ import annotations

import re
import statistics

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")
UNATTRIBUTED = "unattributed"


def instruction(name: str) -> str:
    """An operation's event is named by its whole HLO text, `%_paged_
    kernel.47 = bf16[...] custom-call(...)`: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """`%_paged_kernel.25 = ...` -> `_paged_kernel`: XLA numbers the
    instances of one operation."""
    return _SUFFIX.sub("", instruction(name))


def _events(trace: dict, plane: dict, line_name: str) -> list[tuple]:
    names = trace["names"]
    return [(names[i], start, dur)
            for line in plane["lines"] if line["name"] == line_name
            for i, start, dur in line["events"]]


def device_planes(trace: dict) -> list[dict]:
    """The planes of chips that ran something."""
    return [p for p in trace["planes"]
            if any(line["name"] == OPS_LINE and line["events"]
                   for line in p["lines"])]


def union(intervals) -> list[tuple[int, int]]:
    """Merged (start, end) intervals, sorted."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def reduce(trace: dict, window_s: float | None = None) -> dict | None:
    """busy_s and window_s (averaged over the chips used), per-program
    and per-operation device times, and the longest idle gaps. None when
    no operation ran on a device."""
    planes = device_planes(trace)
    if not planes:
        return None
    busy, spans, gaps = [], [], []
    modules: dict[str, list[float]] = {}
    ops: dict[str, list[float]] = {}
    for plane in planes:
        op_events = _events(trace, plane, OPS_LINE)
        merged = union((s, s + d) for _, s, d in op_events)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        spans.append((merged[-1][1] - merged[0][0]) / 1e9)
        gaps += [(b[0] - a[1]) / 1e9 for a, b in zip(merged, merged[1:])]
        for name, _, dur in op_events:
            ops.setdefault(base_name(name), []).append(dur / 1e9)
        runs: dict[str, list[tuple]] = {}
        for name, start, dur in _events(trace, plane, MODULES_LINE):
            runs.setdefault(name, []).append((start, dur / 1e9))
        for name, events in runs.items():
            # The capture cuts the run it starts in and the run it ends
            # in (a 1.7 s program in a 4 s capture reads 0.6 s at the
            # edge): of three or more runs, the first and last go.
            events.sort()
            kept = events[1:-1] if len(events) >= 3 else events
            modules.setdefault(name, []).extend(d for _, d in kept)
    traced = statistics.fmean(spans)
    window = max(traced, window_s or 0.0)
    return {
        "chips": len(planes),
        "busy_s": statistics.fmean(busy),
        "window_s": window,
        "modules": modules,
        "ops": ops,
        # Gaps in the union of operations, inside programs too; they get
        # names only through host_track.py, which joins the clocks.
        "idle_gaps": sorted(gaps, reverse=True)[:10],
    }


def idle_share(reduced: dict) -> float:
    return 1.0 - reduced["busy_s"] / reduced["window_s"]


def program_runs(reduced: dict, prefix: str) -> dict[str, list[float]]:
    """Runs of the programs whose name starts with `prefix`, by full
    name: each fingerprint is one compiled shape of the function."""
    return {name: runs for name, runs in reduced["modules"].items()
            if name.startswith(prefix)}


def program_ms(reduced: dict, prefix: str) -> float | None:
    """Median device time of one run of the cell's main program, over
    all its compiled shapes."""
    runs = [t for r in program_runs(reduced, prefix).values() for t in r]
    return statistics.median(runs) * 1e3 if runs else None


def kernel_times(reduced: dict, kernel: str) -> list[float]:
    """Device seconds of each call of a Pallas kernel, by its name."""
    return [t for name, times in reduced["ops"].items()
            if name.startswith(kernel) for t in times]


def kernel_share(reduced: dict, kernel: str, prefix: str) -> float | None:
    """The kernel's share of the device time of the programs that call it."""
    program = sum(t for r in program_runs(reduced, prefix).values()
                  for t in r)
    calls = kernel_times(reduced, kernel)
    return sum(calls) / program if program and calls else None


def roofline_share(seconds_per_call: float, flops: float, bytes_moved: float,
                   peak: dict) -> float:
    """The least time the chip could take for the call, the larger of
    operations over peak FLOP/s and bytes over peak bytes/s, over the
    time it took."""
    least = max(flops / peak["bf16_flops_per_s"],
                bytes_moved / peak["hbm_bytes_per_s"])
    return least / seconds_per_call


def breakdown(reduced: dict, named_gaps=None) -> dict:
    """What the next issue's writer sees: the device operations that
    took most time (instances of one name summed) and the longest gaps.
    `named_gaps` are perfbench/host_track.py's `timeline(...)["gaps"]`,
    (name, start s, seconds) longest first: the gaps between two
    programs, each with the name of what the host was doing in it. Where
    the program wrote no host track the gaps of this trace alone stand,
    and those have no name."""
    totals = sorted(((name, sum(times))
                     for name, times in reduced["ops"].items()),
                    key=lambda kv: kv[1], reverse=True)[:10]
    if named_gaps:
        gaps = [[name, seconds] for name, _, seconds in named_gaps[:10]]
    else:
        gaps = [[UNATTRIBUTED, g] for g in reduced["idle_gaps"]]
    return {"device_ops": [[name, t] for name, t in totals],
            "idle_gaps": gaps}
