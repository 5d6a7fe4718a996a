"""Arithmetic on records: percentiles, lateness, window edges."""

from __future__ import annotations

import math


def percentile(values, q: float):
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default rule); None for no values."""
    data = sorted(values)
    if not data:
        return None
    if len(data) == 1:
        return float(data[0])
    rank = (len(data) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(data) - 1)
    return float(data[lo] + (data[hi] - data[lo]) * (rank - lo))


def in_window(t: float, t0: float, t1: float) -> bool:
    """Half-open: an event at the opening instant counts, one at the
    closing instant belongs to what follows."""
    return t0 <= t < t1


def due_in_window(records, t0: float, t1: float) -> list:
    """Requests whose DUE time falls in the window, however late they
    were sent or answered: a stall is charged to the requests it held."""
    return [r for r in records if in_window(r["due"], t0, t1)]


def first_output_ms(record) -> float:
    """Due time to first output: the wait a stall imposes on a request
    counts, not only the time after it was finally sent."""
    return (record["done"] - record["due"]) * 1e3


def lateness_ms(record) -> float:
    """How late the generator sent it: actual send minus due."""
    return (record["sent"] - record["due"]) * 1e3


def completed_in_window(events, t0: float, t1: float) -> int:
    """Outputs whose completion falls in the window. `events` are
    (completion time, outputs) pairs."""
    return sum(n for t, n in events if in_window(t, t0, t1))


def rate_per_s(events, t0: float, t1: float) -> float:
    return completed_in_window(events, t0, t1) / (t1 - t0)


def spread(values) -> float:
    """The contract's spread: distance between the first and the third
    quartile (statistics.quantiles, n=4) as a share of the median."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
