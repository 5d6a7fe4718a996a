"""Median, over the requests DUE in the window, of the time from a
request's due time to its first output (the whole answer of a Predict or
of a whole generation). A failed request has no latency; it counts in
`failed`."""

from perfbench import stats


def latencies(run):
    rows = stats.due_in_window(run.records["requests"], 0.0, run.seconds)
    return [stats.first_output_ms(r) for r in rows if r["ok"]]


def read(run):
    return stats.percentile(latencies(run), 50)
