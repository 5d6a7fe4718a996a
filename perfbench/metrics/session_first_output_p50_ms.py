"""Decode pool, seen from the client: a session's start (the answer to
the previous session's close, in this closed loop) to its first token,
over the sessions started in the window, median."""

from perfbench import stats


def read(run):
    rows = stats.due_in_window(run.records["sessions"], 0.0, run.seconds)
    return stats.percentile(
        [(s["steps"][0] - s["due"]) * 1e3 for s in rows if s["steps"]], 50)
