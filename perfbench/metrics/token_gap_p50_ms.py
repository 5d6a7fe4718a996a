"""Decode pool, seen from the client: the gap between a session's
consecutive step answers, over the answers inside the window, median."""

from perfbench import stats


def read(run):
    gaps = [(b - a) * 1e3 for s in run.records["sessions"]
            for a, b in zip(s["steps"], s["steps"][1:])
            if stats.in_window(b, 0.0, run.seconds)]
    return stats.percentile(gaps, 50)
