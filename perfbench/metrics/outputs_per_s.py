"""Outputs completed inside the window per second of window: examples of
answered Predicts, tokens of answered decode steps. All the work and all
the time of the window."""

from perfbench import stats


def read(run):
    events = [(r["done"], r["outputs"])
              for r in run.records["requests"] if r["ok"]]
    events += [(t, 1) for s in run.records["sessions"] for t in s["steps"]]
    return stats.rate_per_s(events, 0.0, run.seconds) if events else None
