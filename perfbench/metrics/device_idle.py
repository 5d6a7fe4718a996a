"""Device: share of the traced window in which no operation ran, in
percent: 1 - union of the device's operation intervals / window."""

from perfbench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace)
