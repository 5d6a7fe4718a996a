"""Decode pool: how long a step waited for a tick to take it, entry of
`TickBatcher.step` to the snapshot of its round (`decode/wait`), median
over the steps."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        spans.per_request_ms(run.requests, ("decode/wait",)), 50)
