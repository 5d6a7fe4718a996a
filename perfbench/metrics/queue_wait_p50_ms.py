"""Batching: a request's `batching/queue_wait` span, median."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        spans.per_request_ms(run.requests, ("batching/queue_wait",)), 50)
