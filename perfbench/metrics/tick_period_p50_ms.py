"""Decode pool: start to start of consecutive `decode/tick` spans,
median."""

from perfbench import spans, stats


def read(run):
    starts = [ts for ts, _, _ in spans.distinct_spans(run.requests,
                                                      "decode/tick")]
    return stats.percentile(
        [(b - a) / 1e3 for a, b in zip(starts, starts[1:])], 50)
