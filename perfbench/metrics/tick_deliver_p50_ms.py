"""Decode pool: end of a tick's fetch to the riders' wake-up (rows sliced,
session timelines, cost notes), the `decode/deliver` span, median over
the rounds."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/deliver")], 50)
