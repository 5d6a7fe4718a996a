"""Decode pool: one round's delivery (or the next round's first arrival,
if later) to the next leader's snapshot of the waiting steps, the
`decode/handoff` span, median over the rounds."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/handoff")], 50)
