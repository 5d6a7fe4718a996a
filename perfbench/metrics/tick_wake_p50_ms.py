"""Decode pool: end of a round's `decode/tick` to the start of its
`decode/fetch`, the `decode/wake` span, median over the rounds: the
wake-up of the riders of the round before (under the pool's lock) and
the pool's bookkeeping, while the device already runs. A program that
writes no such span gives nothing to read."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/wake")], 50)
