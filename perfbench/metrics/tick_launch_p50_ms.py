"""Decode pool: a tick's three transfers and the enqueue of its program
(not the program's run), the `decode/tick` span, median over the rounds."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/tick")], 50)
