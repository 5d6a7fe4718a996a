"""Decode pool: the wait for a tick's outputs, device to host included,
the `decode/fetch` span, median over the rounds."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/fetch")], 50)
