"""Decode pool: a tick's host work before its first transfer (pool lock,
prefill flush, per-slot pages, the numpy tables), the `decode/prepare`
span, median over the rounds."""

from perfbench import spans, stats


def read(run):
    return stats.percentile(
        [dur / 1e3 for _, dur, _ in
         spans.distinct_spans(run.requests, "decode/prepare")], 50)
