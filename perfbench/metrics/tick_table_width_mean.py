"""Decode pool: the block-table width the ticks ran at, the `width=` of
`decode/tick` spans, mean. A tick's program costs slots x width."""

from perfbench import spans


def read(run):
    widths = [args["width"] for _, _, args in
              spans.distinct_spans(run.requests, "decode/tick")
              if "width" in args]
    return sum(widths) / len(widths) if widths else None
