"""Decode pool: sessions a tick advanced, the `slots=` of `decode/tick`
spans, mean."""

from perfbench import spans


def read(run):
    slots = [args["slots"] for _, _, args in
             spans.distinct_spans(run.requests, "decode/tick")
             if "slots" in args]
    return sum(slots) / len(slots) if slots else None
