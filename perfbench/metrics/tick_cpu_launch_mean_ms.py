"""Decode pool: the loop thread's own CPU inside `decode/tick` (its
`cpu_us`), mean over the rounds, in ms. Beside `tick_launch_p50_ms`, the
span's length: what is length and not CPU was waiting. The mean and not
the median: the thread's CPU clock (`time.thread_time()`) moves in steps
of 10 ms on the chip's host, so one span reads 0 or 10 ms, and only many
of them together say how long the thread ran. A program whose span does
not say `cpu_us` gives nothing to read."""

import statistics

from perfbench import spans


def read(run):
    cpu = [args["cpu_us"] / 1e3 for _, _, args in
           spans.distinct_spans(run.requests, "decode/tick")
           if "cpu_us" in args]
    return statistics.fmean(cpu) if cpu else None
