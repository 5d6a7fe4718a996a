"""Kernels: `_paged_kernel`'s share of its roofline, in percent. The
need of one call is the pages that hold the live sessions' tokens (from
the client's own records of each session's progress, averaged over the
traced window; kernels/_paged_kernel.py turns pages into operations and
bytes), not the slots times table width the program iterates. The time
is the mean device time of a call in the trace."""

import statistics

from perfbench import trace_reduce


def mean_pages(run, page_tokens: int) -> float:
    lo, hi = run.capture["start"], run.capture["end"]
    totals = []
    for k in range(50):
        t = lo + (hi - lo) * (k + 0.5) / 50
        totals.append(sum(
            -(-(sum(x <= t for x in s["steps"]) + 1) // page_tokens)
            for s in run.records["sessions"]
            if s.get("init_done", s["done"]) <= t < s["done"]))
    return statistics.fmean(totals)


def read(run):
    calls = run.trace and trace_reduce.kernel_times(run.trace,
                                                    "_paged_kernel")
    if not calls or not run.records["sessions"]:
        return None
    shape = run.config["kernels"]["_paged_kernel"]
    flops, moved = run.kernel("_paged_kernel").ops_and_bytes(
        pages=mean_pages(run, shape["page_tokens"]),
        heads=run.config[shape["heads"]],
        page_tokens=shape["page_tokens"], d_head=shape["d_head"])
    return 100.0 * trace_reduce.roofline_share(
        statistics.fmean(calls), flops, moved, run.peak)
