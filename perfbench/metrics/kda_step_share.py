"""Kernels: `_kda_step_kernel`'s share of the generation program's device
time, in percent."""

from perfbench import trace_reduce
from perfbench.metrics.program_ms import prefix


def read(run):
    if run.trace is None:
        return None
    share = trace_reduce.kernel_share(run.trace, "_kda_step_kernel",
                                      prefix(run))
    return None if share is None else 100.0 * share
