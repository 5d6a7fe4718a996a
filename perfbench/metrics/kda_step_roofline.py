"""Kernels: `_kda_step_kernel`'s share of its roofline over exactly its
calls in the capture, in percent: the sum of the calls' least times over
the sum of their device times. A call (one a KDA layer and decode step:
`kernels._kda_step_kernel.calls_per_program` a batch) needs what the
batch's real examples need (kernels/_kda_step_kernel.py: each one's
float32 states read once and written once, and the token's rows), over
the bandwidth or the peak, whichever is slower; the rows that pad a batch
need nothing, and the capture's calls are charged the mean batch of the
window."""

import statistics

from perfbench import trace_reduce
from perfbench.metrics.scan_real_share import batches

KERNEL = "_kda_step_kernel"


def read(run):
    calls = run.trace and trace_reduce.kernel_times(run.trace, KERNEL)
    found = batches(run)
    if not calls or not found:
        return None
    model = run.kernel("ling_generate")
    flops, moved = run.kernel(KERNEL).ops_and_bytes(
        **model.kda_shape(run.config))
    least = max(flops / run.peak["bf16_flops_per_s"],
                moved / run.peak["hbm_bytes_per_s"])
    examples = statistics.fmean(len(batch) for batch in found)
    return 100.0 * examples * least * len(calls) / sum(calls)
