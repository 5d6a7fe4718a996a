"""Kernels: `_ssd_kernel`'s share of its roofline over all its calls in
the capture, in percent: the sum of the calls' least times over the sum
of their device times. A batch's calls (one a state-space layer and
prefill chunk: `kernels._ssd_kernel.calls_per_program`) need together
what its real examples need (kernels/_ssd_kernel.py: the chunked scan of
each example's unpadded tokens; each example's length is the
`prompt_tokens` of its `generate/state` span). The least time is taken
per batch, the larger of operations over the peak and bytes over the
bandwidth; the capture's calls are charged the mean batch of the
window."""

import statistics

from perfbench import trace_reduce
from perfbench.metrics.scan_real_share import batches

KERNEL = "_ssd_kernel"


def batch_least_s(run, lengths) -> float:
    """Least seconds for all the kernel's calls of one batch."""
    config, peak = run.config, run.peak
    model = run.kernel("hybrid_generate")
    need = [run.kernel(KERNEL).ops_and_bytes(
        length=length, chunk=config["mamba_chunk_size"],
        **model.ssm_shape(config)) for length in lengths]
    return model.layer_kinds(config).count("mamba") * max(
        sum(f for f, _ in need) / peak["bf16_flops_per_s"],
        sum(b for _, b in need) / peak["hbm_bytes_per_s"])


def read(run):
    calls = run.trace and trace_reduce.kernel_times(run.trace, KERNEL)
    found = batches(run)
    if not calls or not found:
        return None
    per_program = run.config["kernels"][KERNEL]["calls_per_program"]
    mean_batch = statistics.fmean(
        batch_least_s(run, [c["prompt_tokens"] for c in batch])
        for batch in found)
    return 100.0 * mean_batch * (len(calls) / per_program) / sum(calls)
