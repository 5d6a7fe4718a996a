"""Kernels: `_flash_kernel`'s share of its roofline over all its calls in
the capture, in percent, for a configuration whose attention layers are
latent (MLA): flash_roofline.py's reckoning with the model's own keys
(`qk_head_dim`, `v_head_dim`, as many K/V heads as query heads: the
prefill decompresses K and V), because `head_dim` is a published key of
such a configuration and means another size there (PERF.md section 7).
A batch's calls (one an MLA layer and prefill chunk:
`kernels._flash_kernel.calls_per_program`) need together what its real
examples need (kernels/_flash_kernel.py; each example's length is the
`prompt_tokens` of its `generate/route` span); the least time is taken
per batch, the larger of operations over the peak and bytes over the
bandwidth; the capture's calls are charged the mean batch of the
window."""

import statistics

from perfbench import trace_reduce
from perfbench.metrics.expert_held_share import batches

KERNEL = "_flash_kernel"


def batch_least_s(run, lengths) -> float:
    """Least seconds for all the kernel's calls of one batch."""
    config, peak = run.config, run.peak
    shape = run.kernel("ling_generate").mla_shape(config)
    need = [run.kernel(KERNEL).ops_and_bytes(length=length, **shape)
            for length in lengths]
    layers = list(config["layer_types"][:config["layers"]]).count("mla")
    return layers * max(
        sum(f for f, _ in need) / peak["bf16_flops_per_s"],
        sum(b for _, b in need) / peak["hbm_bytes_per_s"])


def read(run):
    calls = run.trace and trace_reduce.kernel_times(run.trace, KERNEL)
    found = batches(run)
    if not calls or not found or "kv_lora_rank" not in run.config:
        return None
    per_program = run.config["kernels"][KERNEL]["calls_per_program"]
    mean_batch = statistics.fmean(
        batch_least_s(run, [c["prompt_tokens"] for c in batch])
        for batch in found)
    return 100.0 * mean_batch * (len(calls) / per_program) / sum(calls)
