"""Kernels: `_flash_kernel`'s share of its roofline over all its calls
in the capture, in percent: the sum of the calls' least times over the
sum of their device times. A batch's calls (one a layer and prefill
chunk: `kernels._flash_kernel.calls_per_program`) need together what its
real examples need (kernels/_flash_kernel.py: unmasked pairs of unpadded
tokens, K/V read once a K/V head; each example's length is the
`prompt_tokens` of its `generate/route` span). The least time is taken
per batch and kind of layer, the larger of operations over the peak and
bytes over the bandwidth, which is at most what the same taken call by
call would give; the capture's calls are charged the mean batch of the
window."""

import statistics

from perfbench import trace_reduce
from perfbench.metrics.expert_held_share import batches


def batch_least_s(run, lengths) -> float:
    """Least seconds for all the kernel's calls of one batch."""
    config, peak = run.config, run.peak
    kernel = run.kernel("_flash_kernel")
    n = config["layers"]
    least = 0.0
    for windowed in (False, True):
        layers = sum(bool(v) == windowed
                     for v in config["hybrid_layer_pattern"][:n])
        need = [kernel.ops_and_bytes(
            length=length, heads=config["num_attention_heads"],
            kv_heads=config["swa_num_key_value_heads" if windowed
                            else "num_key_value_heads"],
            d_qk=config["head_dim"], d_v=config["v_head_dim"],
            window=config["sliding_window"] if windowed else None)
            for length in lengths]
        least += layers * max(
            sum(f for f, _ in need) / peak["bf16_flops_per_s"],
            sum(b for _, b in need) / peak["hbm_bytes_per_s"])
    return least


def read(run):
    calls = run.trace and trace_reduce.kernel_times(run.trace,
                                                    "_flash_kernel")
    found = batches(run)
    if not calls or not found:
        return None
    per_program = run.config["kernels"]["_flash_kernel"]["calls_per_program"]
    mean_batch = statistics.fmean(
        batch_least_s(run, [c["prompt_tokens"] for c in batch])
        for batch in found)
    return 100.0 * mean_batch * (len(calls) / per_program) / sum(calls)
