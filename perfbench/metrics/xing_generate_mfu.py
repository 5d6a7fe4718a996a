"""Models: the floating-point operations a batch's whole generations
NEED (kernels/xing_generate.py: unpadded prompt tokens and the decode
steps of the batch's real examples through the MLA projections with the
query's low rank, the latent up-projection once a token, the maps'
product, the dense layer, the shared expert and the head, the pairs on
held experts through one expert each, attention's unmasked pairs at the
decompressed sizes), the mean over the window's batches, over
`program_ms` times the chip's peak, in percent: the share of the whole
program that a later change in this cell is bounded by. A configuration
with no hyper-connected streams reads nothing."""

import statistics

from perfbench.metrics import program_ms
from perfbench.metrics.expert_held_share import batches


def read(run):
    found = batches(run)
    took_ms = program_ms.read(run)
    if not found or not took_ms or "hc_mult" not in run.config:
        return None
    model = run.kernel("xing_generate")
    steps = run.config["serve"]["signature_kwargs"]["max_decode_len"]
    need = statistics.fmean(
        sum(model.needed_flops(
            run.config, length=c["prompt_tokens"], steps=steps,
            held_pairs=c["held_prefill"] + c["held_decode"])
            for c in batch)
        for batch in found)
    return 100.0 * need / (took_ms / 1e3 * run.peak["bf16_flops_per_s"])
