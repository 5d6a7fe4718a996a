"""Models: the floating-point operations a batch's whole generations
NEED (kernels/ling_generate.py: unpadded prompt tokens and the decode
steps of the batch's real examples through the KDA and MLA projections,
the dense layer, the shared expert and the head, the pairs on held
experts through one expert each, the delta rule's recurrence a token,
attention's unmasked pairs), the mean over the window's batches, over
`program_ms` times the chip's peak, in percent: the share of the whole
program that a later change in this cell is bounded by."""

import statistics

from perfbench.metrics import program_ms
from perfbench.metrics.expert_held_share import batches


def read(run):
    found = batches(run)
    took_ms = program_ms.read(run)
    if not found or not took_ms or "kv_lora_rank" not in run.config:
        return None
    model = run.kernel("ling_generate")
    steps = run.config["serve"]["signature_kwargs"]["max_decode_len"]
    need = statistics.fmean(
        sum(model.needed_flops(
            run.config, length=c["prompt_tokens"], steps=steps,
            held_pairs=c["held_prefill"] + c["held_decode"])
            for c in batch)
        for batch in found)
    return 100.0 * need / (took_ms / 1e3 * run.peak["bf16_flops_per_s"])
