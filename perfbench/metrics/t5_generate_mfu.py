"""Models: the floating-point operations a batch's whole generations
NEED (kernels/t5_generate.py: each rider's unpadded input through the
encoder and once through the cross K/V projections, its decode steps
through the decoder's matrices and the head, attention's unmasked
pairs; `generate/cross` gives a rider's `input_tokens`), the mean over
the window's batches, over `program_ms` times the chip's peak, in
percent: the share of the whole program that a later change in this
cell is bounded by."""

import statistics

from perfbench.metrics import program_ms
from perfbench.metrics.cross_read_share import batches


def read(run):
    found = batches(run)
    took_ms = program_ms.read(run)
    if not found or not took_ms:
        return None
    model = run.kernel("t5_generate")
    steps = run.config["serve"]["signature_kwargs"]["max_decode_len"]
    need = statistics.fmean(
        sum(model.needed_flops(run.config, input_tokens=c["input_tokens"],
                               steps=steps) for c in batch)
        for batch in found)
    return 100.0 * need / (took_ms / 1e3 * run.peak["bf16_flops_per_s"])
