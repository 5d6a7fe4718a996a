"""Models: of the rows of per-token work a batch's prefill ran (norms,
projections, dense layer, router: `prefill_rows` on `generate/route`,
the batch's figure on every rider: blocks run times the block, summed
over the prefill's chunks), the share that were the batch's real prompt
tokens, in percent: the mean over the window's batches. A prefill that
takes every padded position through the stack reads what the traffic's
lengths fill of it; a program that does not say what it ran reads
nothing."""

import statistics

from perfbench.metrics.expert_held_share import batches


def read(run):
    shares = [100.0 * sum(c["prompt_tokens"] for c in batch)
              / batch[0]["prefill_rows"]
              for batch in batches(run) if batch[0].get("prefill_rows")]
    return statistics.fmean(shares) if shares else None
