"""Expert layer: the held experts a decode step's expert layer streamed,
of those this chip holds: `hit_decode` on `generate/route` (the batch's
figure on every rider: the (step, expert layer, hit expert) products
its decode steps ran, all rows through each expert that at least one
row chose) over the batch's decode steps times expert layers, which a
rider's `pairs_decode` holds top-k times; the mean over the window's
batches. Times the bytes of one expert's matrices it is what the
expert layers of a step read. A program that sorts its pairs counts 0
trips and one that does not count them says nothing: both read nothing."""

import statistics

from perfbench.metrics.expert_held_share import batches


def read(run):
    counted = [batch[0] for batch in batches(run)
               if batch[0].get("hit_decode") and batch[0]["pairs_decode"]]
    if not counted:
        return None
    top_k = run.config["num_experts_per_tok"]
    return statistics.fmean(c["hit_decode"] * top_k / c["pairs_decode"]
                            for c in counted)
