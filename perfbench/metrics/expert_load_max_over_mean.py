"""Expert layer: the busiest held expert's rows in a batch's prefill
(one expert in one layer) over the mean rows of a held expert there,
median over the window's batches. Both figures are the program's own
(`max_load`, `load_total` on `generate/route`, the same on every rider
of a batch) and count the rows the batch really put through (a row that
pads a batch is a prompt of length 0 and puts none through)."""

import statistics

from perfbench.metrics.expert_held_share import batches


def read(run):
    found = batches(run)
    if not found:
        return None
    n = run.config["layers"]
    cells = sum(run.config["moe_layer_freq"][:n]) \
        * run.config["n_routed_experts"]
    ratios = [batch[0]["max_load"] * cells / batch[0]["load_total"]
              for batch in found if batch[0]["load_total"]]
    return statistics.median(ratios) if ratios else None
