"""Models: of the blocks of cross-attention K and V a batch's program
holds for a decoder layer (`max_batch_size` rows of `blocks_held`), the
share that the batch's real riders make a decode step read
(`blocks_read` on `generate/cross`: ceil(input tokens / block) each; the
rows that pad a batch are held and unread), in percent: the mean over
the window's batches. A program that reads every held block whatever
the lengths does not say what it reads, and reads nothing here."""

import statistics


def batches(run) -> list[list[dict]]:
    """The `generate/cross` arguments of each executed batch's riders (a
    batch is told apart by its `batching/execute` span, as in
    batch_occupancy)."""
    found: dict = {}
    for r in run.requests:
        at = [(ts, dur) for name, ts, dur, _ in r["spans"]
              if name == "batching/execute"]
        counts = [args for name, _, _, args in r["spans"]
                  if name == "generate/cross" and args]
        if at and counts:
            found.setdefault(at[0], []).append(counts[0])
    return [found[key] for key in sorted(found)]


def read(run):
    found = batches(run)
    if not found:
        return None
    rows = run.config["serve"]["batching"]["max_batch_size"]
    examples = run.traffic.get("examples_per_request", 1)
    return statistics.fmean(
        100.0 * sum(c["blocks_read"] for c in batch)
        / (rows * batch[0]["blocks_held"] / examples)
        for batch in found)
