"""Models: of the rows the chunked scan ran in a state-space layer for a
batch's examples (`scan_rows` on `generate/state`: for each example the
chunks it ran times the chunk), the share that were the batch's real
prompt tokens (`prompt_tokens`), in percent: the mean over the window's
batches. A scan that runs every example to the batch's longest, or to the
padded length, reads what the traffic's lengths fill of that; a program
that does not say what its scan ran reads nothing."""

import statistics


def batches(run) -> list[list[dict]]:
    """The `generate/state` arguments of each executed batch's riders (a
    batch is told apart by its `batching/execute` span, as in
    batch_occupancy)."""
    found: dict = {}
    for r in run.requests:
        at = [(ts, dur) for name, ts, dur, _ in r["spans"]
              if name == "batching/execute"]
        counts = [args for name, _, _, args in r["spans"]
                  if name == "generate/state" and args]
        if at and counts:
            found.setdefault(at[0], []).append(counts[0])
    return [found[key] for key in sorted(found)]


def read(run):
    shares = [100.0 * sum(c["prompt_tokens"] for c in batch)
              / sum(c["scan_rows"] for c in batch)
              for batch in batches(run)
              if sum(c.get("scan_rows", 0) for c in batch)]
    return statistics.fmean(shares) if shares else None
