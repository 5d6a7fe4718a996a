"""Models: of the cached positions the latent cache held for a batch's
examples over their decode steps (`latent_rows_held` on `generate/latent`:
the cache's whole length a step), the share the steps' attention had to
read (`latent_rows_read`: the positions up to each step's own), in
percent: the mean over the window's batches. Lower is better: an
attention that reads the whole cache whatever the lengths reads 100% of
what this figure says it needs. A program that does not say what its
latent cache held reads nothing."""

import statistics


def batches(run) -> list[list[dict]]:
    """The `generate/latent` arguments of each executed batch's riders (a
    batch is told apart by its `batching/execute` span, as in
    batch_occupancy)."""
    found: dict = {}
    for r in run.requests:
        at = [(ts, dur) for name, ts, dur, _ in r["spans"]
              if name == "batching/execute"]
        counts = [args for name, _, _, args in r["spans"]
                  if name == "generate/latent" and args]
        if at and counts:
            found.setdefault(at[0], []).append(counts[0])
    return [found[key] for key in sorted(found)]


def read(run):
    shares = [100.0 * sum(c["latent_rows_read"] for c in batch)
              / sum(c["latent_rows_held"] for c in batch)
              for batch in batches(run)
              if sum(c.get("latent_rows_held", 0) for c in batch)]
    return statistics.fmean(shares) if shares else None
