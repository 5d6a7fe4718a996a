"""Expert layer: of the window's (token, choice) pairs, prefill and
decode together, the share that fell on experts this chip holds, in
percent (6.25 under even routing of 16 held of 256). From the
`generate/route` span each request carries (its own example's counts);
a batch is told apart by its `batching/execute` span, as in
batch_occupancy."""


def batches(run) -> list[list[dict]]:
    """The `generate/route` arguments of each executed batch's riders."""
    found: dict = {}
    for r in run.requests:
        at = [(ts, dur) for name, ts, dur, _ in r["spans"]
              if name == "batching/execute"]
        counts = [args for name, _, _, args in r["spans"]
                  if name == "generate/route" and args]
        if at and counts:
            found.setdefault(at[0], []).append(counts[0])
    return [found[key] for key in sorted(found)]


def read(run):
    riders = [c for batch in batches(run) for c in batch]
    pairs = sum(c["pairs_prefill"] + c["pairs_decode"] for c in riders)
    if not pairs:
        return None
    return 100.0 * sum(c["held_prefill"] + c["held_decode"]
                       for c in riders) / pairs
