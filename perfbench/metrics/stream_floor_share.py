"""Models: the least time the hyper-connected residual streams' own
traffic can take, a batch's sum of stream bytes (`stream_rows` on
`generate/streams`, times 3 x hc_mult x hidden x 4 B:
kernels/xing_generate.py:stream_bytes) over the memory's peak bandwidth,
over `program_ms`, in percent: the mean over the window's batches. What
of the program is the four streams at the memory's peak: a FLOOR and not
a measurement (a measured share waits for an operation with a name of
its own: trace_reduce tells operations by name). A program that does not
say what its streams mixed reads nothing."""

import statistics

from perfbench.metrics import program_ms


def batches(run) -> list[list[dict]]:
    """The `generate/streams` arguments of each executed batch's riders
    (a batch is told apart by its `batching/execute` span, as in
    batch_occupancy)."""
    found: dict = {}
    for r in run.requests:
        at = [(ts, dur) for name, ts, dur, _ in r["spans"]
              if name == "batching/execute"]
        counts = [args for name, _, _, args in r["spans"]
                  if name == "generate/streams" and args]
        if at and counts:
            found.setdefault(at[0], []).append(counts[0])
    return [found[key] for key in sorted(found)]


def read(run):
    found = batches(run)
    took_ms = program_ms.read(run)
    if not found or not took_ms or "hc_mult" not in run.config:
        return None
    model = run.kernel("xing_generate")
    least_s = statistics.fmean(
        model.stream_bytes(run.config, sum(c["stream_rows"] for c in batch))
        for batch in found) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (took_ms / 1e3)
