"""How late the generator ran: actual send minus due, 99th percentile
over the requests due in the window. A starved generator is not a fast
server."""

from perfbench import stats


def read(run):
    rows = stats.due_in_window(run.records["requests"], 0.0, run.seconds)
    return stats.percentile([stats.lateness_ms(r) for r in rows], 99)
