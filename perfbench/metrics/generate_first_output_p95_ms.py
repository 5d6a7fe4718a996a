"""The 95th percentile of what first_output_p50_ms takes the median of,
recorded as a per-layer metric in the cell of whole generations until it
is seen steady enough to decide PRs."""

from perfbench import stats
from perfbench.metrics.first_output_p50_ms import latencies


def read(run):
    return stats.percentile(latencies(run), 95)
