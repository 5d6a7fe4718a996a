"""gc_pause_share where the cell is judged on outputs_per_s."""

from perfbench.metrics.gc_pause_share import read  # noqa: F401
