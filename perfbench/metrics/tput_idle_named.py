"""idle_named where the cell is judged on outputs_per_s."""

from perfbench.metrics.idle_named import read  # noqa: F401
