"""host_idle_named where the cell is judged on outputs_per_s."""

from perfbench.metrics.host_idle_named import read  # noqa: F401
