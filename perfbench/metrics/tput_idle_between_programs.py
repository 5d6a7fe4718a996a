"""idle_between_programs where the cell is judged on outputs_per_s."""

from perfbench.metrics.idle_between_programs import read  # noqa: F401
