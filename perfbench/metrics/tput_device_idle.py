"""device_idle where the cell is judged on outputs_per_s."""

from perfbench.metrics.device_idle import read  # noqa: F401
