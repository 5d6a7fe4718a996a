"""hbm_peak_gb where the cell is judged on outputs_per_s."""

from perfbench.metrics.hbm_peak_gb import read  # noqa: F401
