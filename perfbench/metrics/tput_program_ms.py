"""program_ms where the cell is judged on outputs_per_s."""

from perfbench.metrics.program_ms import read  # noqa: F401
