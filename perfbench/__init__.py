"""The benchmark: one command, run once per cell (BENCHMARK.json).

Everything that decides a number lives in this directory, where a PR that
claims a gain cannot change it: traffic generation (`traffic.py`), the
load generator (`loadgen.py`), the reduction from spans and the device
trace to metrics (`spans.py`, `trace_reduce.py`, `metrics/`), the table
of peaks (`peaks.json`), the kernels' operations and bytes (`kernels/`),
each configuration's sizes and plain reference (`configs/`) and the
comparison that decides `correct`. From the program it takes only the
server under test and its spans, counters and kernel names.
"""
