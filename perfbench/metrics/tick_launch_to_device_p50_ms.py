"""Decode pool: start of a round's `decode/tick` span to the start of
the run of `jit_direct_tick_fn` it enqueued, on the joined clock, median.
The device plane's offset is taken from the capture itself unless
`host_clock.json` records one (perfbench/host_timeline.py), so this
reads relative to the capture's quickest launch; below zero, the join
of the two clocks is broken."""

from perfbench import host_timeline


def read(run):
    return host_timeline.median_launch_ms(run)
