"""Decode pool: end of a round's run of `jit_direct_tick_fn` on the
device to the end of the same round's `decode/fetch` on the host, on the
joined clock, median in ms: what the fetch takes once the program has
ended. The device plane's offset is the capture's own
(perfbench/host_timeline.py: its quickest launch takes no time), so this
reads high by that launch's true length. From `host_track.json`."""

from perfbench import host_track


def read(run):
    found = host_track.of_run(run)
    tail = host_track.median_or_none(found["fetch_tail_ns"] if found else [])
    return None if tail is None else tail / 1e6
