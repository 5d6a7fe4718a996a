"""Host process: the share of the ten seconds before the capture (inside
it the profiler stretches every pause) inside `host/gc`, the
collector's pauses of 1 ms and more (every thread of the process stops
for them), in percent. From `host_track.json`."""

from perfbench import host_track


def read(run):
    return host_track.share(run, "host/gc")
