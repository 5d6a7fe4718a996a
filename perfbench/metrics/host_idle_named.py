"""Device: `idle_named`'s rule over `host_track.json`: of the idle
seconds between programs, the share that perfbench/host_track.py names
from the spans of EVERY request that overlaps the capture, whatever its
signature, and from the process's own spans (a collection, the metrics
drain, a decode loop with nothing due), in percent."""

from perfbench import host_track


def read(run):
    return host_track.idle_named(run)
