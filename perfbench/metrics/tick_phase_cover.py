"""Decode pool: per round of the capture, the share of [its
`decode/handoff` start, the next round's) that the loop's spans
(`decode/handoff`, `prepare`, `tick`, `wake`, `fetch` and the host
track's `decode/idle`) cover together; median, in percent. 100: the
phases add up to the period. From `host_track.json`."""

from perfbench import host_track


def read(run):
    found = host_track.of_run(run)
    cover = host_track.median_or_none(
        host_track.phase_cover(found) if found else [])
    return None if cover is None else 100.0 * cover
