"""Transport: how late the gRPC event loop runs. Over the `loop/sample`
spans that lie whole inside the ten seconds before the capture: the sum
of their `lag_us` (the overshoot of the loop's 100 ms ticker, which
every request on the loop pays too) over their stretch, in percent.
From `host_track.json`."""

from perfbench import host_track


def read(run):
    found = host_track.of_run(run)
    mine = host_track.samples(found) if found else []
    if not mine:
        return None
    return 100.0 * sum(args["lag_us"] for _, _, _, args in mine) \
        / sum(dur for _, _, dur, _ in mine)
