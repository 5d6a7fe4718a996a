"""Transport: the gRPC event loop thread's CPU a step. Over the
`loop/sample` spans that lie whole inside the ten seconds before the
capture (inside it the profiler slows the thread): the sum of their
`cpu_us` (the loop THREAD's `time.thread_time()` between two ticks of
its 100 ms ticker) over the steps the load generator saw answered in the
same stretch, in ms: the w of the station model, measured. From
`host_track.json`."""

from perfbench import host_track


def read(run):
    found = host_track.of_run(run)
    mine = host_track.samples(found) if found else []
    if not mine:
        return None
    steps = host_track.answered(run, found, mine[0][1],
                                mine[-1][1] + mine[-1][2])
    if not steps:
        return None
    return sum(args["cpu_us"] for _, _, _, args in mine) / steps / 1e3
