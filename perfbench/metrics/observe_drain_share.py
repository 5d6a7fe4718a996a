"""Host process: the share of the ten seconds before the capture (inside
it the profiler stretches every burst) inside `observe/drain`, the
bursts in which the thread `trace-metrics-export` folds the finished
traces into the SLO windows, the cost vectors, the watchdog's joins and
the stage histograms, under the interpreter lock; in percent. From
`host_track.json`."""

from perfbench import host_track


def read(run):
    return host_track.share(run, "observe/drain")
