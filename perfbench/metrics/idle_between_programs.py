"""Device: share of the traced window in which no operation ran and no
program was running either, in percent: the idle time that host spans
can explain. `device_idle` less this lies inside the programs' runs."""

from perfbench import host_timeline


def read(run):
    return host_timeline.share_of_window(run, "between")
