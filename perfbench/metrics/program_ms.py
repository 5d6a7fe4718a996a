"""Models: median device time of one run of the cell's main program
(the configuration's `main_program` for the cell's signature), from the
device trace's line "XLA Modules"."""

from perfbench import trace_reduce


def prefix(run) -> str:
    return run.config["main_program"][run.traffic["signature"]]


def read(run):
    if run.trace is None:
        return None
    return trace_reduce.program_ms(run.trace, prefix(run))
