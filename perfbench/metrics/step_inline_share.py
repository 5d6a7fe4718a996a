"""Transport: the share of the window's steps that the gRPC front end
answered on its event-loop thread, with no thread hand-off, in percent:
`decode/wait` spans with `inline=1` (the step found its token parked and
ran on the loop) over all that say `inline`. A program whose
`decode/wait` does not say it gives nothing to read."""


def read(run):
    inline = [args["inline"] for r in run.requests
              for name, _, _, args in r["spans"]
              if name == "decode/wait" and "inline" in args]
    return 100.0 * sum(map(bool, inline)) / len(inline) if inline else None
