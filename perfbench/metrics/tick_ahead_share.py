"""Decode pool: the share of the window's steps that the tick loop was
ahead of, in percent: `decode/wait` spans with `ahead=1` (the step's
token was parked, or its round already snapshotted, when the request
came in) over all that say `ahead`. A program whose `decode/wait` does
not say it gives nothing to read."""


def read(run):
    ahead = [args["ahead"] for r in run.requests
             for name, _, _, args in r["spans"]
             if name == "decode/wait" and "ahead" in args]
    return 100.0 * sum(map(bool, ahead)) / len(ahead) if ahead else None
