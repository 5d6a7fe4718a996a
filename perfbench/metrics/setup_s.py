"""Process start to window open: export or its reuse, boot, load,
check, warm-up, compile, and the lead-in or ramp before the window."""


def read(run):
    return run.setup_s
