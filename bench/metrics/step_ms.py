"""The window's wall time over the calls completed in it, in ms: every
stall inside the window counts."""


def read(run):
    return run.window_s / run.calls * 1e3 if run.calls else None
