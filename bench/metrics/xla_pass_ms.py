"""Device time of the operations that are not Pallas kernels per call,
in ms (stencil RHS, periodic corner corrections, pads and casts), on the
chip that spent most on them."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    per_device = run.trace.other_s()
    if not per_device or max(per_device) <= 0:
        return None
    return max(per_device) / run.calls * 1e3
