"""Device time of the Pallas kernels per call, in ms: the summed
durations of the window's custom-call operations over its calls, on the
slowest chip."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    per_device = run.trace.kernel_s()
    if not per_device or max(per_device) <= 0:
        return None
    return max(per_device) / run.calls * 1e3
