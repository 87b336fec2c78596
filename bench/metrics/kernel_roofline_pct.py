"""The kernels' share of the HBM roofline, in %: the algorithmic bytes of
the banded solve(s) in one call over the peak HBM bandwidth of the
device kind, divided by the kernels' device time per call."""


def read(run):
    if run.trace is None or not run.calls or run.peaks is None:
        return None
    per_device = run.trace.kernel_s()
    if not per_device or max(per_device) <= 0:
        return None
    kernel_s = max(per_device) / run.calls
    least_s = run.counts["kernel_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
