"""The whole step's share of the HBM roofline, in %: the algorithmic
bytes of every call in the traced window over the window and the peak
HBM bandwidth of the device kind."""


def read(run):
    if run.trace is None or not run.trace.devices or run.peaks is None:
        return None
    rate = run.counts["step_bytes"] * run.calls / run.trace.window_s
    return 100.0 * rate / run.peaks["hbm_bytes_per_s"]
