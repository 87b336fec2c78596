"""95th percentile of the window's call times, in ms: every call, each
timed on the host clock from its dispatch to its ``block_until_ready``."""

import statistics


def read(run):
    if len(run.call_s) < 2:
        return None
    return statistics.quantiles(run.call_s, n=20, method="inclusive")[18] \
        * 1e3
