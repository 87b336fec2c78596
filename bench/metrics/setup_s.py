"""Seconds from process start to the first timed call: backend start,
data, factorization, compilation or cache load, and warm-up."""


def read(run):
    return run.setup_s
