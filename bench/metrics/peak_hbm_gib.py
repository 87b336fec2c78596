"""Peak device memory in use over the run, in GiB, on the fullest chip
(the allocator's ``peak_bytes_in_use``, read after the window)."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
