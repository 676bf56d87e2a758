"""Device: memory_stats()["peak_bytes_in_use"] after the window, GiB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**30
