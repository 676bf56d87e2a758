"""Device: 1 - (union of device operation intervals / traced window)."""

from bench import tracefile


def read(run):
    if not run.trace:
        return None
    busy = tracefile.busy_seconds(run.trace)
    if busy is None:
        return None
    return (1.0 - busy / tracefile.window_seconds(run.trace)) * 100.0
