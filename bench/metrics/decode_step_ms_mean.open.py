"""serving/engine decode: host time of each batched decode step inside the
window (ends in the next tokens read back), mean."""

from bench.common import mean


def read(run):
    return mean((d.t1 - d.t0) * 1e3 for d in run.decodes if run.in_window(d.t0))
