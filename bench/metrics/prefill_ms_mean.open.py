"""serving/engine admit: host time of each B=1 prefill of a window request
(ends in the logits read back, so the device work is inside), mean."""

from bench.common import mean


def read(run):
    return mean((t.prefill[1] - t.prefill[0]) * 1e3 for t in run.requests if t.prefill)
