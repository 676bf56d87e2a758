"""serving/engine: active slots per decode step over max_batch, mean over
the window's decode steps."""

from bench.common import mean


def read(run):
    occ = mean(len(d.contexts) / run.max_batch * 100.0 for d in run.decodes if run.in_window(d.t0))
    return occ
