"""serving/admission: from when a request was due to the start of its
prefill, p95 over the window's requests that reached a prefill."""

from bench.common import percentile


def read(run):
    waits = [(t.prefill[0] - t.due) * 1e3 for t in run.requests if t.prefill]
    return percentile(waits, 95)
