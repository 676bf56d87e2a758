"""End-to-end metrics of one run, from the client's side on the host clock.

- ``ttft_p95_ms``: time to first token, from when each request was due, p95
  over every request due in the window; a request that never produced a
  token counts with the time to the end of the drain.
- ``itl_p95_ms``: gap between consecutive output tokens, p95 over every gap
  of every request due in the window.
- ``output_tok_s``: output tokens that came out inside the window, over
  the window.
- ``train_tok_s``: tokens of every train step in the window, over the window.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from bench.common import percentile
from bench.runinfo import Run


def values(run: Run) -> Dict[str, float]:
    if run.kind == "train":
        return {"train_tok_s": run.train["steps"] * run.train["tokens_per_step"] / run.seconds}
    t_end = run.t_end or run.t_close
    ttft = [((t.tokens[0] if t.tokens else t_end) - t.due) * 1e3 for t in run.requests]
    itl = [g * 1e3 for t in run.requests for g in np.diff(t.tokens)]
    out_tok = sum(1 for t in run.requests for x in t.tokens if run.in_window(x))
    return {
        "ttft_p95_ms": percentile(ttft, 95),
        "itl_p95_ms": percentile(itl, 95),
        "output_tok_s": out_tok / run.seconds,
    }
