"""The one traffic generator: reads a mix file and makes a run's requests.

The shapes of the work (prompt and output lengths, the arrival times)
come from the mix's fixed ``shape_seed``; the run's ``--seed`` draws the
token ids (and the benchmark's weights). Every seed therefore offers the
same work at the same times, so runs with different seeds differ by the
system's noise and not by the load.

Serving mixes (``"kind": "serve"``):

- ``loop: open``: ``round(rate_per_s * seconds)`` requests, each due at a
  fixed offset into the window. ``process: poisson`` draws exponential gaps;
  ``process: mmpp`` alternates bursts (``burst_factor`` times the calm
  rate, mean ``burst_mean_s``) with calm spells (mean ``calm_mean_s``),
  with ``rate_per_s`` the mean over both.
- ``loop: closed``: ``clients`` callers, each sending its next request
  when the last completes, from a pool of sizes.

Lengths: ``lognormal`` (median, sigma) or ``uniform`` (min..max), then
rounded up to a multiple of ``round_up`` and clipped to ``min..max``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from bench.common import rng

#: How many sizes a closed loop draws from before it cycles.
CLOSED_POOL = 4096


@dataclass
class Req:
    idx: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    due: Optional[float] = None  # seconds after the window opens; None in a closed loop


def draw_lengths(spec: dict, n: int, r: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * r.standard_normal(n))
    elif spec["dist"] == "uniform":
        x = r.integers(lo, hi + 1, n).astype(np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    step = int(spec.get("round_up", 1))
    x = np.ceil(x / step) * step
    return np.clip(x, lo, hi).astype(np.int64)


def size_pool(mix: dict, n: int) -> np.ndarray:
    """(n, 2) prompt and output lengths from the mix's fixed shape seed."""
    r = rng(int(mix.get("shape_seed", 0)), 11)
    return np.stack(
        [draw_lengths(mix["prompt_len"], n, r), draw_lengths(mix["output_len"], n, r)], axis=1
    )


def n_open(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["arrivals"]["rate_per_s"]) * seconds)))


def _mmpp_segments(a: dict, seconds: float, r: np.random.Generator):
    """Alternating (burst, calm) cycles scaled to fill ``seconds``: a list of
    cycles, each [(duration, relative rate), (duration, relative rate)]."""
    f = float(a["burst_factor"])
    cycles, total = [], 0.0
    while total < seconds or not cycles:
        b = r.exponential(float(a["burst_mean_s"]))
        c = r.exponential(float(a["calm_mean_s"]))
        cycles.append([(b, f), (c, 1.0)])
        total += b + c
    scale = seconds / total
    return [[(d * scale, w) for d, w in cyc] for cyc in cycles]


def arrivals(mix: dict, seconds: float) -> np.ndarray:
    """Sorted due offsets in [0, seconds), from the shape seed."""
    a = mix["arrivals"]
    n = n_open(mix, seconds)
    shape = rng(int(mix.get("shape_seed", 0)), 12)
    if a["process"] == "poisson":
        gaps = shape.exponential(1.0, n + 1)
        return np.cumsum(gaps)[:n] / gaps.sum() * seconds
    if a["process"] == "mmpp":
        cycles = _mmpp_segments(a, seconds, shape)
        segs = [s for cyc in cycles for s in cyc]
        weight = np.array([d * w for d, w in segs])
        counts = shape.multinomial(n, weight / weight.sum())
        # within-segment positions, fixed by the shape seed, in [0, 1)
        pos = [np.sort(shape.random(k)) for k in counts]
        out, t = [], 0.0
        for (d, _), p in zip(segs, pos):
            out.extend(t + p * d)
            t += d
        return np.minimum(np.asarray(out, np.float64), np.nextafter(seconds, 0))
    raise ValueError(f"unknown arrival process {a['process']!r}")


def _prompt(r: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return r.integers(0, vocab, n, dtype=np.int64).astype(np.int32)


def open_schedule(mix: dict, vocab: int, seconds: float, seed: int) -> List[Req]:
    n = n_open(mix, seconds)
    sizes = size_pool(mix, n)
    due = arrivals(mix, seconds)
    toks = rng(seed, 4)
    return [
        Req(i, _prompt(toks, vocab, int(p)), int(o), float(d))
        for i, ((p, o), d) in enumerate(zip(sizes, due))
    ]


def closed_pool(mix: dict, vocab: int, seed: int, n: int = CLOSED_POOL) -> List[Req]:
    toks = rng(seed, 4)
    return [Req(i, _prompt(toks, vocab, int(p)), int(o)) for i, (p, o) in enumerate(size_pool(mix, n))]


def prompt_shapes(mix: dict, seconds: float) -> List[int]:
    """Every prompt length a run of this mix can send: the shapes to warm."""
    n = n_open(mix, seconds) if mix["loop"] == "open" else CLOSED_POOL
    return sorted({int(p) for p in size_pool(mix, n)[:, 0]})
