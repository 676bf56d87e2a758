"""Serving driver: continuous-batching engine + request-level stats.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --requests 8

runs the published widths (an accelerator's job); ``--smoke`` runs the
reduced preset, which is what the CPU can serve.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced preset (CPU sizes)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = api.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, max_batch=args.max_batch, max_len=args.max_len)

    rng = np.random.default_rng(0)
    submit_t = {}
    t0 = time.perf_counter()
    reqs = []
    for i in range(args.requests):
        r = eng.submit(rng.integers(0, cfg.vocab, (4 + i % 7,)), max_new_tokens=args.max_new)
        submit_t[r.rid] = time.perf_counter()
        reqs.append(r)

    steps = 0
    done_t = {}
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
        for r in reqs:
            if r.done and r.rid not in done_t:
                done_t[r.rid] = time.perf_counter()
    wall = time.perf_counter() - t0

    toks = sum(len(r.out_tokens) for r in reqs)
    lats = [done_t[r.rid] - submit_t[r.rid] for r in reqs]
    print(
        f"[serve] arch={args.arch} requests={len(reqs)} tokens={toks} "
        f"steps={steps} wall={wall:.2f}s throughput={toks/wall:.1f} tok/s"
    )
    print(
        f"[serve] latency p50={np.percentile(lats,50)*1e3:.0f}ms "
        f"p95={np.percentile(lats,95)*1e3:.0f}ms max_batch={args.max_batch} "
        f"(continuous batching over {args.max_batch} KV slots)"
    )


if __name__ == "__main__":
    main()
