"""Benchmark harness: one module per paper evaluation.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV rows:
  * message_rate      — paper Fig. 4 (global lock vs per-VCI vs streams)
  * threadcomm_latency— paper Fig. 7 (threadcomm vs MPI-everywhere) +
                        multi-pod all-reduce byte model
  * threadcomm_rate   — host-thread ranks: per-thread VCI vs shared
                        channel message rate + collective latency + the
                        bandwidth axis (Rabenseifner ``allreduce_large``
                        vs binomial over a calibrated link, 64 KB→16 MB)
                        and the grad-overlap exposed-comm bar; also
                        writes ``BENCH_threadcomm.json``
  * progress_overlap  — paper §General Progress RMA example
  * progress_autotune — per-channel wait queues vs stripe CVs (wakeups
                        per notify) + autotuned vs static progress
                        placement; also writes ``BENCH_progress.json``
  * enqueue_window    — depth-N in-flight offload windows per transport
                        (dma / xla / datatype); also writes
                        ``BENCH_enqueue.json``
  * schedule_replay   — recorded schedules: replay-vs-eager per-step
                        issue overhead on the pipeline tick loop and the
                        grad-bucket round-robin; also writes
                        ``BENCH_schedule.json``
  * datatype_iov      — paper §Derived Datatypes iovec costs + the host
                        pack-engine tiers (naive/coalesced/vectorized);
                        also writes ``BENCH_datatype.json`` (machine-
                        readable MB/s + descriptor-vs-enumerate latency)
  * serving_load      — Poisson open-loop serving: contiguous vs paged
                        KV requests/s + p50/p99 token latency, paged
                        token parity and equal-memory concurrency depth
                        asserted; also writes ``BENCH_serving.json``
  * kernels_bench     — Pallas kernels vs references (interpret mode)
  * roofline_table    — §Roofline summary from the dry-run artifacts
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        datatype_iov,
        enqueue_window,
        kernels_bench,
        message_rate,
        progress_autotune,
        progress_overlap,
        roofline_table,
        schedule_replay,
        serving_load,
        threadcomm_latency,
        threadcomm_rate,
    )

    modules = [
        ("message_rate", message_rate),
        ("threadcomm_latency", threadcomm_latency),
        ("threadcomm_rate", threadcomm_rate),
        ("progress_overlap", progress_overlap),
        ("progress_autotune", progress_autotune),
        ("enqueue_window", enqueue_window),
        ("schedule_replay", schedule_replay),
        ("datatype_iov", datatype_iov),
        ("serving_load", serving_load),
        ("kernels_bench", kernels_bench),
        ("roofline_table", roofline_table),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            for row in mod.bench():
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}")
        except Exception:
            failures += 1
            print(f"{name},nan,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
