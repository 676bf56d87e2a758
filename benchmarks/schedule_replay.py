"""Recorded schedules: replay-vs-eager per-step issue overhead.

The schedule subsystem's claim (docs/api/schedule.md): a steady-state
step recorded once replays as ONE fused request set — per-op
validation, window/stream resolution, and per-request progress-engine
registration are paid at record time, not per step. This benchmark
measures that on the two converted training loops, with the device
work held identical (eager and replay dispatch the *same* memoized
jitted executables, so any delta is pure host issue overhead):

(a) **pipeline tick loop** (`parallel.pipeline.gpipe_forward_host`):
    per step, the eager path runs `ticks` iterations of window bracket
    + jit dispatch + `dispatch_enqueue` (one engine-registered request
    per tick) + a drain that waits on all of them; the replay runs the
    recorded closures — reserve + cached dispatch + fused part — and
    one parent wait.

(b) **grad-bucket round-robin** (`optim.grad_overlap.
    bucketed_all_reduce_host`): eager = per-bucket program dispatch +
    `dispatch_enqueue` + one `wait_all` over k requests; replay = the
    recorded per-bucket closures + one fused parent wait.

Both paths are timed end-to-end per step (median over the step loop);
the replay's pure issue phase (`replay(wait=False)`) is recorded as a
third series. Acceptance (asserted): recorded step time beats eager on
both loops (speedup > 1.0), and replay outputs stay byte-identical to
the eager outputs they replace. Results → ``BENCH_schedule.json``
(``BENCH_schedule.smoke.json`` under --smoke).
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.enqueue import OffloadWindow
from repro.core.progress import ProgressEngine
from repro.core.schedule import Schedule
from repro.core.streams import StreamPool, stream_comm_create
from repro.launch.mesh import make_mesh
from repro.optim.grad_overlap import build_buckets, bucketed_all_reduce_host
from repro.parallel.pipeline import gpipe_forward_host


def _median_us(samples) -> float:
    return statistics.median(samples) * 1e6


# ----------------------------------------------------------------------
# (a) pipeline tick loop
# ----------------------------------------------------------------------


def bench_pipeline(steps: int, n_micro: int, mb: int, d: int, layers: int):
    eng = ProgressEngine()
    pool = StreamPool()
    mesh = make_mesh((1,), ("pipe",))
    offload = pool.create(info={"type": "tpu_stream"}, name="sched-pipe")
    comm = stream_comm_create(mesh, ("pipe",), offload)
    Ws = jax.random.normal(jax.random.key(0), (1, layers, d, d)) * 0.3
    xs = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    ticks = n_micro  # 1-stage mesh: ticks == n_micro
    win = OffloadWindow(offload, depth=ticks, engine=eng, name="sched-pipe-win")

    # warm the trace/compile caches so neither series pays them
    ref, _ = gpipe_forward_host(_stage, Ws, xs, comm, window=win)

    sched = Schedule(engine=eng, stream=offload, name="bench-1f1b")
    rec_out, _ = gpipe_forward_host(_stage, Ws, xs, comm, window=win, schedule=sched)
    assert np.array_equal(np.asarray(rec_out), np.asarray(ref)), "record pass diverged"

    # interleave the series per step (A/B) so clock-frequency / cache /
    # GC drift over the run biases neither side
    eager, recorded, issue = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        out, _ = gpipe_forward_host(_stage, Ws, xs, comm, window=win)
        jax.block_until_ready(out)
        eager.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out, _ = gpipe_forward_host(_stage, Ws, xs, comm, window=win, schedule=sched)
        jax.block_until_ready(out)
        recorded.append(time.perf_counter() - t0)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), "replay diverged"
        # pure issue phase: everything before the fused parent wait
        t0 = time.perf_counter()
        ctx = sched.replay(binding={"stage_params": Ws, "x_micro": xs}, wait=False)
        issue.append(time.perf_counter() - t0)
        ctx.wait(timeout=30.0)
    st = sched.stats()
    eng.stop_all()
    return {
        "eager_step_us": _median_us(eager),
        "recorded_step_us": _median_us(recorded),
        "recorded_issue_us": _median_us(issue),
        "speedup": statistics.median(eager) / statistics.median(recorded),
        "ticks": ticks,
        "ops": st["ops"],
        "parts": st["parts"],
        "replays": st["replays"],
    }


def _stage(sp, x):
    y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, sp)
    return y


# ----------------------------------------------------------------------
# (b) grad-bucket round-robin
# ----------------------------------------------------------------------


def bench_grads(steps: int, leaf_shapes, bucket_bytes: int, n_comms: int):
    eng = ProgressEngine()
    pool = StreamPool()
    mesh = make_mesh((1,), ("data",))
    comms = [
        stream_comm_create(mesh, ("data",), pool.create(name=f"sched-gb{i}"))
        for i in range(n_comms)
    ]
    params = [jnp.zeros(s, jnp.float32) for s in leaf_shapes]
    plan = build_buckets(params, bucket_bytes=bucket_bytes)
    flat = jnp.arange(plan.total_elems, dtype=jnp.float32) / plan.total_elems

    ref = bucketed_all_reduce_host(flat, plan, comms, engine=eng)  # warms the programs

    # a dedicated stream keeps the fused parent's wait on one channel
    sched = Schedule(engine=eng, stream=comms[0].stream, name="bench-grads")
    rec_out = bucketed_all_reduce_host(flat, plan, comms, engine=eng, schedule=sched)
    assert np.array_equal(np.asarray(rec_out), np.asarray(ref)), "record pass diverged"

    # interleaved per-step A/B, as in bench_pipeline
    eager, recorded, issue = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = bucketed_all_reduce_host(flat, plan, comms, engine=eng)
        jax.block_until_ready(out)
        eager.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = bucketed_all_reduce_host(flat, plan, comms, engine=eng, schedule=sched)
        jax.block_until_ready(out)
        recorded.append(time.perf_counter() - t0)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), "replay diverged"
        t0 = time.perf_counter()
        ctx = sched.replay(binding={"flat_grads": flat}, wait=False)
        issue.append(time.perf_counter() - t0)
        ctx.wait(timeout=30.0)
    st = sched.stats()
    eng.stop_all()
    return {
        "eager_step_us": _median_us(eager),
        "recorded_step_us": _median_us(recorded),
        "recorded_issue_us": _median_us(issue),
        "speedup": statistics.median(eager) / statistics.median(recorded),
        "n_buckets": plan.n_buckets,
        "ops": st["ops"],
        "parts": st["parts"],
        "replays": st["replays"],
    }


# ----------------------------------------------------------------------
# harness entry
# ----------------------------------------------------------------------


def bench(smoke: bool = False, json_path: str | None = "BENCH_schedule.json"):
    # grad-bucket sizes target a realistic steady state (many small
    # leaves → 8-12 buckets/step): the recorded replay's per-bucket
    # saving (a fused part instead of an engine-registered request) has
    # to amortize its fixed per-replay cost, which it does from ~6
    # buckets up — a 2-3 bucket toy plan measures mostly fixed costs.
    if smoke:
        steps, n_micro, mb, d, layers = 10, 4, 2, 16, 2
        leaf_shapes, bucket_bytes, n_comms = [(512,)] * 8, 2048, 2
    else:
        steps, n_micro, mb, d, layers = 40, 8, 4, 32, 4
        leaf_shapes, bucket_bytes, n_comms = [(256, 64)] * 8 + [(1024,)] * 4, 4096, 2

    data: dict = {
        "smoke": smoke,
        "config": {
            "steps": steps,
            "pipeline": {"n_micro": n_micro, "mb": mb, "d": d, "layers": layers},
            "grad_buckets": {
                "total_elems": int(sum(int(np.prod(s)) for s in leaf_shapes)),
                "bucket_bytes": bucket_bytes,
                "n_comms": n_comms,
            },
        },
    }
    rows = []

    pipe = bench_pipeline(steps, n_micro, mb, d, layers)
    data["pipeline"] = pipe
    rows.append(
        (
            "schedule_replay/pipeline",
            pipe["recorded_step_us"],
            f"step: eager={pipe['eager_step_us']:.0f}us "
            f"recorded={pipe['recorded_step_us']:.0f}us "
            f"issue-only={pipe['recorded_issue_us']:.0f}us "
            f"({pipe['speedup']:.2f}x, {pipe['ticks']} ticks/step)",
        )
    )

    grads = bench_grads(steps, leaf_shapes, bucket_bytes, n_comms)
    data["grad_buckets"] = grads
    rows.append(
        (
            "schedule_replay/grad_buckets",
            grads["recorded_step_us"],
            f"step: eager={grads['eager_step_us']:.0f}us "
            f"recorded={grads['recorded_step_us']:.0f}us "
            f"issue-only={grads['recorded_issue_us']:.0f}us "
            f"({grads['speedup']:.2f}x, {grads['n_buckets']} buckets/step)",
        )
    )

    # acceptance invariants
    data["speedup_recorded_over_eager_min"] = min(pipe["speedup"], grads["speedup"])
    assert pipe["speedup"] > 1.0, (
        f"recorded pipeline step ({pipe['recorded_step_us']:.0f}us) did not beat "
        f"eager ({pipe['eager_step_us']:.0f}us)"
    )
    assert grads["speedup"] > 1.0, (
        f"recorded grad-bucket step ({grads['recorded_step_us']:.0f}us) did not "
        f"beat eager ({grads['eager_step_us']:.0f}us)"
    )
    assert pipe["recorded_issue_us"] < pipe["recorded_step_us"]
    assert grads["recorded_issue_us"] < grads["recorded_step_us"]

    if json_path:
        with open(json_path, "w") as f:
            json.dump(data, f, indent=2)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small sizes for CI")
    args = ap.parse_args()
    # the smoke run must not clobber the committed full-size record
    path = "BENCH_schedule.smoke.json" if args.smoke else "BENCH_schedule.json"
    for r in bench(smoke=args.smoke, json_path=path):
        print(",".join(map(str, r)))
    with open(path) as f:
        d = json.load(f)
    print(
        f"# recorded/eager speedup: pipeline={d['pipeline']['speedup']:.2f}x "
        f"grad_buckets={d['grad_buckets']['speedup']:.2f}x "
        "(target: recorded step beats eager on both loops)"
    )
