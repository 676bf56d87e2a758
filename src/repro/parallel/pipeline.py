"""Pipeline parallelism over an explicit mesh axis, transported by the
enqueue extension (paper ext. 4).

Two schedules share the stage math:

* :func:`gpipe_forward` — the whole schedule as a ``lax.scan`` over clock
  ticks inside one ``shard_map`` region: each tick, every stage applies
  its block stack and "enqueues" its activation to the next stage
  (token-threaded ``ppermute`` — device-ordered, host never blocks).
  Backward is the AD transpose of the schedule (reverse permutes), so
  pipeline training is just ``jax.grad`` through the scan. Bubble
  fraction = (P-1)/(T) with T = n_micro + P - 1 ticks.
* :func:`gpipe_forward_host` — the host-driven 1F1B-style variant: one
  jitted tick per clock step, with the boundary send of each tick
  registered in a per-stream :class:`~repro.core.enqueue.OffloadWindow`
  so up to ``depth`` microbatch sends stay outstanding per stage
  boundary. The host only blocks when the window backpressures (parking
  on the engine's stripe CV), which is exactly the paper's
  get-the-host-out-of-the-loop shape for stream-offloaded communication.

Used by the llama3-405b hillclimb variant and ``examples/pipeline_train``;
the 40-cell baseline uses DP×TP only.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.enqueue import OffloadWindow, _poll_dispatched, dispatch_enqueue
from repro.core.streams import StreamComm, new_token, serialize_on

__all__ = ["gpipe_forward", "gpipe_forward_host", "pipeline_loss_fn", "split_stages"]


def _gpipe_fingerprint(stage_params, x_micro, axis: str, n_stages: int, depth: int,
                       stage_fn: Callable) -> dict:
    """The structure a recorded 1F1B schedule depends on. Compared by
    :meth:`~repro.core.schedule.Schedule.check` on every replay — any
    drift raises ``ScheduleStale`` instead of replaying a wrong graph."""
    leaves = jax.tree_util.tree_leaves(stage_params)
    return {
        "kind": "gpipe_host",
        "axis": axis,
        "n_stages": n_stages,
        "depth": depth,
        "x_shape": tuple(x_micro.shape),
        "x_dtype": str(x_micro.dtype),
        "params_tree": str(jax.tree_util.tree_structure(stage_params)),
        "params_leaves": tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
        "stage_fn": getattr(stage_fn, "__qualname__", repr(stage_fn)),
    }


def gpipe_forward(stage_fn: Callable, stage_params, x_micro, axis_name: str):
    """Run inside shard_map, ``axis_name`` = pipeline axis.

    stage_fn(stage_params, x) -> y with y.shape == x.shape.
    x_micro: (n_micro, mb, S, d) — microbatch activations fed to stage 0.
    Returns (n_micro, mb, S, d) stage-(P-1) outputs (valid on last rank).
    """
    n_stages = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf, token = carry
        idx = jnp.clip(t, 0, n_micro - 1)
        x0 = x_micro[idx]
        x_in = jnp.where(rank == 0, x0, buf)
        y = stage_fn(stage_params, x_in)
        # enqueue to the next stage: device-ordered, token-threaded
        token, (y_s,) = serialize_on(token, y)
        nxt = lax.ppermute(y_s, axis_name, fwd_perm)
        return (nxt, token), y

    (_, _), ys = lax.scan(tick, (jnp.zeros_like(x_micro[0]), new_token()), jnp.arange(ticks))
    return ys[n_stages - 1 :]  # output for microbatch m at tick m + P - 1


_tick_programs: dict = {}


def _tick_program(stage_fn: Callable, mesh, axis: str, n_stages: int):
    """The jitted one-clock-tick program, memoized on (stage_fn, mesh,
    axis, n_stages) — a fresh closure per call would defeat jit's trace
    cache and re-trace every eager step. Shared by the eager loop and
    the recorded replay (byte-identity comes from running the same
    executable)."""
    key = (stage_fn, mesh, axis, n_stages)
    cached = _tick_programs.get(key)
    if cached is not None:
        return cached
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(sp, buf, x0):
        sp = jax.tree.map(lambda a: a[0], sp)  # drop the pipe-shard dim
        rank = lax.axis_index(axis)
        x_in = jnp.where(rank == 0, x0, buf[0])
        y = stage_fn(sp, x_in)
        # the boundary send: device-ordered, token-threaded (enqueue ext.)
        token, (y_s,) = serialize_on(new_token(), y)
        nxt = lax.ppermute(y_s, axis, fwd_perm)
        return nxt[None], y[None]

    prog = jax.jit(
        jax.shard_map(
            tick,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )
    _tick_programs[key] = prog
    return prog


def gpipe_forward_host(
    stage_fn: Callable,
    stage_params,
    x_micro,
    comm: StreamComm,
    depth: Optional[int] = None,
    engine=None,
    window: Optional[OffloadWindow] = None,
    schedule=None,
):
    """Host-driven pipeline forward with a depth-N boundary-send window.

    Same schedule as :func:`gpipe_forward`, but each clock tick is its own
    jitted ``shard_map`` program dispatched from the host; the tick's
    stage-boundary send is registered in an
    :class:`~repro.core.enqueue.OffloadWindow` on ``comm``'s offload
    stream. Up to ``depth`` microbatch sends stay in flight — the host
    keeps issuing (jax dispatch is async) and only blocks when the window
    backpressures, so issue overhead of tick t+1 overlaps device work of
    tick t. Completions are reaped in completion order; the final
    ``drain`` is the schedule's flush.

    ``stage_params``: the (P, L/P, ...) stacked stage stack (global view,
    sharded over ``comm.axes[0]``). ``x_micro``: (n_micro, mb, S, d) fed
    to stage 0, replicated. Returns ``(outs, window)`` with ``outs`` the
    (n_micro, mb, S, d) stage-(P-1) outputs. ``depth`` defaults to 2;
    pass either your own ``window`` or ``depth``/``engine``, not both.

    ``schedule=`` (a :class:`~repro.core.schedule.Schedule`) makes the
    loop record-then-replay: the first call records — it runs the eager
    tick loop unchanged while capturing one pre-resolved issue closure
    per tick (the jitted tick program, the window, the output row pick
    are all bound at record time) and seals the schedule. Every later
    call with the *same* (now sealed) schedule replays the whole graph
    as one fused request set: per tick, just a window reserve, the
    cached jit dispatch, and a fused part — no per-tick validation, no
    per-request engine registration, one wait for the whole step.
    Replay output is byte-identical to the eager loop. Structure drift
    (microbatch shape/dtype, stage-param tree or leaf shapes, stage
    count, window depth) raises ``ScheduleStale``; re-record by calling
    again after ``schedule.record()`` becomes possible (the raise
    already invalidated it).
    """
    if window is not None and (depth is not None or engine is not None):
        raise ValueError(
            "gpipe_forward_host: an explicit window carries its own depth "
            "and engine; passing depth=/engine= alongside it would be "
            "silently ignored"
        )
    mesh = comm.mesh
    axis = comm.axes[0]
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    if schedule is not None and schedule.sealed:
        meta = schedule.meta.get("gpipe")
        if meta is None:
            raise ValueError(
                "gpipe_forward_host: the sealed schedule was not recorded "
                "by this loop (no meta['gpipe'])"
            )
        win = meta["window"]
        if window is not None and window is not win:
            raise ValueError(
                "gpipe_forward_host: replay re-issues into the window bound "
                "at record time; pass the same window or none"
            )
        if depth is not None and depth != win.depth:
            raise ValueError(
                "gpipe_forward_host: replay uses the window depth bound at "
                f"record time ({win.depth}); got depth={depth}"
            )
        # the recorded fingerprint op re-checks shapes/dtypes/geometry on
        # every replay — no second wrapper-level check needed
        ctx = schedule.replay(
            binding={"stage_params": stage_params, "x_micro": x_micro}
        )
        return ctx.outputs["outs"], win
    win = window or OffloadWindow(
        comm.stream, depth=2 if depth is None else depth, engine=engine, name="pipe-1f1b"
    )

    tick_jit = _tick_program(stage_fn, mesh, axis, n_stages)

    buf0 = jnp.zeros((n_stages,) + tuple(x_micro.shape[1:]), x_micro.dtype)

    def run_eager():
        buf, outs = buf0, []
        for t in range(ticks):
            # backpressure bracket: at most `depth` boundary sends in flight
            with win.issue() as submit:
                buf, y = tick_jit(stage_params, buf, x_micro[min(t, n_micro - 1)])
                submit(dispatch_enqueue(y, stream=win.stream, engine=win.engine, name="pipe-tick"), value=t)
            if t >= n_stages - 1:  # microbatch t-(P-1) lands on the last stage
                outs.append(y[n_stages - 1])  # keep only the last stage's row
        win.drain()
        return jnp.stack(outs), win

    if schedule is None:
        return run_eager()

    # record pass: the eager loop runs unchanged; alongside it the
    # schedule captures one issue closure per tick, all sharing tick_jit
    # and `win` — the replayed graph is the same program on the same
    # transport, so its outputs are byte-identical.
    fp = _gpipe_fingerprint(stage_params, x_micro, axis, n_stages, win.depth, stage_fn)

    def check_and_reset(ctx):
        ctx.schedule.check(
            **_gpipe_fingerprint(
                ctx.bound("stage_params"), ctx.bound("x_micro"),
                axis, n_stages, win.depth, stage_fn,
            )
        )
        ctx.scratch["buf"] = buf0
        ctx.scratch["ys"] = []

    def make_tick(t):
        xi = min(t, n_micro - 1)

        def issue(ctx):
            win.reserve(timeout=None)
            try:
                nxt, y = tick_jit(
                    ctx.bound("stage_params"), ctx.scratch["buf"],
                    ctx.bound("x_micro")[xi],
                )
                ctx.scratch["buf"] = nxt
                part = ctx.fused.part(
                    poll_fn=_poll_dispatched, extra_state={"y": y}, name="pipe-tick"
                )
                win.register(part, value=t)
            except BaseException:
                win.unreserve()
                raise
            ctx.scratch["ys"].append(y)

        return issue

    def collect(ctx):
        # blocking completion assist: once the tick outputs are ready the
        # fused parent is satisfied on the first sweep, not poll-detected
        ctx.prewaits.append(lambda: jax.block_until_ready(ctx.scratch["ys"]))

        def fin():
            win.drain()  # completion-recorded before any reap can race
            # record-time fusion of the eager loop's per-tick output row
            # picks: one stack + one slice (same data movement, one
            # dispatch) — byte-identical to stacking the per-tick rows
            ctx.outputs["outs"] = jnp.stack(
                ctx.scratch["ys"][n_stages - 1 :]
            )[:, n_stages - 1]

        ctx.finalizers.append(fin)

    rec = schedule.record()
    try:
        schedule.fingerprint(**fp)
        schedule.add_op("check", check_and_reset, parts=0, label="fingerprint")
        for t in range(ticks):
            schedule.add_op("pipe_tick", make_tick(t), parts=1, label=f"tick{t}")
        schedule.add_op("collect", collect, parts=0, label="stack-outs")
        out = run_eager()
        schedule.meta["gpipe"] = {
            "window": win, "ticks": ticks, "n_stages": n_stages, "n_micro": n_micro,
        }
        rec.seal()
    finally:
        rec.abort()
    return out


def split_stages(stacked_layer_params, n_stages: int):
    """Reshape (L, ...) stacked layer params into (n_stages, L/P, ...)."""

    def resh(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"layers {L} not divisible by {n_stages} stages"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(resh, stacked_layer_params)


def pipeline_loss_fn(
    cfg,
    mesh,
    pipe_axis: str,
    n_micro: int,
    embed_fn: Callable,
    stage_fn: Callable,
    head_loss_fn: Callable,
):
    """Build loss(params, batch) with the block stack pipelined over
    ``pipe_axis``. Embedding + head are replicated (computed on every
    rank; only the last rank's head result contributes via psum-mask).

    params = {"embed": ..., "stages": (P, L/P, ...) stacked, "head": ...}
    """

    def loss(params, batch):
        def inner(stage_params, tokens):
            # drop the pipe-shard leading dim shard_map leaves on the stack
            stage_params = jax.tree.map(lambda a: a[0], stage_params)
            x = embed_fn(params["embed"], tokens)  # (B, S, d)
            B = x.shape[0]
            assert B % n_micro == 0
            xm = x.reshape(n_micro, B // n_micro, *x.shape[1:])
            outs = gpipe_forward(stage_fn, stage_params, xm, pipe_axis)
            outs = outs.reshape(B, *outs.shape[2:])
            rank = lax.axis_index(pipe_axis)
            n_stages = lax.axis_size(pipe_axis)
            l = head_loss_fn(params["head"], outs, tokens)
            l = jnp.where(rank == n_stages - 1, l, 0.0)
            return lax.psum(l, pipe_axis)

        mapped = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(pipe_axis), P()),
            out_specs=P(),
            check_vma=False,
        )
        return mapped(params["stages"], batch["tokens"])

    return loss
