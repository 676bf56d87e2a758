"""Bucketed gradient synchronization with stream-level overlap.

The datatype layer (paper ext. 2) describes each flattened parameter
group as a ``struct`` datatype; buckets are cut at ``bucket_bytes``
boundaries with ``type_iov_len`` (whole segments within a byte budget —
exactly the paper's stated use of ``max_iov_bytes``). Each bucket's
all-reduce/reduce-scatter is issued on its own CommStream (ext. 3) in
round-robin, so XLA overlaps bucket i's collective with bucket i+1's
compute — the explicit-channel schedule the paper's Fig. 4 motivates.

Used by the shard_map trainer variant and the §Perf hillclimb;
the pjit/GSPMD baseline path lets XLA fuse the DP all-reduce itself.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import datatype as dt
from repro.core.collectives import all_gather, all_reduce, reduce_scatter
from repro.core.enqueue import _poll_dispatched, dispatch_enqueue
from repro.core.progress import default_engine
from repro.core.streams import StreamComm, MPIXStream, new_token

__all__ = [
    "GradBuckets",
    "build_buckets",
    "bucketed_all_reduce",
    "bucketed_all_reduce_host",
    "flatten_grads",
    "unflatten_grads",
]


@dataclass(frozen=True)
class GradBuckets:
    """Host-side plan: which flat-leaf slices form each bucket."""

    leaf_sizes: Tuple[int, ...]  # element counts per leaf (flattened order)
    bucket_slices: Tuple[Tuple[int, int], ...]  # (start_elem, n_elem) per bucket
    dtype_descr: object  # the struct datatype describing the full layout
    itemsize: int

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_slices)

    @property
    def total_elems(self) -> int:
        return sum(self.leaf_sizes)


def build_buckets(params_shape, bucket_bytes: int = 4 << 20, itemsize: int = 4) -> GradBuckets:
    """Cut the flattened grad vector into ~bucket_bytes buckets using the
    datatype/iovec machinery on the struct-of-leaves layout."""
    leaves = jax.tree_util.tree_leaves(params_shape)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    # struct datatype: one contiguous block per leaf, packed back to back
    displs, off = [], 0
    for s in sizes:
        displs.append(off * itemsize)
        off += s
    descr = dt.struct([1] * len(sizes), displs, [dt.contiguous(s, dt.predefined(itemsize)) for s in sizes])
    total = off
    # bucket boundaries via type_iov_len: whole segments within byte budget
    slices = []
    seg_off = 0
    elem_off = 0
    n_segs = descr.num_segments
    while seg_off < n_segs:
        # bytes already consumed + budget → how many whole segments fit
        n_in, b_in = dt.type_iov_len(descr, elem_off * itemsize + bucket_bytes)
        n_take = max(1, n_in - seg_off)  # at least one segment per bucket
        take_elems = (descr.cum_bytes(seg_off + n_take) - elem_off * itemsize) // itemsize
        slices.append((elem_off, int(take_elems)))
        seg_off += n_take
        elem_off += int(take_elems)
    return GradBuckets(tuple(sizes), tuple(slices), descr, itemsize)


def flatten_grads(grads):
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])


def unflatten_grads(flat, grads_template):
    leaves, treedef = jax.tree_util.tree_flatten(grads_template)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(flat[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def bucketed_all_reduce(
    flat_grads,
    plan: GradBuckets,
    comms: Sequence[StreamComm],
    scatter: bool = False,
):
    """All-reduce (or reduce-scatter) each bucket on a round-robin stream.

    Independent streams ⇒ independent HLO collectives ⇒ XLA overlaps them;
    one stream ⇒ a serialized chain (the implicit baseline)."""
    k = len(comms)
    tokens = [new_token() for _ in range(k)]
    outs = []
    for i, (start, n) in enumerate(plan.bucket_slices):
        comm_i = comms[i % k]
        chunk = jax.lax.dynamic_slice_in_dim(flat_grads, start, n)
        if scatter:
            y, tokens[i % k] = reduce_scatter(chunk, comm_i, axis=0, token=tokens[i % k])
        else:
            y, tokens[i % k] = all_reduce(chunk, comm_i, token=tokens[i % k])
        outs.append(y)
    return jnp.concatenate(outs), tokens


# ----------------------------------------------------------------------
# Host-driven bucket round-robin (record/replay capable)
# ----------------------------------------------------------------------


_bucket_programs: dict = {}


def _bucket_program(comm: StreamComm, start: int, n: int, scatter: bool):
    """One jitted per-bucket collective program: slice (start, n) baked
    static, reduced over ``comm``'s axis on ``comm``'s stream. Shared by
    the eager host path and the recorded replay — byte-identity between
    the two is inherited from running the *same* executable. Memoized:
    a fresh closure per call would defeat jit's trace cache and re-trace
    every bucket on every eager step."""
    key = (comm, start, n, bool(scatter))
    cached = _bucket_programs.get(key)
    if cached is not None:
        return cached
    mesh, axis = comm.mesh, comm.axes[0]

    def body(flat):
        chunk = jax.lax.dynamic_slice_in_dim(flat, start, n)
        if scatter:
            y, _ = reduce_scatter(chunk, comm, axis=0, token=new_token())
        else:
            y, _ = all_reduce(chunk, comm, token=new_token())
        return y

    out_spec = P(axis) if scatter else P()
    prog = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=out_spec, check_vma=False)
    )
    _bucket_programs[key] = prog
    return prog


def _bucket_rs_program(comm: StreamComm, start: int, n: int):
    """Reduce-scatter half of the split bucket collective: slice the
    bucket and ``psum_scatter`` it over the comm's axis, leaving each
    shard holding its 1/size piece of the reduced bucket."""
    return _bucket_program(comm, start, n, scatter=True)


def _bucket_ag_program(comm: StreamComm, n: int):
    """All-gather half: reassemble a scattered reduced bucket into the
    replicated result. ``RS ∘ AG`` on the same comm equals the bucket's
    all-reduce (the Rabenseifner identity), so the split pair stays
    interchangeable with :func:`_bucket_program`'s psum."""
    key = (comm, n, "ag")
    cached = _bucket_programs.get(key)
    if cached is not None:
        return cached
    mesh, axis = comm.mesh, comm.axes[0]

    def body(y):
        z, _ = all_gather(y, comm, axis=0, token=new_token())
        return z

    prog = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    )
    _bucket_programs[key] = prog
    return prog


def _grad_fingerprint(flat_grads, plan: GradBuckets, comms, scatter: bool,
                      windowed: bool = False) -> dict:
    return {
        "kind": "grad_buckets",
        "flat_shape": tuple(flat_grads.shape),
        "flat_dtype": str(flat_grads.dtype),
        "bucket_slices": tuple(plan.bucket_slices),
        "n_comms": len(comms),
        "comm_axes": tuple(c.axes[0] for c in comms),
        "scatter": bool(scatter),
        "windowed": bool(windowed),
    }


def bucketed_all_reduce_host(
    flat_grads,
    plan: GradBuckets,
    comms: Sequence[StreamComm],
    scatter: bool = False,
    engine=None,
    schedule=None,
    window=None,
    materialize=None,
):
    """Host-driven twin of :func:`bucketed_all_reduce`: each bucket is its
    own jitted collective program dispatched from the host in stream
    round-robin, its completion a generalized request on the bucket's
    stream channel — the host overlaps bucket i's collective with bucket
    i+1's dispatch and blocks once, in one batched ``wait_all``.

    ``window=`` (an :class:`~repro.core.enqueue.OffloadWindow`) switches
    to the backward-overlapped split schedule: each bucket's collective
    is cut into its reduce-scatter and allgather halves, the RS is
    admitted through the window the moment the bucket is ready, and the
    AG for a bucket is issued **in completion order** — whichever RS
    lands first gets its allgather first, regardless of issue order, so
    one slow bucket never serializes the reassembly of the others.
    ``materialize=`` (``fn(i)``) is the backward-pass hook: it is called
    right before bucket i's RS is issued, so the compute producing bucket
    i runs while buckets ``< i`` are in flight — communication hides
    behind the backward walk instead of starting after it. ``RS ∘ AG``
    on one comm is the bucket's all-reduce (the Rabenseifner identity),
    so the result is the unsplit path's, byte-for-byte on a
    single-device axis and numerically equal otherwise.

    ``schedule=`` makes the round-robin record-then-replay: the first
    call records (running the eager path while capturing one pre-resolved
    issue closure per bucket — the jitted program and stream binding are
    resolved at record time) and seals; later calls replay the whole
    round-robin as one fused request set with a single wait — no per-
    bucket request registration, no per-bucket validation. Replay output
    is byte-identical (same executables, same inputs). A changed flat
    length/dtype, bucket plan, or comm set raises ``ScheduleStale``. The
    windowed split records the same way (the RS∘AG pair is the recorded
    program; the window itself is issue pacing, which a fused replay
    already maximizes).

    Returns the reduced flat vector (no tokens: host-side ordering comes
    from dataflow + the engine, the paper's get-the-host-out point).
    """
    if isinstance(flat_grads, jax.core.Tracer):
        raise ValueError(
            "bucketed_all_reduce_host is host-side (engine waits cannot run "
            "under tracing); use bucketed_all_reduce inside shard_map/jit"
        )
    eng = engine or default_engine()
    k = len(comms)
    if schedule is not None and schedule.sealed:
        meta = schedule.meta.get("grad_buckets")
        if meta is None:
            raise ValueError(
                "bucketed_all_reduce_host: the sealed schedule was not "
                "recorded by this loop (no meta['grad_buckets'])"
            )
        # the recorded fingerprint op re-checks on every replay — no
        # second wrapper-level check needed
        ctx = schedule.replay(binding={"flat_grads": flat_grads})
        return ctx.outputs["flat"]

    windowed = window is not None
    if not windowed:
        progs = [
            _bucket_program(comms[i % k], start, n, scatter)
            for i, (start, n) in enumerate(plan.bucket_slices)
        ]
    else:
        rs_progs = [
            _bucket_rs_program(comms[i % k], start, n)
            for i, (start, n) in enumerate(plan.bucket_slices)
        ]
        ag_progs = [
            None if scatter else _bucket_ag_program(comms[i % k], n)
            for i, (start, n) in enumerate(plan.bucket_slices)
        ]

    def run_eager():
        outs, reqs = [], []
        for i, prog in enumerate(progs):
            y = prog(flat_grads)
            reqs.append(
                dispatch_enqueue(y, stream=comms[i % k].stream, engine=eng, name="grad-bucket")
            )
            outs.append(y)
        eng.wait_all([r.grequest for r in reqs])
        return jnp.concatenate(outs)

    def run_windowed():
        outs: List = [None] * plan.n_buckets
        ag_reqs = []

        def issue_ag(slot):
            j, rs_j = slot.value
            if ag_progs[j] is None:  # scatter=True: the RS chunk IS the result
                outs[j] = rs_j
                return
            y = ag_progs[j](rs_j)
            ag_reqs.append(
                dispatch_enqueue(y, stream=comms[j % k].stream, engine=eng, name="grad-ag")
            )
            outs[j] = y

        for i in range(plan.n_buckets):
            if materialize is not None:
                materialize(i)  # backward produces bucket i; earlier RS/AG in flight
            rs = rs_progs[i](flat_grads)
            with window.issue() as submit:
                submit(
                    dispatch_enqueue(
                        rs, stream=comms[i % k].stream, engine=eng, name="grad-rs"
                    ),
                    value=(i, rs),
                )
            for slot in window.reap():  # AGs chase completions, not issue order
                issue_ag(slot)
        for slot in window.drain():
            issue_ag(slot)
        if ag_reqs:
            eng.wait_all([r.grequest for r in ag_reqs])
        return jnp.concatenate(outs)

    if schedule is None:
        return run_windowed() if windowed else run_eager()

    fp = _grad_fingerprint(flat_grads, plan, comms, scatter, windowed)

    def check_and_reset(ctx):
        ctx.schedule.check(
            **_grad_fingerprint(ctx.bound("flat_grads"), plan, comms, scatter, windowed)
        )
        ctx.scratch["outs"] = []

    def make_bucket(i, prog):
        def issue(ctx):
            y = prog(ctx.bound("flat_grads"))
            ctx.fused.part(poll_fn=_poll_dispatched, extra_state={"y": y}, name="grad-bucket")
            ctx.scratch["outs"].append(y)

        return issue

    def make_bucket_split(i, rs_prog, ag_prog):
        def issue(ctx):
            rs = rs_prog(ctx.bound("flat_grads"))
            y = rs if ag_prog is None else ag_prog(rs)
            ctx.fused.part(poll_fn=_poll_dispatched, extra_state={"y": y}, name="grad-bucket")
            ctx.scratch["outs"].append(y)

        return issue

    def collect(ctx):
        # blocking completion assist (see ReplayContext.prewaits)
        ctx.prewaits.append(lambda: jax.block_until_ready(ctx.scratch["outs"]))
        ctx.finalizers.append(
            lambda: ctx.outputs.__setitem__("flat", jnp.concatenate(ctx.scratch["outs"]))
        )

    rec = schedule.record()
    try:
        schedule.fingerprint(**fp)
        schedule.add_op("check", check_and_reset, parts=0, label="fingerprint")
        if windowed:
            for i in range(plan.n_buckets):
                schedule.add_op(
                    "grad_bucket",
                    make_bucket_split(i, rs_progs[i], ag_progs[i]),
                    parts=1,
                    label=f"bucket{i}",
                )
        else:
            for i, prog in enumerate(progs):
                schedule.add_op("grad_bucket", make_bucket(i, prog), parts=1, label=f"bucket{i}")
        schedule.add_op("collect", collect, parts=0, label="concat")
        out = run_windowed() if windowed else run_eager()
        schedule.meta["grad_buckets"] = {
            "n_buckets": plan.n_buckets, "n_comms": k, "windowed": windowed,
        }
        rec.seal()
    finally:
        rec.abort()
    return out
