"""Enqueue semantics: device-ordered communication (paper ext. 4).

``MPIX_Send_enqueue``/``MPIX_Recv_enqueue`` place MPI operations *into a
device stream*: the host never blocks, ordering comes from the stream.
On TPU the device stream IS the XLA program's dataflow: an op "enqueued
after" another is simply an op with a dependency edge. We reproduce the
semantics with token-threaded ``ppermute`` transfers on an *offload*
stream:

* ``send_enqueue``/``recv_enqueue`` return immediately with a token
  (host-async, like the paper's CUDA example that never calls
  ``cudaStreamSynchronize``);
* ``wait_enqueue`` materializes the dependency (the analogue of the
  stream completing);
* the non-blocking pair (``isend_enqueue``) returns an
  :class:`EnqueuedRequest` whose completion is a *host-side* generalized
  request — the paper's three-contexts point (offload stream / host
  start-complete / actual transfer) maps to (XLA dataflow / host dispatch
  / ICI transfer).

The host side is a **depth-N in-flight window transport**, not a
one-token serial model: a per-stream :class:`OffloadWindow` admits up to
``depth`` outstanding enqueued transfers and *backpressures* the issue
path when full by parking on the progress engine's per-stripe condition
variables (never busy-spinning — completion wakes the parked issuer).
Completion is tracked in **completion order**, not issue order: a late
arrival never blocks an earlier completion from being reaped, so the
1F1B pipeline schedule keeps ``depth`` microbatch boundary sends in
flight and reaps whichever lands first. ``OffloadWindow.stats()``
(admitted / reaped / backpressure parks / max depth seen) sits alongside
the engine counters.

Send buffers may be **datatype-described**: ``send_enqueue`` /
``isend_enqueue`` accept ``datatype=`` (an MPI derived datatype from
:mod:`repro.core.datatype`) and pack *on stream* via the
``kernels/ops.pack_datatype`` device kernel when the exact ``pack_info``
proof says the layout is uniform, falling back to the vectorized host
engine for irregular layouts — pipeline and halo sends describe layouts
instead of materializing contiguous staging copies.

This module is the transport of pipeline parallelism
(:mod:`repro.parallel.pipeline`): microbatch activations are "enqueued"
across pipeline-stage boundaries, and the 1F1B schedule relies on sends
of step i overlapping compute of step i+1 — precisely the paper's
motivation for getting the host out of the loop.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as _P

from repro.core import collectives
from repro.core import datatype as dtt
from repro.core.progress import GeneralizedRequest, ProgressEngine, default_engine
from repro.core.streams import (
    MPIXStream,
    STREAM_NULL,
    StreamComm,
    new_token,
    serialize_on,
)

__all__ = [
    "send_enqueue",
    "recv_enqueue",
    "sendrecv_enqueue",
    "isend_enqueue",
    "isend_enqueue_scheduled",
    "wait_enqueue",
    "EnqueuedRequest",
    "shift_enqueue",
    "dispatch_enqueue",
    "pack_send",
    "OffloadWindow",
    "WindowSlot",
]

Token = jax.Array

# Park slice while the window itself must drive progress (no covering
# progress thread): matches _wait_dispatched's readiness-poll granularity.
_SELF_PROGRESS_PARK_S = 0.0005


def _require_offload(comm: StreamComm) -> None:
    if not comm.stream.is_offload and not comm.stream.is_null:
        raise ValueError(
            "enqueue ops need an offload stream (create with "
            "info={'type': 'tpu_stream'}) or STREAM_NULL for implicit mode"
        )


# ----------------------------------------------------------------------
# Datatype-described send buffers
# ----------------------------------------------------------------------


def pack_send(x, datatype: dtt.Datatype, count: int = 1, *, interpret: Optional[bool] = None):
    """Materialize the packed payload of a ``(buffer, Datatype)`` send.

    ``x`` is the flat(tenable) element buffer the datatype addresses.
    When ``pack_info`` *proves* the layout uniform and the dense kernel
    can express it (non-negative displacement, non-overlapping stride,
    element-aligned bytes), the pack runs **on stream** through
    :func:`repro.kernels.ops.pack_datatype` — device work ordered by the
    send's token like any other enqueued op. Otherwise the vectorized
    host engine (:func:`repro.core.datatype.pack`) gathers the bytes; the
    two paths are byte-identical for any layout both accept.

    Traced buffers (inside ``shard_map``/``jit``) can only take the
    device path; an irregular layout there raises with a pointer at the
    host path rather than silently breaking tracing. ``interpret``
    follows :func:`repro.kernels.ops.resolve_interpret`.
    """
    from repro.kernels import ops  # deferred: kernels import jax pallas

    if count != 1 and datatype.extent < 0:
        raise ValueError("pack_send: count>1 with negative extent is ambiguous")
    flat = x.reshape(-1)
    info = dtt.pack_info(datatype)
    device_err: Optional[Exception] = None
    if info is not None and count == 1:
        try:
            return ops.pack_datatype(flat, datatype, info=info, interpret=interpret)
        except ValueError as e:  # kernel-inexpressible uniform layout
            device_err = e
    if isinstance(flat, jax.core.Tracer):
        raise ValueError(
            "pack_send: irregular/kernel-inexpressible datatype on a traced "
            "buffer — the host engine cannot run under tracing. Pre-pack on "
            "the host (core.datatype.pack) or use a uniform layout."
        ) from device_err
    host = np.asarray(flat)
    packed = dtt.pack(host, datatype, count)  # uint8, count*size bytes
    item = host.dtype.itemsize
    if packed.size % item == 0:
        return jnp.asarray(packed.view(host.dtype))
    return jnp.asarray(packed)


# ----------------------------------------------------------------------
# Stream-enqueued transfers (SPMD ppermute with token ordering)
# ----------------------------------------------------------------------


def sendrecv_enqueue(
    x,
    comm: StreamComm,
    perm: Sequence[Tuple[int, int]],
    token: Optional[Token] = None,
):
    """SPMD matched send+recv enqueued on the comm's offload stream.

    Every rank contributes its outgoing shard and receives per ``perm``.
    Returns ``(received, token')`` — the token orders subsequent enqueued
    ops on the same stream (CUDA-stream semantics)."""
    _require_offload(comm)
    token = token if token is not None else new_token()
    y, token = collectives.ppermute(x, comm, perm, token)
    return y, token


def send_enqueue(
    x,
    comm: StreamComm,
    dest_offset: int,
    token: Optional[Token] = None,
    *,
    datatype: Optional[dtt.Datatype] = None,
    count: int = 1,
    window: Optional["OffloadWindow"] = None,
):
    """``MPIX_Send_enqueue`` to ``rank + dest_offset`` on a ring (SPMD: the
    matching recv is implied on the destination).

    ``datatype=`` describes a non-contiguous send buffer: ``x`` is the
    flat element buffer and the payload is packed on stream (device
    kernel for proven-uniform layouts, host engine otherwise — see
    :func:`pack_send`) instead of the caller materializing a staging copy.

    ``window=`` routes the send through an :class:`OffloadWindow`: the
    call *backpressures* (parks on the engine's stripe CV) while the
    window holds ``depth`` incomplete transfers, then dispatches and
    registers the new one. Windowed sends are **host-side** (the window
    is host state): the call builds the SPMD ring-send program itself, so
    ``x`` must be the concrete *global* buffer with leading dim = ring
    size (per-rank payloads stacked), not a traced per-shard value, and
    tokens do not apply — passing one raises, and the returned token is
    None (ordering comes from dataflow + the window). Without a window
    the call is the per-shard fire-and-forget form usable inside
    ``shard_map``, exactly as before."""
    if window is None:
        if datatype is not None:
            x = pack_send(x, datatype, count)
        return sendrecv_enqueue(x, comm, _ring_perm(comm, dest_offset), token)
    if token is not None:
        raise ValueError(
            "windowed sends build their own program; an input token cannot "
            "be threaded through — order host-issued sends via dataflow "
            "(feed y into the next send) or drop the window"
        )
    y, _ = _windowed_isend(x, comm, dest_offset, datatype, count, window)
    return y, None


def _windowed_isend(x, comm, dest_offset, datatype, count, window):
    """Host-side windowed ring send shared by send_enqueue/isend_enqueue."""
    if isinstance(x, jax.core.Tracer):
        raise ValueError(
            "windowed enqueue sends are host-side (window backpressure "
            "cannot run under tracing); call outside shard_map/jit with "
            "the global buffer, or drop the window inside traced code"
        )
    _require_offload(comm)
    if window.stream.sid != comm.stream.sid:
        raise ValueError(
            f"window is bound to stream {window.stream.name!r} but the comm "
            f"sends on {comm.stream.name!r}: the window parks on and "
            "progresses its own stream's channel, so a mismatch would "
            "deadlock backpressure — build the window on the comm's stream"
        )
    n = comm.mesh.shape[comm.axes[0]]
    x = jnp.asarray(x)
    if x.shape[0] != n:
        raise ValueError(
            f"windowed send: leading dim {x.shape[0]} != ring size {n} "
            "(stack each rank's payload)"
        )
    if datatype is not None:
        x = _pack_stacked(x, datatype, count, n)
    with window.issue() as submit:
        y = _mapped_ring_send(comm.mesh, comm.axes, dest_offset)(x)
        req = dispatch_enqueue(y, stream=comm.stream, engine=window.engine, name="isend_enqueue")
        submit(req, value=y)
    return y, req


def _pack_stacked(x, datatype: dtt.Datatype, count: int, n: int):
    """Pack each of the ``n`` stacked per-rank payloads. Multi-rank sends
    pack all rows in ONE vectorized host call when the layout fits inside
    a row (the type resized to the row stride, replicated ``n`` times by
    extent shift) — per-rank kernel launches on the issue hot path would
    scale O(n) per send. The single-rank case keeps the on-stream device
    path of :func:`pack_send`; both produce identical bytes."""
    row_bytes = 0 if x.ndim < 2 else int(x.dtype.itemsize * np.prod(x.shape[1:]))
    if n > 1 and count == 1 and datatype.lb >= 0 and datatype.ub <= row_bytes:
        host = np.asarray(x)
        rowed = dtt.resized(datatype, datatype.lb, row_bytes)
        packed = dtt.pack(host, rowed, count=n)
        item = host.dtype.itemsize
        if datatype.size % item == 0:
            return jnp.asarray(packed.view(host.dtype).reshape(n, -1))
        return jnp.asarray(packed.reshape(n, -1))
    return jnp.stack([pack_send(x[i], datatype, count) for i in range(n)])


@lru_cache(maxsize=None)
def _mapped_ring_send(mesh, axes: Tuple[str, ...], dest_offset: int):
    """Jitted SPMD ring-send program for host-issued (windowed) enqueues:
    one token-sealed ppermute over ``axes[0]``. Cached per (mesh, axes,
    offset) so steady-state windowed sends hit the jit cache."""
    axis = axes[0]
    n = mesh.shape[axis]
    perm = [(i, (i + dest_offset) % n) for i in range(n)]

    def per_shard(xs):
        token, (x_s,) = serialize_on(new_token(), xs[0])
        return lax.ppermute(x_s, axis, perm)[None]

    return jax.jit(
        jax.shard_map(per_shard, mesh=mesh, in_specs=_P(axis), out_specs=_P(axis), check_vma=False)
    )


def _ring_perm(comm: StreamComm, dest_offset: int) -> List[Tuple[int, int]]:
    n = comm.mesh.shape[comm.axes[0]]
    return [(i, (i + dest_offset) % n) for i in range(n)]


def recv_enqueue(x_buffer, comm: StreamComm, src_offset: int, token: Optional[Token] = None):
    """``MPIX_Recv_enqueue`` from ``rank - src_offset``; ``x_buffer`` is the
    value this rank forwards (SPMD symmetry)."""
    return send_enqueue(x_buffer, comm, src_offset, token)


def shift_enqueue(x, comm: StreamComm, shift: int = 1, token: Optional[Token] = None):
    """Pipeline-stage shift: stage s → stage s+shift (non-wrapping edges
    receive zeros). The workhorse of :mod:`repro.parallel.pipeline`."""
    _require_offload(comm)
    n = comm.mesh.shape[comm.axes[0]]
    if shift >= 0:
        perm = [(i, i + shift) for i in range(n - shift)]
    else:
        perm = [(i, i + shift) for i in range(-shift, n)]
    token = token if token is not None else new_token()
    y, token = collectives.ppermute(x, comm, perm, token)
    return y, token


# ----------------------------------------------------------------------
# Host-visible nonblocking wrappers (MPIX_Isend_enqueue / MPIX_Wait_enqueue)
# ----------------------------------------------------------------------


@dataclass
class EnqueuedRequest:
    """Host handle for an enqueued transfer: completion of the *dispatch*
    (host side), distinct from completion of the offload stream itself —
    the paper's separation of the three contexts.

    ``wait`` goes through the engine's parking path: when a progress
    thread covers the offload stream, the waiting host thread parks on the
    stream's CV instead of spinning on ``is_ready``."""

    grequest: GeneralizedRequest
    token: Optional[Token] = None
    engine: Optional[ProgressEngine] = None

    @property
    def done(self) -> bool:
        return self.grequest.done

    def wait(self, timeout: Optional[float] = None) -> bool:
        return (self.engine or default_engine()).wait(self.grequest, timeout)


def _wait_dispatched(states, timeout) -> None:
    """Batched ``wait_fn`` for enqueued transfers: block on every dispatched
    array in the per-stream group (jax futures), honoring the engine's
    deadline budget. Module-level so the engine batches all enqueued
    requests of a stream into one call.

    Arrays exposing ``is_ready`` are polled so a deadline can cut the wait
    short; backends without it fall back to ``block_until_ready`` bounded
    by the remaining budget (run on a daemon helper joined for the
    remainder, since ``block_until_ready`` itself has no timeout) — the
    old path treated such arrays as already complete and returned
    instantly, breaking ``wait_all``'s contract. ``RuntimeError`` from the
    runtime (deleted/donated array) means there is nothing left to wait on
    and is confined to that array, not the whole batch."""
    deadline = None if timeout is None else time.monotonic() + timeout
    for st in states:
        arr = st["y"]
        remaining = None if deadline is None else deadline - time.monotonic()
        if remaining is not None and remaining <= 0:
            return  # budget exhausted; the engine recomputes remaining time
        try:
            if not hasattr(arr, "is_ready"):
                if not hasattr(arr, "block_until_ready"):
                    continue  # plain host value: nothing to wait on
                if remaining is None:
                    arr.block_until_ready()
                else:
                    t = threading.Thread(target=_swallow_runtime_error(arr.block_until_ready), daemon=True)
                    t.start()
                    t.join(remaining)
                continue
            if remaining is None:
                if hasattr(arr, "block_until_ready"):
                    arr.block_until_ready()
                continue
            # block_until_ready has no timeout: under a deadline, poll the
            # future's readiness so the caller's wait_all contract holds
            while time.monotonic() < deadline and not arr.is_ready():
                time.sleep(0.0005)
        except RuntimeError:
            continue  # deleted/donated array counts as complete


def _swallow_runtime_error(fn):
    def run():
        try:
            fn()
        except RuntimeError:
            pass  # deleted/donated array counts as complete

    return run


def _poll_dispatched(state) -> bool:
    """Shared ``poll_fn`` for dispatched device work (``state["y"]``): jax
    arrays expose ready-ness via block-free ``is_ready`` on the underlying
    future; deleted/donated arrays count as done. Used by eager enqueued
    requests and by scheduled-replay fused parts alike."""
    arr = state["y"]
    try:
        return arr.is_ready() if hasattr(arr, "is_ready") else True
    except RuntimeError:
        return True


def dispatch_enqueue(
    y,
    stream: MPIXStream = STREAM_NULL,
    engine: Optional[ProgressEngine] = None,
    token: Optional[Token] = None,
    name: str = "enqueue",
) -> EnqueuedRequest:
    """Register already-dispatched device work ``y`` as an enqueued
    transfer: a generalized request whose ``poll_fn`` queries the device
    future (the ``cudaEventQuery`` analogue) and whose batched ``wait_fn``
    blocks on the per-stream group. The building block under
    :func:`isend_enqueue` and :class:`OffloadWindow`."""
    eng = engine or default_engine()
    req = eng.grequest_start(
        poll_fn=_poll_dispatched,
        wait_fn=_wait_dispatched,
        extra_state={"y": y},
        stream=stream,
        name=name,
    )
    return EnqueuedRequest(req, token, eng)


def isend_enqueue(
    x,
    comm: StreamComm,
    dest_offset: int,
    token: Optional[Token] = None,
    engine: Optional[ProgressEngine] = None,
    *,
    datatype: Optional[dtt.Datatype] = None,
    count: int = 1,
    window: Optional["OffloadWindow"] = None,
) -> Tuple[jax.Array, EnqueuedRequest]:
    """Non-blocking enqueue: returns (result, request). The request
    completes when the dispatched device work is done (poll_fn queries the
    device future, like cudaEventQuery in the paper's grequest example).
    ``datatype=``/``window=`` behave as in :func:`send_enqueue` — with a
    window, the call is host-side (global stacked buffer, no input token,
    see :func:`send_enqueue`), backpressures while ``depth`` transfers
    are in flight, and the request is tracked in the window."""
    if window is not None:
        if token is not None:
            raise ValueError(
                "windowed sends build their own program; an input token "
                "cannot be threaded through — order host-issued sends via "
                "dataflow or drop the window"
            )
        if engine is not None and engine is not window.engine:
            raise ValueError(
                "isend_enqueue: the window carries its own engine; a "
                "different engine= alongside it would be silently ignored"
            )
        return _windowed_isend(x, comm, dest_offset, datatype, count, window)
    if datatype is not None:
        x = pack_send(x, datatype, count)
    y, tok = sendrecv_enqueue(x, comm, _ring_perm(comm, dest_offset), token)
    req = dispatch_enqueue(y, stream=comm.stream, engine=engine or default_engine(), token=tok, name="isend_enqueue")
    return y, req


def _make_stacked_packer(x, datatype: dtt.Datatype, count: int, n: int):
    """Pre-resolved replay twin of :func:`_pack_stacked`: the branch
    decision (vectorized row pack vs per-rank engine), the row-resized
    descriptor, and the :func:`~repro.core.datatype.make_packer` pack
    program (bounds + ``pack_info`` proof) are all resolved once, at
    record time. The returned closure produces bytes identical to
    ``_pack_stacked`` for same-shaped buffers."""
    row_bytes = 0 if x.ndim < 2 else int(x.dtype.itemsize * np.prod(x.shape[1:]))
    if n > 1 and count == 1 and datatype.lb >= 0 and datatype.ub <= row_bytes:
        rowed = dtt.resized(datatype, datatype.lb, row_bytes)
        packer, _proof = dtt.make_packer(rowed, count=n, nbytes=int(x.nbytes))
        item = np.dtype(x.dtype).itemsize
        view_dtype = np.dtype(x.dtype) if datatype.size % item == 0 else None

        def run_vectorized(xv):
            packed = packer(np.asarray(xv))
            if view_dtype is not None:
                return jnp.asarray(packed.view(view_dtype).reshape(n, -1))
            return jnp.asarray(packed.reshape(n, -1))

        return run_vectorized

    def run_per_rank(xv):
        return jnp.stack([pack_send(xv[i], datatype, count) for i in range(n)])

    return run_per_rank


def isend_enqueue_scheduled(
    x,
    comm: StreamComm,
    dest_offset: int,
    *,
    schedule,
    window: "OffloadWindow",
    bind: Optional[str] = None,
    out: Optional[str] = None,
    datatype: Optional[dtt.Datatype] = None,
    count: int = 1,
) -> Tuple[jax.Array, EnqueuedRequest]:
    """Record a windowed ring send into ``schedule``.

    The record pass IS an eager windowed :func:`isend_enqueue`: full
    validation (host-side check, window/stream match, ring-size check),
    the datatype pack-engine branch, and the jitted ring program resolve
    exactly once, here, and the dispatched result is returned as usual.
    The recorded op re-issues with none of that — one shape/dtype compare
    (mismatch raises ``ScheduleStale``), the pre-resolved packer, the
    cached ring program, a window reserve, and a fused *part* registered
    with the window in place of an engine-queued request.

    ``bind=`` names the replay binding supplying the buffer (omit to
    replay the recorded constant); ``out=`` stores each replay's
    dispatched array under ``ctx.outputs[out]``. Returns the record
    pass's ``(y, request)`` — the request is window-owned.
    """
    from repro.core.schedule import ScheduleError

    if not schedule.recording:
        raise ScheduleError("isend_enqueue_scheduled: schedule is not recording")
    x = jnp.asarray(x)
    y, req = _windowed_isend(x, comm, dest_offset, datatype, count, window)
    ring = _mapped_ring_send(comm.mesh, comm.axes, dest_offset)
    n = comm.mesh.shape[comm.axes[0]]
    pack_fn = None if datatype is None else _make_stacked_packer(x, datatype, count, n)
    shape0, dtype0 = tuple(x.shape), x.dtype

    def issue(ctx):
        xv = jnp.asarray(ctx.bound(bind)) if bind is not None else x
        if tuple(xv.shape) != shape0 or xv.dtype != dtype0:
            ctx.schedule._stale(
                f"isend buffer changed: recorded {shape0}/{dtype0}, "
                f"now {tuple(xv.shape)}/{xv.dtype}"
            )
        if pack_fn is not None:
            xv = pack_fn(xv)
        window.reserve(timeout=None)
        try:
            yv = ring(xv)
            part = ctx.fused.part(
                poll_fn=_poll_dispatched, extra_state={"y": yv}, name="sched-isend"
            )
            window.register(part, value=yv)
        except BaseException:
            window.unreserve()
            raise
        if out is not None:
            ctx.outputs[out] = yv

    schedule.add_op("isend_enqueue", issue, parts=1, label=f"isend+{dest_offset}")
    return y, req


def wait_enqueue(req: EnqueuedRequest, engine: Optional[ProgressEngine] = None) -> None:
    """``MPIX_Wait_enqueue``."""
    (engine or req.engine or default_engine()).wait(req.grequest)


# ----------------------------------------------------------------------
# Depth-N in-flight windows
# ----------------------------------------------------------------------


@dataclass
class WindowSlot:
    """One admitted transfer. ``completion_index`` is assigned the moment
    the request completes — the window's global completion order, which is
    NOT issue order: slot 3 may carry completion_index 0."""

    request: GeneralizedRequest
    issue_index: int
    value: object = None
    token: Optional[Token] = None
    completion_index: Optional[int] = None

    @property
    def done(self) -> bool:
        return self.request.done


class OffloadWindow:
    """Bounded in-flight window over one stream's enqueued transfers.

    Admits up to ``depth`` *incomplete* transfers. ``reserve`` (the
    backpressure point, called by ``send_enqueue``/``isend_enqueue`` with
    ``window=``) blocks while the window is full by parking on the
    progress engine's per-channel wait queue for the stream's channel —
    request completion notifies exactly the waiters it satisfies, so a
    parked issuer wakes immediately and bystanders on the same stripe
    stay asleep; there is no busy-spin. If no progress thread covers the
    channel, the window is its own poller: it blocks in
    ``engine.wait_any`` over its in-flight requests, which actively
    progresses the stream and returns at the first completion.

    Completions are tracked in **completion order**: whichever transfer
    lands first is reapable first, regardless of issue order — a late
    arrival never holds up earlier ones. ``reap`` drains completed slots;
    ``wait_all`` drains the whole window (one batched ``MPI_Waitall``
    through the engine).

    The window is transport-agnostic: any
    :class:`~repro.core.progress.GeneralizedRequest` can be admitted, so
    checkpoint saves and reshard reads reuse the same backpressure (see
    ``checkpoint.manager`` / ``ft.elastic``).
    """

    def __init__(
        self,
        stream: Union[MPIXStream, StreamComm] = STREAM_NULL,
        depth: int = 2,
        engine: Optional[ProgressEngine] = None,
        adaptive: bool = False,
        min_depth: int = 1,
        max_depth: Optional[int] = None,
        adapt_every: int = 8,
        name: str = "window",
    ):
        if isinstance(stream, StreamComm):
            stream = stream.stream
        if depth < 1:
            raise ValueError(f"OffloadWindow depth must be >= 1, got {depth}")
        self.stream = stream
        self.depth = depth
        self.engine = engine or default_engine()
        self.name = name
        # adaptive depth: every ``adapt_every`` reserves, grow by one while
        # issuers are hitting backpressure parks (completions are flowing
        # but the window is the bottleneck), shrink by one when the window
        # sat idle — the high-water in-flight count since the last
        # adjustment never reached the current depth and nobody parked.
        # Bounds: [min_depth, max_depth]; max_depth defaults to 4× the
        # starting depth. Shrinking never cancels in-flight work — depth
        # only gates NEW admissions.
        self.adaptive = adaptive
        self.min_depth = max(1, min_depth)
        self.max_depth = max_depth if max_depth is not None else depth * 4
        self.adapt_every = max(1, adapt_every)
        self._lock = threading.Lock()
        self._issue_seq = itertools.count()
        self._completion_seq = itertools.count()
        self._in_flight: Dict[int, WindowSlot] = {}
        self._reserved = 0  # slots claimed by reserve() awaiting register()
        self._completed: deque = deque()  # completion order
        self._admitted = 0
        self._reaped = 0
        self._parks = 0
        self._max_depth_seen = 0
        self._reserves = 0
        self._parks_at_adjust = 0
        self._max_inflight_since = 0
        self._grows = 0
        self._shrinks = 0

    # -- admission (the backpressure point) -----------------------------
    def _free_slots(self) -> int:
        with self._lock:
            return self.depth - len(self._in_flight) - self._reserved

    def reserve(self, timeout: Optional[float] = None) -> bool:
        """Claim one window slot, blocking while ``depth`` transfers are
        incomplete. With a progress thread covering the stream the caller
        parks on the channel's wait queue (woken by any completion);
        without one the window is **its own poller** and blocks in
        ``engine.wait_any`` over its in-flight requests — the engine
        actively progresses the stream and returns at the *first*
        completion, instead of slicing short CV parks between sweeps.
        Never busy-spins; returns False only on timeout. Call before
        dispatching, then :meth:`register` the request — or use
        :meth:`admit` when the request already exists."""
        deadline = None if timeout is None else time.monotonic() + timeout
        ch = self.stream.channel
        grew = False
        if self.adaptive:
            with self._lock:
                self._reserves += 1
                if self._reserves % self.adapt_every == 0:
                    grew = self._adjust_depth_locked()
            if grew:
                # wider window → slots exist now; wake parked reservers
                self.engine.notify_channel(ch)
        while True:
            with self._lock:
                if self.depth - len(self._in_flight) - self._reserved > 0:
                    self._reserved += 1
                    return True
                inflight = [s.request for s in self._in_flight.values() if not s.request.done]
            if deadline is not None and time.monotonic() >= deadline:
                return False
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            with self._lock:
                self._parks += 1
            if self.engine.has_poller(ch):
                # a progress thread retires our requests: park until a
                # completion wakes us (bounded slices so a poller that
                # stops mid-park can't strand us — the loop re-checks)
                slice_s = 0.05
                if remaining is not None:
                    slice_s = min(slice_s, remaining)
                self.engine.park_on_channel(ch, lambda: self._free_slots() > 0, slice_s)
            else:
                # nobody else polls this stream: we are our own poller.
                # wait_any progresses the stream and returns on the FIRST
                # completion (bounded so a reserve()-only full window — no
                # registered requests yet — still re-checks the deadline)
                if inflight:
                    slice_s = 0.25
                    if remaining is not None:
                        slice_s = min(slice_s, remaining)
                    self.engine.wait_any(inflight, slice_s)
                if self._free_slots() > 0:
                    continue
                # a completion may be recorded (slot freed) a beat after the
                # request flips done: absorb the race with a short park
                slice_s = _SELF_PROGRESS_PARK_S
                if remaining is not None:
                    slice_s = min(slice_s, remaining)
                self.engine.park_on_channel(ch, lambda: self._free_slots() > 0, slice_s)

    def _adjust_depth_locked(self) -> bool:
        """One adaptive step (caller holds ``_lock``). Returns True on a
        grow (the caller must notify the channel outside the lock)."""
        parks_since = self._parks - self._parks_at_adjust
        grew = False
        if parks_since > 0 and self.depth < self.max_depth:
            self.depth += 1
            self._grows += 1
            grew = True
        elif (
            parks_since == 0
            and self._max_inflight_since < self.depth
            and self.depth > self.min_depth
        ):
            self.depth -= 1
            self._shrinks += 1
        self._parks_at_adjust = self._parks
        self._max_inflight_since = 0
        return grew

    def unreserve(self) -> None:
        """Release a slot claimed by :meth:`reserve` without registering a
        request — the cleanup path when dispatch fails between the two
        (otherwise the slot would leak and eventually deadlock reserve).
        Wakes parked reservers."""
        with self._lock:
            if self._reserved <= 0:
                raise RuntimeError("unreserve() without a matching reserve()")
            self._reserved -= 1
        self.engine.notify_channel(self.stream.channel)

    def register(
        self,
        request: Union[GeneralizedRequest, EnqueuedRequest],
        value: object = None,
        token: Optional[Token] = None,
    ) -> WindowSlot:
        """Attach a dispatched request to a slot claimed by
        :meth:`reserve`. Completion (from any thread) assigns the slot its
        completion index, frees the window slot, and wakes parked
        reservers via the stripe CV."""
        if isinstance(request, EnqueuedRequest):
            if token is None:
                token = request.token
            request = request.grequest
        with self._lock:
            if self._reserved <= 0:
                raise RuntimeError("register() without a matching reserve()")
            self._reserved -= 1
            slot = WindowSlot(
                request=request, issue_index=next(self._issue_seq), value=value, token=token
            )
            self._in_flight[slot.issue_index] = slot
            self._admitted += 1
            depth_now = len(self._in_flight) + self._reserved
            if depth_now > self._max_depth_seen:
                self._max_depth_seen = depth_now
            if depth_now > self._max_inflight_since:
                self._max_inflight_since = depth_now
        request.add_done_callback(lambda _r, _s=slot: self._on_done(_s))
        return slot

    def admit(
        self,
        request: Union[GeneralizedRequest, EnqueuedRequest],
        value: object = None,
        token: Optional[Token] = None,
        timeout: Optional[float] = None,
    ) -> Optional[WindowSlot]:
        """``reserve`` + ``register`` for an already-dispatched request.
        Returns None on reserve timeout."""
        if not self.reserve(timeout):
            return None
        return self.register(request, value=value, token=token)

    @contextmanager
    def issue(self, timeout: Optional[float] = None):
        """The safe issue bracket: reserve a slot, yield a
        ``submit(request, value=None, token=None)`` callable for the
        dispatched work, and give the slot back if the body exits —
        normally or exceptionally — without submitting. Use this instead
        of hand-rolling reserve/register so a failed dispatch can never
        leak the slot (a leaked slot eventually deadlocks ``reserve``).

            with window.issue() as submit:
                y = dispatch_device_work()
                submit(dispatch_enqueue(y, ...), value=y)
        """
        if not self.reserve(timeout):
            raise TimeoutError(f"OffloadWindow({self.name}): reserve timed out")
        submitted: List[WindowSlot] = []

        def submit(request, value=None, token=None) -> WindowSlot:
            slot = self.register(request, value=value, token=token)
            submitted.append(slot)
            return slot

        try:
            yield submit
        finally:
            if not submitted:
                self.unreserve()

    def _on_done(self, slot: WindowSlot) -> None:
        with self._lock:
            if slot.completion_index is not None:
                return
            slot.completion_index = next(self._completion_seq)
            self._in_flight.pop(slot.issue_index, None)
            self._completed.append(slot)
        # free slot → wake reservers parked on the stream's stripe
        self.engine.notify_channel(self.stream.channel)

    # -- the reap side ---------------------------------------------------
    def reap(self) -> List[WindowSlot]:
        """Drain every completed slot, in **completion order** (the order
        transfers actually landed, not the order they were issued)."""
        with self._lock:
            out = list(self._completed)
            self._completed.clear()
            self._reaped += len(out)
        return out

    def in_flight(self) -> int:
        with self._lock:
            return len(self._in_flight)

    def wait_all(self, timeout: Optional[float] = None) -> bool:
        """Drain the window: one batched ``MPI_Waitall`` over every
        incomplete transfer (engine-side wait_fn batching + parking).
        Returns only after each of those transfers' completions has been
        *recorded* (completion index assigned, slot reapable) — a request
        flips done before its callbacks run, so waiting on doneness alone
        could let a reap race the recording thread."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            slots = list(self._in_flight.values())
        if not self.engine.wait_all([s.request for s in slots], timeout):
            return False
        remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
        return self.engine.park_on_channel(
            self.stream.channel,
            lambda: all(s.completion_index is not None for s in slots),
            remaining,
        )

    def drain(self, timeout: Optional[float] = None) -> List[WindowSlot]:
        """``wait_all`` then ``reap``: every remaining completion, in
        completion order. Raises on timeout (partial completions stay
        reapable)."""
        if not self.wait_all(timeout):
            raise TimeoutError(f"OffloadWindow({self.name}): drain timed out")
        return self.reap()

    # -- instrumentation -------------------------------------------------
    def stats(self, engine: bool = True) -> dict:
        """Window counters, with the engine's beside them (``engine=False``
        omits the engine block): ``admitted``/``reaped`` totals,
        ``backpressure_parks`` (reserve() park events), ``max_depth_seen``
        (high-water in-flight count), current ``in_flight`` and
        ``completed_unreaped``."""
        with self._lock:
            out = {
                "depth": self.depth,
                "admitted": self._admitted,
                "reaped": self._reaped,
                "backpressure_parks": self._parks,
                "max_depth_seen": self._max_depth_seen,
                "in_flight": len(self._in_flight),
                "completed_unreaped": len(self._completed),
                "adaptive": self.adaptive,
                "depth_grows": self._grows,
                "depth_shrinks": self._shrinks,
            }
        if engine:
            out["engine"] = self.engine.stats()
        return out
