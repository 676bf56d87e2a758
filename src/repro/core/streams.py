"""MPIX Streams for JAX (paper ext. 3).

An :class:`MPIXStream` is an *explicit execution context*: a named, serial
communication context that the runtime maps onto a dedicated channel
("VCI" in MPICH terms). On TPU there are no host-side network endpoints —
the adaptation (see docs/ARCHITECTURE.md §3) is:

* each stream owns a **channel id** drawn from a finite pool (mirroring
  MPICH's finite network endpoints: creation *fails* when the pool is
  exhausted, giving predictable performance);
* collectives tagged with different streams are lowered **independently**
  (disjoint tensor chunks / disjoint mesh axes, no false dependency), so
  XLA can schedule them concurrently — the analogue of lock-free parallel
  VCIs;
* ops on the *same* stream are serialized with explicit dependency
  tokens (``optimization_barrier``), preserving the stream's serial
  semantics;
* "offload" streams (``info={'type': 'cudaStream_t'|'tpu_stream'}``) may
  share channels, as in the paper ("for streams representing GPU streams,
  MPICH may reuse network endpoints") — their ordering comes from the
  device-side dataflow (the enqueue extension).

``StreamComm`` pairs a device mesh + axis subset with attached streams,
mirroring ``MPIX_Stream_comm_create[_multiplex]``.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_NUM_CHANNELS",
    "MPIXStream",
    "STREAM_NULL",
    "StreamPool",
    "default_pool",
    "stream_create",
    "stream_free",
    "StreamComm",
    "stream_comm_create",
    "stream_comm_create_multiplex",
    "comm_get_stream",
    "new_token",
    "token_join",
    "serialize_on",
    "info_set_hex",
]


# ----------------------------------------------------------------------
# Streams & the finite channel (VCI) pool
# ----------------------------------------------------------------------

#: Width of the channel space. The progress engine sizes its lock-stripe
#: table to this, so with the default pool every compute stream's channel
#: maps 1:1 onto its own stripe (no false lock sharing between streams).
DEFAULT_NUM_CHANNELS = 64


@dataclass(frozen=True)
class MPIXStream:
    """A local serial execution context (thread, host task, device queue)."""

    sid: int
    name: str
    kind: str = "compute"  # "compute" | "offload" | "null"
    channel: int = -1  # VCI index; -1 = implicit/shared
    info: Tuple[Tuple[str, str], ...] = ()

    @property
    def is_null(self) -> bool:
        return self.kind == "null"

    @property
    def is_offload(self) -> bool:
        return self.kind == "offload"


STREAM_NULL = MPIXStream(sid=-1, name="MPIX_STREAM_NULL", kind="null", channel=-1)


class StreamPool:
    """Finite pool of communication channels (MPICH VCIs).

    MPICH "will try to allocate distinct network endpoints for each new
    stream and return failure if it runs out" — we reproduce that contract
    so applications get predictable channel isolation.
    """

    def __init__(self, max_channels: int = DEFAULT_NUM_CHANNELS):
        self.max_channels = max_channels
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._free_channels = list(range(max_channels))[::-1]
        self._offload_rr = 0  # offload streams round-robin over channels
        self.live: Dict[int, MPIXStream] = {}

    def create(self, info: Optional[dict] = None, name: Optional[str] = None) -> MPIXStream:
        info = dict(info or {})
        kind = "offload" if info.get("type") in ("cudaStream_t", "hipStream_t", "tpu_stream") else "compute"
        with self._lock:
            sid = next(self._ids)
            if kind == "offload":
                # offload streams may share endpoints (async device ordering
                # makes isolation less critical — paper §Offloading)
                channel = self._offload_rr % self.max_channels
                self._offload_rr += 1
            else:
                if not self._free_channels:
                    raise RuntimeError(
                        "MPIX_Stream_create: out of communication channels "
                        f"(pool={self.max_channels}); free streams to reuse endpoints"
                    )
                channel = self._free_channels.pop()
            s = MPIXStream(
                sid=sid,
                name=name or f"stream{sid}",
                kind=kind,
                channel=channel,
                info=tuple(sorted((str(k), str(v)) for k, v in info.items())),
            )
            self.live[sid] = s
            return s

    def free(self, stream: MPIXStream) -> None:
        if stream.is_null:
            return
        with self._lock:
            if stream.sid not in self.live:
                raise RuntimeError("MPIX_Stream_free: stream already freed/unknown")
            del self.live[stream.sid]
            if stream.kind == "compute":
                self._free_channels.append(stream.channel)

    @property
    def n_live(self) -> int:
        return len(self.live)


_default_pool = StreamPool()


def default_pool() -> StreamPool:
    return _default_pool


def stream_create(info: Optional[dict] = None, name: Optional[str] = None, pool: Optional[StreamPool] = None) -> MPIXStream:
    """``MPIX_Stream_create``. ``info`` may carry an opaque device-stream
    handle set via :func:`info_set_hex`."""
    return (pool or _default_pool).create(info, name)


def stream_free(stream: MPIXStream, pool: Optional[StreamPool] = None) -> None:
    (pool or _default_pool).free(stream)


def info_set_hex(info: dict, key: str, value: bytes) -> dict:
    """``MPIX_Info_set_hex``: stash an opaque binary (e.g. a device-stream
    handle) into string-only info as hex."""
    info[key] = bytes(value).hex()
    return info


# ----------------------------------------------------------------------
# Stream communicators
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class StreamComm:
    """A communicator over a mesh-axis subset with local streams attached.

    ``axes`` is ordered major→minor; collectives over this comm flatten the
    axes (threadcomm-style). ``streams`` holds the attached local streams —
    one for single-stream comms, several for multiplex comms.
    """

    axes: Tuple[str, ...]
    streams: Tuple[MPIXStream, ...] = (STREAM_NULL,)
    mesh: object = None  # jax Mesh / AbstractMesh; optional (axis names suffice inside shard_map)

    def __post_init__(self):
        if not self.axes:
            raise ValueError("StreamComm needs at least one mesh axis")
        if not self.streams:
            raise ValueError("StreamComm needs at least one (possibly NULL) stream")

    # -- stream accessors ------------------------------------------------
    @property
    def stream(self) -> MPIXStream:
        return self.streams[0]

    @property
    def is_multiplex(self) -> bool:
        return len(self.streams) > 1

    @property
    def channel(self) -> int:
        return self.stream.channel

    # -- communicator geometry -------------------------------------------
    def size(self) -> int:
        if self.mesh is None:
            raise ValueError("size() needs a bound mesh")
        return int(jnp.prod(jnp.array([self.mesh.shape[a] for a in self.axes])))

    def axis_sizes(self) -> Tuple[int, ...]:
        return tuple(self.mesh.shape[a] for a in self.axes)

    def rank(self):
        """Flattened rank inside a shard_map region (traced value)."""
        r = jax.lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return r

    def with_axes(self, axes: Sequence[str]) -> "StreamComm":
        return StreamComm(tuple(axes), self.streams, self.mesh)


def stream_comm_create(mesh, axes: Sequence[str], stream: MPIXStream = STREAM_NULL) -> StreamComm:
    """``MPIX_Stream_comm_create``: collective over ``mesh[axes]`` with one
    local stream. A NULL stream reverts to conventional-communicator
    behaviour (implicit channel, global ordering)."""
    if isinstance(axes, str):
        axes = (axes,)
    return StreamComm(tuple(axes), (stream,), mesh)


def stream_comm_create_multiplex(mesh, axes: Sequence[str], streams: Sequence[MPIXStream]) -> StreamComm:
    """``MPIX_Stream_comm_create_multiplex``: several local streams; p2p ops
    then take source/dest stream indices (see collectives.stream_send)."""
    if isinstance(axes, str):
        axes = (axes,)
    return StreamComm(tuple(axes), tuple(streams), mesh)


def comm_get_stream(comm: StreamComm, idx: int = 0) -> MPIXStream:
    """``MPIX_Comm_get_stream``."""
    return comm.streams[idx]


# ----------------------------------------------------------------------
# Tokens: serial semantics within a stream, independence across streams
# ----------------------------------------------------------------------


def new_token():
    """A fresh dependency token (device scalar). Ops on the same stream are
    chained through their token; ops on different streams get different
    tokens and may execute concurrently. float32 so the token stays an
    ordinary zero under AD (an int token's float0 cotangent breaks older
    shard_map transpose spec checks)."""
    return jnp.zeros((), dtype=jnp.float32)


def token_join(*tokens):
    """Merge tokens (e.g. before a joint synchronization point)."""
    out = tokens[0]
    for t in tokens[1:]:
        out = out + t  # cheap, keeps dataflow edges to all inputs
    return out


def serialize_on(token, *arrays):
    """Tie ``arrays`` to ``token``: none of them may be reordered before the
    op that produced the token. Returns (new_token, arrays).

    Uses ``lax.optimization_barrier`` — the XLA-native way to impose
    ordering without data dependence (the TPU analogue of issuing on a
    serial stream context). It differentiates as the identity, so
    device-ordered sends stay differentiable (pipeline backward = AD
    transpose of the forward's enqueued ops).
    """
    sealed = jax.lax.optimization_barrier((token, *arrays))
    return sealed[0], sealed[1:]
