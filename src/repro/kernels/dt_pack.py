"""Datatype pack/unpack — Pallas TPU kernel (the MPI datatype engine's
hot loop, TPU-blocked).

The classic MPI datatype engine gathers strided segments into a
contiguous send buffer (pack) and scatters back (unpack). On CPU that's a
memcpy loop; the TPU adaptation streams HBM→VMEM tiles of the strided
source and writes dense tiles — bandwidth-bound, zero compute, and the
natural consumer of ``datatype.pack_info()``'s uniform fast path (the
irregular path stays on the host iovec engine).

Source viewed as (nseg, stride) elements; output (nseg, seg_len):
out[i, :] = src[i, :seg_len]. Block over segments so VMEM holds
(block_seg × stride) elements.

Blocking is two-level: the block size is chosen purely by VMEM budget
(no search for a divisor of nseg) — the divisible prefix runs on the
blocked grid and the remainder segments run as one tail block. The old
single-level path shrank the block until it divided nseg, which
degenerated to block=1 (one grid step per segment) for prime nseg.

The TPU compiler accepts a block whose second-to-last dim is a multiple
of the dtype's sublane tiling (8 rows of 32-bit, 16 of 16-bit, 32 of
8-bit) or the array's full extent. So the blocked prefix uses a multiple
of that tiling, and the tail block spans its whole (sliced) array. The
last dim of every block is the array's full width, which is always
accepted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pack_kernel", "dt_pack", "dt_unpack"]


def pack_kernel(src_ref, out_ref, *, seg_len):
    out_ref[...] = src_ref[:, :seg_len]


def unpack_kernel(packed_ref, out_ref, *, seg_len):
    if seg_len == out_ref.shape[1]:  # dense: no gaps to zero
        out_ref[...] = packed_ref[...]
        return
    pad = jnp.zeros((packed_ref.shape[0], out_ref.shape[1] - seg_len), out_ref.dtype)
    out_ref[...] = jnp.concatenate([packed_ref[...], pad], axis=1)


#: Bytes of VMEM that one grid step's blocks may take, input and output
#: each double-buffered by the pipeline: half of v5e's 16 MiB default
#: scoped-VMEM limit.
VMEM_BUDGET = 8 << 20


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublane, 128-lane) VMEM tile for this element size."""
    return max(8, 32 // itemsize)


def _block_segs(nseg: int, stride: int, seg_len: int, itemsize: int) -> int:
    """Segments per grid step: the whole array when it fits the budget,
    else the largest multiple of the sublane tiling that does (at least
    one tile). Rows are counted at their lane-padded VMEM width."""
    lanes = lambda n: -(-n // 128) * 128
    per_seg = 2 * (lanes(stride) + lanes(seg_len)) * itemsize
    fit = VMEM_BUDGET // per_seg
    if fit >= nseg:
        return nseg
    sub = _sublanes(itemsize)
    return max(sub, fit // sub * sub)


def _pack_call(src, seg_len: int, bs: int, interpret: bool):
    nseg, stride = src.shape
    kernel = functools.partial(pack_kernel, seg_len=seg_len)
    return pl.pallas_call(
        kernel,
        grid=(nseg // bs,),
        in_specs=[pl.BlockSpec((bs, stride), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bs, seg_len), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nseg, seg_len), src.dtype),
        interpret=interpret,
    )(src)


def dt_pack(src, seg_len: int, *, interpret: Optional[bool] = None):
    """src (nseg, stride) → (nseg, seg_len): gather strided segments.
    ``interpret`` follows :func:`repro.kernels.ops.resolve_interpret`."""
    from repro.kernels.ops import resolve_interpret  # deferred: ops imports this module

    interpret = resolve_interpret(interpret)
    nseg, stride = src.shape
    assert seg_len <= stride
    bs = _block_segs(nseg, stride, seg_len, src.dtype.itemsize)
    main = (nseg // bs) * bs
    if main == nseg:
        return _pack_call(src, seg_len, bs, interpret)
    parts = []
    if main:
        parts.append(_pack_call(src[:main], seg_len, bs, interpret))
    parts.append(_pack_call(src[main:], seg_len, nseg - main, interpret))
    return jnp.concatenate(parts, axis=0)


def _unpack_call(packed, stride: int, bs: int, interpret: bool):
    nseg, seg_len = packed.shape
    kernel = functools.partial(unpack_kernel, seg_len=seg_len)
    return pl.pallas_call(
        kernel,
        grid=(nseg // bs,),
        in_specs=[pl.BlockSpec((bs, seg_len), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bs, stride), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nseg, stride), packed.dtype),
        interpret=interpret,
    )(packed)


def dt_unpack(packed, stride: int, *, interpret: Optional[bool] = None):
    """packed (nseg, seg_len) → (nseg, stride): scatter back (gaps zeroed).
    ``interpret`` follows :func:`repro.kernels.ops.resolve_interpret`."""
    from repro.kernels.ops import resolve_interpret  # deferred: ops imports this module

    interpret = resolve_interpret(interpret)
    nseg, seg_len = packed.shape
    assert seg_len <= stride
    bs = _block_segs(nseg, stride, seg_len, packed.dtype.itemsize)
    main = (nseg // bs) * bs
    if main == nseg:
        return _unpack_call(packed, stride, bs, interpret)
    parts = []
    if main:
        parts.append(_unpack_call(packed[:main], stride, bs, interpret))
    parts.append(_unpack_call(packed[main:], stride, nseg - main, interpret))
    return jnp.concatenate(parts, axis=0)
