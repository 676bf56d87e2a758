"""Jitted public wrappers around the Pallas kernels.

These are the integration points the model zoo calls (flash attention for
GQA layers, chunked WKV for RWKV-6, dt_pack for the checkpoint/comm
buffer path).

The kernel backend is decided here and nowhere else
(:func:`resolve_interpret`): a kernel runs in the Pallas interpreter only
when JAX's default backend is the CPU, and is compiled everywhere else.
Every ``interpret`` argument in :mod:`repro.kernels` defaults to None,
meaning "ask :func:`resolve_interpret`".
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dt_pack as _dtp
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _wkv
from repro.core import datatype as dt

__all__ = [
    "resolve_interpret",
    "gqa_flash_attention",
    "wkv6",
    "pack_datatype",
    "unpack_datatype",
]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one rule for running a Pallas kernel: ``None`` interprets on the
    CPU backend and compiles on any other. ``False`` always compiles (a
    test may lower for a described TPU from a CPU process). ``True`` off
    the CPU is refused: an accelerator must never silently run the
    interpreter."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend != "cpu":
        raise ValueError(
            f"interpret=True on the {backend!r} backend: Pallas kernels are "
            "compiled on accelerators (pass interpret=None)"
        )
    return bool(interpret)


@partial(jax.jit, static_argnames=("causal", "interpret", "block_q", "block_k"))
def gqa_flash_attention(q, k, v, causal=True, interpret=None, block_q=128, block_k=128):
    """q (B,S,nq,hd); k/v (B,S,nkv,hd) → (B,S,nq,hd). GQA via KV repeat at
    the head-folding level (no HBM copy on TPU: it lowers to a broadcast
    in the BlockSpec index map domain)."""
    B, S, nq, hd = q.shape
    nkv = k.shape[2]
    G = nq // nkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * nq, S, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * nq, S, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * nq, S, hd)
    o = _fa.flash_attention(
        qf, kf, vf, causal=causal, interpret=interpret, block_q=block_q, block_k=block_k
    )
    return o.reshape(B, nq, S, hd).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(w, r, k, v, bonus, state0, chunk=64, interpret=None):
    return _wkv.wkv6_chunked(w, r, k, v, bonus, state0, chunk=chunk, interpret=interpret)


def _kernel_info(dtype_descr: dt.Datatype, info, itemsize: int):
    """Resolve + validate the exact uniform descriptor for the dense
    kernel.  ``pack_info`` is structurally exact (a returned tuple proves
    segment i == disp0 + i*stride), so a non-None info can be trusted;
    layouts the (nseg, stride)-window kernel cannot express — descending
    or overlapping strides — are rejected with a clear redirect to the
    host engine rather than corrupting the window math."""
    if info is None:
        info = dt.pack_info(dtype_descr)
    if info is None:
        raise ValueError("irregular datatype: use core.datatype.pack/unpack (host path)")
    nseg, seg_bytes, stride_bytes, disp = info
    if nseg > 1 and stride_bytes < seg_bytes:
        raise ValueError(
            "uniform layout with descending/overlapping stride "
            f"(stride {stride_bytes} < segment {seg_bytes}): use the host path"
        )
    if disp < 0:
        raise ValueError("negative displacement (lb < 0): use the host path, which rebases")
    if seg_bytes % itemsize or stride_bytes % itemsize or disp % itemsize:
        raise ValueError(
            f"descriptor bytes ({seg_bytes}/{stride_bytes}/{disp}) not divisible "
            f"by element size {itemsize}"
        )
    return nseg, seg_bytes, stride_bytes, disp


def pack_datatype(
    buf_flat, dtype_descr: dt.Datatype, *, info=None, interpret: Optional[bool] = None
):
    """Pack a uniform-strided datatype from a flat element buffer using the
    Pallas kernel; raises on irregular layouts (host iovec path covers
    those — see core.datatype.pack).  ``info`` accepts a precomputed
    ``pack_info`` tuple so batch callers resolve the descriptor once."""
    nseg, seg_bytes, stride_bytes, disp = _kernel_info(
        dtype_descr, info, buf_flat.dtype.itemsize
    )
    item = buf_flat.dtype.itemsize
    seg_len = seg_bytes // item
    if nseg == 1:
        return jax.lax.dynamic_slice(buf_flat, (disp // item,), (seg_len,))
    stride = stride_bytes // item
    start = disp // item
    window = jax.lax.dynamic_slice(buf_flat, (start,), ((nseg - 1) * stride + seg_len,))
    pad = jnp.zeros((nseg * stride - window.shape[0],), buf_flat.dtype)
    src = jnp.concatenate([window, pad]).reshape(nseg, stride)
    return _dtp.dt_pack(src, seg_len, interpret=interpret).reshape(-1)


def unpack_datatype(
    packed_flat,
    dtype_descr: dt.Datatype,
    out_len: int,
    *,
    info=None,
    interpret: Optional[bool] = None,
):
    """Inverse of pack_datatype into a zeroed flat buffer of out_len elems."""
    nseg, seg_bytes, stride_bytes, disp = _kernel_info(
        dtype_descr, info, packed_flat.dtype.itemsize
    )
    item = packed_flat.dtype.itemsize
    seg_len = seg_bytes // item
    if nseg == 1:
        out = jnp.zeros((out_len,), packed_flat.dtype)
        return jax.lax.dynamic_update_slice(out, packed_flat, (disp // item,))
    stride = stride_bytes // item
    start = disp // item
    strided = _dtp.dt_unpack(packed_flat.reshape(nseg, seg_len), stride, interpret=interpret)
    flat = strided.reshape(-1)[: (nseg - 1) * stride + seg_len]
    out = jnp.zeros((out_len,), packed_flat.dtype)
    return jax.lax.dynamic_update_slice(out, flat, (start,))
