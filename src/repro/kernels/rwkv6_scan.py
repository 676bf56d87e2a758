"""RWKV-6 WKV recurrence — chunk-parallel Pallas TPU kernel.

The naive recurrence is one tiny (hs×hs) outer-product update per token —
hopeless on the MXU. The chunk-parallel form turns a CHUNK of tokens into
three MXU-shaped matmuls (the standard linear-attention chunking, adapted
to RWKV's per-channel data-dependent decay):

With cw_t = Σ_{i≤t} log w_i (per channel, within the chunk):

  intra-chunk:  scores[t,j] = Σ_i  r_t[i]·e^{cw_{t-1}[i]} · k_j[i]·e^{-cw_j[i]}   (j < t)
                + bonus diag:  scores[t,t] = Σ_i r_t[i]·u[i]·k_t[i]
                Y_intra = scores @ V
  cross-chunk:  Y_cross[t] = (r_t ⊙ e^{cw_{t-1}}) @ S_in
  state:        S_out = diag(e^{cw_last}) S_in + (k ⊙ e^{cw_last - cw})ᵀ @ V

Grid = (B·H, n_chunks); the chunk dim iterates sequentially so the (hs,
hs) fp32 state lives in VMEM scratch. exp() of NEGATIVE log-cumsums keeps
everything in (0, 1] — no underflow for chunk ≤ 128 at fp32.

ref.py holds the per-token oracle; tests sweep shapes/dtypes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_kernel", "wkv6_chunked"]


def wkv6_kernel(w_ref, r_ref, k_ref, v_ref, u_ref, o_ref, s_out_ref, state_scr, *, chunk):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    w = w_ref[0]  # (c, hs) decay in (0,1), fp32
    r = r_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    u = u_ref[0]  # (1, hs) bonus

    logw = jnp.log(jnp.maximum(w, 1e-38))
    cw = jnp.cumsum(logw, axis=0)  # (c, hs), ≤ 0
    cw_prev = cw - logw  # Σ_{i<t}
    cw_last = cw[-1:]  # (1, hs)

    r_dec = r * jnp.exp(cw_prev)  # r_t ⊙ e^{cw_{t-1}}  (≤ |r|, safe)
    k_rem = k * jnp.exp(cw_last - cw)  # decay j→chunk end (≤ |k|, safe)

    # intra-chunk scores via the EXACT log-difference (cw_{t-1} - cw_j ≤ 0
    # for j < t, so exp never overflows even under w → 0 strong decay —
    # the factored r_dec·k_decᵀ matmul form blows up as e^{-cw_j}):
    # scores[t,j] = Σ_i r[t,i]·k[j,i]·e^{cw_{t-1}[i] - cw[j,i]}
    D = cw_prev[:, None, :] - cw[None, :, :]  # (c, c, hs)
    t_idx3 = jax.lax.broadcasted_iota(jnp.int32, D.shape, 0)
    j_idx3 = jax.lax.broadcasted_iota(jnp.int32, D.shape, 1)
    D = jnp.where(j_idx3 < t_idx3, D, -jnp.inf)  # strictly lower triangle
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(D), axis=-1)  # (c, c)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (c,1) bonus term
    y = jax.lax.dot(scores, v) + diag * v  # intra-chunk + bonus
    y = y + jax.lax.dot(r_dec, state_scr[...])  # cross-chunk

    o_ref[0] = y.astype(o_ref.dtype)
    new_state = jnp.exp(cw_last).T * state_scr[...] + jax.lax.dot_general(
        k_rem, v, (((0,), (0,)), ((), ()))
    )  # (hs, hs)
    state_scr[...] = new_state

    @pl.when(ci == nc - 1)
    def _emit_state():
        s_out_ref[0] = new_state


def wkv6_chunked(
    w, r, k, v, bonus, state0, *, chunk: int = 64, interpret: Optional[bool] = None
):
    """w/r/k/v (B, S, H, hs) fp32; bonus (H, hs); state0 (B, H, hs, hs).

    Returns (y (B, S, H, hs) fp32, state (B, H, hs, hs)). Initial state is
    added outside the kernel (cheap) so the kernel scratch starts at zero:
    y += (r ⊙ e^{cw_prev + chunk offsets}) @ state0 — handled by folding
    state0 via a pre-pass below for exactness. ``interpret`` follows
    :func:`repro.kernels.ops.resolve_interpret`.
    """
    from repro.kernels.ops import resolve_interpret  # deferred: ops imports this module

    B, S, H, hs = w.shape
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, hs)
    wf, rf, kf, vf = fold(w), fold(r), fold(k), fold(v)
    uf = jnp.broadcast_to(bonus[None], (B, H, hs)).reshape(B * H, 1, hs)

    kernel = functools.partial(wkv6_kernel, chunk=chunk)
    y, s_last = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hs), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hs), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hs), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hs), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, hs), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hs), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, hs, hs), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hs), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(wf, rf, kf, vf, uf.reshape(B * H, 1, hs))

    # fold the initial state in exactly: the kernel computed with S_0 = 0;
    # linearity gives y += (r ⊙ e^{CW_{t-1}}) @ S0 and
    # S_last += diag(e^{CW_end}) S0, with CW the GLOBAL log-decay cumsum.
    logw = jnp.log(jnp.maximum(wf, 1e-38))
    CW = jnp.cumsum(logw, axis=1)
    CW_prev = CW - logw
    s0 = state0.reshape(B * H, hs, hs).astype(jnp.float32)
    y = y + jnp.einsum("nsh,nhj->nsj", rf * jnp.exp(CW_prev), s0)
    s_last = s_last + jnp.exp(CW[:, -1])[..., None] * s0
    unfold = lambda a: a.reshape(B, H, S, hs).transpose(0, 2, 1, 3)
    return unfold(y), s_last.reshape(B, H, hs, hs)
