"""Blocked causal (flash) attention — Pallas TPU kernel.

Motivation (from the dry-run roofline): the XLA einsum path materializes
the (S, S) logits in fp32, which makes long-sequence cells memory-bound
(e.g. whisper-tiny train: most HBM traffic is attention logits). This
kernel streams K/V blocks through VMEM with an online softmax — O(S·d)
HBM traffic instead of O(S²).

Layout: q/k/v (BH, S, d) with GQA group folding done in ops.py.
Grid = (BH, nQ, nK); the last grid dim iterates sequentially on TPU, so
the fp32 (m, l, acc) scratch carries across K blocks. Causal blocks above
the diagonal are skipped via pl.when (no MXU work for them).

Default blocks (128, 128): q/k/v tiles and the 128×128 logit tile are
MXU-shaped and fit VMEM for d ≤ 256 ((3·128·d + 128·128)·4B ≈ 460 KiB at
d = 256, well under the ~16 MiB/core VMEM budget).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention"]

NEG_INF = -1e30


def flash_attention_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, block_q, block_k, causal
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal
    run = ((qi + 1) * block_q > ki * block_k) if causal else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """q/k/v (BH, S, d) — pre-expanded heads (see ops.gqa_flash).

    ``interpret`` follows :func:`repro.kernels.ops.resolve_interpret`.
    """
    from repro.kernels.ops import resolve_interpret  # deferred: ops imports this module

    BH, S, d = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (BH, S // block_q, S // block_k)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        flash_attention_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
