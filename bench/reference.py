"""The parts of the plain float32 references that every architecture
shares, written from the published conventions and not from the program
(nothing here imports ``repro``). Each architecture's forward pass is in
its module under ``bench/arch/``, found from the spec.

Every matrix product at ``Precision.HIGHEST``, softmax and norms in
float32. The conventions are the ones the configuration files state:
RMSNorm scale applied as ``(1 + w)``, rotary embedding on split halves
(``rotate_half``).

``quant=True`` is the control: the same model with both operands of every
linear layer rounded to float8 e4m3 (per-row scale for activations,
per-output-column scale for weights), the lower precision a later change
would be tempted to serve in.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def fake_quant(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``; the
    gradient passes straight through."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def linear(x, w, quant: bool):
    """x (..., k) @ w (k, n)."""
    if quant:
        x, w = fake_quant(x, -1), fake_quant(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rope(x, theta: float):
    """x (S, heads, hd), positions 0..S-1, rotation on split halves."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ----------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# ----------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0,))
def served_gaps(s, w, tokens, chosen):
    """tokens (L,), chosen (L,): the gap by which ``chosen[i]``'s logit at
    position i lies below the reference's best there."""
    ref = arch.of(s).forward(s, w, tokens)
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


@partial(jax.jit, static_argnums=(0,))
def control_gaps(s, w, tokens):
    """The gap, in the float32 reference, of the token the float8 control
    puts first at each position."""
    ref = arch.of(s).forward(s, w, tokens)
    ctl = arch.of(s).forward(s, w, tokens, quant=True)
    chosen = jnp.argmax(ctl, axis=-1)
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


# ----------------------------------------------------------------------
# training: loss, gradients and AdamW
# ----------------------------------------------------------------------


def xent(s, w, rows, quant: bool = False):
    """Mean next-token cross-entropy over every row of ``rows`` (B, S)."""

    def one(t):
        logits = arch.of(s).forward(s, w, t, quant=quant, remat=True)
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, t[1:, None], axis=-1))

    return jnp.mean(jax.lax.map(one, rows))


@partial(jax.jit, static_argnums=(0, 3))
def loss_and_grad(s, w32, rows, quant: bool = False):
    return jax.value_and_grad(lambda p: xent(s, p, rows, quant))(w32)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def lr_at(opt: dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    t = np.clip((count - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + np.cos(np.pi * t))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, g):
    return jax.tree.map(jnp.add, total, g)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3, 4))
def _adamw(opt_items, grads, m, v, w, n_micro, count, lr):
    """One AdamW step on the mean of ``n_micro`` summed microbatch
    gradients; returns the new moments and weights and the clipped
    gradient the update used."""
    opt = dict(opt_items)
    grads = jax.tree.map(lambda g: g / n_micro, grads)
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-9))
    b1c, b2c = 1 - opt["b1"] ** count, 1 - opt["b2"] ** count
    clipped = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g, m, clipped)
    v = jax.tree.map(lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g, v, clipped)
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"]) + opt["weight_decay"] * w), w, m, v
    )
    return m, v, w, clipped


def train_steps(s, w, batches: Sequence[np.ndarray], opt: dict, micro: int, quant: bool = False,
                on_first=lambda clipped: clipped):
    """AdamW over ``batches`` from the weights ``w``, microbatches of
    ``micro`` rows. Returns the losses, ``on_first`` of the first step's
    clipped gradient, and the weights after the last step. Moments,
    weights and the gradient sum are updated in place, so the float32
    state takes four copies of the weights."""
    w32 = {k: jnp.asarray(a, jnp.float32) for k, a in w.items()}
    m = jax.tree.map(jnp.zeros_like, w32)
    v = jax.tree.map(jnp.zeros_like, w32)
    items = tuple(sorted((k, float(val)) for k, val in opt.items()))
    losses, first = [], None
    for i, b in enumerate(batches, start=1):
        rows = jnp.asarray(b)
        total, loss, n = None, 0.0, 0
        for j in range(0, rows.shape[0], micro):
            l, g = loss_and_grad(s, w32, rows[j : j + micro], quant)
            total = g if total is None else _accumulate(total, g)
            loss += float(l)
            n += 1
        m, v, w32, clipped = _adamw(items, total, m, v, w32, jnp.float32(n), jnp.float32(i), jnp.float32(lr_at(opt, i)))
        losses.append(loss / n)
        if first is None:
            first = on_first(clipped)
        del clipped
    return losses, first, w32
