"""Shared neural building blocks (pure-JAX, functional, pytree params).

Conventions:
* params are nested dicts of jnp arrays; per-layer params are stacked on a
  leading axis and consumed by ``lax.scan`` (small HLO, fast AOT compile —
  essential for the 512-device dry-run);
* activations run in ``cfg.compute_dtype`` (bf16), softmax/norms in fp32;
* attention covers MHA/GQA, optional bias, optional sliding window, and
  both full-sequence (train/prefill) and single-token cached decode.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig

# ----------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.bfloat16):
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_params(cfg: ModelConfig, key, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), cfg.pdtype), "b": jnp.zeros((d,), cfg.pdtype)}
    return {"w": jnp.zeros((d,), cfg.pdtype)}


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"])


# ----------------------------------------------------------------------
# rotary position embedding (partial-dim capable, for MLA)
# ----------------------------------------------------------------------


def rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, n, hd) rotated over its full last dim; positions (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------


def attend(q, k, v, mask, scale: float):
    """q (B,S,nq,hd), k/v (B,T,nkv,hd), mask broadcastable to (B,nkv,G,S,T).

    GQA via head grouping; softmax in fp32.
    """
    B, S, nq, hd = q.shape
    T, nkv = k.shape[1], k.shape[2]
    G = nq // nkv
    qg = q.reshape(B, S, nkv, G, hd)
    logits = jnp.einsum("bsngh,btnh->bngst", qg, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v)
    return out.reshape(B, S, nq, hd)


def causal_mask(S: int, T: int, offset: int = 0, window: int = 0):
    """(S,T) mask: query s (absolute pos offset+s) sees keys t <= offset+s,
    and within ``window`` if window > 0."""
    qpos = offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (kpos > qpos - window)
    return m[None, None, None]  # (1,1,1,S,T)


def gqa_params(cfg: ModelConfig, key, theta_unused=None):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq * hd), dtype=cfg.pdtype),
        "wk": dense_init(ks[1], (d, nkv * hd), dtype=cfg.pdtype),
        "wv": dense_init(ks[2], (d, nkv * hd), dtype=cfg.pdtype),
        "wo": dense_init(ks[3], (nq * hd, d), dtype=cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((nkv * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((nkv * hd,), cfg.pdtype)
    return p


def gqa_qkv(cfg: ModelConfig, p, x, positions, theta: float, use_rope: bool = True):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"].astype(cfg.cdtype)
    k = x @ p["wk"].astype(cfg.cdtype)
    v = x @ p["wv"].astype(cfg.cdtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cfg.cdtype)
        k = k + p["bk"].astype(cfg.cdtype)
        v = v + p["bv"].astype(cfg.cdtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if use_rope:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def gqa_attention_full(
    cfg: ModelConfig,
    p,
    x,
    positions,
    window: int = 0,
    theta: float = 10_000.0,
    kv_override=None,
    causal: bool = True,
    use_rope: bool = True,
):
    """Full-sequence (train/prefill) self-attention. Returns (out, (k, v))
    so callers can seed a KV cache. ``kv_override`` supplies cross-attn
    K/V source."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    if kv_override is None:
        q, k, v = gqa_qkv(cfg, p, x, positions, theta, use_rope=use_rope)
        if (
            cfg.attn_impl == "flash"
            and causal
            and window == 0
            and S % 128 == 0
        ):
            # Pallas blocked attention: O(S·d) HBM traffic instead of the
            # einsum path's O(S²) logit materialization (see EXPERIMENTS
            # §Perf kernel notes). kernels.ops picks the backend.
            from repro.kernels.ops import gqa_flash_attention

            out = gqa_flash_attention(q, k, v, causal=True)
            out = out.reshape(B, S, cfg.n_heads * hd) @ p["wo"].astype(cfg.cdtype)
            return out, (k, v)
        if causal:
            mask = causal_mask(S, S, window=window)
        else:
            mask = jnp.ones((1, 1, 1, S, S), bool)
    else:
        q = (x @ p["wq"].astype(cfg.cdtype)).reshape(B, S, cfg.n_heads, hd)
        if cfg.qkv_bias and "bq" in p:
            q = q + p["bq"].astype(cfg.cdtype).reshape(cfg.n_heads, hd)
        k, v = kv_override
        mask = jnp.ones((1, 1, 1, S, k.shape[1]), bool)
    out = attend(q, k, v, mask, 1.0 / math.sqrt(hd))
    out = out.reshape(B, S, cfg.n_heads * hd) @ p["wo"].astype(cfg.cdtype)
    return out, (k, v)


def gqa_attention_decode(
    cfg: ModelConfig,
    p,
    x,
    cache_kv,
    layer,
    pos,
    window: int = 0,
    theta: float = 10_000.0,
    use_rope: bool = True,
):
    """One-token decode against a layer-stacked cache, written in place.

    x (B,1,d); cache_kv = (K, V), each (L, B, T, nkv*hd): every kv head of
    a position side by side on the minor axis, so the leaf keeps its
    natural layout on the chip (hd alone is narrower than a lane tile).
    ``layer`` indexes axis 0; pos (B,) is the new token's absolute position.
    Only the B new rows are written, at [layer, b, slot_b] (slot = pos, or
    pos % T on a ring when window > 0, T == window), and attention reads
    K[layer] / V[layer] in the flat layout: the head split is a 0/1
    indicator E (nkv*hd, nkv) applied to q and to the read-out, never a
    reshape of the cache. Callers carry the stack through their layer
    scan, so no layer of it is sliced out and written back.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    nkv = cfg.n_kv_heads
    G = cfg.n_heads // nkv
    q, k_new, v_new = gqa_qkv(cfg, p, x, pos[:, None], theta, use_rope=use_rope)
    K, V = cache_kv
    T = K.shape[2]
    slot = pos % T if window > 0 else pos
    bidx = jnp.arange(B)
    K = K.at[layer, bidx, slot].set(k_new.reshape(B, nkv * hd).astype(K.dtype))
    V = V.at[layer, bidx, slot].set(v_new.reshape(B, nkv * hd).astype(V.dtype))
    # group member g of every kv head side by side: qg[b, g] lines up with a cache row
    qg = q.reshape(B, nkv, G, hd).transpose(0, 2, 1, 3).reshape(B, G, nkv * hd)
    E = (jnp.arange(nkv * hd)[:, None] // hd == jnp.arange(nkv)[None, :]).astype(cfg.cdtype)
    qe = qg[..., None] * E  # (B, G, nkv*hd, nkv): exact, one nonzero per row
    scores = jnp.einsum("btd,bgdn->bgnt", K[layer], qe, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd))
    # slot t holds a position <= pos once written; on a ring every slot is
    # written (and within the window) from pos = T-1 on, so one test serves both
    mask = (jnp.arange(T)[None, :] <= pos[:, None])[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1).astype(cfg.cdtype)
    o = jnp.einsum("bgnt,btd->bgnd", probs, V[layer], preferred_element_type=jnp.float32)
    out = (o * E.T).sum(axis=2).astype(cfg.cdtype)  # each row keeps its own head
    out = out.reshape(B, G, nkv, hd).transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"].astype(cfg.cdtype), (K, V)


# ----------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------


def swiglu_params(cfg: ModelConfig, key, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], (cfg.d_model, d_ff), dtype=cfg.pdtype),
        "w_up": dense_init(ks[1], (cfg.d_model, d_ff), dtype=cfg.pdtype),
        "w_down": dense_init(ks[2], (d_ff, cfg.d_model), dtype=cfg.pdtype),
    }


def swiglu(cfg: ModelConfig, p, x):
    g = jax.nn.silu(x @ p["w_gate"].astype(cfg.cdtype))
    u = x @ p["w_up"].astype(cfg.cdtype)
    return (g * u) @ p["w_down"].astype(cfg.cdtype)


def gelu_mlp_params(cfg: ModelConfig, key, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = split_keys(key, 2)
    return {
        "w_in": dense_init(ks[0], (cfg.d_model, d_ff), dtype=cfg.pdtype),
        "b_in": jnp.zeros((d_ff,), cfg.pdtype),
        "w_out": dense_init(ks[1], (d_ff, cfg.d_model), dtype=cfg.pdtype),
        "b_out": jnp.zeros((cfg.d_model,), cfg.pdtype),
    }


def gelu_mlp(cfg: ModelConfig, p, x):
    h = jax.nn.gelu(x @ p["w_in"].astype(cfg.cdtype) + p["b_in"].astype(cfg.cdtype))
    return h @ p["w_out"].astype(cfg.cdtype) + p["b_out"].astype(cfg.cdtype)


# ----------------------------------------------------------------------
# embeddings / logits / loss
# ----------------------------------------------------------------------


def embed_params(cfg: ModelConfig, key):
    ks = split_keys(key, 2)
    p = {"tok": dense_init(ks[0], (cfg.vocab, cfg.d_model), scale=0.02, dtype=cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["out"] = dense_init(ks[1], (cfg.d_model, cfg.vocab), dtype=cfg.pdtype)
    return p


def embed(cfg: ModelConfig, p, tokens):
    return p["tok"].astype(cfg.cdtype)[tokens]


def logits_out(cfg: ModelConfig, p, x):
    w = p["tok"].T if cfg.tie_embeddings else p["out"]
    return (x @ w.astype(cfg.cdtype)).astype(jnp.dtype(cfg.logit_dtype))


def next_token_xent(logits, tokens, mask=None):
    """Mean cross-entropy of logits[:, :-1] predicting tokens[:, 1:]."""
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return -ll.mean()


# ----------------------------------------------------------------------
# scan-over-layers helper with remat
# ----------------------------------------------------------------------


def remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    if cfg.remat == "save_acts":
        # save the post-collective sublayer outputs (tagged attn_out /
        # ffn_out) so the backward pass does NOT re-run the TP all-reduces
        # — trades ~2 saved activations/layer for 1/3 of collective bytes
        policy = jax.checkpoint_policies.save_only_these_names("attn_out", "ffn_out")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def tag_act(cfg: ModelConfig, x, name: str):
    """checkpoint_name + optional sequence-parallel sharding constraint on
    the (B, S, d) sublayer output (hillclimb knobs; no-ops by default)."""
    from jax.ad_checkpoint import checkpoint_name

    if cfg.seq_shard_acts and x.ndim == 3:
        from jax.sharding import PartitionSpec as _P

        try:
            x = jax.lax.with_sharding_constraint(x, _P(None, "model", None))
        except Exception:
            pass  # no mesh context (smoke tests) — constraint is advisory
    if cfg.remat == "save_acts":
        x = checkpoint_name(x, name)
    return x


def scan_layers(cfg: ModelConfig, body, x, stacked_params, *stacked_extra):
    """Run ``body(layer_params, x, *extra) -> (x, y)`` over stacked layers.

    Returns (x, stacked_ys). ``stacked_extra`` are additional per-layer
    inputs (e.g. KV caches); ys collect per-layer outputs (updated caches).
    """
    wrapped = remat_wrap(cfg, body)

    def scan_body(carry, layer_in):
        lp, *extra = layer_in
        out, y = wrapped(lp, carry, *extra)
        return out, y

    if cfg.scan_layers:
        return lax.scan(scan_body, x, (stacked_params, *stacked_extra))
    # unrolled fallback (debugging)
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], (stacked_params, *stacked_extra))
        x, y = wrapped(sl[0], x, *sl[1:])
        ys.append(y)
    stack = None
    if ys and ys[0] is not None:
        stack = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return x, stack


def stack_layer_params(init_one, key, n: int):
    """vmap an init function over layer keys → params stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)
