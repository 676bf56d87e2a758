"""Whisper-tiny (arXiv:2212.04356): encoder-decoder audio transformer.

The conv frontend is a STUB per the assignment: ``input_specs()`` feeds
precomputed frame embeddings (B, n_audio_ctx, d_model) directly into the
encoder. LayerNorm everywhere, GELU MLPs, bias on QKV. Positions are
sinusoidal for the encoder (faithful) and sinusoidal for the decoder too
(adaptation: the real model's learned 448-entry table can't cover the
assigned 32k decode shapes — see docs/ARCHITECTURE.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import (
    apply_norm,
    embed,
    embed_params,
    gelu_mlp,
    gelu_mlp_params,
    gqa_attention_decode,
    gqa_attention_full,
    gqa_params,
    logits_out,
    next_token_xent,
    norm_params,
    remat_wrap,
    split_keys,
)
from repro.models.config import ModelConfig

__all__ = [
    "init_whisper",
    "whisper_loss",
    "init_cache",
    "whisper_prefill",
    "whisper_decode_step",
    "encode",
]


def sinusoids(length: int, channels: int):
    log_timescale = jnp.log(10_000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


def _enc_layer_params(cfg, key):
    ks = split_keys(key, 3)
    return {
        "ln1": norm_params(cfg, ks[0]),
        "attn": gqa_params(cfg, ks[1]),
        "ln2": norm_params(cfg, ks[2]),
        "mlp": gelu_mlp_params(cfg, ks[2]),
    }


def _dec_layer_params(cfg, key):
    ks = split_keys(key, 5)
    return {
        "ln1": norm_params(cfg, ks[0]),
        "attn": gqa_params(cfg, ks[1]),
        "lnx": norm_params(cfg, ks[2]),
        "xattn": gqa_params(cfg, ks[3]),
        "ln2": norm_params(cfg, ks[4]),
        "mlp": gelu_mlp_params(cfg, ks[4]),
    }


def init_whisper(cfg: ModelConfig, key):
    ks = split_keys(key, 5)
    ek = jax.random.split(ks[2], cfg.n_enc_layers)
    dk = jax.random.split(ks[3], cfg.n_layers)
    return {
        "embed": embed_params(cfg, ks[0]),
        "enc_layers": jax.vmap(lambda k: _enc_layer_params(cfg, k))(ek),
        "enc_ln_post": norm_params(cfg, ks[1]),
        "dec_layers": jax.vmap(lambda k: _dec_layer_params(cfg, k))(dk),
        "final_norm": norm_params(cfg, ks[4]),
    }


def encode(cfg: ModelConfig, params, frames):
    """frames (B, Se, d) — stub conv output. Returns encoder states."""
    B, Se, d = frames.shape
    x = frames.astype(cfg.cdtype) + sinusoids(Se, d).astype(cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(lp, x):
        h = apply_norm(cfg, lp["ln1"], x)
        a, _ = gqa_attention_full(cfg, lp["attn"], h, positions, causal=False, use_rope=False)
        x = x + a
        x = x + gelu_mlp(cfg, lp["mlp"], apply_norm(cfg, lp["ln2"], x))
        return x, None

    wrapped = remat_wrap(cfg, body)
    x, _ = lax.scan(lambda c, lp: wrapped(lp, c), x, params["enc_layers"])
    return apply_norm(cfg, params["enc_ln_post"], x)


def _cross_kv(cfg, lp, enc):
    B, Se, _ = enc.shape
    hd = cfg.resolved_head_dim
    k = (enc @ lp["xattn"]["wk"].astype(cfg.cdtype)).reshape(B, Se, cfg.n_kv_heads, hd)
    v = (enc @ lp["xattn"]["wv"].astype(cfg.cdtype)).reshape(B, Se, cfg.n_kv_heads, hd)
    if cfg.qkv_bias:
        k = k + lp["xattn"]["bk"].astype(cfg.cdtype).reshape(cfg.n_kv_heads, hd)
        v = v + lp["xattn"]["bv"].astype(cfg.cdtype).reshape(cfg.n_kv_heads, hd)
    return k, v


def _decode_full(cfg: ModelConfig, params, tokens, enc):
    B, S = tokens.shape
    d = cfg.d_model
    x = embed(cfg, params["embed"], tokens) + sinusoids(S, d).astype(cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(lp, x):
        h = apply_norm(cfg, lp["ln1"], x)
        a, kv = gqa_attention_full(cfg, lp["attn"], h, positions, causal=True, use_rope=False)
        x = x + a
        h = apply_norm(cfg, lp["lnx"], x)
        xkv = _cross_kv(cfg, lp, enc)
        a, _ = gqa_attention_full(cfg, lp["xattn"], h, positions, kv_override=xkv)
        x = x + a
        x = x + gelu_mlp(cfg, lp["mlp"], apply_norm(cfg, lp["ln2"], x))
        return x, (kv, xkv)

    wrapped = remat_wrap(cfg, body)
    x, seeds = lax.scan(lambda c, lp: wrapped(lp, c), x, params["dec_layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    return logits_out(cfg, params["embed"], x), seeds


def whisper_loss(cfg: ModelConfig, params, batch):
    enc = encode(cfg, params, batch["enc_frames"])
    logits, _ = _decode_full(cfg, params, batch["tokens"], enc)
    loss = next_token_xent(logits, batch["tokens"], batch.get("loss_mask"))
    return loss, {"xent": loss, "loss": loss}


# -- serving ---------------------------------------------------------------


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    """Self-attention K/V flat, (L, B, T, nkv*hd), written in place by
    decode (see gqa_attention_decode); cross-attention K/V keep their heads
    split, (L, B, n_audio_ctx, nkv, hd): prefill writes them once and the
    full-sequence attention reads them as they are."""
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    kv = lambda *tail: tuple(jnp.zeros((L, B) + tail, cfg.cdtype) for _ in range(2))
    return {"self": kv(max_len, cfg.n_kv_heads * hd), "cross": kv(cfg.n_audio_ctx, cfg.n_kv_heads, hd)}


def whisper_prefill(cfg: ModelConfig, params, batch, max_len=None):
    """Teacher-forced prefill over the prompt tokens + cross-KV from the
    encoder. Returns (last logits, cache)."""
    enc = encode(cfg, params, batch["enc_frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    logits, seeds = _decode_full(cfg, params, tokens, enc)
    (k_self, v_self), (k_x, v_x) = seeds

    def pad_to(a, T):
        a = a.reshape(a.shape[:3] + (-1,))  # the self cache's flat leaf
        if a.shape[2] == T:
            return a
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, T - a.shape[2])
        return jnp.pad(a, pad)

    cache = {
        "self": (pad_to(k_self, max_len), pad_to(v_self, max_len)),
        "cross": (k_x, v_x),
    }
    return logits[:, -1], cache


def whisper_decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    B = tokens.shape[0]
    d = cfg.d_model
    x = embed(cfg, params["embed"], tokens[:, None])
    # sinusoidal position for the current step
    half = d // 2
    log_timescale = jnp.log(10_000.0) / (half - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    x = x + pe.astype(cfg.cdtype)

    def body(carry, xs):
        x, kv = carry
        lp, l, (kx, vx) = xs
        h = apply_norm(cfg, lp["ln1"], x)
        a, kv = gqa_attention_decode(cfg, lp["attn"], h, kv, l, pos, use_rope=False)
        x = x + a
        h = apply_norm(cfg, lp["lnx"], x)
        a, _ = gqa_attention_full(cfg, lp["xattn"], h, None, kv_override=(kx, vx))
        x = x + a
        x = x + gelu_mlp(cfg, lp["mlp"], apply_norm(cfg, lp["ln2"], x))
        return (x, kv), None

    # the self cache rides in the carry (written in place); cross K/V are read-only
    xs = (params["dec_layers"], jnp.arange(cfg.n_layers), cache["cross"])
    (x, self_kv), _ = lax.scan(body, (x, tuple(cache["self"])), xs)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = logits_out(cfg, params["embed"], x)
    return logits[:, 0], {"self": self_kv, "cross": cache["cross"]}
