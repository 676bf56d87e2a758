"""Jamba (arXiv:2403.19887): hybrid Mamba/attention with MoE.

Block pattern of ``hybrid_period`` (8) layers: attention at position
``hybrid_attn_pos`` (4), Mamba elsewhere; MoE FFN at odd positions, dense
MLP at even ones. 32 layers = lax.scan over 4 such blocks.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    apply_norm,
    embed,
    embed_params,
    gqa_attention_decode,
    gqa_attention_full,
    gqa_params,
    logits_out,
    next_token_xent,
    norm_params,
    remat_wrap,
    split_keys,
    swiglu,
    swiglu_params,
)
from repro.models.config import ModelConfig

__all__ = [
    "init_jamba",
    "jamba_loss",
    "init_cache",
    "jamba_prefill",
    "jamba_decode_step",
    "block_layout",
]


def block_layout(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """[(mixer, ffn)] for one period: mixer ∈ {attn, mamba}, ffn ∈ {moe, mlp}."""
    out = []
    for i in range(cfg.hybrid_period):
        mixer = "attn" if i == cfg.hybrid_attn_pos else "mamba"
        ffn = "moe" if (cfg.moe.enabled and i % cfg.hybrid_moe_every == 1) else "mlp"
        out.append((mixer, ffn))
    return out


def n_blocks(cfg: ModelConfig) -> int:
    assert cfg.n_layers % cfg.hybrid_period == 0
    return cfg.n_layers // cfg.hybrid_period


def _init_position(cfg: ModelConfig, mixer: str, ffn: str, key):
    ks = split_keys(key, 4)
    p = {"ln1": norm_params(cfg, ks[0]), "ln2": norm_params(cfg, ks[1])}
    p["mixer"] = gqa_params(cfg, ks[2]) if mixer == "attn" else mamba_mod.mamba_params(cfg, ks[2])
    p["ffn"] = moe_mod.moe_params(cfg, ks[3]) if ffn == "moe" else swiglu_params(cfg, ks[3])
    return p


def init_jamba(cfg: ModelConfig, key):
    layout = block_layout(cfg)
    nb = n_blocks(cfg)
    ks = split_keys(key, 2 + len(layout))
    positions = []
    for pi, (mixer, ffn) in enumerate(layout):
        lkeys = jax.random.split(ks[2 + pi], nb)
        positions.append(jax.vmap(lambda k, m=mixer, f=ffn: _init_position(cfg, m, f, k))(lkeys))
    return {
        "embed": embed_params(cfg, ks[0]),
        "final_norm": norm_params(cfg, ks[1]),
        "blocks": positions,  # list per period-position, stacked over blocks
    }


# -- cache ---------------------------------------------------------------


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    layout = block_layout(cfg)
    nb = n_blocks(cfg)
    D = cfg.n_kv_heads * cfg.resolved_head_dim  # flat: see gqa_attention_decode
    entries = []
    for mixer, _ in layout:
        if mixer == "attn":
            one = (jnp.zeros((B, max_len, D), cfg.cdtype), jnp.zeros((B, max_len, D), cfg.cdtype))
        else:
            one = mamba_mod.mamba_init_state(cfg, B)
        entries.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (nb,) + a.shape).copy(), one))
    return entries


# -- forward ---------------------------------------------------------------


def _apply_position_full(cfg, mixer, ffn, lp, x, positions, st, train: bool = False):
    h = apply_norm(cfg, lp["ln1"], x)
    if mixer == "attn":
        a, st2 = gqa_attention_full(cfg, lp["mixer"], h, positions, theta=cfg.rope_theta)
        st2 = tuple(t.reshape(t.shape[:2] + (-1,)) for t in st2)  # the cache's flat leaf
    else:
        a, st2 = mamba_mod.mamba_full(cfg, lp["mixer"], h, st)
    x = x + a
    h = apply_norm(cfg, lp["ln2"], x)
    if ffn == "moe":
        f, aux = moe_mod.moe_apply(cfg, lp["ffn"], h, train=train)
    else:
        f, aux = swiglu(cfg, lp["ffn"], h), jnp.float32(0)
    return x + f, aux, st2


def _apply_position_decode(cfg, mixer, ffn, lp, x, cache, l, pos):
    """Decode block ``l`` at one period position; ``cache`` is that
    position's entry stacked over blocks, returned updated."""
    h = apply_norm(cfg, lp["ln1"], x)
    if mixer == "attn":
        a, cache = gqa_attention_decode(cfg, lp["mixer"], h, cache, l, pos, theta=cfg.rope_theta)
    else:
        one = jax.tree.map(lambda c: c[l], cache)
        a, one = mamba_mod.mamba_decode(cfg, lp["mixer"], h, one)
        cache = jax.tree.map(lambda c, o: c.at[l].set(o), cache, one)
    x = x + a
    h = apply_norm(cfg, lp["ln2"], x)
    f = moe_mod.moe_apply(cfg, lp["ffn"], h)[0] if ffn == "moe" else swiglu(cfg, lp["ffn"], h)
    return x + f, cache


def _forward(cfg: ModelConfig, params, tokens, cache=None, train=False):
    layout = block_layout(cfg)
    B, S = tokens.shape
    x = embed(cfg, params["embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cache is None:
        cache = init_cache(cfg, B, S)

    def block(lps_caches, carry):
        x, aux = carry
        lps, caches = lps_caches
        new_entries = []
        for (mixer, ffn), lp, cv in zip(layout, lps, caches):
            x, a, cv2 = _apply_position_full(cfg, mixer, ffn, lp, x, positions, cv, train=train)
            aux = aux + a
            new_entries.append(cv2)
        return (x, aux), tuple(new_entries)

    wrapped = remat_wrap(cfg, block)

    def scan_body(carry, xs):
        return wrapped(xs, carry)

    (x, aux), new_cache = lax.scan(scan_body, (x, jnp.float32(0)), (params["blocks"], tuple(cache)))
    x = apply_norm(cfg, params["final_norm"], x)
    return logits_out(cfg, params["embed"], x), aux, list(new_cache)


def jamba_loss(cfg: ModelConfig, params, batch):
    logits, aux, _ = _forward(cfg, params, batch["tokens"], train=True)
    loss = next_token_xent(logits, batch["tokens"], batch.get("loss_mask"))
    total = loss + aux
    return total, {"xent": loss, "aux": aux, "loss": total}


def jamba_prefill(cfg: ModelConfig, params, batch, max_len=None):
    tokens = batch["tokens"]
    S = tokens.shape[1]
    max_len = max_len or S
    cache = init_cache(cfg, tokens.shape[0], max_len)
    # seed attention caches by running full forward at length S then padding
    logits, _, cache_s = _forward(cfg, params, tokens, cache=init_cache(cfg, tokens.shape[0], S))

    def fit(a, template):
        if a.shape == template.shape:
            return a
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, template.shape[2] - a.shape[2])
        return jnp.pad(a, pad)

    cache = jax.tree.map(fit, cache_s, cache)
    return logits[:, -1], cache


def jamba_decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One-token decode; the cache rides in the block scan's carry, so the
    attention position writes its new token into the stack in place."""
    layout = block_layout(cfg)
    x = embed(cfg, params["embed"], tokens[:, None])

    def scan_body(carry, xs):
        x, caches = carry
        lps, l = xs
        new_entries = []
        for (mixer, ffn), lp, cv in zip(layout, lps, caches):
            x, cv = _apply_position_decode(cfg, mixer, ffn, lp, x, cv, l, pos)
            new_entries.append(cv)
        return (x, tuple(new_entries)), None

    xs = (params["blocks"], jnp.arange(n_blocks(cfg)))
    (x, cache), _ = lax.scan(scan_body, (x, tuple(cache)), xs)
    x = apply_norm(cfg, params["final_norm"], x)
    return logits_out(cfg, params["embed"], x)[:, 0], list(cache)
