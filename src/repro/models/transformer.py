"""Unified decoder-only transformer LM.

Covers: dense GQA (llama3/internlm2/qwen), MoE (granite), MLA+MoE+MTP
(deepseek-v3), 5:1 local:global attention (gemma3), QKV bias (qwen), and
the VLM prefix mode (phi-3-vision backbone with stub patch embeddings).

Layers are organised into *groups* of a repeated block pattern so mixed
architectures still lower as ``lax.scan`` (small HLO for the 512-device
dry-run): gemma3 = scan over 5×(5 local + 1 global) + a tail of 4 locals;
deepseek = 3 dense layers + scan over 58 MoE layers.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    apply_norm,
    dense_init,
    embed,
    embed_params,
    gqa_attention_decode,
    gqa_attention_full,
    gqa_params,
    next_token_xent,
    norm_params,
    logits_out,
    remat_wrap,
    split_keys,
    swiglu,
    swiglu_params,
    tag_act,
)
from repro.models.config import ModelConfig

__all__ = [
    "LayerSpec",
    "layer_groups",
    "init_lm",
    "lm_loss",
    "lm_forward",
    "init_cache",
    "prefill",
    "decode_step",
]


@dataclass(frozen=True)
class LayerSpec:
    attn: str = "gqa"  # gqa | mla
    window: int = 0  # 0 = full attention
    theta: float = 10_000.0
    moe: bool = False
    d_ff: int = 0


def layer_groups(cfg: ModelConfig) -> List[Tuple[Tuple[LayerSpec, ...], int]]:
    """Return [(block_pattern, reps), ...] covering cfg.n_layers."""
    if cfg.mla is not None:
        groups = []
        kd = cfg.moe.first_k_dense
        if kd:
            dense_spec = LayerSpec("mla", 0, cfg.rope_theta, False, cfg.moe.dense_d_ff or cfg.d_ff)
            groups.append(((dense_spec,), kd))
        moe_spec = LayerSpec("mla", 0, cfg.rope_theta, True, cfg.d_ff)
        groups.append(((moe_spec,), cfg.n_layers - kd))
        return groups
    if cfg.local_global_pattern > 0:
        k = cfg.local_global_pattern
        local = LayerSpec("gqa", cfg.sliding_window, cfg.rope_theta_local, False, cfg.d_ff)
        glob = LayerSpec("gqa", 0, cfg.rope_theta, False, cfg.d_ff)
        pattern = (local,) * k + (glob,)
        reps = cfg.n_layers // (k + 1)
        groups = [(pattern, reps)] if reps else []
        tail = cfg.n_layers - reps * (k + 1)
        if tail:
            groups.append(((local,), tail))
        return groups
    spec = LayerSpec("gqa", 0, cfg.rope_theta, cfg.moe.enabled, cfg.d_ff)
    groups = []
    kd = cfg.moe.first_k_dense if cfg.moe.enabled else 0
    if kd:
        dense_spec = LayerSpec("gqa", 0, cfg.rope_theta, False, cfg.moe.dense_d_ff or cfg.d_ff)
        groups.append(((dense_spec,), kd))
    groups.append(((spec,), cfg.n_layers - kd))
    return groups


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, spec: LayerSpec, key):
    ks = split_keys(key, 4)
    p = {"ln1": norm_params(cfg, ks[0]), "ln2": norm_params(cfg, ks[1])}
    if spec.attn == "mla":
        p["attn"] = mla_mod.mla_params(cfg, ks[2])
    else:
        p["attn"] = gqa_params(cfg, ks[2])
    if spec.moe:
        p["ffn"] = moe_mod.moe_params(cfg, ks[3])
    else:
        p["ffn"] = swiglu_params(cfg, ks[3], d_ff=spec.d_ff or cfg.d_ff)
    return p


def init_lm(cfg: ModelConfig, key):
    groups = layer_groups(cfg)
    ks = split_keys(key, 4 + len(groups))
    params = {
        "embed": embed_params(cfg, ks[0]),
        "final_norm": norm_params(cfg, ks[1]),
        "groups": [],
    }
    for gi, (pattern, reps) in enumerate(groups):
        gkeys = split_keys(ks[2 + gi], len(pattern))
        stacked = []
        for pi, spec in enumerate(pattern):
            init_one = lambda k, spec=spec: _init_layer(cfg, spec, k)
            lkeys = jax.random.split(gkeys[pi], reps)
            stacked.append(jax.vmap(init_one)(lkeys))
        params["groups"].append(stacked)
    if cfg.vlm:
        params["img_proj"] = dense_init(ks[-2], (cfg.d_model, cfg.d_model), dtype=cfg.pdtype)
    if cfg.mtp_depth:
        mk = split_keys(ks[-1], 3)
        spec = layer_groups(cfg)[-1][0][0]
        params["mtp"] = {
            "proj": dense_init(mk[0], (2 * cfg.d_model, cfg.d_model), dtype=cfg.pdtype),
            "norm_h": norm_params(cfg, mk[1]),
            "norm_e": norm_params(cfg, mk[1]),
            "layer": _init_layer(cfg, spec, mk[2]),
        }
    return params


# ----------------------------------------------------------------------
# layer application
# ----------------------------------------------------------------------


def _constrain_layer(cfg, lp):
    """FSDP fix (hillclimb): re-constrain the scan-sliced layer params to
    their TP-only layout at block entry. The gathered copy is transient
    (freed after the layer), so XLA emits one small weight all-gather per
    layer instead of partial-summing activation-sized tensors over the
    fsdp axis — the measured 2.5× collective regression of pure-spec FSDP."""
    if not cfg.fsdp_gather_layers:
        return lp
    import jax as _jax
    from repro.parallel import sharding as _shd

    def one(path, leaf):
        pstr = _shd._path_str(path)
        spec = _shd._match_rule(pstr, leaf.ndim, None)  # rules are mesh-free
        try:
            return _jax.lax.with_sharding_constraint(leaf, spec)
        except Exception:
            return leaf

    return _jax.tree_util.tree_map_with_path(one, lp)


def _apply_layer_full(cfg, spec: LayerSpec, lp, x, positions, train: bool = False):
    lp = _constrain_layer(cfg, lp)
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.attn == "mla":
        a, seed = mla_mod.mla_full(cfg, lp["attn"], h, positions, spec.theta)
    else:
        a, seed = gqa_attention_full(cfg, lp["attn"], h, positions, window=spec.window, theta=spec.theta)
    a = tag_act(cfg, a, "attn_out")
    x = x + a
    h = apply_norm(cfg, lp["ln2"], x)
    if spec.moe:
        f, aux = moe_mod.moe_apply(cfg, lp["ffn"], h, train=train)
    else:
        f, aux = swiglu(cfg, lp["ffn"], h), jnp.float32(0)
    f = tag_act(cfg, f, "ffn_out")
    return x + f, aux, seed


def _apply_layer_decode(cfg, spec: LayerSpec, lp, x, cache, l, pos):
    """Decode layer ``l`` of a group; ``cache`` is this pattern position's
    entry stacked over the group's reps, returned updated."""
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.attn == "mla":
        one = jax.tree.map(lambda c: c[l], cache)
        a, one = mla_mod.mla_decode(cfg, lp["attn"], h, one, pos, spec.theta)
        cache = jax.tree.map(lambda c, o: c.at[l].set(o), cache, one)
    else:
        a, cache = gqa_attention_decode(cfg, lp["attn"], h, cache, l, pos, window=spec.window, theta=spec.theta)
    x = x + a
    h = apply_norm(cfg, lp["ln2"], x)
    if spec.moe:
        f, _ = moe_mod.moe_apply(cfg, lp["ffn"], h)
    else:
        f = swiglu(cfg, lp["ffn"], h)
    return x + f, cache


# ----------------------------------------------------------------------
# full forward (train / prefill)
# ----------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params, batch):
    tokens = batch["tokens"]
    x = embed(cfg, params["embed"], tokens)
    n_img = 0
    if cfg.vlm and "img_embeds" in batch:
        img = batch["img_embeds"].astype(cfg.cdtype) @ params["img_proj"].astype(cfg.cdtype)
        x = jnp.concatenate([img, x], axis=1)
        n_img = img.shape[1]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions, n_img


def lm_forward(cfg: ModelConfig, params, batch, collect_cache: bool = False, train: bool = False):
    """Returns (logits, aux_loss, cache_seeds|None, n_img, h_trunk). VLM
    prefix included in the sequence; logits cover the full sequence."""
    x, positions, n_img = _embed_inputs(cfg, params, batch)
    aux = jnp.float32(0)
    seeds: List = []
    for (pattern, reps), gp in zip(layer_groups(cfg), params["groups"]):

        def block(lps, carry):
            x, aux = carry
            block_seeds = []
            for spec, lp in zip(pattern, lps):
                x, a, seed = _apply_layer_full(cfg, spec, lp, x, positions, train=train)
                aux = aux + a
                block_seeds.append(seed if collect_cache else jnp.zeros((), cfg.cdtype))
            return (x, aux), tuple(block_seeds)

        wrapped = remat_wrap(cfg, block)

        def scan_body(carry, lps):
            return wrapped(lps, carry)

        (x, aux), g_seeds = lax.scan(scan_body, (x, aux), gp)
        seeds.append(g_seeds)
    h = x
    x = apply_norm(cfg, params["final_norm"], x)
    logits = logits_out(cfg, params["embed"], x)
    return logits, aux, (seeds if collect_cache else None), n_img, h


def _mtp_loss(cfg: ModelConfig, params, h_final, tokens):
    """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from the
    main trunk state at t combined with the embedding of token t+1."""
    mp = params["mtp"]
    B, S, d = h_final.shape
    h = apply_norm(cfg, mp["norm_h"], h_final[:, : S - 1])
    e = apply_norm(cfg, mp["norm_e"], embed(cfg, params["embed"], tokens[:, 1:]))
    z = jnp.concatenate([h, e], axis=-1) @ mp["proj"].astype(cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(S - 1, dtype=jnp.int32), (B, S - 1))
    spec = layer_groups(cfg)[-1][0][0]
    z, _, _ = _apply_layer_full(cfg, spec, mp["layer"], z, positions, train=True)
    logits = logits_out(cfg, params["embed"], apply_norm(cfg, params["final_norm"], z))
    # logits[t] predicts tokens[t+2]
    return next_token_xent(logits, tokens[:, 1:])


def lm_loss(cfg: ModelConfig, params, batch):
    """Scalar training loss (+metrics dict)."""
    logits, aux, _, n_img, h = lm_forward(cfg, params, batch, train=True)
    tokens = batch["tokens"]
    text_logits = logits[:, n_img:] if n_img else logits
    loss = next_token_xent(text_logits, tokens, batch.get("loss_mask"))
    metrics = {"xent": loss, "aux": aux}
    total = loss + aux
    if cfg.mtp_depth:
        mtp = _mtp_loss(cfg, params, h[:, n_img:] if n_img else h, tokens)
        metrics["mtp"] = mtp
        total = total + cfg.mtp_loss_weight * mtp
    metrics["loss"] = total
    return total, metrics


# ----------------------------------------------------------------------
# KV cache / prefill / decode
# ----------------------------------------------------------------------


def _layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, B: int, max_len: int):
    T = min(spec.window, max_len) if spec.window else max_len
    if spec.attn == "mla":
        m = cfg.mla
        return (
            jnp.zeros((B, T, m.kv_lora_rank), cfg.cdtype),
            jnp.zeros((B, T, m.qk_rope_head_dim), cfg.cdtype),
        )
    D = cfg.n_kv_heads * cfg.resolved_head_dim  # flat: see gqa_attention_decode
    return jnp.zeros((B, T, D), cfg.cdtype), jnp.zeros((B, T, D), cfg.cdtype)


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    """Zero cache pytree mirroring the group structure: per group, a tuple
    per pattern position, each stacked over reps on axis 0."""
    cache = []
    for pattern, reps in layer_groups(cfg):
        entries = []
        for spec in pattern:
            one = _layer_cache_shape(cfg, spec, B, max_len)
            entries.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), one))
        cache.append(tuple(entries))
    return cache


def prefill(cfg: ModelConfig, params, batch, max_len: Optional[int] = None):
    """Full forward returning (last-position logits, cache filled to S)."""
    logits, aux, seeds, n_img, _ = lm_forward(cfg, params, batch, collect_cache=True)
    S = logits.shape[1]
    max_len = max_len or S
    cache = []
    for (pattern, reps), g_seeds in zip(layer_groups(cfg), seeds):
        entries = []
        for pi, spec in enumerate(pattern):
            seed = g_seeds[pi]  # tuple of (reps,B,S,...) arrays

            def to_cache(a):
                a = a.reshape(a.shape[:3] + (-1,))  # GQA (nkv, hd) -> nkv*hd
                T = min(spec.window, max_len) if spec.window else max_len
                S_seed = a.shape[2]
                if S_seed >= T:
                    # ring convention: position p lives at slot p % T
                    sliced = a[:, :, S_seed - T :]  # positions S-T .. S-1
                    return jnp.roll(sliced, shift=(S_seed - T) % T, axis=2)
                pad = [(0, 0)] * a.ndim
                pad[2] = (0, T - a.shape[2])
                return jnp.pad(a, pad)

            entries.append(jax.tree.map(to_cache, seed))
        cache.append(tuple(entries))
    return logits[:, -1], cache


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One-token decode. tokens (B,) int32, pos (B,) absolute positions.
    Returns (logits (B,vocab), new_cache). Each group's cache rides in the
    layer scan's carry, so every layer writes its new token into the stack
    in place; only the parameters are scanned over."""
    x = embed(cfg, params["embed"], tokens[:, None])
    new_cache = []
    for (pattern, reps), gp, gc in zip(layer_groups(cfg), params["groups"], cache):

        def scan_body(carry, xs):
            x, caches = carry
            lps, l = xs
            new_entries = []
            for spec, lp, cv in zip(pattern, lps, caches):
                x, cv = _apply_layer_decode(cfg, spec, lp, x, cv, l, pos)
                new_entries.append(cv)
            return (x, tuple(new_entries)), None

        (x, gc2), _ = lax.scan(scan_body, (x, gc), (gp, jnp.arange(reps)))
        new_cache.append(gc2)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = logits_out(cfg, params["embed"], x)
    return logits[:, 0], new_cache
