"""Sharding rules: param/batch/cache PartitionSpecs per architecture.

Pattern-matched on leaf names so one rule table covers the whole zoo;
per-layer stacking dims are absorbed automatically (rules describe the
TRAILING dims, leading dims get None).

Baseline layout (single-pod (data=16, model=16); multi-pod adds a leading
"pod" axis folded into data-parallel):
* TP over "model": attention heads / FFN hidden / experts (EP) / vocab
* DP over ("pod","data"): batch dims of activations & inputs
* decode KV caches: batch over "data", cache length T over "model"
  (sequence-parallel decode: QK^T/softmax/PV lower to sharded reductions
  — GSPMD's flash-decode analogue)
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

__all__ = [
    "dp_axes",
    "param_specs",
    "batch_specs",
    "cache_specs",
    "opt_state_specs",
    "logical_rules",
]

M = "model"


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel axes: ('pod','data') on the multi-pod mesh."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in names if a in ("pod", "data"))


# rule table: (leaf-name regex, trailing-dim spec entries)
# entries may be None, "model", or "dp" (replaced by the dp axes tuple)
_RULES = [
    # embeddings
    (r"embed/tok$", ("model", None)),
    (r"embed/out$", (None, "model")),
    (r"img_proj$", (None, None)),
    # attention (gqa)
    (r"attn/wq$", (None, "model")),
    (r"attn/wk$", (None, "model")),
    (r"attn/wv$", (None, "model")),
    (r"attn/wo$", ("model", None)),
    (r"attn/b[qkv]$", ("model",)),
    (r"xattn/wq$", (None, "model")),
    (r"xattn/wk$", (None, "model")),
    (r"xattn/wv$", (None, "model")),
    (r"xattn/wo$", ("model", None)),
    (r"xattn/b[qkv]$", ("model",)),
    # MLA
    (r"attn/wq_a$", (None, None)),
    (r"attn/wq_b$", (None, "model")),
    (r"attn/wkv_a$", (None, None)),
    (r"attn/wkv_b$", (None, "model")),
    (r"attn/(q_norm|kv_norm)$", (None,)),
    # dense MLPs
    (r"(ffn|mlp|shared)/w_gate$", (None, "model")),
    (r"(ffn|mlp|shared)/w_up$", (None, "model")),
    (r"(ffn|mlp|shared)/w_down$", ("model", None)),
    (r"mlp/w_in$", (None, "model")),
    (r"mlp/b_in$", ("model",)),
    (r"mlp/w_out$", ("model", None)),
    (r"mlp/b_out$", (None,)),
    # MoE (leading experts dim → EP); we_* keys are the expert stacks
    (r"ffn/router$", (None, None)),
    (r"ffn/we_(gate|up|down)$", ("model", None, None)),
    # RWKV6
    (r"w[rkvg]$", (None, "model")),
    (r"wo$", ("model", None)),
    (r"maa_w1$", (None, None)),
    (r"maa_w2$", (None, None, None)),
    (r"decay_w[12]$", (None, None)),
    (r"bonus$", ("model", None)),
    (r"wk_c$", (None, "model")),
    (r"wv_c$", ("model", None)),
    (r"wr_c$", (None, "model")),
    # Mamba
    (r"mixer/in_proj$", (None, "model")),
    (r"mixer/conv_w$", (None, "model")),
    (r"mixer/conv_b$", ("model",)),
    (r"mixer/x_proj$", ("model", None)),
    (r"mixer/dt_proj$", (None, "model")),
    (r"mixer/dt_bias$", ("model",)),
    (r"mixer/A_log$", ("model", None)),
    (r"mixer/D$", ("model",)),
    (r"mixer/out_proj$", ("model", None)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _match_rule(path: str, ndim: int, mesh) -> P:
    for pat, trailing in _RULES:
        if re.search(pat, path):
            entries = []
            for e in trailing:
                if e == "dp":
                    entries.append(dp_axes(mesh))
                else:
                    entries.append(e)
            lead = [None] * (ndim - len(entries))
            return P(*(lead + entries)) if (lead or entries) else P()
    return P(*([None] * ndim))  # replicate by default (norms, scalars)


def _divisible(shape, spec, mesh) -> bool:
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % n != 0:
            return False
    return True


def param_specs(cfg: ModelConfig, params_shape, mesh):
    """PartitionSpec pytree for params (ShapeDtypeStruct pytree input).

    Falls back to replication for any leaf whose matched spec doesn't
    divide (e.g. a reduced smoke config whose d_ff < model-axis size)."""

    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    # ep_only: keep EP sharding of the expert stacks, replicate dense/attn
    # weights (small models where TP hidden shards are tiny — the granite
    # hillclimb). Expert rules are the 3-D ffn/w_* entries.
    ep_paths = re.compile(r"ffn/we_(gate|up|down)$")

    def one(path, leaf):
        pstr = _path_str(path)
        spec = _match_rule(pstr, len(leaf.shape), mesh)
        if cfg.tp_strategy == "ep_only":
            is_expert = ep_paths.search(pstr)
            is_embed = re.search(r"embed/(tok|out)$", pstr)
            if not (is_expert or is_embed):
                spec = P(*(None if e == M else e for e in (list(spec) + [None] * (len(leaf.shape) - len(spec)))))
        if not _divisible(leaf.shape, spec, mesh):
            return P(*([None] * len(leaf.shape)))
        if cfg.fsdp:
            # ZeRO-3 / FSDP: additionally shard the first open dim over the
            # DP axes (GSPMD inserts the per-layer all-gather).
            entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
            # i >= 1 skips the layer-stacking dim (scan carries it whole)
            for i in range(len(entries)):
                if entries[i] is None and leaf.shape[i] % n_dp == 0 and leaf.shape[i] >= n_dp and i >= 1:
                    entries[i] = dp
                    break
            spec2 = P(*entries)
            if _divisible(leaf.shape, spec2, mesh):
                return spec2
        return spec

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_specs(cfg: ModelConfig, batch_shape, mesh):
    dp = dp_axes(mesh)

    def one(path, leaf):
        nd = len(leaf.shape)
        return P(*((dp,) + (None,) * (nd - 1)))

    return jax.tree_util.tree_map_with_path(one, batch_shape)


def cache_specs(cfg: ModelConfig, cache_shape, mesh, seq_axis_model: bool = True):
    """Decode caches: (L, B, T, nkv*hd) → batch over data, T over
    model (sequence-parallel decode). Recurrent states (RWKV/Mamba) shard
    their channel/head dim over model instead."""
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))

    def dp_if(dim):
        return dp if dim % n_dp == 0 and dim >= n_dp else None

    def one(path, leaf):
        p = _path_str(path)
        nd = len(leaf.shape)
        shape = leaf.shape
        if re.search(r"(wkv|ssm)", p):
            # (L,B,H,hs,hs) / (nb,B,di,ds): batch→data, channel→model
            spec = [None] * nd
            spec[1] = dp_if(shape[1])
            if _divisible_dim(shape[2], M, mesh):
                spec[2] = M
            return P(*spec)
        if re.search(r"(x_tm|x_cm|conv)", p):
            spec = [None] * nd
            spec[1] = dp_if(shape[1])
            spec[-1] = M if _divisible_dim(shape[-1], M, mesh) else None
            return P(*spec)
        # attention KV / MLA latent: (L,B,T,·), whisper's cross K/V (L,B,T,·,·)
        spec = [None] * nd
        if nd >= 3:
            spec[1] = dp_if(shape[1])
            if seq_axis_model and _divisible_dim(shape[2], M, mesh):
                spec[2] = M
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def _divisible_dim(dim, axis, mesh) -> bool:
    return dim % mesh.shape[axis] == 0


def opt_state_specs(cfg: ModelConfig, pspecs, params_shape, mesh, zero1: bool = True):
    """Optimizer-moment specs = param specs, optionally ZeRO-1-extended:
    the first unsharded, data-divisible dim also shards over the dp axes."""
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))

    def one(spec, leaf):
        if not zero1:
            return spec
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, (e, dim) in enumerate(zip(entries, leaf.shape)):
            if e is None and dim % n_dp == 0 and dim >= n_dp:
                entries[i] = dp
                return P(*entries)
        return P(*entries)

    return jax.tree.map(one, pspecs, params_shape)


def logical_rules(cfg: ModelConfig):
    """Human-readable summary for DESIGN/EXPERIMENTS docs."""
    return [(pat, spec) for pat, spec in _RULES]
