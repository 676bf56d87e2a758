"""GQA decoder whose first ``first_k_dense`` layers have a dense SwiGLU FFN
and the rest a top-k softmax MoE, with an untied output head: two layer
groups in the program's tree, and a head of its own.

A test fixture: a copy of the benchmark gains this file as
``bench/arch/gqa_dense_first.py`` and serves and trains a configuration
that names it, without a line of the copy's other files edited. Each
group is a GQA decoder's stack of layers, so the ``gqa`` module's layout
and reference layers serve for both.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from bench.arch import gqa
from bench.reference import linear, rms_norm

PREFIXES = ("dense.", "moe.")


@dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    dense_d_ff: int
    vocab: int
    n_experts: int
    top_k: int
    d_expert: int
    first_k_dense: int
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    eps: float = 1e-6

    @staticmethod
    def from_model(m: dict) -> "Spec":
        moe = m["moe"]
        return Spec(
            n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
            head_dim=m.get("head_dim") or m["d_model"] // m["n_heads"],
            dense_d_ff=moe.get("dense_d_ff") or m["d_ff"], vocab=m["vocab"],
            n_experts=moe["n_experts"], top_k=moe["top_k"], d_expert=moe["d_expert"],
            first_k_dense=moe["first_k_dense"], qkv_bias=bool(m.get("qkv_bias", False)),
            rope_theta=float(m.get("rope_theta", 10_000.0)),
        )

    def groups(self):
        """Each group as a GQA decoder of its own depth."""
        common = dict(d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                      head_dim=self.head_dim, vocab=self.vocab, qkv_bias=self.qkv_bias,
                      rope_theta=self.rope_theta, eps=self.eps)
        dense = gqa.Spec(n_layers=self.first_k_dense, d_ff=self.dense_d_ff, **common)
        moe = gqa.Spec(n_layers=self.n_layers - self.first_k_dense, d_ff=self.d_expert, n_experts=self.n_experts,
                       top_k=self.top_k, d_expert=self.d_expert, **common)
        return dense, moe

    def active_matmul_params(self) -> int:
        """Each group's layers as the GQA decoder counts them; the untied head once."""
        return sum(g.active_matmul_params() - g.vocab * g.d_model for g in self.groups()) + self.vocab * self.d_model

    def attention_flops(self, pairs: int) -> int:
        return self.n_layers * self.n_heads * self.head_dim * 4 * pairs

    flash_attention_call = gqa.Spec.flash_attention_call


def _group_weights(w: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def shapes(s: Spec) -> Dict[str, tuple]:
    out = {}
    for g, prefix in zip(s.groups(), PREFIXES):
        out.update({prefix + k: v for k, v in gqa.shapes(g).items() if k not in gqa.LAYER_FREE})
    d = s.d_model
    out.update(tok=((s.vocab, d), 0.02, jnp.bfloat16), final_norm=((d,), 0.1, jnp.bfloat16),
               out=((d, s.vocab), d ** -0.5, jnp.bfloat16))
    return out


def layer_free(s: Spec):
    return ("tok", "out", "final_norm")


def to_program(s: Spec, w: Dict):
    groups = []
    for g, prefix in zip(s.groups(), PREFIXES):
        one = gqa.to_program(g, dict(_group_weights(w, prefix), tok=w["tok"], final_norm=w["final_norm"]))
        groups.append(one["groups"][0])
    return {"embed": {"tok": w["tok"], "out": w["out"]}, "final_norm": {"w": w["final_norm"]}, "groups": groups}


def from_program(s: Spec, tree) -> Dict:
    out = {"tok": tree["embed"]["tok"], "out": tree["embed"]["out"], "final_norm": tree["final_norm"]["w"]}
    for g, prefix, group in zip(s.groups(), PREFIXES, tree["groups"]):
        flat = gqa.from_program(g, {"embed": tree["embed"], "final_norm": tree["final_norm"], "groups": [group]})
        out.update({prefix + k: v for k, v in flat.items() if k not in gqa.LAYER_FREE})
    return out


def forward(s: Spec, w: Dict, tokens, quant: bool = False, remat: bool = False):
    """tokens (S,) -> logits (S, vocab), float32."""
    x = w["tok"].astype(jnp.float32)[tokens]
    for g, prefix in zip(s.groups(), PREFIXES):
        body = partial(gqa.layer, g, quant)
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(lambda x, lw: (body(x, lw), None), x, _group_weights(w, prefix))
    x = rms_norm(x, w["final_norm"].astype(jnp.float32), s.eps)
    return linear(x, w["out"].astype(jnp.float32), quant)
