"""GQA decoder: grouped-query attention with rotary embedding and optional
QKV bias, a SwiGLU FFN or a top-k softmax MoE in every layer, one layer
group, tied input and output embedding (qwen1.5-0.5b, granite-moe-1b-a400m).

The flat layout stacks every per-layer leaf on axis 0. Norm scales and QKV
biases are drawn too (the program's own init leaves them at zero), so the
comparison covers those paths. The reference is written from the published
architecture and not from the program: one sequence at a time, attention
as a masked softmax over the whole sequence, every expert computed for
every token and weighted by the renormalised top-k gates.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import BF16, KernelCall
from bench.reference import HI, fake_quant, linear, rms_norm, rope

LAYER_FREE = ("tok", "final_norm")


@dataclass(frozen=True)
class Spec:
    """The sizes the reference needs, read from a configuration file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    eps: float = 1e-6
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0

    @property
    def moe(self) -> bool:
        return self.n_experts > 0

    @staticmethod
    def from_model(m: dict) -> "Spec":
        moe = m.get("moe", {})
        return Spec(
            n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
            n_kv_heads=m["n_kv_heads"], head_dim=m.get("head_dim") or m["d_model"] // m["n_heads"],
            d_ff=m["d_ff"], vocab=m["vocab"], qkv_bias=bool(m.get("qkv_bias", False)),
            rope_theta=float(m.get("rope_theta", 10_000.0)),
            n_experts=moe.get("n_experts", 0), top_k=moe.get("top_k", 0),
            d_expert=moe.get("d_expert", 0),
        )

    def active_matmul_params(self) -> int:
        """Weights one token multiplies by, LM head included, embedding lookup not."""
        d, hq, hkv = self.d_model, self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        attn = d * hq + 2 * d * hkv + hq * d
        ffn = self.top_k * 3 * d * self.d_expert + d * self.n_experts if self.moe else 3 * d * self.d_ff
        return self.n_layers * (attn + ffn) + self.vocab * d

    def attention_flops(self, pairs: int) -> int:
        """Scores and values over ``pairs`` query-key pairs, in every layer."""
        return self.n_layers * self.n_heads * self.head_dim * 4 * pairs

    def flash_attention_call(self, S: int, batch: int = 1) -> KernelCall:
        """One causal flash-attention call of one layer: q and o over the query
        heads, k and v over the key-value heads, bf16."""
        pairs = S * (S + 1) // 2
        flops = batch * self.n_heads * self.head_dim * 4 * pairs
        bytes_ = batch * S * self.head_dim * BF16 * (2 * self.n_heads + 2 * self.n_kv_heads)
        return KernelCall(flops, bytes_)


# ----------------------------------------------------------------------
# the flat layout and the program's tree
# ----------------------------------------------------------------------


def shapes(s: Spec) -> Dict[str, tuple]:
    """name -> (shape, init scale, dtype) of every leaf."""
    L, d, hq, hkv = s.n_layers, s.d_model, s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    bf = jnp.bfloat16
    out = {
        "tok": ((s.vocab, d), 0.02, bf),
        "final_norm": ((d,), 0.1, bf),
        "ln1": ((L, d), 0.1, bf),
        "ln2": ((L, d), 0.1, bf),
        "wq": ((L, d, hq), d ** -0.5, bf),
        "wk": ((L, d, hkv), d ** -0.5, bf),
        "wv": ((L, d, hkv), d ** -0.5, bf),
        "wo": ((L, hq, d), hq ** -0.5, bf),
    }
    if s.qkv_bias:
        out.update(bq=((L, hq), 0.1, bf), bk=((L, hkv), 0.1, bf), bv=((L, hkv), 0.1, bf))
    if s.moe:
        E, de = s.n_experts, s.d_expert
        out.update(
            router=((L, d, E), d ** -0.5, jnp.float32),
            we_gate=((L, E, d, de), d ** -0.5, bf),
            we_up=((L, E, d, de), d ** -0.5, bf),
            we_down=((L, E, de, d), de ** -0.5, bf),
        )
    else:
        out.update(
            w_gate=((L, d, s.d_ff), d ** -0.5, bf),
            w_up=((L, d, s.d_ff), d ** -0.5, bf),
            w_down=((L, s.d_ff, d), s.d_ff ** -0.5, bf),
        )
    return out


def layer_free(s: Spec):
    return LAYER_FREE


def to_program(s: Spec, w: Dict[str, jax.Array]):
    """Nest the flat weights into the program's transformer tree (the same
    arrays, no copy)."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if s.qkv_bias:
        attn.update({k: w[k] for k in ("bq", "bk", "bv")})
    ffn_keys = ("router", "we_gate", "we_up", "we_down") if s.moe else ("w_gate", "w_up", "w_down")
    layer = {
        "ln1": {"w": w["ln1"]},
        "ln2": {"w": w["ln2"]},
        "attn": attn,
        "ffn": {k: w[k] for k in ffn_keys},
    }
    return {"embed": {"tok": w["tok"]}, "final_norm": {"w": w["final_norm"]}, "groups": [[layer]]}


def from_program(s: Spec, tree) -> Dict:
    """Flatten the program's transformer tree into the benchmark's names."""
    layer = tree["groups"][0][0]
    out = {"tok": tree["embed"]["tok"], "final_norm": tree["final_norm"]["w"],
           "ln1": layer["ln1"]["w"], "ln2": layer["ln2"]["w"]}
    out.update(layer["attn"])
    out.update(layer["ffn"])
    return out


# ----------------------------------------------------------------------
# the plain float32 reference
# ----------------------------------------------------------------------


def attention(s: Spec, lw, h, quant: bool):
    S = h.shape[0]
    q = linear(h, lw["wq"], quant)
    k = linear(h, lw["wk"], quant)
    v = linear(h, lw["wv"], quant)
    if s.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(S, s.n_heads, s.head_dim), s.rope_theta)
    k = rope(k.reshape(S, s.n_kv_heads, s.head_dim), s.rope_theta)
    v = v.reshape(S, s.n_kv_heads, s.head_dim)
    g = s.n_heads // s.n_kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, s.n_heads * s.head_dim)
    return linear(o, lw["wo"], quant)


def swiglu(lw, h, quant: bool):
    return linear(jax.nn.silu(linear(h, lw["w_gate"], quant)) * linear(h, lw["w_up"], quant), lw["w_down"], quant)


def moe(s: Spec, lw, h, quant: bool):
    probs = jax.nn.softmax(linear(h, lw["router"], quant), axis=-1)  # (S, E)
    topv, topi = jax.lax.top_k(probs, s.top_k)
    gates = topv / jnp.sum(topv, axis=-1, keepdims=True)
    dense_gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], topi].set(gates)
    wg, wu, wd = lw["we_gate"], lw["we_up"], lw["we_down"]
    x = h
    if quant:
        x, wg, wu, wd = fake_quant(h, -1), fake_quant(wg, 1), fake_quant(wu, 1), fake_quant(wd, 1)
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", x, wg, precision=HI))
    a = a * jnp.einsum("sd,edf->esf", x, wu, precision=HI)
    if quant:
        a = fake_quant(a, -1)
    return jnp.einsum("esf,efd,se->sd", a, wd, dense_gates, precision=HI)


def layer(s: Spec, quant: bool, x, lw):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = x + attention(s, lw, rms_norm(x, lw["ln1"], s.eps), quant)
    h = rms_norm(x, lw["ln2"], s.eps)
    return x + (moe(s, lw, h, quant) if s.moe else swiglu(lw, h, quant))


def layer_weights(w: Dict) -> Dict:
    return {k: v for k, v in w.items() if k not in LAYER_FREE}


def forward(s: Spec, w: Dict, tokens, quant: bool = False, remat: bool = False):
    """tokens (S,) -> logits (S, vocab), float32."""
    tok = w["tok"].astype(jnp.float32)
    x = tok[tokens]
    body = partial(layer, s, quant)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda x, lw: (body(x, lw), None), x, layer_weights(w))
    x = rms_norm(x, w["final_norm"].astype(jnp.float32), s.eps)
    return linear(x, tok.T, quant)
