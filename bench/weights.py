"""Random weights made by the benchmark from ``--seed``, on the device, in
one jitted call and in the type they are served in.

The benchmark keeps them in its own flat layout (``Spec`` below, per-layer
leaves stacked on axis 0); the plain reference reads that layout, and
:func:`to_program` nests the same arrays into the tree the program's
``api`` takes. Norm scales and QKV biases are drawn too (the program's
own init leaves them at zero), so the comparison covers those paths.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp


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


@partial(jax.jit, static_argnums=0)
def _make(s: Spec, key):
    out = {}
    for i, (name, (shape, scale, dtype)) in enumerate(sorted(shapes(s).items())):
        k = jax.random.fold_in(key, i)
        out[name] = (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
    return out


def make(s: Spec, seed32: int) -> Dict[str, jax.Array]:
    return _make(s, jax.random.key(seed32))


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


def check_layout(program_tree, abstract) -> None:
    """Refuse a run whose weights do not have the tree, shapes and dtypes
    of the program's own ``api.init_params`` (``abstract`` is its
    ``jax.eval_shape``)."""
    got = jax.tree_util.tree_structure(program_tree)
    want = jax.tree_util.tree_structure(abstract)
    if got != want:
        raise SystemExit(f"bench: weight tree {got} is not the program's {want}")
    for a, b in zip(jax.tree.leaves(program_tree), jax.tree.leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise SystemExit(f"bench: weight leaf {a.shape} {a.dtype} is not the program's {b.shape} {b.dtype}")
