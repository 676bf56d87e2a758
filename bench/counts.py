"""Operations and bytes from shapes, and the table of peaks.

Counts are of what the algorithm needs, not of what the program happens
to do: a matrix product of an (m, k) by a (k, n) operand is 2·m·k·n
operations; causal attention over S positions needs S(S+1)/2 query-key
pairs, each 2·hd operations for the scores and 2·hd for the values; a
MoE token pays for its top-k experts and the router, not for the experts
a dropless dispatch computes besides; weights count once per token,
recomputation never. A kernel's bytes are its inputs read once and its
output written once.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from bench.common import HERE, load_json
from bench.weights import Spec

BF16 = 2


def peaks(kind: str) -> dict:
    """Peaks of the chip named ``kind`` (``device_kind``); an unknown kind
    is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return dict(table["kinds"][kind], source=table["source"])


def active_matmul_params(s: Spec) -> int:
    """Weights one token multiplies by, LM head included, embedding lookup not."""
    d, hq, hkv = s.d_model, s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    attn = d * hq + 2 * d * hkv + hq * d
    ffn = s.top_k * 3 * d * s.d_expert + d * s.n_experts if s.moe else 3 * d * s.d_ff
    return s.n_layers * (attn + ffn) + s.vocab * d


def attention_pairs_flops(s: Spec, pairs: int) -> int:
    """Scores and values over ``pairs`` query-key pairs, in every layer."""
    return s.n_layers * s.n_heads * s.head_dim * 4 * pairs


def prefill_flops(s: Spec, S: int) -> int:
    return 2 * active_matmul_params(s) * S + attention_pairs_flops(s, S * (S + 1) // 2)


def decode_token_flops(s: Spec, context: int) -> int:
    """One generated token attending to ``context`` positions (itself included)."""
    return 2 * active_matmul_params(s) + attention_pairs_flops(s, context)


def train_step_flops(s: Spec, batch: int, seq: int) -> int:
    """Forward and backward (three forwards' worth), no recomputation."""
    return 3 * batch * prefill_flops(s, seq)


@dataclass(frozen=True)
class KernelCall:
    flops: int
    bytes: int

    def min_seconds(self, pk: dict) -> float:
        return max(self.flops / pk["bf16_flop_per_s"], self.bytes / pk["hbm_bytes_per_s"])

    def bound(self, pk: dict) -> str:
        c = self.flops / pk["bf16_flop_per_s"]
        return "compute" if c >= self.bytes / pk["hbm_bytes_per_s"] else "memory"


def flash_attention_call(s: Spec, S: int, batch: int = 1) -> KernelCall:
    """One causal flash-attention call of one layer: q and o over the query
    heads, k and v over the key-value heads, bf16."""
    pairs = S * (S + 1) // 2
    flops = batch * s.n_heads * s.head_dim * 4 * pairs
    bytes_ = batch * S * s.head_dim * BF16 * (2 * s.n_heads + 2 * s.n_kv_heads)
    return KernelCall(flops, bytes_)
