"""Operations and bytes from shapes, and the table of peaks. What depends
on the architecture (the weights a token multiplies by, attention's
operations, a kernel call's operations and bytes) the spec of
``bench/arch/<name>.py`` counts; the sums over a run's work are here.

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
from typing import Optional

from bench.common import HERE, load_json

BF16 = 2


def peaks(kind: str) -> dict:
    """Peaks of the chip named ``kind`` (``device_kind``); an unknown kind
    is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return dict(table["kinds"][kind], source=table["source"])


def prefill_flops(s, S: int) -> int:
    return 2 * s.active_matmul_params() * S + s.attention_flops(S * (S + 1) // 2)


def decode_token_flops(s, context: int) -> int:
    """One generated token attending to ``context`` positions (itself included)."""
    return 2 * s.active_matmul_params() + s.attention_flops(context)


def train_step_flops(s, batch: int, seq: int) -> int:
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


def flash_attention_call(s, S: int, batch: int = 1) -> Optional[KernelCall]:
    """One causal flash-attention call of one layer, as the architecture
    counts it; None where its prefill runs no flash kernel."""
    call = getattr(s, "flash_attention_call", None)
    return None if call is None else call(S, batch)
