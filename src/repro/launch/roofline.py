"""Roofline-term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips × 197 TFLOP/s)
    memory term     = HLO_bytes / (chips × 819 GB/s)
    collective term = collective_bytes / (chips × 50 GB/s/link)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-
program, all partitions). collective_bytes is parsed from the optimized
HLO text: we sum the OPERAND sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute instruction (shapes in
the post-SPMD module are already per-partition, so the sum is per-chip
wire bytes up to the ring factor ~(n-1)/n ≈ 1).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.launch.mesh import HW

__all__ = [
    "CollectiveStats",
    "collective_bytes",
    "RooflineTerms",
    "roofline_terms",
    "fmt_seconds",
]


_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\]{,}0-9]+)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_WHILE_RE = re.compile(r"\bwhile\(.*?condition=%?([\w\.\-]+),?\s*body=%?([\w\.\-]+)")
_WHILE_RE2 = re.compile(r"\bwhile\(.*?body=%?([\w\.\-]+),?\s*condition=%?([\w\.\-]+)")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_bytes(result_str: str) -> int:
    return sum(_shape_bytes(m.group(1), m.group(2)) for m in _SHAPE_RE.finditer(result_str))


def _group_size(line: str) -> int:
    m = _GROUPS_BRACKET_RE.search(line)
    if m:  # replica_groups=[num_groups, group_size]
        return max(1, int(m.group(2)))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(1, len([x for x in m.group(1).split(",") if x != ""]))
    return 1


@dataclass
class CollectiveStats:
    per_op_bytes: Dict[str, float] = field(default_factory=dict)
    per_op_count: Dict[str, float] = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return sum(self.per_op_bytes.values())

    def add(self, op: str, nbytes: float, count: float = 1.0):
        self.per_op_bytes[op] = self.per_op_bytes.get(op, 0) + nbytes
        self.per_op_count[op] = self.per_op_count.get(op, 0) + count

    def merge_scaled(self, other: "CollectiveStats", scale: float):
        for op, b in other.per_op_bytes.items():
            self.add(op, b * scale, other.per_op_count.get(op, 0) * scale)

    def summary(self) -> str:
        parts = [
            f"{op}: {self.per_op_count.get(op,0):.0f} ops, {self.per_op_bytes.get(op,0)/1e9:.3f} GB"
            for op in _COLL_OPS
            if self.per_op_count.get(op)
        ]
        return "; ".join(parts) if parts else "none"


def _parse_computations(hlo_text: str) -> Dict[str, list]:
    """computation name → list of instruction lines."""
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and "{" in line:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", line)
            if m:
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None and line.strip():
            comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list) -> int:
    """Scan trip count: the largest integer constant in the while cond."""
    best = 1
    for l in cond_lines:
        for m in _CONST_RE.finditer(l):
            best = max(best, int(m.group(1)))
    return best


def collective_bytes(hlo_text: str, entry: Optional[str] = None) -> CollectiveStats:
    """Per-chip collective operand bytes of the post-SPMD module, with
    while-loop (lax.scan) bodies multiplied by their trip counts.

    Operand-size convention per op (shapes in the module are already
    per-partition): all-reduce/all-to-all/collective-permute = result
    bytes; all-gather = result / group; reduce-scatter = result × group.
    """
    comps = _parse_computations(hlo_text)
    entry_name = entry
    if entry_name is None:
        for name in comps:
            if "main" in name:
                entry_name = name
                break
        else:
            entry_name = next(iter(comps), None)
    memo: Dict[str, CollectiveStats] = {}

    def walk(name: str) -> CollectiveStats:
        if name in memo:
            return memo[name]
        stats = CollectiveStats()
        memo[name] = stats  # guard cycles
        for line in comps.get(name, []):
            cm = _COLL_RE.search(line)
            if cm and "-done(" not in line:
                result, op = cm.group(1), cm.group(2)
                rb = _result_bytes(result)
                g = _group_size(line)
                if op == "all-gather":
                    nb = rb / g
                elif op == "reduce-scatter":
                    nb = rb * g
                else:
                    nb = rb
                stats.add(op, nb)
            wm = _WHILE_RE.search(line) or _WHILE_RE2.search(line)
            if wm:
                if _WHILE_RE.search(line):
                    cond, body = wm.group(1), wm.group(2)
                else:
                    body, cond = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                stats.merge_scaled(walk(body), trips)
            # conditionals: count each branch once (upper bound is fine)
            for bm in re.finditer(r"(?:true_computation|false_computation|branch_computations)=\{?%?([\w\.\-]+)", line):
                stats.merge_scaled(walk(bm.group(1)), 1.0)
            callm = re.search(r"\bcall\(.*to_apply=%?([\w\.\-]+)", line)
            if callm:
                stats.merge_scaled(walk(callm.group(1)), 1.0)
        return stats

    return walk(entry_name) if entry_name else CollectiveStats()


@dataclass
class RooflineTerms:
    """All byte/FLOP inputs are PER-CHIP: the compiled module is the SPMD
    per-partition program, so ``cost_analysis()`` reports one chip's work."""

    flops: float  # per-chip HLO FLOPs
    hbm_bytes: float  # per-chip bytes accessed
    coll_bytes_per_chip: float  # per-chip collective operand bytes
    n_chips: int
    model_flops: float = 0.0  # whole-model 6·N·D convention

    @property
    def t_compute(self) -> float:
        return self.flops / HW.PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HW.HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / HW.ICI_BW_PER_LINK

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total compiled FLOPs (remat/redundancy waste)."""
        return self.model_flops / (self.flops * self.n_chips) if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MFU bound: useful model FLOPs per chip over peak, if the
        dominant roofline term were the step wall time."""
        if self.t_bound <= 0:
            return 0.0
        return (self.model_flops / self.n_chips / self.t_bound) / HW.PEAK_FLOPS_BF16

    def row(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_flops": self.flops,
            "hlo_bytes": self.hbm_bytes,
            "coll_bytes_per_chip": self.coll_bytes_per_chip,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "mfu_bound": self.roofline_fraction,
        }


def roofline_terms(cost: dict, coll: CollectiveStats, n_chips: int, model_flops: float = 0.0) -> RooflineTerms:
    return RooflineTerms(
        flops=float(cost.get("flops", 0.0)),
        hbm_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_chip=float(coll.total_bytes),
        n_chips=n_chips,
        model_flops=model_flops,
    )


def fmt_seconds(t: float) -> str:
    if t >= 1:
        return f"{t:.2f}s"
    if t >= 1e-3:
        return f"{t*1e3:.2f}ms"
    return f"{t*1e6:.1f}us"


def model_step_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for train (D = tokens), 2·N·D for fwd-only."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
