"""What one run recorded, handed to every per-layer metric reader.

Times are ``time.perf_counter`` seconds. A reader takes a :class:`Run`
and returns a number, or None where the run holds nothing to read.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass
class Track:
    """One request of the window, from the client's side."""

    idx: int
    prompt_len: int
    max_new: int
    due: float  # when it was due (open loop) or sent (closed loop)
    rid: Optional[int] = None
    issued: Optional[float] = None  # the generator handed it to the front end
    rejected: bool = False
    prefill: Optional[Tuple[float, float]] = None  # start and end of its prefill
    tokens: List[float] = field(default_factory=list)  # when each output token came out
    done: Optional[float] = None
    out: Optional[List[int]] = None
    prompt: Any = None


@dataclass
class Prefill:
    S: int
    t0: float
    t1: float


@dataclass
class Decode:
    t0: float
    t1: float
    contexts: List[int]  # positions attended by each active slot


@dataclass
class Run:
    kind: str  # serve | train
    cell: str
    spec: Any  # the architecture's Spec (bench/arch/<name>.py)
    peaks: Optional[dict]
    t_open: float
    t_close: float
    t_end: Optional[float] = None  # the drain's end (serving)
    max_batch: int = 0
    flash_prefill: bool = False
    requests: List[Track] = field(default_factory=list)
    prefills: List[Prefill] = field(default_factory=list)
    decodes: List[Decode] = field(default_factory=list)
    trace: Optional[list] = None  # tracefile.Event list of the traced window
    trace_span: Optional[Tuple[float, float]] = None  # host clock of the traced window
    train: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None
    n_chips: int = 1  # the chips the cell runs on; a utilization is over all of them

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def traced(self, t0: float, t1: float) -> bool:
        return self.trace_span is not None and self.trace_span[0] <= t0 and t1 <= self.trace_span[1]
