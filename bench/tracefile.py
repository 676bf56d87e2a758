"""From a profiler trace to numbers: device busy and idle time, kernel time
by stable name, the top device operations, and idle gaps attributed to
what the host was doing.

A trace is read once into a list of :class:`Event` (device operations and
the benchmark's own ``bench.*`` host spans); every reduction below works
on that list, so the tests can feed it a small recorded trace. On a TPU
the device plane is ``/device:TPU:<n>``, its line ``XLA Ops`` holds one
event per operation, named by the whole HLO instruction (``%fusion.12 =
bf16[...] fusion(...)``); control-flow operations (``while`` of a scanned
layer stack) span the events of their bodies. The Pallas flash kernel is
the custom call named after its jitted wrapper, ``gqa_flash_attention``.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "TPU" in name and "SparseCore" not in name


def load(trace_dir: str) -> List[Event]:
    """Device operations and ``bench.*`` host spans of the one
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = is_device_plane(plane.name)
            for line in plane.lines:
                if device and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if not device and not ev.name.startswith(HOST_PREFIX):
                        continue
                    name = ev.name.split(" = ", 1)[0] if device else ev.name  # the op's own name
                    out.append(Event(plane.name, line.name, name, float(ev.start_ns), float(ev.duration_ns)))
    return out


def read_saved(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(**e) for e in json.load(f)]


# ----------------------------------------------------------------------
# reductions
# ----------------------------------------------------------------------


def window(events: List[Event]) -> Tuple[float, float]:
    """[start, end] in ns of the traced window (the ``bench.window`` span)."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no bench.window span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def device_ops(events: List[Event]) -> Dict[str, List[Event]]:
    by_plane: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if is_device_plane(e.plane) and e.line == OPS_LINE:
            by_plane[e.plane].append(e)
    return dict(by_plane)


def merged(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(events: List[Event]) -> Optional[float]:
    """Seconds of the window in which an operation ran on the device,
    averaged over the devices traced; None where no device op was seen."""
    lo, hi = window(events)
    planes = device_ops(events)
    if not planes:
        return None
    per = [sum(b - a for a, b in merged(((e.start_ns, e.end_ns) for e in evs), lo, hi)) for evs in planes.values()]
    return sum(per) / len(per) / 1e9


def window_seconds(events: List[Event]) -> float:
    lo, hi = window(events)
    return (hi - lo) / 1e9


def op_name(e: Event) -> str:
    """The operation's own name: a TPU trace names an op by its whole HLO
    instruction (``%fusion.12 = bf16[...] fusion(...)``); keep what is left
    of `` = `` without the ``%``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def matches(e: Event, pattern: str) -> bool:
    return pattern in op_name(e)


def kernel_seconds(events: List[Event], pattern: str) -> Tuple[float, int]:
    """Device seconds (summed over devices) and number of events of the
    operations whose own name contains ``pattern``, inside the window."""
    lo, hi = window(events)
    hits = [e for evs in device_ops(events).values() for e in evs if matches(e, pattern) and lo <= e.start_ns < hi]
    return sum(e.dur_ns for e in hits) / 1e9, len(hits)


_NUM = re.compile(r"[._-]?\d+$")
#: Control-flow operations: their events span the operations inside them.
CONTAINERS = ("while", "conditional", "call")


def stable_name(e: Event) -> str:
    """An operation's own name without the numeric suffix XLA gives each
    instance (``fusion.12`` -> ``fusion``)."""
    return _NUM.sub("", op_name(e))


def top_ops(events: List[Event], n: int = 10) -> List[List]:
    """Device seconds by stable operation name, largest first, averaged over
    devices; control-flow operations are left out, their bodies count."""
    lo, hi = window(events)
    planes = device_ops(events)
    tot: Dict[str, float] = defaultdict(float)
    for evs in planes.values():
        for e in evs:
            if stable_name(e) in CONTAINERS:
                continue
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                tot[stable_name(e)] += (b - a) / 1e9
    k = max(len(planes), 1)
    return [[name, s / k] for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_spans(events: List[Event]) -> List[Event]:
    return [e for e in events if e.name.startswith(HOST_PREFIX) and e.name != WINDOW_SPAN]


def span_timeline(spans: List[Event]) -> Tuple[List[float], List[str]]:
    """Cut time at every span edge; each piece is labelled by the shortest
    span open over it (``host.other`` where none is). Returns the piece
    starts and their labels."""
    points = sorted({p for s in spans for p in (s.start_ns, s.end_ns)})
    starts, labels = [], []
    by_start = sorted(spans, key=lambda s: s.start_ns)
    active: List[Event] = []
    i = 0
    for p in points:
        while i < len(by_start) and by_start[i].start_ns <= p:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s.end_ns > p]
        starts.append(p)
        labels.append(min(active, key=lambda s: s.dur_ns).name if active else "host.other")
    return starts, labels


def idle_gaps(events: List[Event], n: int = 10) -> List[List]:
    """Idle device time in the window, summed by the innermost ``bench.*``
    host span open at each gap's midpoint (``host.other`` where none is),
    averaged over devices."""
    import bisect

    lo, hi = window(events)
    planes = device_ops(events)
    starts, labels = span_timeline(host_spans(events))
    tot: Dict[str, float] = defaultdict(float)
    for evs in planes.values():
        busy = merged(((e.start_ns, e.end_ns) for e in evs), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            j = bisect.bisect_right(starts, (a + b) / 2) - 1
            tot[labels[j] if j >= 0 else "host.other"] += (b - a) / 1e9
    k = max(len(planes), 1)
    return [[name, s / k] for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
