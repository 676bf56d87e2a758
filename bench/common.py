"""Files, names and small statistics shared by every part of the benchmark.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric is a file of its own, found by the name
that ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     model sizes as run, beside the published ones
    bench/arch/<arch>.py            what depends on the architecture the config names
    bench/traffic/<mix>.json        parameters of one traffic mix
    bench/limits/<cell>.json        limits of the comparison that decides ``correct``
    bench/metrics/<metric>.py       ``read(run)`` -> number or None
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(os.path.join(HERE, "configs", f"{name}.json"))


def mix_file(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def limits_file(cell: str) -> dict:
    return load_json(os.path.join(HERE, "limits", f"{cell}.json"))


def load_metric(name: str):
    """The reader of one per-layer metric: ``bench/metrics/<name>.py``,
    which defines ``read(run) -> float | None``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, section: str) -> List[dict]:
    """The metrics of ``section`` (end_to_end or per_layer) this cell reports:
    those that list it, and those with no ``workloads`` key."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def seed32(seed: int, salt: int = 0) -> int:
    """A 31-bit integer drawn from any whole-number seed (the CLI's reach past
    32 signed bits; jax.random.key wants a small one)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), int(seed) >> 64, salt])
    return int(ss.generate_state(1, dtype=np.uint32)[0] & 0x7FFFFFFF)


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), int(seed) >> 64, *salt])


def percentile(values, q: float) -> Optional[float]:
    v = [x for x in values]
    if not v:
        return None
    return float(np.percentile(np.asarray(v, np.float64), q))


def mean(values) -> Optional[float]:
    v = list(values)
    return float(sum(v) / len(v)) if v else None

