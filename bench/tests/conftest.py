"""CPU tests of the benchmark at the smoke sizes: ``pytest bench/tests``."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402

SMOKE_MODEL = {
    "qwen1.5-0.5b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                         vocab=256, remat="none", grad_accum=1),
    "granite-moe-1b-a400m": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32,
                                 vocab=256, moe={"n_experts": 4, "top_k": 2, "d_expert": 32},
                                 remat="none", grad_accum=1),
}


def smoke_config(name: str) -> dict:
    c = copy.deepcopy(common.config_file(name))
    c["model"].update(SMOKE_MODEL[name])
    return c


def smoke_serve_mix(traffic: str, **over) -> dict:
    m = copy.deepcopy(common.mix_file(traffic))
    m["engine"] = {"max_batch": 4, "max_len": 64}
    m["model_options"] = {}
    m["prompt_len"] = {"dist": "uniform", "min": 8, "max": 24, "round_up": 8}
    m["output_len"] = {"dist": "uniform", "min": 4, "max": 12}
    if m["loop"] == "open":
        m["arrivals"]["rate_per_s"] = 6.0
    else:
        m["arrivals"]["clients"] = 6
    m["drain_s"] = 30
    m["check"] = {"requests": 3}
    m.update(over)
    return m


def smoke_train_mix(**over) -> dict:
    m = copy.deepcopy(common.mix_file("train"))
    m.update(batch=2, seq=16)
    m.update(over)
    return m


@pytest.fixture(scope="session")
def bench_json():
    return common.benchmark()
