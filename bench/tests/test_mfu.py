"""A utilization is over every chip of the cell: ``mfu.*`` on a run of four
chips read a quarter of the same run on one, and on one chip they read
the one-chip formula to the last digit."""

import pytest
from conftest import smoke_config

from bench import arch, counts
from bench.common import load_metric
from bench.runinfo import Decode, Prefill, Run, Track

PEAKS = {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = ["mfu.train", "mfu.prefill", "mfu.offline"]


def recorded(name: str, n_chips: int) -> Run:
    s = arch.spec(smoke_config("granite-moe-1b-a400m"))
    r = Run(kind="train" if name == "mfu.train" else "serve", cell="c", spec=s, peaks=PEAKS,
            t_open=10.0, t_close=13.0, n_chips=n_chips)
    r.train = {"steps": 40, "tokens_per_step": 8192, "batch": 8, "seq": 1024}
    r.requests = [Track(idx=0, prompt_len=256, max_new=8, due=10.0, prefill=(10.5, 10.6))]
    r.prefills = [Prefill(S=256, t0=10.5, t1=10.6)]
    r.decodes = [Decode(t0=11.0, t1=11.1, contexts=[257, 300])]
    return r


def one_chip_formula(name: str, r: Run) -> float:
    s, peak = r.spec, r.peaks["bf16_flop_per_s"]
    if name == "mfu.train":
        flops = counts.train_step_flops(s, 8, 1024) * 40
        return flops / (r.seconds * peak) * 100.0
    if name == "mfu.prefill":
        return counts.prefill_flops(s, 256) / ((10.6 - 10.5) * peak) * 100.0
    flops = counts.prefill_flops(s, 256) + sum(counts.decode_token_flops(s, c) for c in (257, 300))
    return flops / (r.seconds * peak) * 100.0


@pytest.mark.parametrize("name", NAMES)
def test_mfu_over_four_chips_is_a_quarter_of_one(name):
    one, four = load_metric(name)(recorded(name, 1)), load_metric(name)(recorded(name, 4))
    assert one > 0 and four == pytest.approx(one / 4, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_mfu_on_one_chip_is_the_one_chip_formula(name):
    r = recorded(name, 1)
    assert load_metric(name)(r) == one_chip_formula(name, r)
