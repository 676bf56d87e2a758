"""The generator: deterministic from the seed, the same work for every seed."""

import numpy as np
import pytest

from bench import common, mixes


@pytest.mark.parametrize("traffic", ["chat", "offline"])
def test_same_seed_same_requests(traffic):
    mix = common.mix_file(traffic)
    make = (lambda s: mixes.closed_pool(mix, 1000, s, 64)) if mix["loop"] == "closed" else \
        (lambda s: mixes.open_schedule(mix, 1000, 30.0, s))
    a, b = make(2**40 + 3), make(2**40 + 3)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = make(5)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


BURSTY = {"loop": "open", "shape_seed": 0,
          "arrivals": {"process": "mmpp", "rate_per_s": 4.0, "burst_factor": 4.0, "burst_mean_s": 2.0, "calm_mean_s": 6.0},
          "prompt_len": {"dist": "lognormal", "median": 1536, "sigma": 0.4, "round_up": 512, "min": 512, "max": 3072},
          "output_len": {"dist": "uniform", "min": 8, "max": 64}}


@pytest.mark.parametrize("mix", [common.mix_file("chat"), BURSTY], ids=["poisson", "mmpp"])
def test_every_seed_offers_the_same_work(mix):
    runs = [mixes.open_schedule(mix, 1000, 30.0, s) for s in (1, 2, 3**30)]
    shapes = [[(len(r.prompt), r.max_new, r.due) for r in run] for run in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    assert len(runs[0]) == mixes.n_open(mix, 30.0)
    due = np.array([r.due for r in runs[0]])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 30.0
    # only the token ids differ from seed to seed
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)


def test_lengths_follow_the_mix():
    mix = common.mix_file("chat")
    sizes = mixes.size_pool(mix, 4000)
    p, o = sizes[:, 0], sizes[:, 1]
    assert set(np.unique(p)) <= set(range(128, 769, 128))
    assert p.min() >= 128 and p.max() <= 768 and o.min() >= 16 and o.max() <= 255
    assert 200 <= np.median(p) <= 300 and 80 <= np.median(o) <= 110
    assert mixes.prompt_shapes(mix, 30.0) == sorted(set(int(x) for x in mixes.size_pool(mix, mixes.n_open(mix, 30.0))[:, 0]))


def test_bursts_raise_the_rate():
    """In the bursty mix, arrivals are denser in the burst segments."""
    due = mixes.arrivals(BURSTY, 40.0)
    counts, _ = np.histogram(due, bins=40, range=(0, 40))
    assert counts.max() >= 3 * max(1, np.median(counts))
