"""The comparison that decides ``correct`` catches a broken timed path.

Each test skips the harness's look for a chip and drives the rest of a
run at the smoke sizes with one fault from ``bench/faults.py`` planted
underneath: ``correct`` must come out false. The cells run on one chip, so
there is no exchange between chips to leave out. The limits here are the
smoke sizes' own: sound runs read near 0 and the faults read far above.
The float8 control, put in the program's place, must come out false too.
"""

import time

import jax
import pytest
from conftest import smoke_config, smoke_serve_mix, smoke_train_mix

from bench import faults
from bench import run as runmod

SERVE_LIMITS = {"unanswered": {"limit": 0}, "wrong_length": {"limit": 0}, "logit_gap": {"limit": 0.05}}
TRAIN_LIMITS = {"grad_gap": {"limit": 0.05}, "delta_gap": {"limit": 0.2}}


def serve(bench_json, cell, faults=None, control=False, limits=SERVE_LIMITS):
    cfg, traffic = cell.rsplit(".", 1)
    return runmod.measure(bench_json, cell, 21, 2.0, False, control, cfg_file=smoke_config(cfg),
                          mix=smoke_serve_mix(traffic), limits=limits,
                          t_process_start=time.perf_counter(), faults=faults)


def train(bench_json, faults=None, control=False, limits=TRAIN_LIMITS):
    return runmod.measure(bench_json, "qwen1.5-0.5b.train", 22, 1.0, False, control,
                          cfg_file=smoke_config("qwen1.5-0.5b"), mix=smoke_train_mix(batch=4), limits=limits,
                          t_process_start=time.perf_counter(), faults=faults)


CELLS = ["qwen1.5-0.5b.chat", "granite-moe-1b-a400m.offline"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_serving_is_correct(bench_json, cell):
    out = serve(bench_json, cell)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.SERVING))
def test_serving_fault_is_caught(bench_json, cell, fault):
    out = serve(bench_json, cell, faults=faults.SERVING[fault])
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_gap"]["value"] > 3 * SERVE_LIMITS["logit_gap"]["limit"]


def donating_decode(eng):
    """The engine's decode, donating its cache as a later program may."""
    from repro.models import api

    cfg = eng.cfg
    eng._decode = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos), donate_argnums=(1,))


@pytest.mark.parametrize("cell", CELLS)
def test_cache_unchanged_is_caught_under_a_donating_decode(bench_json, cell):
    """A decode that donates its cache reads correct, and the fault planted
    over it reads not correct instead of crashing on a deleted buffer."""
    assert serve(bench_json, cell, faults=donating_decode)["correct"] is True

    def plant(eng):
        donating_decode(eng)
        faults.cache_unchanged(eng)

    out = serve(bench_json, cell, faults=plant)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_gap"]["value"] > 3 * SERVE_LIMITS["logit_gap"]["limit"]


def test_sound_training_is_correct(bench_json):
    out = train(bench_json)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.TRAINING))
def test_training_fault_is_caught(bench_json, fault):
    out = train(bench_json, faults=faults.TRAINING[fault])
    assert out["correct"] is False, out["checks"]


# Limits between the smoke sizes' sound readings and their float8 control
# (CPU, seeds 21 and 22): chat's widest gap 0.0015 against 0.025, the first
# gradient's worst unit 0.0024 against 0.045.
CONTROL_CASES = {
    "serve": (lambda b, **kw: serve(b, "qwen1.5-0.5b.chat", **kw),
              {"unanswered": {"limit": 0}, "wrong_length": {"limit": 0}, "logit_gap": {"limit": 0.007}}),
    "train": (train, {"grad_gap": {"limit": 0.008}}),
}


@pytest.mark.parametrize("kind", sorted(CONTROL_CASES))
def test_control_in_the_program_place_is_not_correct(bench_json, kind):
    """``--control 1`` puts the float8 control's numbers in the comparison:
    under the same limits the sound run is correct and the control is not."""
    drive, limits = CONTROL_CASES[kind]
    assert drive(bench_json, limits=limits)["correct"] is True
    out = drive(bench_json, control=True, limits=limits)
    assert out["correct"] is False, out["checks"]
