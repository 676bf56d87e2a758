"""The harness on the CPU at the smoke sizes: the result's schema, requests
timed from when they were due, the generator's lateness, and refusal off
a TPU."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import ROOT, smoke_config, smoke_serve_mix, smoke_train_mix

from bench import harness
from bench import run as runmod
from repro.configs import registry

SERVE_LIMITS = {"unanswered": {"limit": 0}, "wrong_length": {"limit": 0}, "logit_gap": {"limit": 1.0}}
TRAIN_LIMITS = {"grad_gap": {"limit": 1.0}, "delta_gap": {"limit": 1.0}}
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def serve(bench_json, cell="qwen1.5-0.5b.chat", seed=5, seconds=2.0, trace=False, faults=None, **mix_over):
    cfg = cell.split(".chat")[0].split(".offline")[0].split(".rag")[0]
    traffic = cell[len(cfg) + 1 :]
    return runmod.measure(bench_json, cell, seed, seconds, trace, cfg_file=smoke_config(cfg),
                          mix=smoke_serve_mix(traffic, **mix_over), limits=SERVE_LIMITS,
                          t_process_start=time.perf_counter(), faults=faults)


def test_serve_result_schema(bench_json):
    out = serve(bench_json)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "readings", "checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {"setup_s", "ttft_p95_ms", "itl_p95_ms"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["checks"]) == {"unanswered", "wrong_length", "logit_gap"}
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())
    json.dumps(out)


def test_traced_result_has_per_layer_metrics(bench_json):
    out = serve(bench_json, trace=True, seconds=3.0)
    assert list(out)[-1] == "checks" and "breakdown" in out
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"queue_wait_ms_p95", "prefill_ms_mean.open", "decode_step_ms_mean.open"} <= set(out["metrics"])
    assert "ttft_p95_ms" not in out["metrics"]
    assert out["device"]["window_s"] > 0


def test_train_result_schema(bench_json):
    out = runmod.measure(bench_json, "qwen1.5-0.5b.train", 2**35 + 1, 1.0, False,
                         cfg_file=smoke_config("qwen1.5-0.5b"), mix=smoke_train_mix(), limits=TRAIN_LIMITS,
                         t_process_start=time.perf_counter())
    assert out["correct"] is True and set(out["metrics"]) == {"setup_s", "train_tok_s"}
    assert set(out["checks"]) == {"grad_gap", "delta_gap"}


def test_requests_are_timed_from_when_they_were_due(bench_json, capfd):
    """A stall of the engine delays every request due during it; the time
    to first token counts the stall from each request's due time, and the
    generator's lateness is reported on a line of its own."""
    stall = 0.8

    def stall_once(eng):
        step, state = eng.step, {"n": 0}

        def slow():
            state["n"] += 1
            if state["n"] == 2:
                time.sleep(stall)
            return step()

        eng.step = slow

    calm = serve(bench_json, seed=9, seconds=3.0)
    capfd.readouterr()
    slow = serve(bench_json, seed=9, seconds=3.0, faults=stall_once)
    err = capfd.readouterr().err
    assert "[generator] offers=18 late_p50_ms=" in err
    assert slow["metrics"]["ttft_p95_ms"]["value"] > max(stall * 1e3 * 0.5, 2 * calm["metrics"]["ttft_p95_ms"]["value"])


def test_refused_off_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen1.5-0.5b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=ENV, capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refused_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ gives no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen1.5-0.5b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=ENV, capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("name", registry.list_archs())
def test_model_config_round_trips(name):
    """Every nested dataclass of ModelConfig (moe, mla, ssm) is built from
    its dict, as the config class's own fields say."""
    c = registry.get_config(name, smoke=True)
    assert harness.model_config(dataclasses.asdict(c)) == c
