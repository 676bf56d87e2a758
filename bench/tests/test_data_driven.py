"""A new configuration, traffic mix, cell limits and per-layer metric are
added as files and entries alone: a copy of the benchmark gains all four
without a line of its code edited, and a run of the new cell reports the
new metric. So is a new architecture: a module under bench/arch/."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT, smoke_config, smoke_serve_mix, smoke_train_mix

SCRIPT = """
import json, sys, time
sys.path[0:0] = [sys.argv[1], sys.argv[2]]
from bench import common, run
bench = common.benchmark(sys.argv[1])
out = run.measure(bench, "tiny.burst", 3, 2.0, True, t_process_start=time.perf_counter())
print(json.dumps(out))
"""


def test_new_files_and_entries_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    cfg = smoke_config("qwen1.5-0.5b")
    cfg["name"] = cfg["model"]["name"] = "tiny"
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "burst.json").write_text(json.dumps(smoke_serve_mix("chat")))
    (b / "limits" / "tiny.burst.json").write_text(json.dumps({"logit_gap": {"limit": 1.0}}))
    (b / "metrics" / "tokens_served.py").write_text(
        "def read(run):\n    return float(sum(len(t.tokens) for t in run.requests))\n"
    )
    bench["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny", "traffic": "burst", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tokens_served", "unit": "tokens", "better": "higher", "source": "host_clock",
                               "layer": "serving/engine", "moves": "itl_p95_ms", "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path), os.path.join(ROOT, "src")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["tokens_served"]["value"] > 0
    # the shipped cells' metrics stay out of the new cell unless they list it
    assert "queue_wait_ms_p95" not in out["metrics"]


ARCH_SCRIPT = """
import json, sys, time
sys.path[0:0] = [sys.argv[1], sys.argv[2]]
from bench import common, counts, run
bench = common.benchmark(sys.argv[1])
peaks = counts.peaks("TPU v5 lite")
for cell in ("tiny-moe.serve", "tiny-moe.train"):
    out = run.measure(bench, cell, 2**33 + 5, 2.0, True, peaks=peaks, t_process_start=time.perf_counter())
    print(json.dumps(dict(out, cell=cell)))
"""

# A GQA MoE decoder with one leading dense layer (two program groups) and an
# untied output head: neither fits the gqa module. Every expert has room for
# every token in training and the balance loss is off, so the program's
# loss is the plain one the reference computes.
TINY_MOE = {
    "name": "tiny-moe", "family": "moe", "n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 32, "vocab": 256, "qkv_bias": False, "tie_embeddings": False,
    "rope_theta": 10000.0,
    "moe": {"n_experts": 4, "top_k": 2, "d_expert": 32, "first_k_dense": 1, "dense_d_ff": 128,
            "capacity_factor": 2.0, "router_aux_weight": 0.0},
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "logit_dtype": "float32",
    "remat": "none", "grad_accum": 1,
}


def test_new_architecture_by_files_alone(tmp_path):
    """The copy gains an architecture module, a configuration naming it, two
    mixes and two cells' limits, all new files; none of its files is
    edited. A serving and a training cell of it read correct and report
    their model step's utilization."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    shutil.copy(os.path.join(ROOT, "bench", "tests", "data", "gqa_dense_first.py"), b / "arch" / "gqa_dense_first.py")
    cfg = {"name": "tiny-moe", "arch": "gqa_dense_first", "model": TINY_MOE}
    (b / "configs" / "tiny-moe.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-serve.json").write_text(json.dumps(smoke_serve_mix("chat")))
    (b / "traffic" / "tiny-train.json").write_text(json.dumps(smoke_train_mix(batch=4)))
    (b / "limits" / "tiny-moe.serve.json").write_text(json.dumps(
        {"unanswered": {"limit": 0}, "wrong_length": {"limit": 0}, "logit_gap": {"limit": 0.05}}))
    (b / "limits" / "tiny-moe.train.json").write_text(json.dumps(
        {"grad_gap": {"limit": 0.05}, "delta_gap": {"limit": 0.2}}))
    bench["configs"].append({"name": "tiny-moe", "source": "test", "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny-moe.serve", "config": "tiny-moe", "traffic": "tiny-serve", "chips": 1, "why": "test"},
        {"name": "tiny-moe.train", "config": "tiny-moe", "traffic": "tiny-train", "chips": 1, "why": "test"},
    ]
    for m in bench["per_layer"]:
        if m["name"] in ("mfu.prefill", "mfu.offline"):
            m["workloads"].append("tiny-moe.serve")
        elif m["name"] == "mfu.train":
            m["workloads"].append("tiny-moe.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == data for p, data in before.items())

    p = subprocess.run([sys.executable, "-c", ARCH_SCRIPT, str(tmp_path), os.path.join(ROOT, "src")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    serve, train = [json.loads(line) for line in p.stdout.strip().splitlines()[-2:]]
    assert serve["cell"] == "tiny-moe.serve" and train["cell"] == "tiny-moe.train"
    for out in (serve, train):
        assert out["correct"] is True, out["checks"]
    assert {"mfu.prefill", "mfu.offline"} <= set(serve["metrics"]) and "mfu.train" in train["metrics"]
    assert all(serve["metrics"][k]["value"] > 0 for k in ("mfu.prefill", "mfu.offline"))
    assert train["metrics"]["mfu.train"]["value"] > 0
