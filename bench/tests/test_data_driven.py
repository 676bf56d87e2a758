"""A new configuration, traffic mix, cell limits and per-layer metric are
added as files and entries alone: a copy of the benchmark gains all four
without a line of its code edited, and a run of the new cell reports the
new metric."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT, smoke_config, smoke_serve_mix

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
