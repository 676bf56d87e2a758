"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and limits are found by name from
``BENCHMARK.json``. With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of part of the window. The last line of standard output
is one JSON object; the numbers that decided ``correct`` are the last
lines of standard error and the last key of that object. Anything but a
TPU is refused with a non-zero exit and no result. ``--control 1`` puts
the float8 control in the program's place in the comparison, so its run
reads ``correct`` false (for setting limits; the cells' own runs never
do).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

# the TPU runtime warns at start-up and exit; the checks stay the last lines of stderr
warnings.filterwarnings("ignore", message=".*[Hh]ugepages.*")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def measure(bench: dict, cell: str, seed: int, seconds: float, trace: bool, control: bool = False,
            device: dict = None, peaks: dict = None, t_process_start: float = T_PROCESS_START,
            faults=None, cfg_file: dict = None, mix: dict = None, limits: dict = None) -> dict:
    """One run of ``cell``; returns the result object. Each number named in
    the cell's limits file is compared with its limit there (``checks``,
    last); ``readings`` holds every number the comparison read, those the
    limits leave out as well. With ``control`` the control's numbers stand
    in the program's place. The tests call this on the CPU with small files
    in place of the cell's."""
    from bench import harness, serving, training, tracefile

    wl = common.find(bench["workloads"], cell, "workload")
    cfg_file = cfg_file or common.config_file(wl["config"])
    mix = mix or common.mix_file(wl["traffic"])
    limits = limits or common.limits_file(cell)
    drive = {"serve": serving.run, "train": training.run}[mix["kind"]]
    run, readings, attempted, failed, setup_s = drive(
        cell, cfg_file, mix, seed, seconds, trace, control, common.seed32(seed), peaks,
        int(wl["chips"]), t_process_start, faults=faults,
    )
    checks = {k: {"value": readings.get(k), "limit": lim["limit"]} for k, lim in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    dev = dict(device or {}, memory_peak_bytes=run.memory_peak_bytes)
    breakdown = None
    if trace:
        metrics = harness.per_layer(bench, cell, run)
        if run.trace:
            busy = tracefile.busy_seconds(run.trace)
            dev["busy_s"] = busy
            dev["window_s"] = tracefile.window_seconds(run.trace)
            breakdown = {"device_ops": tracefile.top_ops(run.trace), "idle_gaps": tracefile.idle_gaps(run.trace)}
    else:
        metrics = end_to_end(bench, cell, run, setup_s)
    out = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["readings"] = readings
    out["checks"] = checks
    return out


def end_to_end(bench: dict, cell: str, run, setup_s: float) -> dict:
    from bench import e2e

    values = {"setup_s": setup_s, **e2e.values(run)}
    out = {}
    for m in common.cell_metrics(bench, cell, "end_to_end"):
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import json

    from bench import counts, harness

    bench = common.benchmark()
    wl = common.find(bench["workloads"], args.workload, "workload")
    device = harness.device_info(int(wl["chips"]))
    peaks = counts.peaks(device["kind"])
    cache = harness.compile_cache()
    watch = harness.CompileWatch()
    common.log(f"[bench] cell={args.workload} seed={args.seed} seconds={args.seconds} trace={args.trace} "
               f"device={device} compile_cache={cache}")
    out = measure(bench, args.workload, args.seed, args.seconds, bool(args.trace), bool(args.control),
                  device=device, peaks=peaks)
    common.log(f"[cache] backend compiles={watch.compiles} ({watch.compile_s:.3f} s) hits={watch.hits} "
               f"misses={watch.misses}; total {time.perf_counter() - T_PROCESS_START:.3f} s")
    for k, c in out["checks"].items():
        common.log(f"[check] {k} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
