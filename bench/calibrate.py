"""Tools for defining a cell, never run by the cells' own runs.

    python3 bench/calibrate.py sweep --workload <cell> --rates 4,6,8 --seconds 20
        One process, one set-up, then one window per offered rate: the knee
        is the highest rate whose requests still finish as they arrive.

    python3 bench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 10
            [--control 1 | --fault <name>]
        One process, one whole run of the cell per seed, each with its own
        set-up and a short window at the cell's own load. Prints, per seed,
        ``correct`` and every number the comparison read: the readings that
        the limits are set from (sound runs, the float8 control, or a fault
        from ``bench/faults.py`` planted under the timed path).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0:1] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

from bench import common  # noqa: E402


def all_shapes(mix: dict):
    p = mix["prompt_len"]
    step = int(p.get("round_up", 1))
    return list(range(int(p["min"]), int(p["max"]) + 1, step))


def sweep(cell: str, rates, seconds: float, seed: int) -> None:
    import numpy as np

    from bench import e2e, harness, serving
    from bench.runinfo import Run

    bench = common.benchmark()
    wl = common.find(bench["workloads"], cell, "workload")
    cfg_file, mix = common.config_file(wl["config"]), common.mix_file(wl["traffic"])
    harness.device_info(int(wl["chips"]))
    harness.compile_cache()
    cfg, s, w, eng, inst, profiler = serving.setup(cfg_file, mix, seed, common.seed32(seed), all_shapes(mix), False)
    common.log(f"[sweep] set-up {time.perf_counter() - T0:.3f} s")
    for rate in rates:
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        inst.forget()
        t_open, t_close, t_end, _ = serving.serve_window(eng, inst, m, s.vocab, seed, seconds, profiler, T0)
        tracks = sorted(inst.tracks.values(), key=lambda t: t.idx)
        serving.log_ttft_tail(tracks, inst.decodes, inst.prefills, k=5)
        r = Run(kind="serve", cell=cell, spec=s, peaks=None, t_open=t_open, t_close=t_close, t_end=t_end,
                requests=tracks)
        v = e2e.values(r)
        late = [t for t in tracks if t.done is None or t.done > t_close]
        waits = [t.prefill[0] - t.due for t in tracks if t.prefill]
        third = max(1, len(waits) // 3)
        print(
            f"SWEEP rate={rate} due={len(tracks)} unfinished_at_close={len(late)} "
            f"drain_s={t_end - t_close:.3f} ttft_p50_ms={common.percentile([(t.tokens[0] - t.due) * 1e3 for t in tracks if t.tokens], 50):.3f} "
            f"ttft_p95_ms={v['ttft_p95_ms']:.3f} itl_p95_ms={v['itl_p95_ms']:.3f} "
            f"queue_wait_first_third_ms={1e3 * np.mean(waits[:third]):.3f} "
            f"queue_wait_last_third_ms={1e3 * np.mean(waits[-third:]):.3f} "
            f"decode_ms_mean={1e3 * np.mean([d.t1 - d.t0 for d in inst.decodes]):.3f} "
            f"prefill_ms_mean={1e3 * np.mean([p.t1 - p.t0 for p in inst.prefills]):.3f}",
            flush=True,
        )


def readings(cell: str, seeds, seconds: float, control: bool, fault: str) -> None:
    import json

    from bench import counts, faults, harness
    from bench import run as runmod

    bench = common.benchmark()
    wl = common.find(bench["workloads"], cell, "workload")
    device = harness.device_info(int(wl["chips"]))
    peaks = counts.peaks(device["kind"])
    harness.compile_cache()
    for seed in seeds:
        t0 = time.perf_counter()
        out = runmod.measure(bench, cell, seed, seconds, False, control, device=device, peaks=peaks,
                             t_process_start=t0, faults=faults.ALL[fault] if fault else None)
        print(f"READING cell={cell} seed={seed} control={int(control)} fault={fault or '-'} "
              f"correct={out['correct']} failed={out['failed']} s={time.perf_counter() - t0:.1f} "
              f"{json.dumps(out['readings'])}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    a = ap.parse_args()
    if a.what == "sweep":
        sweep(a.workload, [float(x) for x in a.rates.split(",")], a.seconds, a.seed)
    else:
        readings(a.workload, [int(x) for x in a.seeds.split(",")], a.seconds, bool(a.control), a.fault)


if __name__ == "__main__":
    main()
