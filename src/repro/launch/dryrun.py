import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory/cost/roofline artifacts.

MUST be run as its own process (the XLA_FLAGS line above runs before any
jax import — 512 host devices exist only here, never in tests/benches).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.jaxpr_cost import step_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.train import (  # noqa: E402
    make_prefill_step,
    make_serve_step,
    make_train_step,
    named,
    train_shardings,
)
from repro.models import api  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_init  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402

__all__ = ["lower_cell", "run_cells"]


def _opt_cfg(cfg) -> AdamWConfig:
    big = cfg.fsdp
    return AdamWConfig(
        moments_dtype="bfloat16" if big else "float32",
        master=not big,
    )


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False, verbose: bool = True, cfg_transform=None, tag: str = ""):
    """Lower + compile one cell. Returns a result dict (JSON-safe).
    ``cfg_transform(cfg) -> cfg`` applies hillclimb variants."""
    cfg = registry.get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = registry.get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.perf_counter()

    params_abs = api.abstract_params(cfg)
    pspecs = shd.param_specs(cfg, params_abs, mesh)

    if shape.mode == "train":
        opt_cfg = _opt_cfg(cfg)
        batch_abs = registry.input_specs(cfg, shape)
        pspecs, ospecs, bspecs, opt_abs = train_shardings(cfg, opt_cfg, mesh, params_abs, batch_abs)
        step = make_train_step(cfg, opt_cfg, dp=shd.dp_axes(mesh))
        jitted = jax.jit(
            step,
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
            out_shardings=(named(mesh, pspecs), named(mesh, ospecs), None),
        )
        with mesh:
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            global_cost = step_cost(step, params_abs, opt_abs, batch_abs)
    elif shape.mode == "prefill":
        batch_abs = registry.input_specs(cfg, shape)
        bspecs = shd.batch_specs(cfg, batch_abs, mesh)
        cache_abs = jax.eval_shape(
            lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = shd.cache_specs(cfg, cache_abs, mesh)
        step = make_prefill_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(named(mesh, pspecs), named(mesh, bspecs)),
            out_shardings=(None, named(mesh, cspecs)),
        )
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
            global_cost = step_cost(step, params_abs, batch_abs)
    else:  # decode
        io_abs = registry.input_specs(cfg, shape)
        cache_abs = registry.decode_cache_specs(cfg, shape)
        cspecs = shd.cache_specs(cfg, cache_abs, mesh)
        dp = shd.dp_axes(mesh)
        import numpy as _np
        n_dp = int(_np.prod([mesh.shape[a] for a in dp]))
        B = shape.global_batch
        tok_sh = NamedSharding(mesh, P(dp) if (B % n_dp == 0 and B >= n_dp) else P(None))
        step = make_serve_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(named(mesh, pspecs), named(mesh, cspecs), tok_sh, tok_sh),
            out_shardings=(None, named(mesh, cspecs)),
        )
        with mesh:
            lowered = jitted.lower(params_abs, cache_abs, io_abs["tokens"], io_abs["pos"])
            global_cost = step_cost(step, params_abs, cache_abs, io_abs["tokens"], io_abs["pos"])

    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    mflops = rl.model_step_flops(cfg, shape)
    # jaxpr-exact accounting (XLA-CPU cost_analysis undercounts: loop
    # bodies counted once, custom-call matmuls uncounted — see
    # tests/test_roofline.py); per-chip = global / chips.
    cost = {
        "flops": global_cost.flops / n_chips,
        "bytes accessed": global_cost.bytes / n_chips,
    }
    terms = rl.roofline_terms(cost, coll, n_chips, mflops)

    result = {
        "arch": arch,
        "variant": tag or "baseline",
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "multi_pod": multi_pod,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes_per_device": _peak_per_device(mem, n_chips),
        },
        "collectives": {"summary": coll.summary(), **coll.per_op_bytes},
        "roofline": terms.row(),
        "xla_cost_raw": {
            "flops": xla_cost.get("flops"),
            "bytes_accessed": xla_cost.get("bytes accessed"),
        },
    }
    if verbose:
        m = result["memory"]
        print(
            f"[dryrun] {arch}{('['+tag+']') if tag else ''} × {shape_name} × "
            f"{'2x16x16' if multi_pod else '16x16'}: "
            f"lower {t_lower:.1f}s compile {t_compile:.1f}s"
        )
        print(f"  memory_analysis: args={_gb(m['argument_bytes'])} temps={_gb(m['temp_bytes'])} "
              f"peak/device={_gb(m['peak_bytes_per_device'])}")
        print(f"  cost_analysis: flops={terms.flops:.3e} bytes={terms.hbm_bytes:.3e}")
        print(f"  collectives: {coll.summary()}")
        r = terms.row()
        print(
            f"  roofline: compute={rl.fmt_seconds(terms.t_compute)} memory={rl.fmt_seconds(terms.t_memory)} "
            f"collective={rl.fmt_seconds(terms.t_collective)} -> {terms.bottleneck}-bound; "
            f"useful={r['useful_ratio']:.3f} mfu_bound={r['mfu_bound']:.3f}"
        )
    return result


def _gb(x):
    return "n/a" if x is None else f"{x/2**30:.2f}GiB"


def _peak_per_device(mem, n_chips):
    """memory_analysis of the partitioned module is per-device (verified:
    argument bytes == params+opt shard for TP-only cells). Outputs alias
    donated inputs at runtime; peak ~= args + temps."""
    try:
        return int(
            (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0)
        )
    except Exception:
        return None


def run_cells(archs, shapes, pods, out_path=None, stop_on_error=False):
    results = []
    for arch in archs:
        cfg = registry.get_config(arch)
        valid = registry.applicable_shapes(cfg)
        for shape in shapes:
            if shape not in valid:
                print(f"[dryrun] SKIP {arch} × {shape} (arch-applicability constraint)")
                results.append({"arch": arch, "shape": shape, "skipped": True})
                continue
            for mp in pods:
                try:
                    results.append(lower_cell(arch, shape, multi_pod=mp))
                except Exception as e:
                    traceback.print_exc()
                    results.append(
                        {"arch": arch, "shape": shape, "multi_pod": mp, "error": repr(e)}
                    )
                    if stop_on_error:
                        raise
                if out_path:
                    with open(out_path, "w") as f:
                        json.dump(results, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]
    archs = registry.list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = registry.list_shapes() if (args.all or not args.shape) else [args.shape]
    run_cells(archs, shapes, pods, args.out)


if __name__ == "__main__":
    main()
