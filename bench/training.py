"""Training cells: ``Trainer.run`` with its own step, prefetch threads and
progress engine, on batches the benchmark makes from the seed.

Set-up builds one Trainer, puts the benchmark's weights in its state,
drives its first three steps through ``run`` and hands the same object to
the window. Those three steps are what the reference follows: the loss of
each, the first gradient as AdamW received it (read back from the first
moment after one step), and the change of the weights after three.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, reference, weights
from bench.common import log, rng
from bench.harness import CompileWatch, Profiler, memory_peak_bytes, model_config
from bench.runinfo import Run

CLOCK = time.perf_counter


@partial(jax.jit, static_argnums=0)
def unit_norms(s, flat: Dict):
    """L2 norm of each leaf, per layer for the stacked ones: name -> (L,) or (1,)."""
    free = arch.of(s).layer_free(s)
    out = {}
    for k, v in flat.items():
        v = v.astype(jnp.float32)
        if k in free:
            out[k] = jnp.sqrt(jnp.sum(v * v))[None]
        else:
            out[k] = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
    return out


@partial(jax.jit, static_argnums=0)
def change_norms(s, after: Dict, before: Dict):
    return unit_norms(s, {k: after[k].astype(jnp.float32) - before[k].astype(jnp.float32) for k in after})


def host(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(tree).items()}


def unit_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray], keep: Dict[str, np.ndarray]) -> float:
    """Worst unit: |prog - ref| over the larger of ref and the median unit's ref."""
    med = float(np.median(np.concatenate([ref[k][keep[k]] for k in ref])))
    worst = 0.0
    for k in ref:
        r, p = ref[k][keep[k]], prog[k][keep[k]]
        if r.size:
            worst = max(worst, float(np.max(np.abs(p - r) / np.maximum(r, med))))
    return worst


def kept_units(g_ref: Dict[str, np.ndarray], rule: float = 1e-3) -> Dict[str, np.ndarray]:
    """Units whose reference gradient is not nought to rounding (under
    ``rule`` times the median unit's), such as the key bias under softmax."""
    med = float(np.median(np.concatenate(list(g_ref.values()))))
    return {k: v >= rule * med for k, v in g_ref.items()}


def compare(s, opt: dict, init, batches, micro: int, prog: dict, quant: bool = False) -> dict:
    """Run the reference (or the float8 control) over ``batches`` from
    ``init`` and return the three gaps against the program's readings."""
    losses, g_ref, after = reference.train_steps(s, init, batches, opt, micro, quant=quant,
                                                 on_first=lambda g: host(unit_norms(s, g)))
    d_ref = host(change_norms(s, after, {k: jnp.asarray(v) for k, v in init.items()}))
    del after
    keep = kept_units(g_ref)
    dropped = sorted(f"{k}[{i}]" for k, m in keep.items() for i in np.flatnonzero(~m))
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], losses))
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": unit_gap(prog["grad"], g_ref, keep),
        "delta_gap": unit_gap(prog["delta"], d_ref, keep),
        "ref_losses": [float(x) for x in losses],
        "dropped": dropped,
    }


def run(cell: str, cfg_file: dict, mix: dict, seed: int, seconds: float, trace: bool,
        control: bool, seed32: int, peaks: Optional[dict], n_chips: int, t_process_start: float,
        faults=None):
    """One run of a training cell. Returns (Run, readings, steps, 0,
    setup_s). With ``control`` the float8 control's readings stand in the
    program's place."""
    from repro.data.pipeline import DataConfig
    from repro.launch.train import Trainer
    from repro.models import api
    from repro.optim.adamw import AdamWConfig, adamw_init

    quiet = contextlib.redirect_stdout(sys.stderr)  # the Trainer prints; stdout keeps the result alone
    cfg = model_config(cfg_file["model"])
    s = arch.spec(cfg_file)
    A = arch.of(s)
    B, S = int(mix["batch"]), int(mix["seq"])
    opt = dict(mix["optimizer"])
    opt_cfg = AdamWConfig(**opt)
    n_check = int(mix.get("check_steps", 3))
    with quiet:
        tr = Trainer(cfg, opt_cfg, DataConfig(batch=B, seq=S, seed=seed32), seed=seed32)
    # the benchmark's weights in place of the Trainer's own
    tr.params = tr.opt_state = None
    gc.collect()
    w = weights.make(s, seed32)
    params = A.to_program(s, w)
    weights.check_layout(params, jax.eval_shape(lambda k: api.init_params(cfg, k), jax.random.key(0)))
    init = jax.device_get(w)
    tr.params = params
    tr.opt_state = jax.jit(partial(adamw_init, opt_cfg))(params)
    del w, params

    def build_batch(step: int) -> dict:
        return {"tokens": rng(seed, 7, step).integers(0, s.vocab, (B, S), dtype=np.int64).astype(np.int32)}

    profiler = Profiler(trace, CLOCK)
    pipe_get, step_fn = tr.pipeline.get_batch, tr.step_fn

    def get_batch(step):
        with jax.profiler.TraceAnnotation("bench.get_batch"):
            return pipe_get(step)

    def step(params, opt_state, batch):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            return step_fn(params, opt_state, batch)

    tr.pipeline.build_batch = build_batch
    tr.pipeline.get_batch = get_batch
    tr.step_fn = step
    if faults is not None:
        faults(tr)

    with quiet:
        tr.run(1, log_every=1 << 30)
        grad = host(unit_norms(s, {k: v / (1 - opt["b1"]) for k, v in A.from_program(s, tr.opt_state["m"]).items()}))
        tr.start_step = 1
        tr.run(n_check - 1, log_every=1 << 30)
    delta = host(change_norms(s, A.from_program(s, tr.opt_state["master"]),
                              {k: jnp.asarray(v) for k, v in init.items()}))
    prog = {"losses": list(tr.history[:n_check]), "grad": grad, "delta": delta}

    step_s = float(np.mean(tr.step_times[1:n_check])) if n_check > 1 else float(tr.step_times[0])
    n_steps = max(2, int(round(seconds / step_s)))
    tr.start_step = n_check
    watch = CompileWatch()
    profiler.begin()
    t_open = CLOCK()
    setup_s = t_open - t_process_start
    profiler.open()
    with quiet:
        tr.run(n_steps, log_every=1 << 30)
    profiler.close()
    t_close = CLOCK()
    profiler.finish()
    log(f"[window] {n_steps} steps in {t_close - t_open:.3f} s (planned {seconds} s from a "
        f"{step_s * 1e3:.3f} ms set-up step); backend compiles inside: {watch.compiles}")
    out = Run(kind="train", cell=cell, spec=s, peaks=peaks, t_open=t_open, t_close=t_close,
              trace=profiler.events, trace_span=profiler.span, n_chips=n_chips)
    out.train = {"steps": n_steps, "tokens_per_step": B * S, "batch": B, "seq": S,
                 "step_times": list(tr.step_times[n_check:])}
    out.memory_peak_bytes = memory_peak_bytes(n_chips)
    tr.params = tr.opt_state = None
    gc.collect()

    t0 = CLOCK()
    batches = [build_batch(i)["tokens"] for i in range(n_check)]
    micro = B // max(1, cfg.grad_accum)
    got = compare(s, opt, init, batches, micro, prog)
    readings = {k: got[k] for k in ("loss_gap", "grad_gap", "delta_gap")}
    log(f"[reference] {n_check} steps in {CLOCK() - t0:.3f} s; program losses {prog['losses']} "
        f"reference {got['ref_losses']}; program {readings}; "
        f"units left out (reference gradient nought): {got['dropped']}")
    if control:
        readings = control_readings(s, opt, init, batches, micro)
        log(f"[control] the float8 control in the program's place: {readings}")
    return out, readings, n_steps, 0, setup_s


def control_readings(s, opt: dict, init, batches, micro: int) -> dict:
    """The control in the program's place: the float8 reference's losses,
    first gradient and change, compared with the float32 reference."""
    losses, grad, after = reference.train_steps(s, init, batches, opt, micro, quant=True,
                                                on_first=lambda g: host(unit_norms(s, g)))
    ctl = {
        "losses": [float(x) for x in losses],
        "grad": grad,
        "delta": host(change_norms(s, after, {k: jnp.asarray(v) for k, v in init.items()})),
    }
    del after
    got = compare(s, opt, init, batches, micro, ctl)
    return {k: got[k] for k in ("loss_gap", "grad_gap", "delta_gap")}
