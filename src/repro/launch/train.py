"""Training driver: step builder + fault-tolerant loop.

``make_train_step`` builds the jitted (params, opt, batch) → (params, opt,
metrics) function with microbatch gradient accumulation (``lax.scan``, so
one microbatch's HLO regardless of accum factor).

``Trainer`` wires every substrate together the way the paper intends its
extensions to be used: data prefetch + async checkpoints + heartbeats are
generalized requests completed by ONE progress engine; the checkpoint
stream gets its own progress thread (spin-up at save, spin-down after);
failures trigger the elastic re-mesh plan + restore-from-latest.

Run: PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --steps 20

at the published widths (an accelerator's job); add ``--smoke`` for the
reduced preset the CPU can train.
"""

from __future__ import annotations

import argparse
import threading
import time
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.core.progress import AutotunePolicy, ProgressEngine
from repro.core.streams import stream_create, stream_free
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.ft.heartbeat import HeartbeatMonitor
from repro.ft.straggler import StragglerMonitor
from repro.models import api
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.parallel import sharding as shd

__all__ = ["make_grad_step", "make_train_step", "make_serve_step", "Trainer"]


def make_grad_step(cfg: ModelConfig, dp: tuple = ()):
    """The backward half of the train step: (params, batch) → (grads, loss),
    with the same microbatch-accumulation scan as :func:`make_train_step`.
    ``make_train_step`` composes this with ``adamw_update`` under one jit,
    so factoring it out leaves the fused step's traced HLO unchanged —
    while the Trainer's windowed grad path can jit JUST this and drive
    the bucketed allreduce from the host between backward and update."""

    def grad_step(params, batch):
        accum = cfg.grad_accum
        vg = jax.value_and_grad(lambda p, b: api.loss_fn(cfg, p, b), has_aux=True)
        if accum <= 1:
            (loss, metrics), grads = vg(params, batch)
        else:
            adt = jnp.dtype(cfg.accum_dtype)
            micro = jax.tree.map(
                lambda a: a.reshape(accum, a.shape[0] // accum, *a.shape[1:]), batch
            )
            if dp:
                micro = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, P(*((None, dp) + (None,) * (a.ndim - 2)))
                    ),
                    micro,
                )

            def mb(carry, b):
                gsum, lsum = carry
                (l, _m), g = vg(params, b)
                gsum = jax.tree.map(lambda s, gi: s + gi.astype(s.dtype), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
            (gsum, lsum), _ = lax.scan(mb, (g0, jnp.float32(0)), micro)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        return grads, loss

    return grad_step


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, dp: tuple = ()):
    """dp: data-parallel mesh axes — used to pin the microbatch sharding
    after the accumulation reshape (GSPMD would otherwise be free to put
    the batch sharding on the accumulation dim, serializing DP)."""
    grad_step = make_grad_step(cfg, dp)

    def train_step(params, opt_state, batch):
        grads, loss = grad_step(params, batch)
        new_params, new_state, om = adamw_update(opt_cfg, grads, opt_state, params)
        return new_params, new_state, {"loss": loss, **om}

    return train_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens, pos):
        return api.decode_step(cfg, params, cache, tokens, pos)

    return serve_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return api.prefill(cfg, params, batch)

    return prefill_step


# ----------------------------------------------------------------------
# sharded-step construction helpers (shared with dryrun)
# ----------------------------------------------------------------------


def named(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def train_shardings(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh, params_abs, batch_abs):
    pspecs = shd.param_specs(cfg, params_abs, mesh)
    opt_abs = jax.eval_shape(lambda p: adamw_init(opt_cfg, p), params_abs)
    ospecs = {
        "m": shd.opt_state_specs(cfg, pspecs, params_abs, mesh),
        "v": shd.opt_state_specs(cfg, pspecs, params_abs, mesh),
        "count": P(),
    }
    if opt_cfg.master:
        ospecs["master"] = shd.opt_state_specs(cfg, pspecs, params_abs, mesh)
    bspecs = shd.batch_specs(cfg, batch_abs, mesh)
    return pspecs, ospecs, bspecs, opt_abs


# ----------------------------------------------------------------------
# fault-tolerant training loop (CPU-runnable end-to-end)
# ----------------------------------------------------------------------


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        opt_cfg: AdamWConfig,
        data_cfg: DataConfig,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 50,
        ckpt_keep: int = 3,
        seed: int = 0,
        autotune: bool = True,
        autotune_policy: Optional[AutotunePolicy] = None,
        mesh_shape=(2, 16, 16),
        mesh_axes=("pod", "data", "model"),
        ranks=(0,),
        hb_timeout: float = 3600.0,
        hb_clock=None,
        hb_tick: float = 0.0,
        fault_injector=None,
        grad_overlap: str = "jit",
        grad_bucket_bytes: int = 1 << 16,
        grad_comms: int = 2,
        grad_window_depth: int = 2,
    ):
        self.cfg, self.opt_cfg, self.data_cfg = cfg, opt_cfg, data_cfg
        self.engine = ProgressEngine()
        # progress placement: the stats()-driven autotuner promotes the
        # streams that are actually hot (ckpt during save bursts, data
        # during prefetch) and demotes them between bursts — the old
        # static hand placement (one thread per known stream for the whole
        # run) is kept behind autotune=False for comparison/benchmarks
        self.autotune = autotune
        # default policy closes both feedback loops: thread placement AND
        # the spin budget (stats() spin_hits/parks ratio -> configure())
        if autotune and autotune_policy is None:
            autotune_policy = AutotunePolicy(tune_spin=True)
        self.tuner = self.engine.autotune(autotune_policy) if autotune else None
        self.ckpt_stream = stream_create(name="ckpt")
        self.data_stream = stream_create(name="data")
        self.pipeline = SyntheticPipeline(cfg, data_cfg, self.engine, self.data_stream)
        self.ckpt = (
            CheckpointManager(ckpt_dir, self.engine, self.ckpt_stream, keep=ckpt_keep)
            if ckpt_dir
            else None
        )
        self.ckpt_every = ckpt_every
        self.params = api.init_params(cfg, jax.random.key(seed))
        self.opt_state = adamw_init(opt_cfg, self.params)
        self.step_fn = jax.jit(make_train_step(cfg, opt_cfg))
        # grad_overlap="windowed" drives the REAL backward through the
        # backward-overlapped bucketed allreduce (ROADMAP item 2's carried
        # follow-on): the step becomes jitted grad_step → flatten →
        # bucketed_all_reduce_host(window=) with per-bucket RS admitted as
        # grads materialize and AGs reaped in completion order → unflatten
        # → jitted adamw_update. Numerically identical to the fused "jit"
        # step (RS∘AG on the 1-rank data axis is the identity; multi-rank
        # it is the bucket's allreduce), pinned by
        # tests/test_grad_overlap_window.py::test_trainer_windowed_*.
        if grad_overlap not in ("jit", "windowed"):
            raise ValueError(
                f"grad_overlap must be 'jit' or 'windowed', got {grad_overlap!r}"
            )
        self.grad_overlap = grad_overlap
        if grad_overlap == "windowed":
            from repro.core.enqueue import OffloadWindow
            from repro.core.streams import stream_comm_create
            from repro.launch.mesh import make_mesh
            from repro.optim.grad_overlap import build_buckets

            self._grad_fn = jax.jit(make_grad_step(cfg))
            self._update_fn = jax.jit(
                lambda g, o, p: adamw_update(opt_cfg, g, o, p)
            )
            mesh = make_mesh((1,), ("data",))
            self._grad_comms = [
                stream_comm_create(mesh, ("data",), stream_create(name=f"grad{i}"))
                for i in range(max(1, grad_comms))
            ]
            self._grad_window = OffloadWindow(
                stream_create(name="grad-win"),
                depth=grad_window_depth,
                engine=self.engine,
                name="grad-win",
            )
            self._grad_plan = build_buckets(
                jax.tree.leaves(self.params), bucket_bytes=grad_bucket_bytes
            )
        self.start_step = 0
        # elastic state: the mesh the run believes in, the monitored rank
        # set, and the detect → replan → reshard → resume machinery. The
        # heartbeat's on_failure fires on the detector's polling thread,
        # so it only *notes* the failure; the training loop consumes the
        # note at the next step boundary (recover() rebuilds state there,
        # where the params/opt live).
        self.mesh_shape = tuple(mesh_shape)
        self.mesh_axes = tuple(mesh_axes)
        self.mesh_plan = None
        self.ranks = list(ranks)
        self.fault_injector = fault_injector
        self._failure_lock = threading.Lock()
        self._pending_failures: list = []
        self.recoveries: list = []
        self.straggler = StragglerMonitor(ranks=self.ranks)
        # straggler mitigation is enacted, not just logged: run() feeds
        # advice through rebalance_shares into the pipeline's weighted
        # prefetch split (see _apply_straggler_advice)
        self.microbatch_total = max(len(self.ranks), int(getattr(cfg, "grad_accum", 1) or 1))
        self.microbatch_shares: Dict[int, int] = {}
        # named communication schedules riding this run (grad buckets,
        # halo exchanges): recover() invalidates and re-records them on
        # the new membership so a replay never runs against a stale rank
        # set (the serving engine already does this eagerly; training
        # now does too)
        self.schedules: Dict[str, dict] = {}
        # hb_clock + hb_tick: a virtual clock the loop advances by hb_tick
        # per step makes detection latency a deterministic step count
        # (timeout / tick steps after the last heartbeat) instead of a
        # wall-time race — fault-injection tests never sleep real timeouts
        self.hb_clock = hb_clock
        self.hb_tick = hb_tick
        hb_kwargs = {} if hb_clock is None else {"clock": hb_clock}
        self.heartbeat = HeartbeatMonitor(
            ranks=self.ranks,
            timeout=hb_timeout,
            engine=self.engine,
            on_failure=self._note_failure,
            **hb_kwargs,
        )
        self.history = []
        self.step_times: list = []  # seconds per step, loss read back included

    def maybe_restore(self):
        if self.ckpt is None:
            return
        try:
            (state, step) = self.ckpt.restore_latest(
                {"params": self.params, "opt": self.opt_state}
            )
            self.params, self.opt_state = state["params"], state["opt"]
            self.start_step = step + 1
            print(f"[trainer] restored step {step}")
        except FileNotFoundError:
            pass

    # -- fault-tolerance path ------------------------------------------------
    def handle_failure(self, failed_ranks, mesh_shape=(2, 16, 16), axes=("pod", "data", "model")):
        """Elastic recovery: plan a shrunken mesh (DP axes only) and roll
        back to the latest complete checkpoint. Returns the MeshPlan —
        the launcher would rebuild the jit artifacts against it (the
        iovec checkpoint store reads the SAME files under any mesh, see
        ft/elastic.py). Wired to HeartbeatMonitor.on_failure."""
        from repro.ft.elastic import plan_remesh

        plan = plan_remesh(mesh_shape, axes, n_failed=len(failed_ranks))
        print(f"[trainer] failure of ranks {failed_ranks}: re-mesh -> {plan.shape} {plan.dropped}")
        self.maybe_restore()
        return plan

    def _note_failure(self, failed_ranks) -> None:
        """HeartbeatMonitor.on_failure target — runs on whichever thread
        drove the detector poll, so it must not touch params/jit state;
        the training loop picks the note up at its next step boundary."""
        with self._failure_lock:
            self._pending_failures.extend(failed_ranks)

    def pending_failures(self) -> list:
        with self._failure_lock:
            return list(self._pending_failures)

    def _take_failures(self) -> list:
        with self._failure_lock:
            out, self._pending_failures = self._pending_failures, []
        return sorted(set(out))

    # -- straggler mitigation ------------------------------------------------
    def _apply_straggler_advice(self, advice) -> None:
        """Enact 'rebalance' advice: recompute inverse-speed microbatch
        shares and push them into the live pipeline's weighted prefetch
        split. Loader rank w serves mesh rank ``ranks[(w-1) % n]``, so a
        straggling stage's loader receives proportionally fewer
        microbatches starting with the very next prefetch."""
        if not any(a.action == "rebalance" for a in advice):
            return
        shares = self.straggler.rebalance_shares(self.microbatch_total)
        if not shares:
            return
        self.microbatch_shares = shares
        if self.pipeline.threadcomm is not None and self.ranks:
            weights = {
                w + 1: float(shares.get(self.ranks[w % len(self.ranks)], 1))
                for w in range(self.pipeline.n_workers)
            }
            self.pipeline.set_shares(weights)

    # -- recorded schedules across remesh ------------------------------------
    def register_schedule(self, name: str, schedule, record_fn: Callable) -> None:
        """Track a recorded communication schedule whose graph depends on
        the current membership (grad buckets, pipeline sends).
        ``record_fn(schedule)`` must (re-)record it eagerly against the
        trainer's current mesh; recover() invalidates the schedule and
        calls it after every remesh so replays resume on a fresh graph
        instead of dying ScheduleStale mid-step."""
        self.schedules[name] = {"schedule": schedule, "record": record_fn, "rerecords": 0}

    def _rerecord_schedules(self, plan) -> list:
        done = []
        for name, ent in self.schedules.items():
            sch = ent["schedule"]
            if sch is not None and not getattr(sch, "recording", False):
                sch.invalidate(f"membership changed: re-mesh -> {plan.shape}")
            ent["record"](sch)
            ent["rerecords"] += 1
            done.append(name)
        return done

    def recover(self, failed_ranks, reshard_depth: int = 4) -> "object":
        """The end-to-end elastic path: drop the dead ranks from the
        monitors, plan the shrunken mesh, stream the latest checkpoint's
        largest leaf through a depth-bounded reshard window onto the new
        data-parallel grid, and reload live state from the same files.
        Returns the MeshPlan; the reshard bytes + window stats land in
        ``self.recoveries[-1]`` for the invariant checks (byte-equality
        vs a clean restart)."""
        from repro.ft.elastic import plan_remesh

        failed_ranks = sorted(set(failed_ranks))
        plan = plan_remesh(self.mesh_shape, self.mesh_axes, n_failed=len(failed_ranks))
        print(
            f"[trainer] failure of ranks {failed_ranks}: re-mesh "
            f"{self.mesh_shape} -> {plan.shape} {plan.dropped}"
        )
        for r in failed_ranks:
            self.straggler.drop_rank(r)
            self.heartbeat.remove_rank(r)
            if r in self.ranks:
                self.ranks.remove(r)
        # survivors keep fresh straggler slates on the new mesh (a rank
        # with pre-failure history must not carry stale medians into the
        # resharded epoch's different per-step work)
        for r in self.ranks:
            self.straggler.add_rank(r)
        shards, win_stats = None, None
        if self.ckpt is not None:
            # saves are async: settle them so "latest available step" is a
            # deterministic fact of the run, not of save-thread timing
            self.ckpt.wait_for_pending()
        ckpt_step = None
        if self.ckpt is not None and self.ckpt.available_steps():
            ckpt_step = self.ckpt.available_steps()[-1]
            ckpt_dir = self.ckpt._dir_for(ckpt_step)
            shards, win_stats = self._reshard_checkpoint(
                ckpt_dir, plan, depth=reshard_depth
            )
            self.maybe_restore()
        self.mesh_shape = plan.shape
        self.mesh_plan = plan
        # membership changed: every registered schedule's recorded graph
        # (channel bindings, rank fan-out) is stale — invalidate and
        # re-record eagerly against the shrunken mesh before resuming
        rerecorded = self._rerecord_schedules(plan)
        self.recoveries.append(
            {
                "failed": failed_ranks,
                "plan": plan,
                "ckpt_step": ckpt_step,
                "shards": shards,
                "reshard_stats": win_stats,
                "schedules_rerecorded": rerecorded,
            }
        )
        return plan

    def _reshard_checkpoint(self, ckpt_dir: str, plan, depth: int = 4):
        """Windowed reshard of the checkpoint's largest leaf against the
        new mesh's DP degree: the iovec store addresses the GLOBAL array,
        so the new shards are just different coalesced subarray reads
        over the same .bin files."""
        import json
        import os

        from repro.checkpoint.iovec_store import manifest_path
        from repro.ft.elastic import execute_reshard, reshard_plan

        with open(manifest_path(ckpt_dir)) as f:
            manifest = json.load(f)
        name, meta = max(
            manifest["leaves"].items(),
            key=lambda kv: int(np.prod(kv[1]["shape"] or [1])),
        )
        shape = tuple(meta["shape"]) or (1,)
        itemsize = np.dtype(meta["dtype"] if meta["dtype"] != "bfloat16" else "uint16").itemsize
        # DP degree on the new mesh, clipped to the largest divisor of the
        # leaf's leading dim (a grid must block-partition the array)
        dp = 1
        for ax in ("pod", "data"):
            if ax in plan.axis_names:
                dp *= plan.shape[plan.axis_names.index(ax)]
        g = max(d for d in range(1, min(dp, shape[0]) + 1) if shape[0] % d == 0)
        grid = (g,) + (1,) * (len(shape) - 1)
        plans = reshard_plan(shape, grid, itemsize)
        path = os.path.join(ckpt_dir, meta["file"])

        def read_run(iov):
            with open(path, "rb") as fh:
                fh.seek(iov.offset)
                return fh.read(iov.length)

        shards, stats = execute_reshard(
            plans, read_run, depth=depth, engine=self.engine, stream=self.ckpt_stream
        )
        return {"leaf": name, "grid": grid, "shards": shards}, stats

    def _windowed_step(self, batch) -> Dict:
        """One step on the windowed grad path: jitted backward → flatten →
        per-bucket reduce-scatter admitted through the OffloadWindow as
        the grads materialize (allgathers reaped in completion order) →
        unflatten → jitted optimizer update."""
        from repro.optim.grad_overlap import (
            bucketed_all_reduce_host,
            flatten_grads,
            unflatten_grads,
        )

        grads, loss = self._grad_fn(self.params, batch)
        flat = flatten_grads(grads)
        reduced = bucketed_all_reduce_host(
            flat,
            self._grad_plan,
            self._grad_comms,
            engine=self.engine,
            window=self._grad_window,
            # the materialize hook is the backward seam: bucket i's RS
            # may not read flat before the producing compute lands
            materialize=lambda i: jax.block_until_ready(flat),
        )
        grads = unflatten_grads(reduced, grads)
        self.params, self.opt_state, om = self._update_fn(
            grads, self.opt_state, self.params
        )
        return {"loss": loss, **om}

    def _run_step(self, step: int, log_every: int) -> None:
        """One step of :meth:`run`, each phase in its own ``repro.train.*``
        profiler span (about a microsecond each when no profile is taken)."""
        # detect → replan → reshard → resume: a failure the heartbeat
        # detector noted since the last step boundary is recovered HERE,
        # then the loop keeps stepping on the shrunken mesh (history stays
        # continuous)
        failed = self._take_failures()
        if failed:
            with TraceAnnotation("repro.train.recover"):
                self.recover(failed)
        t0 = time.perf_counter()
        with TraceAnnotation("repro.train.prefetch"):
            self.pipeline.prefetch(step + 1)
        with TraceAnnotation("repro.train.get_batch"):
            host_batch = self.pipeline.get_batch(step)
        with TraceAnnotation("repro.train.h2d"):
            batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            if "img_embeds" in batch:
                batch["img_embeds"] = batch["img_embeds"].astype(self.cfg.cdtype)
            if "enc_frames" in batch:
                batch["enc_frames"] = batch["enc_frames"].astype(self.cfg.cdtype)
        with TraceAnnotation("repro.train.dispatch"):
            if self.grad_overlap == "windowed":
                metrics = self._windowed_step(batch)
            else:
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch
                )
        with TraceAnnotation("repro.train.readback"):
            loss = float(metrics["loss"])
        dt_step = time.perf_counter() - t0
        with TraceAnnotation("repro.train.bookkeeping"):
            durations = {}
            for r in list(self.ranks):
                d = dt_step
                if self.fault_injector is not None:
                    # straggle faults report extra step seconds — the
                    # monitor sees the slowdown without anyone sleeping
                    d += self.fault_injector.stage_delay(r)
                durations[r] = d
            self.straggler.record_step(durations)
            advice = self.straggler.check()
            if advice:
                # rebalance advice is enacted on the live pipeline; evict
                # escalation stays with the heartbeat/recover path (a
                # straggler is slow, not dead)
                self._apply_straggler_advice(advice)
            for r in list(self.ranks):
                self.heartbeat.record(r)
            if self.hb_clock is not None and self.hb_tick > 0:
                self.hb_clock.advance(self.hb_tick)
            # one synchronous detector visit per step: a rank whose
            # heartbeats stopped (dead, or suppressed by injection) is
            # noted here and recovered at the next step boundary
            self.heartbeat.check()
            self.history.append(loss)
            self.step_times.append(dt_step)
            if step % log_every == 0:
                print(f"[trainer] step {step} loss {loss:.4f} ({dt_step*1e3:.0f} ms)")
        if self.ckpt and step > 0 and step % self.ckpt_every == 0:
            with TraceAnnotation("repro.train.ckpt"):
                self.ckpt.save_async(step, {"params": self.params, "opt": self.opt_state})

    def run(self, steps: int, log_every: int = 10):
        # background progress only where async work is actually in flight —
        # the paper's control knob (ext. 6), now driven by stats(): the
        # autotuner promotes hot channels onto dedicated (parked) progress
        # threads and demotes them when the burst ends. autotune=False
        # falls back to static hand placement on the two known streams.
        if self.tuner is not None:
            self.tuner.start()
        else:
            self.engine.start_progress_thread(self.ckpt_stream, interval=0.01)
            self.engine.start_progress_thread(self.data_stream, interval=0.0)
        # loader ranks are per-run epochs: re-open the threadcomm bracket
        # if a previous run() closed it
        if self.data_cfg.loader_threads > 0 and self.pipeline.threadcomm is None:
            self.pipeline.start_workers(self.data_cfg.loader_threads)
        try:
            self.pipeline.prefetch(self.start_step)
            for step in range(self.start_step, self.start_step + steps):
                with StepTraceAnnotation("repro.train.step", step_num=step):
                    self._run_step(step, log_every)
            if self.ckpt:
                final = self.start_step + steps - 1
                self.ckpt.save_async(final, {"params": self.params, "opt": self.opt_state})
                self.ckpt.wait_for_pending()
        finally:
            # progress threads are per-run; the heartbeat request stays live
            # (heartbeat.stop() is for Trainer teardown, not between runs).
            # Threadcomm loader ranks (data_cfg.loader_threads > 0) are also
            # per-run: detach them so their VCI channels return to the pool.
            self.pipeline.stop_workers()
            if self.tuner is not None:
                self.tuner.stop()  # demotes every autotuner-placed thread
            self.engine.stop_all()
            st = self.engine.stats()
            print(
                f"[trainer] progress engine: {st['completions']} completions, "
                f"{st['polls']} polls, {st['lock_waits']} lock waits, "
                f"{st['parks']} parks / {st['wakes']} wakes "
                f"({st['spin_hits']} spin hits)"
            )
            if self.tuner is not None:
                ts = self.tuner.stats()
                print(
                    f"[trainer] autotuner: {ts['ticks']} ticks, "
                    f"{ts['promotions']} promotions / {ts['demotions']} demotions, "
                    f"spin_s {ts['spin_s']*1e6:.0f}us "
                    f"({ts['spin_grows']} grows / {ts['spin_shrinks']} shrinks)"
                )
        return self.history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", help="reduced preset (CPU sizes)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    tr = Trainer(
        cfg,
        AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=args.steps),
        DataConfig(batch=args.batch, seq=args.seq),
        ckpt_dir=args.ckpt_dir,
    )
    tr.maybe_restore()
    hist = tr.run(args.steps)
    print(f"[trainer] loss {hist[0]:.4f} -> {hist[-1]:.4f}")


if __name__ == "__main__":
    main()
