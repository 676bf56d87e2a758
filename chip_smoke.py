"""Bring-up smoke run: qwen1.5-0.5b at its full published width on a TPU.

    python chip_smoke.py                # one chip: serve, paged serve, train, kernels
    python chip_smoke.py --four-chips   # a 2x2 host: the sharded train step and the
                                        # hybrid allreduce, each against its reference

Everything runs in this one process, since a chip belongs to one process
at a time. Weights and data are random, made from ``--seed``. Every phase
checks what it produced and raises on a mismatch, so the script exits
non-zero; no error is caught. Only when every phase passed is the last
line of standard output one JSON object naming the device. The times
printed are smoke output, not benchmark numbers.

On anything but a TPU the script stops after printing the platform it
found: there is no CPU fallback. The tests drive the phase functions on
the CPU at the reduced preset (``tests/test_chip_smoke.py``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import datatype as dtt  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.kernels import dt_pack as dtp  # noqa: E402
from repro.kernels import flash_attention as fa  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.train import Trainer, make_train_step, named, train_shardings  # noqa: E402
from repro.models import api  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_init  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402
from repro.serving.engine import PagedServeEngine, ServeEngine  # noqa: E402

ARCH = "qwen1.5-0.5b"

#: Cached decode against the full forward, both in bf16: the largest
#: logit difference over the largest reference logit (the bound the
#: per-architecture decode tests use).
DECODE_REL_TOL = 0.05
#: Flash attention in bf16 against the float32-softmax reference.
FLASH_BF16_TOL = 2e-2
#: Sharded against one-device train step (loss, gradient norm), bf16.
SHARDED_STEP_REL_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  PASS {what}")


def peak_gib() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "n/a"
    return f"{stats['peak_bytes_in_use'] / 2**30:.3f}"


# ----------------------------------------------------------------------
# one-chip phases
# ----------------------------------------------------------------------


def make_prompts(cfg, seed: int, lengths, n: int):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.vocab, (lengths[i % len(lengths)],), dtype=np.int32) for i in range(n)
    ]


def drive(engine, prompts, max_new: int, tag: str):
    """Warm every prompt length and the decode step, then serve
    ``prompts`` together and check each returns exactly ``max_new``
    tokens. Returns the token streams."""
    t0 = time.perf_counter()
    by_len = {len(p): p for p in prompts}
    for p in by_len.values():
        engine.submit(p, max_new_tokens=2)
    engine.run_until_done()
    warm = time.perf_counter() - t0

    reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    t0 = time.perf_counter()
    done = {}
    steps = 0
    while not all(r.done for r in reqs):
        engine.step()
        steps += 1
        now = time.perf_counter()
        for r in reqs:
            if r.done and r.rid not in done:
                done[r.rid] = now - t0
    wall = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    lat = sorted(done.values())
    log(
        f"[{tag}] requests={len(reqs)} prompt_lens={sorted(by_len)} max_new={max_new} "
        f"steps={steps} warmup_s={warm:.3f} wall_s={wall:.3f} tokens={toks} "
        f"tok_per_s={toks / wall:.1f} p50_latency_ms={np.median(lat) * 1e3:.1f} "
        f"peak_gib={peak_gib()}"
    )
    check(
        all(len(r.out_tokens) == max_new for r in reqs),
        f"{tag}: every request returned exactly {max_new} tokens",
    )
    return [list(r.out_tokens) for r in reqs]


def decode_matches_prefill(cfg, params, prompt, max_len: int) -> None:
    """prefill(prompt) then one cached decode_step must give the last
    logits of prefill(prompt + [tok]), with the same top-1."""
    prefill = jax.jit(lambda p, t: api.prefill(cfg, p, {"tokens": t}, max_len=max_len))
    decode = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos))
    last, cache = prefill(params, prompt[None])
    tok = int(jnp.argmax(last[0]))
    dec, _ = decode(
        params, cache, jnp.asarray([tok], jnp.int32), jnp.asarray([len(prompt)], jnp.int32)
    )
    full, _ = prefill(params, np.append(prompt, np.int32(tok))[None])
    a = np.asarray(dec[0], np.float32)
    b = np.asarray(full[0], np.float32)
    rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))
    log(
        f"[serve] cached decode vs full forward: prompt_len={len(prompt)} rel_max_diff={rel:.3e} "
        f"(tol {DECODE_REL_TOL}) top1 decode={int(a.argmax())} full={int(b.argmax())}"
    )
    check(
        rel < DECODE_REL_TOL and int(a.argmax()) == int(b.argmax()),
        "serve: cached decode matches the full forward",
    )


def serve_phases(cfg, seed: int, max_batch: int, max_len: int, paged_max_len: int,
                 prompt_lens, n_requests: int, max_new: int) -> None:
    params = api.init_params(cfg, jax.random.key(seed))
    prompts = make_prompts(cfg, seed, prompt_lens, n_requests)

    decode_matches_prefill(cfg, params, prompts[0], max_len)
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len)
    dense = drive(eng, prompts, max_new, f"serve max_batch={max_batch} max_len={max_len}")
    del eng
    gc.collect()

    paged = PagedServeEngine(cfg, params, max_batch=max_batch, max_len=paged_max_len)
    streams = drive(paged, prompts, max_new, f"paged-serve max_batch={max_batch} max_len={paged_max_len}")
    kv = paged.kv.stats()
    log(
        f"[paged-serve] pages={kv['num_pages']} page_bytes={kv['page_bytes']} "
        f"peak_pages={kv['peak_pages']} appends={kv['appends']} gathers={kv['gathers']}"
    )
    check(streams == dense, "paged-serve: token for token equal to ServeEngine")


def train_phase(cfg, seed: int, batch: int, seq: int, steps: int) -> None:
    tr = Trainer(
        cfg,
        AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps),
        DataConfig(batch=batch, seq=seq, seed=seed),
        seed=seed,
    )
    hist = tr.run(steps, log_every=1)
    times = ", ".join(f"{t * 1e3:.1f}" for t in tr.step_times[1:])
    log(
        f"[train] batch={batch} seq={seq} steps={steps} grad_accum={cfg.grad_accum} "
        f"first_step_s={tr.step_times[0]:.3f} later_step_ms=[{times}] "
        f"losses=[{', '.join(f'{l:.4f}' for l in hist)}] peak_gib={peak_gib()}"
    )
    check(len(hist) == steps and all(np.isfinite(hist)), f"train: {steps} steps, every loss finite")


def kernel_phase(seed: int, attn_shape, nseg: int, seg: int, stride: int) -> None:
    """Flash attention and the datatype pack/unpack kernels against their
    references, each through the backend that kernels.ops picks."""
    mosaic = not ops.resolve_interpret(None)
    log(f"[kernels] backend={jax.default_backend()} compiled={mosaic}")

    def lowered(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        if mosaic:
            check("tpu_custom_call" in compiled.as_text(), f"kernels: {fn.__name__} is a Mosaic call")
        return compiled

    ks = jax.random.split(jax.random.key(seed), 3)
    q, k, v = (jax.random.normal(kk, attn_shape, jnp.float32).astype(jnp.bfloat16) for kk in ks)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v)

    out = np.asarray(lowered(flash, q, k, v)(q, k, v), np.float32)
    want = np.asarray(ref.attention_ref(q, k, v), np.float32)
    err = float(np.max(np.abs(out - want)))
    log(f"[kernels] flash_attention {attn_shape} bf16 max_abs_err={err:.3e} (tol {FLASH_BF16_TOL})")
    check(err <= FLASH_BF16_TOL, "kernels: flash_attention matches kernels.ref")

    buf = np.random.default_rng(seed).standard_normal(nseg * stride).astype(np.float32)
    vec = dtt.vector(nseg, seg, stride, dtt.predefined(4))

    def dt_pack(b):
        return ops.pack_datatype(b, vec)

    def dt_unpack(p):
        return ops.unpack_datatype(p, vec, nseg * stride)

    host = dtt.pack(buf.view(np.uint8), vec).view(np.float32)
    dev = np.asarray(lowered(dt_pack, jnp.asarray(buf))(jnp.asarray(buf)))
    log(f"[kernels] dt_pack nseg={nseg} stride={stride} seg={seg} f32 block_segs="
        f"{dtp._block_segs(nseg, stride, seg, 4)}")
    check(np.array_equal(dev, host), "kernels: dt_pack equals core.datatype.pack")
    host_back = np.zeros(nseg * stride, np.float32)
    dtt.unpack(host.view(np.uint8), vec, host_back.view(np.uint8))
    back = np.asarray(lowered(dt_unpack, jnp.asarray(host))(jnp.asarray(host)))
    check(np.array_equal(back, host_back), "kernels: dt_unpack equals core.datatype.unpack")


# ----------------------------------------------------------------------
# four-chip phases
# ----------------------------------------------------------------------


def sharded_train_phase(cfg, mesh, seed: int, batch: int, seq: int) -> None:
    """One train step sharded over ``mesh`` by ``train_shardings`` against
    the same step on one device, from the same state and batch."""
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    params = api.init_params(cfg, jax.random.key(seed))
    opt = adamw_init(opt_cfg, params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, seq), 0, cfg.vocab, jnp.int32)
    data = {"tokens": tokens}
    pspecs, ospecs, bspecs, _ = train_shardings(cfg, opt_cfg, mesh, params, data)
    step = jax.jit(
        make_train_step(cfg, opt_cfg, dp=shd.dp_axes(mesh)),
        in_shardings=(named(mesh, pspecs), named(mesh, ospecs), named(mesh, bspecs)),
        out_shardings=(named(mesh, pspecs), named(mesh, ospecs), None),
    )
    with mesh:
        args = (
            jax.device_put(params, named(mesh, pspecs)),
            jax.device_put(opt, named(mesh, ospecs)),
            jax.device_put(data, named(mesh, bspecs)),
        )
        t0 = time.perf_counter()
        _, _, m = step(*args)
        loss_s, gn_s = float(m["loss"]), float(m["grad_norm"])
        t_sharded = time.perf_counter() - t0
    del args, m
    gc.collect()

    t0 = time.perf_counter()
    _, _, m = jax.jit(make_train_step(cfg, opt_cfg))(params, opt, data)
    loss_1, gn_1 = float(m["loss"]), float(m["grad_norm"])
    t_one = time.perf_counter() - t0
    rel_loss = abs(loss_s - loss_1) / abs(loss_1)
    rel_gn = abs(gn_s - gn_1) / abs(gn_1)
    log(
        f"[sharded-train] mesh={dict(mesh.shape)} batch={batch} seq={seq} "
        f"loss sharded={loss_s:.6f} one_device={loss_1:.6f} rel={rel_loss:.3e}; "
        f"grad_norm sharded={gn_s:.6f} one_device={gn_1:.6f} rel={rel_gn:.3e} "
        f"(tol {SHARDED_STEP_REL_TOL}); first_call_s sharded={t_sharded:.3f} one_device={t_one:.3f}"
    )
    check(
        np.isfinite(loss_s) and rel_loss < SHARDED_STEP_REL_TOL and rel_gn < SHARDED_STEP_REL_TOL,
        "sharded-train: the sharded step matches the one-device step",
    )


def hybrid_allreduce_phase(mesh, seed: int, n_threads: int, cols: int) -> None:
    """``HybridThreadComm.allreduce_large`` over (mesh × host threads)
    against one plain ``psum`` over the mesh of the threads' sum."""
    from repro.core.progress import ProgressEngine
    from repro.core.streams import StreamPool
    from repro.core.threadcomm import HostThreadComm, threadcomm_init

    axes = tuple(mesh.axis_names)
    tc = threadcomm_init(mesh, axes)
    host = HostThreadComm(n_threads, engine=ProgressEngine(), pool=StreamPool(), name="smoke-hyb")
    hybrid = tc.with_host_threads(host)
    rng = np.random.default_rng(seed)
    vals = [rng.standard_normal((tc.size(), cols), dtype=np.float32) for _ in range(n_threads)]
    out, errors = {}, []

    def worker(t):
        h = host.attach(rank=t)
        try:
            out[t] = hybrid.allreduce_large(h, vals[t], timeout=300.0)
        except Exception as e:  # re-raised below, after the epoch closes
            errors.append(e)
        finally:
            h.detach()

    host.start()
    t0 = time.perf_counter()
    try:
        threads = [threading.Thread(target=worker, args=(t,), daemon=True) for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600.0)
    finally:
        host.finish(timeout=30.0)
    t_hyb = time.perf_counter() - t0
    if errors:
        raise errors[0]
    check(len(out) == n_threads, "hybrid-allreduce: every host thread finished")

    spec = P(axes)
    psum = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.psum(x, axes), mesh=mesh, in_specs=spec, out_specs=P(), check_vma=False
        )
    )
    t0 = time.perf_counter()
    want = np.asarray(psum(jax.device_put(sum(vals), NamedSharding(mesh, spec))))[0]
    t_psum = time.perf_counter() - t0
    err = max(float(np.max(np.abs(out[t] - want))) for t in range(n_threads))
    log(
        f"[hybrid-allreduce] mesh={dict(mesh.shape)} threads={n_threads} cols={cols} f32 "
        f"max_abs_err={err:.3e} first_call_s hybrid={t_hyb:.3f} psum={t_psum:.3f}"
    )
    check(
        all(np.allclose(out[t], want, rtol=1e-5, atol=1e-5) for t in range(n_threads)),
        "hybrid-allreduce: allreduce_large matches psum",
    )


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------


def device_info(need: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform {info['platform']!r}")
    if info["count"] < need:
        raise SystemExit(f"chip_smoke: needs {need} chips; JAX found {info['count']}")
    return info


def watch_compiles() -> dict:
    """Count persistent-cache hits and misses and sum backend compile
    seconds (which include reading an executable back from the cache)."""
    counts = {"hits": 0, "misses": 0, "backend_compile_s": 0.0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["backend_compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the multi-chip path on a 2x2 mesh: sharded train step and hybrid allreduce",
    )
    args = ap.parse_args(argv)

    info = device_info(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = watch_compiles()
    cfg = get_config(ARCH)
    log(
        f"[config] {ARCH} n_layers={cfg.n_layers} d_model={cfg.d_model} n_heads={cfg.n_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} params={cfg.param_counts()['total']} "
        f"seed={args.seed} compile_cache={cache_dir}"
    )
    t0 = time.perf_counter()
    if args.four_chips:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
        sharded_train_phase(cfg, mesh, args.seed, batch=8, seq=128)
        gc.collect()
        hybrid_allreduce_phase(mesh, args.seed, n_threads=2, cols=1 << 20)
    else:
        serve_phases(
            cfg, args.seed, max_batch=8, max_len=2048, paged_max_len=512,
            prompt_lens=(128, 384), n_requests=8, max_new=16,
        )
        gc.collect()
        train_phase(cfg, args.seed, batch=8, seq=128, steps=4)
        gc.collect()
        kernel_phase(args.seed, attn_shape=(16, 1024, 64), nseg=1000, seg=1000, stride=3000)
    log(
        f"[cache] dir={cache_dir} hits={compiles['hits']} misses={compiles['misses']} "
        f"backend_compile_s={compiles['backend_compile_s']:.3f} total_s={time.perf_counter() - t0:.3f}"
    )
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
