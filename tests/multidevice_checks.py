"""Multi-device correctness checks, run as a SUBPROCESS by
test_multidevice.py (the 8-device XLA flag must never leak into the main
pytest process — smoke tests and benches see 1 device).

Covers: stream collectives, threadcomm flatten/rank, hierarchical vs flat
all-reduce, multi-stream chunked all-reduce, enqueue shift, the hybrid
host×mesh Rabenseifner allreduce, GPipe pipeline forward/backward,
bucketed grad overlap, int8-EF hierarchical all-reduce, and a
distributed one-step trainer on a (2,2,2) pod mesh.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.core as C
from repro.launch.mesh import make_mesh
from repro.core import collectives as col
from repro.core import enqueue as enq
from repro.core.hierarchical import flat_all_reduce, hierarchical_all_reduce
from repro.optim.grad_overlap import build_buckets, bucketed_all_reduce
from repro.optim.compression import hierarchical_compressed_all_reduce

PASS = []


def check(name, cond):
    assert cond, name
    PASS.append(name)


def main():
    mesh = make_mesh((2, 4), ("pod", "data"))
    tc = C.threadcomm_init(mesh, ("pod", "data"))
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    shard_sum = np.asarray(x).reshape(8, 1, 16).sum(0)

    # threadcomm rank/size + flat == hierarchical
    def body(xs):
        r = tc.rank().reshape(1)
        f, _ = flat_all_reduce(xs, tc)
        h, _ = hierarchical_all_reduce(xs, tc, axis=1)
        return r, f, h

    r, f, h = tc.run(body, x, in_specs=P(("pod", "data")), out_specs=(P(("pod", "data")), P(), P()))
    check("threadcomm_rank", np.array_equal(np.asarray(r), np.arange(8)))
    check("flat_allreduce", np.allclose(np.asarray(f)[0:1], shard_sum))
    check("hier_eq_flat", np.allclose(np.asarray(f), np.asarray(h)))
    check("is_threadcomm", C.comm_test_threadcomm(tc) and not C.comm_test_threadcomm(tc.outer()))

    # multi-stream chunked all-reduce == single all-reduce
    streams = [C.stream_create(name=f"s{i}") for i in range(4)]
    comms = [C.stream_comm_create(mesh, ("pod", "data"), s) for s in streams]

    def body2(xs):
        toks = [C.new_token() for _ in comms]
        y, _ = col.multi_stream_all_reduce(xs, comms, toks, axis=1)
        return y

    y = tc.run(body2, x, in_specs=P(("pod", "data")), out_specs=P())
    check("multistream_allreduce", np.allclose(np.asarray(y)[0:1], shard_sum))

    # reduce_scatter + all_gather == all_reduce
    def body3(xs):
        rs, _ = col.reduce_scatter(xs, comms[0], axis=1)
        ag, _ = col.all_gather(rs, comms[0], axis=1)
        return ag

    y3 = tc.run(body3, x, in_specs=P(("pod", "data")), out_specs=P())
    check("rs_ag_eq_ar", np.allclose(np.asarray(y3)[0:1], shard_sum))

    # enqueue ring shift on the data axis
    off = C.stream_create(info={"type": "tpu_stream"}, name="off")
    ec = C.stream_comm_create(mesh, ("data",), off)

    def body4(xs):
        y, tok = enq.shift_enqueue(xs, ec, shift=1)
        return y

    y4 = tc.run(body4, x, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")))
    y4 = np.asarray(y4)
    xs_np = np.asarray(x)
    check("enqueue_shift_zerofill", np.all(y4[0] == 0) and np.all(y4[4] == 0))
    check("enqueue_shift_payload", np.allclose(y4[1], xs_np[0]) and np.allclose(y4[5], xs_np[4]))

    # bucketed all-reduce over streams == plain sum
    params_shape = {"a": jax.ShapeDtypeStruct((96,), jnp.float32), "b": jax.ShapeDtypeStruct((40,), jnp.float32)}
    plan = build_buckets(params_shape, bucket_bytes=128)
    flat = jnp.arange(8 * 136, dtype=jnp.float32).reshape(8, 136)

    def body5(g):
        y, _ = bucketed_all_reduce(g.reshape(-1), plan, comms[:2])
        return y

    y5 = tc.run(body5, flat, in_specs=P(("pod", "data")), out_specs=P())
    check(
        "bucketed_allreduce",
        np.allclose(np.asarray(y5).reshape(-1), np.asarray(flat).sum(0), rtol=1e-5),
    )

    # hierarchical compressed all-reduce ≈ exact (within int8 error)
    g = jnp.tile(jnp.linspace(-1, 1, 4096)[None], (8, 1)) * 0.01

    def body6(gs):
        y, ef = hierarchical_compressed_all_reduce(gs.reshape(-1), tc, block=256)
        return y

    y6 = tc.run(body6, g, in_specs=P(("pod", "data")), out_specs=P())
    exact = np.asarray(g).sum(0)
    err = np.max(np.abs(np.asarray(y6).reshape(-1) - exact)) / (np.abs(exact).max() + 1e-9)
    check("compressed_allreduce", err < 0.05)

    # hybrid host×mesh Rabenseifner allreduce: host ring reduce-scatter
    # (axis=1 column chunks) → device-level hierarchical allreduce of
    # each thread's chunk through its own stream comm → host allgather
    import threading

    from repro.core.progress import ProgressEngine as _PE
    from repro.core.streams import StreamPool
    from repro.core.threadcomm import HostThreadComm

    host = HostThreadComm(2, engine=_PE(), pool=StreamPool(), name="hyb")
    hybrid = tc.with_host_threads(host)
    host.start()
    vals = [
        (np.arange(8 * 60, dtype=np.float32).reshape(8, 60) + 1) * (t + 1)
        for t in range(2)
    ]
    hyb_out = {}

    def hyb_worker(t):
        h = host.attach(rank=t)
        try:
            hyb_out[t] = hybrid.allreduce_large(h, vals[t], timeout=60.0)
        finally:
            h.detach()

    try:
        hts = [threading.Thread(target=hyb_worker, args=(t,), daemon=True) for t in range(2)]
        for t in hts:
            t.start()
        for t in hts:
            t.join(timeout=120.0)
    finally:
        host.finish(timeout=10.0)
    hyb_expected = (vals[0] + vals[1]).sum(0)
    check(
        "hybrid_allreduce_large",
        all(
            hyb_out[t].shape == (60,) and np.allclose(hyb_out[t], hyb_expected, rtol=1e-5)
            for t in range(2)
        ),
    )

    # GPipe pipeline: forward/backward equivalence vs sequential stack
    from repro.parallel.pipeline import gpipe_forward, split_stages

    P_STAGES, L, D, MB, NM = 4, 8, 16, 2, 4
    keys = jax.random.split(jax.random.key(0), L)
    Ws = jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in keys])
    xs = jax.random.normal(jax.random.key(1), (NM, MB, D))

    def stage_fn(stage_params, x):
        def lyr(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(lyr, x, stage_params)
        return y

    pmesh = make_mesh((4, 2), ("pipe", "dp"))

    def loss_pipe(Ws_stacked, xs):
        def inner(sp, xm):
            sp = jax.tree.map(lambda a: a[0], sp)  # drop the pipe-shard dim
            outs = gpipe_forward(stage_fn, sp, xm, "pipe")
            rank = jax.lax.axis_index("pipe")
            l = jnp.sum(outs**2)
            l = jnp.where(rank == P_STAGES - 1, l, 0.0)
            return jax.lax.psum(l, "pipe")

        return jax.shard_map(
            inner, mesh=pmesh, in_specs=(P("pipe"), P()), out_specs=P(), check_vma=False
        )(split_stages(Ws_stacked, P_STAGES), xs)

    def loss_seq(Ws_stacked, xs):
        def lyr(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(lyr, xs.reshape(NM * MB, D), Ws_stacked)
        return jnp.sum(y**2)

    with pmesh:
        lp = float(loss_pipe(Ws, xs))
    ls = float(loss_seq(Ws, xs))
    check("gpipe_forward", abs(lp - ls) / abs(ls) < 1e-4)

    with pmesh:
        gp = jax.grad(lambda W: loss_pipe(W, xs))(Ws)
    gs_ = jax.grad(lambda W: loss_seq(W, xs))(Ws)
    gerr = float(jnp.max(jnp.abs(gp - gs_)) / (jnp.max(jnp.abs(gs_)) + 1e-9))
    check("gpipe_backward", gerr < 1e-4)

    # host-driven windowed 1F1B: depth boundary sends in flight, same math
    from repro.core.progress import ProgressEngine
    from repro.parallel.pipeline import gpipe_forward_host

    pipe_mesh = make_mesh((4,), ("pipe",))
    off_pipe = C.stream_create(info={"type": "tpu_stream"}, name="pipe-off")
    pipe_comm = C.stream_comm_create(pipe_mesh, ("pipe",), off_pipe)
    outs_w, win = gpipe_forward_host(
        stage_fn, split_stages(Ws, P_STAGES), xs, pipe_comm, depth=3, engine=ProgressEngine()
    )
    ref_seq = jnp.stack(
        [jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), xs[m], Ws)[0] for m in range(NM)]
    )
    wstats = win.stats(engine=False)
    check("gpipe_windowed_forward", bool(jnp.allclose(outs_w, ref_seq, atol=1e-4)))
    check("gpipe_windowed_depth", wstats["max_depth_seen"] == 3 and wstats["in_flight"] == 0)
    C.stream_free(off_pipe)

    # distributed one-step training on a (2,2,2) pod mesh via the real
    # train-step builder + sharding rules
    from repro.configs import get_config
    from repro.launch.train import make_train_step, named, train_shardings
    from repro.models import api
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.parallel import sharding as shd

    cfg = get_config("qwen1.5-0.5b", smoke=True).replace(grad_accum=2)
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = api.init_params(cfg, jax.random.key(0))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    opt = adamw_init(opt_cfg, params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)}
    pspecs, ospecs, bspecs, _ = train_shardings(cfg, opt_cfg, mesh3, params, batch)
    step = jax.jit(
        make_train_step(cfg, opt_cfg, dp=shd.dp_axes(mesh3)),
        in_shardings=(named(mesh3, pspecs), named(mesh3, ospecs), named(mesh3, bspecs)),
        out_shardings=(named(mesh3, pspecs), named(mesh3, ospecs), None),
    )
    with mesh3:
        params_d = jax.device_put(params, named(mesh3, pspecs))
        opt_d = jax.device_put(opt, named(mesh3, ospecs))
        batch_d = jax.device_put(batch, named(mesh3, bspecs))
        p2, o2, m = step(params_d, opt_d, batch_d)
    check("dist_train_step_finite", np.isfinite(float(m["loss"])))
    # distributed step == single-device step
    step1 = jax.jit(make_train_step(cfg, opt_cfg))
    p2_ref, _, m_ref = step1(params, opt, batch)
    check("dist_matches_single", abs(float(m["loss"]) - float(m_ref["loss"])) / abs(float(m_ref["loss"])) < 5e-2)

    for s in streams:
        C.stream_free(s)
    C.stream_free(off)
    print("MULTIDEVICE_OK " + " ".join(PASS))


if __name__ == "__main__":
    main()
