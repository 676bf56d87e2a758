"""The paper's technique on a device mesh: stream-tagged, bucketed
gradient synchronization (multi-VCI) vs one serialized channel, plus the
hierarchical multi-pod all-reduce. Runs on 8 forced host devices — set
BEFORE jax import, so this example is its own process.

    PYTHONPATH=src python examples/streams_overlap.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.core as C
from repro.core.collectives import all_reduce, multi_stream_all_reduce
from repro.core.hierarchical import hierarchical_all_reduce, hierarchical_collective_bytes
from repro.launch.mesh import make_mesh
from repro.optim.grad_overlap import build_buckets, bucketed_all_reduce


def main():
    mesh = make_mesh((2, 4), ("pod", "data"))
    tc = C.threadcomm_init(mesh, ("pod", "data"))
    print(f"[mesh] {dict(mesh.shape)} — threadcomm size {tc.size()}")

    grads = jnp.arange(8 * 4096, dtype=jnp.float32).reshape(8, 4096) / 1e4

    # (a) one implicit channel: a single serialized all-reduce chain
    single = C.stream_comm_create(mesh, ("pod", "data"))

    def serialized(g):
        tok = C.new_token()
        out = []
        for chunk in jnp.split(g.reshape(-1), 4):
            y, tok = all_reduce(chunk, single, tok)  # same stream ⇒ chained
            out.append(y)
        return jnp.concatenate(out)

    # (b) explicit streams: four independent channels, no false dependency
    streams = [C.stream_create(name=f"vci{i}") for i in range(4)]
    comms = [C.stream_comm_create(mesh, ("pod", "data"), s) for s in streams]

    def streamed(g):
        y, _ = multi_stream_all_reduce(g.reshape(-1), comms, axis=0)
        return y

    ys = tc.run(serialized, grads, in_specs=P(("pod", "data")), out_specs=P())
    ym = tc.run(streamed, grads, in_specs=P(("pod", "data")), out_specs=P())
    assert np.allclose(np.asarray(ys), np.asarray(ym))
    print("[streams] serialized chain == 4-stream concurrent result ✓ "
          "(HLO: chained vs independent all-reduces)")

    # (c) bucketed grad sync through the datatype layer
    params_shape = {
        "wq": jax.ShapeDtypeStruct((1024,), jnp.float32),
        "wo": jax.ShapeDtypeStruct((2048,), jnp.float32),
        "mlp": jax.ShapeDtypeStruct((1024,), jnp.float32),
    }
    plan = build_buckets(params_shape, bucket_bytes=4096)
    print(f"[buckets] {plan.n_buckets} buckets over {plan.total_elems} elems: {plan.bucket_slices}")

    def bucketed(g):
        y, _ = bucketed_all_reduce(g.reshape(-1), plan, comms)
        return y

    yb = tc.run(bucketed, grads, in_specs=P(("pod", "data")), out_specs=P())
    assert np.allclose(np.asarray(yb), np.asarray(ys))
    print("[buckets] bucketed round-robin-stream all-reduce ✓")

    # (d) hierarchical multi-pod schedule + its byte model
    def hier(g):
        y, _ = hierarchical_all_reduce(g, tc, axis=1)
        return y

    yh = tc.run(hier, grads, in_specs=P(("pod", "data")), out_specs=P())
    assert np.allclose(np.asarray(yh).sum(), np.asarray(ys).sum(), rtol=1e-5)
    m = hierarchical_collective_bytes(1 << 30, n_outer=2, n_inner=256)
    print(f"[hier] 1GiB all-reduce cross-pod bytes: flat={m['flat']['outer_bytes']/2**20:.0f}MiB "
          f"→ hier={m['hierarchical']['outer_bytes']/2**20:.0f}MiB")

    for s in streams:
        C.stream_free(s)
    print("OK")


if __name__ == "__main__":
    main()
