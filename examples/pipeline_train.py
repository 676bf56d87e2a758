"""Pipeline-parallel training over the enqueue extension (paper ext. 4):
GPipe schedule on a 4-stage pipe axis, backward = AD transpose of the
device-ordered sends. Runs on 8 forced host devices.

    PYTHONPATH=src python examples/pipeline_train.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.pipeline import gpipe_forward, split_stages
from repro.launch.mesh import make_mesh

N_STAGES, LAYERS, D, MB, N_MICRO, VOCAB = 4, 8, 64, 4, 4, 512


def init(key):
    ks = jax.random.split(key, 3)
    return {
        "embed": jax.random.normal(ks[0], (VOCAB, D)) * 0.02,
        "stages": split_stages(jax.random.normal(ks[1], (LAYERS, D, D)) * 0.2, N_STAGES),
        "head": jax.random.normal(ks[2], (D, VOCAB)) * 0.02,
    }


def stage_fn(stage_params, x):
    def lyr(c, w):
        return jnp.tanh(c @ w), None

    y, _ = jax.lax.scan(lyr, x, stage_params)
    return y


def main():
    mesh = make_mesh((N_STAGES, 2), ("pipe", "dp"))
    params = init(jax.random.key(0))

    def loss_fn(params, tokens):
        def inner(sp, toks):
            sp = jax.tree.map(lambda a: a[0], sp)
            x = params["embed"][toks]  # embed replicated on every stage
            B = x.shape[0]
            xm = x.reshape(N_MICRO, B // N_MICRO, *x.shape[1:])
            outs = gpipe_forward(stage_fn, sp, xm, "pipe")  # enqueue transport
            outs = outs.reshape(B, -1, D)
            logits = outs @ params["head"]
            tgt = toks[:, 1:]
            lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            ll = jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
            rank = jax.lax.axis_index("pipe")
            l = jnp.where(rank == N_STAGES - 1, -ll.mean(), 0.0)
            return jax.lax.psum(l, "pipe")

        return jax.shard_map(
            inner, mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(), check_vma=False
        )(params["stages"], tokens)

    @jax.jit
    def step(params, tokens, lr=0.5):
        l, g = jax.value_and_grad(loss_fn)(params, tokens)
        params = jax.tree.map(lambda p, gi: p - lr * gi, params, g)
        return params, l

    rng = np.random.default_rng(0)
    with mesh:
        for it in range(30):
            start = rng.integers(0, 64, (MB * N_MICRO, 1))
            toks = jnp.asarray((start + np.arange(32)[None, :]) % 64, jnp.int32)
            params, l = step(params, toks)
            if it % 5 == 0:
                print(f"[pipeline] iter {it}: loss {float(l):.4f}")
    print(f"[pipeline] final loss {float(l):.4f} (4-stage GPipe, {N_MICRO} microbatches)")
    assert float(l) < 2.0
    print("OK")


if __name__ == "__main__":
    main()
