"""Pallas-kernel micro-benchmarks vs their XLA reference paths.

Kernels run where ``kernels.ops.resolve_interpret`` puts them: in the
Pallas interpreter on the CPU backend, compiled on a TPU. Wall times from
a CPU run time the interpreter and are NOT TPU numbers. What IS
meaningful on CPU: the HBM-traffic model (flash attention's O(S·d) vs the
reference's O(S²) materialization), which we report as derived bytes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(f, *args, reps=3):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else np.asarray(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
        jax.tree.map(lambda a: a.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps


def bench():
    rows = []
    key = jax.random.key(0)
    # flash attention traffic model
    B, S, nq, nkv, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, nq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
    t_kern = _time(lambda q, k, v: ops.gqa_flash_attention(q, k, v, block_q=128, block_k=128), q, k, v)
    bytes_ref = B * nq * S * S * 4  # materialized logits (one pass)
    bytes_flash = 3 * B * nq * S * hd * 4
    rows.append(
        (
            "flash_attn/S512",
            t_kern * 1e6,
            f"logit-traffic {bytes_ref/2**20:.0f}MiB -> {bytes_flash/2**20:.1f}MiB ({bytes_ref/bytes_flash:.0f}x less)",
        )
    )
    # wkv6 chunked kernel vs naive scan oracle
    B, S, H, hs = 1, 256, 2, 64
    ks = jax.random.split(key, 6)
    w = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, H, hs))) * 0.5 + 0.45
    r = jax.random.normal(ks[1], (B, S, H, hs))
    kk = jax.random.normal(ks[2], (B, S, H, hs))
    vv = jax.random.normal(ks[3], (B, S, H, hs))
    u = jax.random.normal(ks[4], (H, hs)) * 0.1
    s0 = jnp.zeros((B, H, hs, hs))
    t_k = _time(lambda *a: ops.wkv6(*a, chunk=64), w, r, kk, vv, u, s0)
    t_r = _time(lambda *a: ref.wkv6_ref(*a), w, r, kk, vv, u, s0)
    # MXU utilization argument: chunked form does 3 matmuls per chunk vs
    # S outer products
    rows.append(("wkv6_chunked/S256", t_k * 1e6, f"naive-scan={t_r*1e6:.0f}us; chunked form is 3 matmuls/chunk"))
    # dt_pack
    src = jax.random.normal(key, (4096, 64), jnp.float32)
    t_p = _time(lambda s: ops._dtp.dt_pack(s, 16), src)
    rows.append(("dt_pack/4096x16of64", t_p * 1e6, f"{4096*16*4/t_p/1e6:.0f} MB/s on {jax.default_backend()}"))
    return rows


if __name__ == "__main__":
    for r in bench():
        print(",".join(map(str, r)))
