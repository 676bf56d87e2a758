"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
pure-jnp oracles (interpret mode executes kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datatype as dt
from repro.kernels import ops, ref
from repro.kernels import dt_pack as dtp
from repro.kernels import flash_attention as fa
from repro.kernels import rwkv6_scan as wkv

KEY = jax.random.key(7)


# ------------------------------------------------------------ flash attn


@pytest.mark.parametrize("S,hd,dtype", [
    (128, 64, jnp.float32),
    (256, 64, jnp.float32),
    (128, 128, jnp.float32),
    (256, 64, jnp.bfloat16),
])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 64)])
def test_flash_attention_sweep(S, hd, dtype, blocks):
    bq, bk = blocks
    B = 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, hd), jnp.float32).astype(dtype)
    o = fa.flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    o_ref = ref.attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_gqa_groups(nq, nkv):
    B, S, hd = 1, 128, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, nq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
    o = ops.gqa_flash_attention(q, k, v, block_q=64, block_k=64)
    G = nq // nkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * nq, S, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * nq, S, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * nq, S, hd)
    o_ref = ref.attention_ref(qf, kf, vf).reshape(B, nq, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-5, rtol=3e-5)


def test_flash_attention_noncausal():
    B, S, hd = 1, 128, 64
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, hd), jnp.float32) for i in range(3))
    o = fa.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    o_ref = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ wkv6


@pytest.mark.parametrize("S,chunk", [(64, 32), (128, 64), (256, 64), (96, 32)])
def test_wkv6_sweep(S, chunk):
    B, H, hs = 2, 2, 64
    ks = jax.random.split(KEY, 6)
    w = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, H, hs))) * 0.5 + 0.45
    r = jax.random.normal(ks[1], (B, S, H, hs))
    k = jax.random.normal(ks[2], (B, S, H, hs))
    v = jax.random.normal(ks[3], (B, S, H, hs))
    u = jax.random.normal(ks[4], (H, hs)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, hs, hs)) * 0.1
    y, sT = wkv.wkv6_chunked(w, r, k, v, u, s0, chunk=chunk, interpret=True)
    y_ref, sT_ref = ref.wkv6_ref(w, r, k, v, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref), atol=5e-4, rtol=5e-4)


def test_wkv6_strong_decay_stability():
    """Near-zero decay (w→0) must not overflow the log-space ratios."""
    B, S, H, hs = 1, 64, 1, 64
    ks = jax.random.split(KEY, 5)
    w = jnp.full((B, S, H, hs), 1e-6)
    r = jax.random.normal(ks[1], (B, S, H, hs))
    k = jax.random.normal(ks[2], (B, S, H, hs))
    v = jax.random.normal(ks[3], (B, S, H, hs))
    u = jnp.zeros((H, hs))
    s0 = jnp.zeros((B, H, hs, hs))
    y, sT = wkv.wkv6_chunked(w, r, k, v, u, s0, chunk=32, interpret=True)
    assert np.all(np.isfinite(np.asarray(y)))
    y_ref, _ = ref.wkv6_ref(w, r, k, v, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3, rtol=1e-2)


def test_wkv6_model_integration():
    """models.rwkv6 with use_kernel=True matches the default path."""
    from repro.configs import get_config
    from repro.models import rwkv6 as R

    cfg = get_config("rwkv6-7b", smoke=True)
    params = R.init_rwkv(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 128), 0, cfg.vocab)
    logits_default, _ = R.rwkv_forward(cfg, params, {"tokens": toks})
    logits_kernel, _ = R.rwkv_forward(cfg, params, {"tokens": toks}, use_kernel=True)
    a, b = np.asarray(logits_default, np.float32), np.asarray(logits_kernel, np.float32)
    assert np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9) < 0.02


# ------------------------------------------------------------ dt_pack


@pytest.mark.parametrize("nseg,seg,stride,dtype", [
    (64, 8, 16, jnp.float32),
    (128, 16, 64, jnp.float32),
    (256, 4, 8, jnp.bfloat16),
    (32, 32, 32, jnp.float32),  # dense: seg == stride
    (61, 8, 16, jnp.float32),   # odd nseg, fits one block
    (300, 16, 4096, jnp.float32),  # nseg % vmem-block != 0: main+tail path
    (1000, 1000, 3000, jnp.float32),  # sublane-rounded blocks + tail
])
def test_dt_pack_sweep(nseg, seg, stride, dtype):
    src = jax.random.normal(KEY, (nseg, stride), jnp.float32).astype(dtype)
    out = dtp.dt_pack(src, seg, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.pack_ref(src, seg)))
    back = dtp.dt_unpack(out, stride, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(ref.unpack_ref(out, stride)))


@pytest.mark.parametrize("nseg,seg,stride,dtype", [
    (1000, 1000, 3000, jnp.float32),
    (300, 16, 4096, jnp.float32),
    (4096, 512, 1024, jnp.bfloat16),
    (2048, 100, 256, jnp.uint8),
    (100000, 64, 128, jnp.uint8),
    (5, 1 << 20, 1 << 20, jnp.float32),  # wider than the budget: one tile
])
def test_dt_pack_blocks_fit_tiling_and_vmem(nseg, seg, stride, dtype):
    """The TPU compiler takes a block of whole sublane tiles or the full
    extent; the double-buffered in+out blocks stay within the budget
    unless one tile alone exceeds it."""
    item = jnp.dtype(dtype).itemsize
    bs = dtp._block_segs(nseg, stride, seg, item)
    sub = dtp._sublanes(item)
    assert bs == nseg or bs % sub == 0
    lanes = lambda n: -(-n // 128) * 128
    if bs > sub:
        assert 2 * bs * (lanes(stride) + lanes(seg)) * item <= dtp.VMEM_BUDGET


def test_resolve_interpret_is_the_one_backend_rule(monkeypatch):
    assert ops.resolve_interpret(None) is True  # this process runs on the CPU
    assert ops.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    assert ops.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True"):
        ops.resolve_interpret(True)


def test_pack_datatype_matches_host_engine():
    base = dt.predefined(4)
    v = dt.vector(32, 5, 9, base)
    buf = np.arange(32 * 9 + 7, dtype=np.float32)
    dev = ops.pack_datatype(jnp.asarray(buf), v)
    host = dt.pack(buf.view(np.uint8), v).view(np.float32)
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_pack_datatype_rejects_irregular():
    irr = dt.indexed([1, 2, 1], [0, 3, 9], dt.predefined(4))
    with pytest.raises(ValueError, match="irregular"):
        ops.pack_datatype(jnp.zeros(64, jnp.float32), irr)


def test_pack_datatype_rejects_adversarial_affine_probes():
    """Regression: the sampled pack_info routed this hindexed layout
    (first/middle/last segments affine, segment 2 off-grid) to the dense
    kernel, which packed the wrong bytes. The exact check must refuse."""
    adv = dt.hindexed([1] * 6, [0, 40, 100, 120, 160, 200], dt.predefined(8))
    with pytest.raises(ValueError, match="irregular"):
        ops.pack_datatype(jnp.zeros(64, jnp.float32), adv)


def test_pack_datatype_accepts_precomputed_info():
    v = dt.vector(8, 2, 4, dt.predefined(4))
    buf = jnp.arange(8 * 4, dtype=jnp.float32)
    info = dt.pack_info(v)
    np.testing.assert_array_equal(
        np.asarray(ops.pack_datatype(buf, v, info=info)),
        np.asarray(ops.pack_datatype(buf, v)),
    )
