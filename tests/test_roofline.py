"""Roofline machinery: the jaxpr cost model's calibration against XLA
(documenting WHY we don't use XLA's numbers directly), and the HLO
collective parser on synthetic modules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.launch import roofline as rl
from repro.launch.jaxpr_cost import step_cost


def test_xla_cost_analysis_counts_loop_bodies_once():
    """The calibration fact (this is the reason dryrun uses jaxpr_cost):
    XLA-CPU flops are identical for 2 vs 32 scan iterations."""

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def flops(n):
        ws = jax.ShapeDtypeStruct((n, 128, 128), jnp.float32)
        return jax.jit(f).lower(x, ws).compile().cost_analysis()["flops"]

    assert flops(2) == flops(32)


def test_jaxpr_cost_multiplies_trip_counts():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c2 = step_cost(f, x, jax.ShapeDtypeStruct((2, 128, 128), jnp.float32))
    c32 = step_cost(f, x, jax.ShapeDtypeStruct((32, 128, 128), jnp.float32))
    assert abs(c32.flops / c2.flops - 16.0) < 0.5
    assert c32.flops >= 32 * 2 * 128**3


def test_jaxpr_cost_exact_for_plain_matmul():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = step_cost(lambda a, b: a @ b, a, b)
    assert c.flops == 2 * 256 * 512 * 128
    xla = jax.jit(lambda a, b: a @ b).lower(a, b).compile().cost_analysis()["flops"]
    assert c.flops == xla


def test_jaxpr_cost_counts_grad_and_remat():
    def loss(w, x):
        f = jax.checkpoint(lambda w, x: jnp.tanh(x @ w))
        return jnp.sum(f(w, x) ** 2)

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    fwd = step_cost(lambda w, x: jnp.sum(jnp.tanh(x @ w) ** 2), w, x)
    bwd = step_cost(lambda w, x: jax.grad(loss)(w, x), w, x)
    # grad-with-remat ≥ 3 matmul passes (fwd + recompute + 2 bwd dots share)
    assert bwd.flops >= 2.9 * fwd.flops


# ------------------------------------------------------------ HLO parser

HLO_SAMPLE = """
HloModule test

%scan_cond (arg: (s32[], f32[16])) -> pred[] {
  %gte = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(24)
  ROOT %cmp = pred[] compare(%gte, %c), direction=LT
}

%scan_body (arg: (s32[], f32[16])) -> (s32[], f32[16]) {
  %x = f32[16]{0} get-tuple-element(%arg), index=1
  %ar = f32[16]{0} all-reduce(%x), channel_id=1, replica_groups=[2,8]<=[16], to_apply=%add
  %ag = f32[64]{0} all-gather(%ar), channel_id=2, replica_groups=[4,4]<=[16], dimensions={0}
  ROOT %t = (s32[], f32[16]) tuple(%gte, %ar)
}

ENTRY %main (p: f32[16]) -> f32[16] {
  %rs = f32[4]{0} reduce-scatter(%p), channel_id=3, replica_groups=[4,4]<=[16], dimensions={0}
  %cp = f32[16]{0} collective-permute(%p), channel_id=4, source_target_pairs={{0,1}}
  %w = (s32[], f32[16]) while(%init), condition=%scan_cond, body=%scan_body
  ROOT %out = f32[16]{0} get-tuple-element(%w), index=1
}
"""


def test_collective_parser_trip_counts_and_ops():
    stats = rl.collective_bytes(HLO_SAMPLE)
    # while body executes 24×: AR 16 f32 = 64 B; AG result 64 f32 / group 4 = 64 B
    assert stats.per_op_bytes["all-reduce"] == 24 * 64
    assert stats.per_op_bytes["all-gather"] == 24 * 64
    # entry: RS result 4 f32 × group 4 = 64 B; CP = 64 B
    assert stats.per_op_bytes["reduce-scatter"] == 64
    assert stats.per_op_bytes["collective-permute"] == 64
    assert stats.per_op_count["all-reduce"] == 24


def test_roofline_terms_and_bottleneck():
    t = rl.RooflineTerms(
        flops=197e12, hbm_bytes=819e9 / 2, coll_bytes_per_chip=50e9 * 2, n_chips=256,
        model_flops=197e12 * 256 * 0.5,
    )
    assert abs(t.t_compute - 1.0) < 1e-6
    assert abs(t.t_memory - 0.5) < 1e-6
    assert abs(t.t_collective - 2.0) < 1e-6
    assert t.bottleneck == "collective"
    assert abs(t.useful_flops_ratio - 0.5) < 1e-6
    assert abs(t.roofline_fraction - 0.25) < 1e-6


def test_model_step_flops_conventions():
    from repro.configs import get_config, registry

    cfg = get_config("llama3-405b")
    tr = rl.model_step_flops(cfg, registry.get_shape("train_4k"))
    n = cfg.param_counts()["active"]
    assert abs(tr - 6 * n * 256 * 4096) / tr < 1e-9
    de = rl.model_step_flops(cfg, registry.get_shape("decode_32k"))
    assert abs(de - 2 * n * 128) / de < 1e-9
