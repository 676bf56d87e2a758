"""The plain float32 references agree with the program's prefill, cached
decode and loss at the smoke sizes.

The program is run here in float32 (``param_dtype``/``compute_dtype``), so
the two must agree to float32 rounding; the cells serve in bfloat16, where
the gap is what the limits measure. Departures of the program from the
reference, each checked below: none in the forward; in the training loss
the MoE layer drops tokens past ``capacity_factor`` (the reference never
drops), so the MoE loss test gives every expert room for every token, and
the program adds the router balance loss, which the test sets to weight 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import smoke_config

from bench import reference, weights
from bench.harness import model_config

F32 = {"param_dtype": "float32", "compute_dtype": "float32"}
CONFIGS = ["qwen1.5-0.5b", "granite-moe-1b-a400m"]


def setup(name, **opts):
    cf = smoke_config(name)
    s = weights.Spec.from_model(cf["model"])
    cfg = model_config(cf["model"], dict(F32, **opts))
    w = weights.make(s, 1234)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    return s, cfg, w32, weights.to_program(s, w32)


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_and_decode_match(name):
    from repro.models import api

    s, cfg, w, params = setup(name)
    toks = np.random.default_rng(0).integers(0, s.vocab, 24).astype(np.int32)
    ref = np.asarray(reference.forward(s, w, jnp.asarray(toks)))
    with jax.default_matmul_precision("highest"):
        last, cache = api.prefill(cfg, params, {"tokens": toks[None, :20]}, max_len=32)
        np.testing.assert_allclose(np.asarray(last[0]), ref[19], rtol=2e-4, atol=2e-4)
        for i in range(20, 24):
            logits, cache = api.decode_step(cfg, params, cache, jnp.asarray(toks[i : i + 1]), jnp.asarray([i], jnp.int32))
            np.testing.assert_allclose(np.asarray(logits[0]), ref[i], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_gradient_match(name):
    from repro.models import api
    from repro.models.config import MoEConfig

    s, cfg, w, params = setup(name)
    if s.moe:
        cfg = cfg.replace(moe=MoEConfig(**dict(cfg.moe.__dict__, capacity_factor=s.n_experts / s.top_k,
                                                router_aux_weight=0.0)))
    rows = np.random.default_rng(1).integers(0, s.vocab, (2, 16)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(lambda p: api.loss_fn(cfg, p, {"tokens": jnp.asarray(rows)}), has_aux=True)(params)
    ref_loss, ref_g = reference.loss_and_grad(s, w, jnp.asarray(rows))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    from bench.training import from_program

    g = from_program(s, g)
    for k in ref_g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(ref_g[k]), rtol=2e-3, atol=1e-6, err_msg=k)


def test_flash_prefill_matches():
    """The prefill through the flash kernel (interpret mode here) against the reference."""
    from repro.models import api

    s, cfg, w, params = setup("qwen1.5-0.5b", attn_impl="flash")
    toks = np.random.default_rng(2).integers(0, s.vocab, 128).astype(np.int32)
    ref = np.asarray(reference.forward(s, w, jnp.asarray(toks)))
    with jax.default_matmul_precision("highest"):
        last, _ = api.prefill(cfg, params, {"tokens": toks[None]}, max_len=256)
    np.testing.assert_allclose(np.asarray(last[0]), ref[-1], rtol=2e-4, atol=2e-4)


def test_control_is_coarser_than_bf16():
    """The float8 control lies further from the float32 reference than a
    bfloat16 rounding of the same weights does."""
    s, cfg, w, params = setup("qwen1.5-0.5b")
    toks = jnp.asarray(np.random.default_rng(3).integers(0, s.vocab, 32).astype(np.int32))
    ref = reference.forward(s, w, toks)
    bf = reference.forward(s, {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in w.items()}, toks)
    ctl = reference.forward(s, w, toks, quant=True)
    assert float(jnp.max(jnp.abs(ctl - ref))) > 3 * float(jnp.max(jnp.abs(bf - ref)))
