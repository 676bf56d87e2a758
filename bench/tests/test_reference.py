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

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import smoke_config

from bench import arch, reference, weights
from bench.arch import gqa
from bench.harness import model_config

F32 = {"param_dtype": "float32", "compute_dtype": "float32"}
CONFIGS = ["qwen1.5-0.5b", "granite-moe-1b-a400m"]


def setup(name, **opts):
    cf = smoke_config(name)
    s = arch.spec(cf)
    cfg = model_config(cf["model"], dict(F32, **opts))
    w = weights.make(s, 1234)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    return s, cfg, w32, gqa.to_program(s, w32)


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_and_decode_match(name):
    from repro.models import api

    s, cfg, w, params = setup(name)
    toks = np.random.default_rng(0).integers(0, s.vocab, 24).astype(np.int32)
    ref = np.asarray(gqa.forward(s, w, jnp.asarray(toks)))
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
    g = gqa.from_program(s, g)
    for k in ref_g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(ref_g[k]), rtol=2e-3, atol=1e-6, err_msg=k)


def test_flash_prefill_matches():
    """The prefill through the flash kernel (interpret mode here) against the reference."""
    from repro.models import api

    s, cfg, w, params = setup("qwen1.5-0.5b", attn_impl="flash")
    toks = np.random.default_rng(2).integers(0, s.vocab, 128).astype(np.int32)
    ref = np.asarray(gqa.forward(s, w, jnp.asarray(toks)))
    with jax.default_matmul_precision("highest"):
        last, _ = api.prefill(cfg, params, {"tokens": toks[None]}, max_len=256)
    np.testing.assert_allclose(np.asarray(last[0]), ref[-1], rtol=2e-4, atol=2e-4)


def test_control_is_coarser_than_bf16():
    """The float8 control lies further from the float32 reference than a
    bfloat16 rounding of the same weights does."""
    s, cfg, w, params = setup("qwen1.5-0.5b")
    toks = jnp.asarray(np.random.default_rng(3).integers(0, s.vocab, 32).astype(np.int32))
    ref = gqa.forward(s, w, toks)
    bf = gqa.forward(s, {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in w.items()}, toks)
    ctl = gqa.forward(s, w, toks, quant=True)
    assert float(jnp.max(jnp.abs(ctl - ref))) > 3 * float(jnp.max(jnp.abs(bf - ref)))


# sha256 of the weights the benchmark made at each smoke configuration from
# seed 0 on the CPU before its architecture code moved into bench/arch/.
WEIGHTS_SHA256 = {
    "qwen1.5-0.5b": "149a4a3c8705a3cc5343cd70a0f362eb3abb79e24a3524eb16a682f3904c6087",
    "granite-moe-1b-a400m": "afe96d2875d98cccdf99192d2db0e80afd808ab2b5d3f66b2610a4c65a7cfc7a",
}


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_pinned(name):
    w = weights.make(arch.spec(smoke_config(name)), 0)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[name]


@pytest.mark.parametrize("value", [None, "no_such_arch", "../gqa", ""])
def test_architecture_must_be_named(value):
    """A configuration with no "arch", or one naming no module under
    bench/arch/, is refused: never a default."""
    cf = smoke_config("qwen1.5-0.5b")
    if value is None:
        del cf["arch"]
    else:
        cf["arch"] = value
    with pytest.raises(SystemExit, match="arch"):
        arch.spec(cf)
