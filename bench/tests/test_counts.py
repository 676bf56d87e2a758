"""Peaks table and operation / byte counts from shapes."""

import pytest

from bench import common, counts
from bench.weights import Spec


def test_peaks_known_and_unknown():
    pk = counts.peaks("TPU v5 lite")
    assert pk["bf16_flop_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_active_params_match_the_program_count():
    """Dense: every weight but the QKV biases multiplies each token (the
    tied embedding once, as the LM head)."""
    from repro.models.config import ModelConfig

    cfg_file = common.config_file("qwen1.5-0.5b")
    s = Spec.from_model(cfg_file["model"])
    cfg = ModelConfig(**cfg_file["model"])
    biases = s.n_layers * (s.n_heads + 2 * s.n_kv_heads) * s.head_dim
    assert counts.active_matmul_params(s) == cfg.param_counts()["total"] - biases


def test_moe_counts_top_k_not_every_expert():
    from repro.models.config import ModelConfig, MoEConfig

    m = dict(common.config_file("granite-moe-1b-a400m")["model"])
    s = Spec.from_model(m)
    cfg = ModelConfig(**dict(m, moe=MoEConfig(**m["moe"])))
    assert counts.active_matmul_params(s) == cfg.param_counts()["active"]
    assert counts.active_matmul_params(s) < cfg.param_counts()["total"] / 2


def test_flops_by_hand():
    s = Spec(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, vocab=10)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert counts.active_matmul_params(s) == 2 * per_layer + 10 * 8
    # 3 positions: 6 causal pairs, 4 operations per pair per head-dim element
    attn = 2 * 2 * 4 * 4 * 6
    assert counts.prefill_flops(s, 3) == 2 * counts.active_matmul_params(s) * 3 + attn
    assert counts.train_step_flops(s, 2, 3) == 3 * 2 * counts.prefill_flops(s, 3)
    assert counts.decode_token_flops(s, 5) == 2 * counts.active_matmul_params(s) + 2 * 2 * 4 * 4 * 5


def test_flash_call_bound():
    pk = counts.peaks("TPU v5 lite")
    s = Spec.from_model(common.config_file("qwen1.5-0.5b")["model"])
    short, long_ = counts.flash_attention_call(s, 128), counts.flash_attention_call(s, 3072)
    assert short.bound(pk) == "memory" and long_.bound(pk) == "compute"
    assert short.bytes == 128 * 64 * 2 * 4 * 16
    assert long_.min_seconds(pk) == pytest.approx(long_.flops / 197e12)
