"""Peaks table and operation / byte counts from shapes."""

import pytest

from bench import arch, common, counts
from bench.arch.gqa import Spec


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
    s = arch.spec(cfg_file)
    cfg = ModelConfig(**cfg_file["model"])
    biases = s.n_layers * (s.n_heads + 2 * s.n_kv_heads) * s.head_dim
    assert s.active_matmul_params() == cfg.param_counts()["total"] - biases


def test_moe_counts_top_k_not_every_expert():
    from repro.models.config import ModelConfig, MoEConfig

    cfg_file = common.config_file("granite-moe-1b-a400m")
    m = dict(cfg_file["model"])
    s = arch.spec(cfg_file)
    cfg = ModelConfig(**dict(m, moe=MoEConfig(**m["moe"])))
    assert s.active_matmul_params() == cfg.param_counts()["active"]
    assert s.active_matmul_params() < cfg.param_counts()["total"] / 2


def test_flops_by_hand():
    s = Spec(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, vocab=10)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert s.active_matmul_params() == 2 * per_layer + 10 * 8
    # 3 positions: 6 causal pairs, 4 operations per pair per head-dim element
    attn = 2 * 2 * 4 * 4 * 6
    assert counts.prefill_flops(s, 3) == 2 * s.active_matmul_params() * 3 + attn
    assert counts.train_step_flops(s, 2, 3) == 3 * 2 * counts.prefill_flops(s, 3)
    assert counts.decode_token_flops(s, 5) == 2 * s.active_matmul_params() + 2 * 2 * 4 * 4 * 5


def test_flash_call_bound():
    pk = counts.peaks("TPU v5 lite")
    s = arch.spec(common.config_file("qwen1.5-0.5b"))
    short, long_ = counts.flash_attention_call(s, 128), counts.flash_attention_call(s, 3072)
    assert short.bound(pk) == "memory" and long_.bound(pk) == "compute"
    assert short.bytes == 128 * 64 * 2 * 4 * 16
    assert long_.min_seconds(pk) == pytest.approx(long_.flops / 197e12)


# Counts at the shipped configurations' published sizes, pinned to what the
# benchmark computed before its architecture code moved into bench/arch/:
# prefill and decode at S in {128, 256, 768}, the train cell's step of
# 4 x 512, and qwen's flash-attention call (operations, bytes).
PINNED = {
    "qwen1.5-0.5b": {
        "prefill": {128: 119560732672, 256: 240732078080, 768: 741523587072},
        "decode": {128: 940310528, 256: 952893440, 768: 1003225088},
        "train_4x512": 5854879285248,
        "flash": {128: (33816576, 1048576), 256: (134742016, 2097152), 768: (1209532416, 6291456)},
    },
    "granite-moe-1b-a400m": {
        "prefill": {128: 110535376896, 256: 222681366528, 768: 687371452416},
        "decode": {128: 869799936, 256: 882382848, 768: 932714496},
        "train_4x512": 5421662208000,
        "flash": {},
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_pinned_at_published_sizes(name):
    s = arch.spec(common.config_file(name))
    want = PINNED[name]
    assert {S: counts.prefill_flops(s, S) for S in want["prefill"]} == want["prefill"]
    assert {S: counts.decode_token_flops(s, S) for S in want["decode"]} == want["decode"]
    assert counts.train_step_flops(s, 4, 512) == want["train_4x512"]
    for S, (flops, bytes_) in want["flash"].items():
        assert counts.flash_attention_call(s, S) == counts.KernelCall(flops, bytes_)


def test_no_flash_call_where_the_architecture_has_none():
    """An architecture whose spec counts no flash call reads None, so the
    kernel's roofline reader finds nothing to read."""

    class NoFlash:
        pass

    assert counts.flash_attention_call(NoFlash(), 128) is None
