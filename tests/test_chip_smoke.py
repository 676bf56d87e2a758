"""``chip_smoke.py`` on the CPU: its phases at the reduced preset, and its
refusal to run anywhere but a TPU. The full-width run is the chip's."""

import importlib.util
import os

import jax
import pytest

from repro.configs import get_config

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen1.5-0.5b", smoke=True)


def test_refuses_a_host_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "cpu" in str(exc.value)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_serve_and_paged_serve_phases(smoke, cfg, capsys):
    smoke.serve_phases(
        cfg, 0, max_batch=2, max_len=64, paged_max_len=32,
        prompt_lens=(8, 12), n_requests=4, max_new=4,
    )
    out = capsys.readouterr().out
    assert "cached decode matches" in out
    assert out.count("every request returned exactly 4 tokens") == 2
    assert "paged-serve: token for token" in out


def test_train_phase(smoke, cfg, capsys):
    smoke.train_phase(cfg, 0, batch=2, seq=16, steps=2)
    assert "every loss finite" in capsys.readouterr().out


def test_kernel_phase(smoke, capsys):
    smoke.kernel_phase(0, attn_shape=(2, 128, 64), nseg=40, seg=24, stride=200)
    out = capsys.readouterr().out
    assert "compiled=False" in out and "dt_unpack equals" in out


def test_four_chip_phases_on_a_one_device_mesh(smoke, cfg, capsys):
    """The ``--four-chips`` phases' control flow, on the one CPU device
    (the 8-device suite covers the same paths across devices)."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    smoke.sharded_train_phase(cfg, mesh, 0, batch=4, seq=16)
    smoke.hybrid_allreduce_phase(mesh, 0, n_threads=2, cols=64)
    out = capsys.readouterr().out
    assert "sharded step matches" in out and "allreduce_large matches psum" in out


def test_mismatch_raises(smoke, cfg, monkeypatch):
    """A phase that finds a wrong answer raises; nothing swallows it."""
    monkeypatch.setattr(smoke, "FLASH_BF16_TOL", -1.0)
    with pytest.raises(AssertionError, match="flash_attention"):
        smoke.kernel_phase(0, attn_shape=(1, 128, 64), nseg=8, seg=4, stride=16)
    assert jax.default_backend() == "cpu"
