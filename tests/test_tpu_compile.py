"""Compile the main path's Pallas kernels at real widths for one described
TPU v5e chip. Nothing runs: each case shows that the chip's compiler
accepts the kernel (block tiling, VMEM) and that it lowers to a Mosaic
custom call rather than to the interpreter, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every xdist
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dt_pack as dtp
from repro.kernels import flash_attention as fa


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without a chip, so the cache stays off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


# name -> (function, argument shapes). interpret=False because this
# process's backend is the CPU, where the default would interpret.
CASES = {
    "flash_attention-16x2048x64-bf16": (
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        [((16, 2048, 64), jnp.bfloat16)] * 3,
    ),
    "dt_pack-1000x3000-f32": (
        lambda s: dtp.dt_pack(s, 1000, interpret=False),
        [((1000, 3000), jnp.float32)],
    ),
    "dt_unpack-1000x3000-f32": (
        lambda p: dtp.dt_unpack(p, 3000, interpret=False),
        [((1000, 1000), jnp.float32)],
    ),
    "dt_pack-2048x256-u8": (
        lambda s: dtp.dt_pack(s, 100, interpret=False),
        [((2048, 256), jnp.uint8)],
    ),
    "dt_unpack-2048x256-u8": (
        lambda p: dtp.dt_unpack(p, 256, interpret=False),
        [((2048, 100), jnp.uint8)],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
