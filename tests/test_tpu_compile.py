"""Compile the main path's Pallas kernels, and the serving cells' decode
step, at real widths for one described TPU v5e chip. Nothing runs: each
kernel case shows that the chip's compiler accepts the kernel (block
tiling, VMEM) and that it lowers to a Mosaic custom call rather than to the
interpreter; each decode case reads the compiled program's layer loop for
whole-cache traffic, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every xdist
worker imports this file.
"""

import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dt_pack as dtp
from repro.kernels import flash_attention as fa
from repro.models import api
from repro.models.config import ModelConfig, MoEConfig

BENCH = Path(__file__).resolve().parents[1] / "bench"


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


# The serving cells' decode shapes: (configuration, traffic mix) under bench/.
SERVING_CELLS = {
    "qwen1.5-0.5b.chat": ("qwen1.5-0.5b", "chat"),
    "granite-moe-1b-a400m.offline": ("granite-moe-1b-a400m", "offline"),
}
_CACHE_PASS_OPS = {"copy", "dynamic-slice", "dynamic-update-slice"}
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _computations(hlo: str) -> dict:
    """name -> instruction lines of every computation in an HLO module's text."""
    blocks = re.findall(r"^(?:ENTRY )?%([\w.\-]+) [^\n]*\{\n(.*?)\n\}", hlo, re.S | re.M)
    return {name: body.split("\n") for name, body in blocks}


def _root_opcode(lines) -> str:
    m = _INSTR.match(next(l for l in lines if l.lstrip().startswith("ROOT ")))
    return m.group(3) if m else ""


def _cache_passes(hlo: str, min_elems: int):
    """Instructions in a while body that copy or (update-)slice at least
    ``min_elems`` elements: a copy, a dynamic-(update-)slice, or a fusion
    named constant_dynamic-* or rooted in one. In-place scatters pass."""
    comps = _computations(hlo)
    bodies = re.findall(r"body=%([\w.\-]+)", hlo)
    found = []
    for body in bodies:
        for line in comps[body]:
            m = _INSTR.match(line)
            if not m:
                continue
            name, dims, op = m.groups()
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n < min_elems:
                continue
            if op == "fusion":
                called = _CALLS.search(line)
                root = _root_opcode(comps[called.group(1)]) if called else ""
                if name.startswith("constant_dynamic-") or root in _CACHE_PASS_OPS:
                    found.append(f"{name} ({root})")
            elif op in _CACHE_PASS_OPS:
                found.append(name)
    return bodies, found


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_decode_step_updates_cache_in_place_on_v5e(cell, one_chip, no_compile_cache):
    """The layer scan of ``api.decode_step`` writes each layer's new token
    into the carried cache stack and reads it there: nothing of a layer's
    cache size is copied, sliced out or written back inside the loop, and
    the step needs no cache-sized scratch."""
    config, traffic = SERVING_CELLS[cell]
    model = dict(json.loads((BENCH / "configs" / f"{config}.json").read_text())["model"])
    if "moe" in model:
        model["moe"] = MoEConfig(**model["moe"])
    engine = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())["engine"]
    cfg = ModelConfig(**model)
    B, T = int(engine["max_batch"]), int(engine["max_len"])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, api.abstract_params(cfg))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: api.init_cache(cfg, B, T)))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos))
    compiled = step.lower(params, cache, vec, vec).compile()

    layer_elems = B * T * cfg.n_kv_heads * cfg.resolved_head_dim
    bodies, passes = _cache_passes(compiled.as_text(), layer_elems)
    assert bodies, f"{cell}: no layer loop in the compiled decode step"
    assert not passes, f"{cell}: whole-layer cache passes inside the layer loop: {passes}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5e9, f"{cell}: {temp / 1e9:.2f} GB of temp space"
