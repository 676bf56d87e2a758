"""Random weights made by the benchmark from ``--seed``, on the device, in
one jitted call and in the type they are served in.

The benchmark keeps them in its own flat layout, which the architecture's
module gives (``shapes`` in ``bench/arch/<name>.py``, per-layer leaves
stacked on axis 0); the plain reference reads that layout, and the
module's ``to_program`` nests the same arrays into the tree the program's
``api`` takes.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from bench import arch


@partial(jax.jit, static_argnums=0)
def _make(s, key):
    out = {}
    for i, (name, (shape, scale, dtype)) in enumerate(sorted(arch.of(s).shapes(s).items())):
        k = jax.random.fold_in(key, i)
        out[name] = (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
    return out


def make(s, seed32: int) -> Dict[str, jax.Array]:
    return _make(s, jax.random.key(seed32))


def check_layout(program_tree, abstract) -> None:
    """Refuse a run whose weights do not have the tree, shapes and dtypes
    of the program's own ``api.init_params`` (``abstract`` is its
    ``jax.eval_shape``)."""
    got = jax.tree_util.tree_structure(program_tree)
    want = jax.tree_util.tree_structure(abstract)
    if got != want:
        raise SystemExit(f"bench: weight tree {got} is not the program's {want}")
    for a, b in zip(jax.tree.leaves(program_tree), jax.tree.leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise SystemExit(f"bench: weight leaf {a.shape} {a.dtype} is not the program's {b.shape} {b.dtype}")
