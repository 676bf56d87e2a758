"""Everything in the benchmark that depends on a model's architecture, one
module per architecture: ``bench/arch/<name>.py``, named by the ``"arch"``
key of a configuration file. A new architecture is a new file here; the
rest of the benchmark reaches it through the spec. Each module provides:

    Spec                      frozen, hashable; Spec.from_model(model) from the
                              configuration's "model" block; n_layers, vocab;
                              active_matmul_params(), attention_flops(pairs),
                              and flash_attention_call(S, batch=1) only where
                              the architecture's prefill runs the flash kernel
    shapes(s)                 name -> (shape, init scale, dtype) of the flat
                              layout, per-layer leaves stacked on axis 0
    layer_free(s)             the names of that layout without a layer axis
    to_program(s, w)          the flat layout nested into the program's tree
    from_program(s, tree)     the program's tree (or one shaped like it) flat
    forward(s, w, tokens, quant=False, remat=False)
                              the plain float32 reference: (S,) -> (S, vocab)
"""

from __future__ import annotations

import importlib
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    """The module ``bench/arch/<name>.py``; a missing or unknown name is an
    error, never a default."""
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) \
            or not os.path.isfile(os.path.join(HERE, f"{name}.py")):
        raise SystemExit(f"bench: no architecture module bench/arch/{name}.py")
    return importlib.import_module(f"{__name__}.{name}")


def spec(cfg_file: dict):
    """The Spec of a configuration file, by its architecture's module."""
    if "arch" not in cfg_file:
        raise SystemExit(f"bench: configuration {cfg_file.get('name')!r} names no \"arch\"")
    return load(cfg_file["arch"]).Spec.from_model(cfg_file["model"])


def of(s):
    """The architecture module that defines the class of the spec ``s``."""
    return sys.modules[type(s).__module__]
