"""Faults planted under the timed path, to show that the comparison which
decides ``correct`` catches them. The cells' own runs never plant one; the
tests under ``bench/tests`` do at the smoke sizes, and
``bench/calibrate.py readings --fault <name>`` does at a cell's own size.

Each takes the engine (serving) or the Trainer (training) after set-up has
built it and before anything runs through it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def altered_token(eng):
    """A token altered where it is produced: every decoded token moved by one."""
    decode = eng._decode_active

    def bad():
        active, nxt = decode()
        return active, None if nxt is None else (nxt + 1) % eng.cfg.vocab

    eng._decode_active = bad


def cache_unchanged(eng):
    """A step that returns its state unchanged: decode never writes the KV
    cache. The decode is handed a copy, so one that donates its cache
    consumes the copy and the unchanged cache is still there to return."""
    decode = eng._decode

    def bad(p, c, t, pos):
        return decode(p, jax.tree.map(jnp.copy, c), t, pos)[0], c

    eng._decode = bad


def state_unchanged(tr):
    """A train step that returns its parameters and optimizer state unchanged."""
    step = tr.step_fn

    def bad(params, opt_state, batch):
        return params, opt_state, step(params, opt_state, batch)[2]

    tr.step_fn = bad


def half_batch(tr):
    """Half of the batch left out, the mean taken over the rest."""
    step = tr.step_fn

    def bad(params, opt_state, batch):
        return step(params, opt_state, {"tokens": batch["tokens"][: batch["tokens"].shape[0] // 2]})

    tr.step_fn = bad


SERVING = {"altered_token": altered_token, "cache_unchanged": cache_unchanged}
TRAINING = {"state_unchanged": state_unchanged, "half_batch": half_batch}
ALL = {**SERVING, **TRAINING}
