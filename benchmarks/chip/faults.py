"""Faults planted beneath the harness, to show that ``correct`` catches
them: each wraps the compiled round (``Program(..., break_step=...)``) and
breaks it in one way a later change could.

* ``unchanged`` — the round computes its loss but hands back the state it
  was given;
* ``half_batch`` — each client trains on the first half of its rows, seen
  twice, so every mean is taken over half of its batch;
* ``loss_altered`` — the loss the round reports is off by 1%.

The fourth fault, the exchange between chips left out, cannot be planted
around the compiled round; ``no_exchange`` plants it in the program's
client collective before the round is built (four-chip cells only).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np


def unchanged(step):
    def run(state, batch, seed):
        keep = jax.tree.map(jnp.copy, state)
        _, met = step(state, batch, seed)
        return keep, met
    return run


def half_batch(step, clients: int):
    def run(state, batch, seed):
        def halve(x):
            rows = x.shape[1] // clients
            idx = np.concatenate([c * rows + np.tile(np.arange(rows // 2), 2)
                                  for c in range(clients)])
            return jax.device_put(x[:, idx], x.sharding)
        return step(state, jax.tree.map(halve, batch), seed)
    return run


def loss_altered(step):
    def run(state, batch, seed):
        state, met = step(state, batch, seed)
        return state, {**met, "loss": met["loss"] * 1.01}
    return run


@contextlib.contextmanager
def no_exchange():
    """While the round is built: the client-axis all_gather hands every
    chip the first client's contribution alone, as if the others had never
    been exchanged (kept the same on every chip, so the round still types)."""
    from repro.sharding.rules import ParallelContext
    orig = ParallelContext.all_gather_clients
    ParallelContext.all_gather_clients = (
        lambda self, x, axis=0: jax.lax.slice_in_dim(
            orig(self, x, axis), 0, x.shape[axis], axis=axis))
    try:
        yield
    finally:
        ParallelContext.all_gather_clients = orig


def wrapper(name: str, clients: int):
    """The ``break_step`` of a fault that wraps the compiled round."""
    if name == "unchanged":
        return unchanged
    if name == "half_batch":
        return lambda step: half_batch(step, clients)
    if name == "loss_altered":
        return loss_altered
    raise KeyError(name)
