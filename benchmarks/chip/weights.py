"""Seeded weights, made by the benchmark and fed to the program and to the
reference alike, so the reference takes nothing the program has made.

Each leaf is drawn from its own key: the run's key folded with a CRC-32 of
the leaf's path, so a leaf's values do not depend on which other leaves
exist or on their order.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from reference.common import is_leaf


def run_key(seed: int):
    """A PRNG key for any whole ``seed`` up to 2**62: its low 31 bits seed
    the key and the rest are folded in."""
    lo, hi = seed & 0x7FFFFFFF, seed >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def path_name(path) -> str:
    return jax.tree_util.keystr(path)


def leaf_value(key, path, leaf):
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, jnp.float32)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(path_name(path).encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, leaf.shape, jnp.float32) * leaf.scale


def generate(defs, key):
    """float32 parameters for a tree of :class:`~reference.common.Leaf`.
    Call it under ``jax.jit`` so they are made on the device."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: leaf_value(key, p, l), defs, is_leaf=is_leaf)


def shapes(defs) -> dict:
    """Flat ``{path: shape}`` of a tree of leaves."""
    flat, _ = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_leaf)
    return {path_name(p): tuple(l.shape) for p, l in flat}


def leaf_norms(tree) -> dict:
    """``{path: L2 norm}`` of every leaf (float32 accumulation)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_name(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def change_norms(params, defs, key) -> dict:
    """``{path: |params - initial|}``, the initial leaves drawn afresh from
    ``key`` inside the same program, so no copy of them is kept."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    dflat = dict((path_name(p), (p, l)) for p, l in
                 jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_leaf)[0])
    out = {}
    for p, x in flat:
        dp, leaf = dflat[path_name(p)]
        d = x.astype(jnp.float32) - leaf_value(key, dp, leaf)
        out[path_name(p)] = jnp.sqrt(jnp.sum(jnp.square(d)))
    return out
