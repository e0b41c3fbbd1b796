"""Single-source-of-truth parameter definitions.

Each model module exposes ``*_defs(cfg, ...) -> pytree[ParamDef]`` describing
GLOBAL parameter shapes together with their mesh ``PartitionSpec``. From one
defs tree we derive:

  * concrete params        (``init_params`` — tests, examples, real training)
  * abstract params         (``abstract_params`` — dry-run ShapeDtypeStructs)
  * the in/out sharding specs for pjit / shard_map (``param_specs``)

so concrete init, dry-run and distribution can never drift apart.
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: P = P()
    scale: float = 1.0
    dtype: str = "float32"
    init: str = "normal"      # normal | zeros | ones

    def stacked(self, n: int) -> "ParamDef":
        """Prepend a scan (layer-stack) dimension."""
        return dataclasses.replace(
            self, shape=(n,) + tuple(self.shape), spec=P(None, *self.spec)
        )


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _leaves(defs):
    return jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_def)


def stack_defs(defs, n: int):
    return jax.tree.map(lambda d: d.stacked(n), defs, is_leaf=is_def)


def param_specs(defs):
    return jax.tree.map(lambda d: d.spec, defs, is_leaf=is_def)


def abstract_params(defs, mesh=None):
    """ShapeDtypeStructs (with NamedSharding when a mesh is given)."""

    def mk(d: ParamDef):
        if mesh is not None:
            sh = jax.sharding.NamedSharding(mesh, d.spec)
            return jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype), sharding=sh)
        return jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype))

    return jax.tree.map(mk, defs, is_leaf=is_def)


def leaf_key(rng, path):
    """The init key of the leaf at ``path``: ``rng`` folded with a CRC-32
    of the path string — stable across processes (Python's ``hash`` of a
    str is salted per process) and independent of leaf order."""
    digest = zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(rng, digest)


def init_params(defs, rng):
    """Concretely initialize a defs tree (see :func:`leaf_key`)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_def)
    leaves = []
    for path, d in flat:
        key = leaf_key(rng, path)
        if d.init == "zeros":
            arr = jnp.zeros(d.shape, jnp.dtype(d.dtype))
        elif d.init == "ones":
            arr = jnp.ones(d.shape, jnp.dtype(d.dtype))
        else:
            arr = jax.random.normal(key, d.shape, jnp.dtype(d.dtype)) * d.scale
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def count_params(defs) -> int:
    flat, _ = _leaves(defs)
    total = 0
    for _, d in flat:
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total


# -- convenience constructors ------------------------------------------------


def linear(in_dim: int, out_dim: int, *, shard: Optional[str] = None,
           shard_dim: int = 1, dtype="float32") -> ParamDef:
    """A (in, out) weight. ``shard``: mesh axis name for ``shard_dim``."""
    spec = [None, None]
    if shard is not None:
        spec[shard_dim] = shard
    return ParamDef((in_dim, out_dim), P(*spec), scale=in_dim ** -0.5, dtype=dtype)


def bias(dim: int, *, shard: Optional[str] = None, dtype="float32") -> ParamDef:
    return ParamDef((dim,), P(shard), scale=0.0, dtype=dtype, init="zeros")


def norm_scale(dim: int, *, shard: Optional[str] = None) -> ParamDef:
    return ParamDef((dim,), P(shard), init="ones")


def embedding(vocab: int, dim: int, *, shard: Optional[str] = None,
              scale: float = 1.0) -> ParamDef:
    # vocab-sharded embedding table
    return ParamDef((vocab, dim), P(shard, None), scale=scale)
