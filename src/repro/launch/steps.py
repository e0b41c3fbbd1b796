"""Builders that bind (architecture × input shape × mesh) to a lowerable
SPMD step function plus its abstract input specs.

Used by launch/dryrun.py (lower+compile+roofline), launch/train.py and
launch/serve.py (real execution on small meshes). ``input_specs`` follow the
required dry-run pattern: ShapeDtypeStructs with NamedShardings — weak-type
correct, shardable, no device allocation.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import (FedConfig, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.configs.registry import ArchSpec
from repro.core.mesh import fed_batch_defs, fed_state_defs, jit_fed_round
from repro.models import params as pdefs
from repro.models.model import Model
from repro.sharding.rules import ParallelContext


# ---------------------------------------------------------------------------
# Resolution helpers
# ---------------------------------------------------------------------------


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_fed(spec: ArchSpec, fed: FedConfig, mesh) -> FedConfig:
    """Bind client axes + client count to the mesh per the arch's FL mode."""
    sizes = mesh_axis_sizes(mesh)
    if spec.client_mode == "per_pod":
        axes = tuple(a for a in ("pod",) if a in sizes)
    else:
        axes = tuple(a for a in ("pod", "data") if a in sizes)
    m = 1
    for a in axes:
        m *= sizes[a]
    shard_axes = axes if axes else tuple(a for a in ("data",) if a in sizes)
    shards = 1
    for a in shard_axes:
        shards *= sizes[a]
    return dataclasses.replace(fed, client_axes=axes, num_clients=m,
                               state_shards=shards)


def serve_ctx(mesh, *, seq_sharded: bool) -> ParallelContext:
    sizes = mesh_axis_sizes(mesh)
    return ParallelContext(
        model_axis="model", tp=sizes.get("model", 1),
        seq_axis="data" if seq_sharded else None,
        seq_shards=sizes.get("data", 1) if seq_sharded else 1)


def serve_batch_axes(mesh) -> Tuple[str, ...]:
    sizes = mesh_axis_sizes(mesh)
    return tuple(a for a in ("pod", "data") if a in sizes)


def remap_defs(defs, mapping: Dict[str, Any]):
    """Rewrite mesh-axis names inside ParamDef specs (e.g. "data" ->
    ("pod","data") when a batch dim spreads over two axes)."""

    def one(d: pdefs.ParamDef) -> pdefs.ParamDef:
        spec = P(*(mapping.get(e, e) if isinstance(e, str) else e
                   for e in d.spec))
        return dataclasses.replace(d, spec=spec)

    return jax.tree.map(one, defs, is_leaf=pdefs.is_def)


def variant_for_shape(spec: ArchSpec, shape: ShapeConfig) -> ModelConfig:
    """Apply the (flagged) sliding-window long-context variant if needed."""
    cfg = spec.model
    if shape.name == "long_500k" and spec.long_500k == "variant":
        w = cfg.long_context_variant_window or 4096
        cfg = dataclasses.replace(cfg, attn_pattern=(w,))
    return cfg


def shape_allowed(spec: ArchSpec, shape: ShapeConfig) -> Tuple[bool, str]:
    if shape.kind == "decode" and not spec.has_decode:
        return False, "encoder-only architecture: no decode step"
    if shape.name == "long_500k" and spec.long_500k == "skip":
        return False, "pure full-attention / encoder arch: long_500k skipped"
    return True, ""


# ---------------------------------------------------------------------------
# Step bundles
# ---------------------------------------------------------------------------


@dataclass
class StepBundle:
    """A jit-wrapped SPMD step plus abstract inputs for .lower()."""

    fn: Callable
    abstract_args: Tuple
    model: Model
    fed: Optional[FedConfig] = None
    description: str = ""

    def lower(self):
        return self.fn.lower(*self.abstract_args)


def _specs(defs):
    return jax.tree.map(lambda d: d.spec, defs, is_leaf=pdefs.is_def)


def build_train_step(spec: ArchSpec, shape: ShapeConfig, mesh,
                     fed: FedConfig, train: TrainConfig,
                     *, kernel_impl=None, chunk: int = 2048) -> StepBundle:
    """The paper's fed_round as the train step for this (arch, mesh)."""
    assert shape.kind == "train"
    cfg = spec.model
    sizes = mesh_axis_sizes(mesh)
    fed = resolve_fed(spec, fed, mesh)
    train = dataclasses.replace(train, global_batch=shape.global_batch,
                                seq_len=shape.seq_len)
    model = Model(cfg, tp=sizes.get("model", 1))
    sdefs = fed_state_defs(model, fed)
    bdefs = fed_batch_defs(model, fed, train)
    fn = jit_fed_round(model, fed, train, mesh, kernel_impl=kernel_impl,
                       chunk=chunk)
    abstract = (pdefs.abstract_params(sdefs, mesh),
                pdefs.abstract_params(bdefs, mesh),
                jax.ShapeDtypeStruct((), jnp.int32))
    return StepBundle(fn=fn, abstract_args=abstract, model=model, fed=fed,
                      description=f"fed_round[{fed.algorithm}/"
                                  f"{fed.compressor}:{fed.aggregation}] "
                                  f"K={fed.local_steps} m={fed.num_clients}")


def build_prefill_step(spec: ArchSpec, shape: ShapeConfig, mesh,
                       *, chunk: int = 2048) -> StepBundle:
    cfg = variant_for_shape(spec, shape)
    sizes = mesh_axis_sizes(mesh)
    model = Model(cfg, tp=sizes.get("model", 1))
    ctx = serve_ctx(mesh, seq_sharded=False)
    baxes = serve_batch_axes(mesh)
    bax = baxes[0] if len(baxes) == 1 else tuple(baxes)

    pdefs_tree = model.defs()
    param_specs = _specs(pdefs_tree)

    if cfg.is_encoder:
        bdefs = {"embeddings": pdefs.ParamDef(
            (shape.global_batch, shape.seq_len, cfg.d_model),
            P(bax, None, None), dtype=cfg.dtype)}
        bspecs = _specs(bdefs)

        def step(params, batch):
            return model.encode(params, batch, ctx, chunk=chunk)

        out_specs = P(bax, None, "model")
        fn = jax.jit(jax.shard_map(step, mesh=mesh,
                                   in_specs=(param_specs, bspecs),
                                   out_specs=out_specs))
        abstract = (model.abstract_params(mesh),
                    pdefs.abstract_params(bdefs, mesh))
        return StepBundle(fn=fn, abstract_args=abstract, model=model,
                          description="encode (encoder-only prefill)")

    cdefs = model.cache_defs(shape.global_batch, shape.seq_len,
                             seq_sharded=False)
    if len(baxes) > 1:
        cdefs = remap_defs(cdefs, {"data": bax})
    cache_specs = _specs(cdefs)
    tok_def = pdefs.ParamDef((shape.global_batch, shape.seq_len),
                             P(bax, None), dtype="int32")

    def step(params, tokens):
        return model.prefill(params, tokens, ctx, max_len=shape.seq_len,
                             chunk=chunk)

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(param_specs, tok_def.spec),
        out_specs=(P(bax, "model"), cache_specs)))
    abstract = (model.abstract_params(mesh),
                pdefs.abstract_params({"t": tok_def}, mesh)["t"])
    return StepBundle(fn=fn, abstract_args=abstract, model=model,
                      description="prefill")


def build_decode_step(spec: ArchSpec, shape: ShapeConfig, mesh,
                      *, chunk: int = 2048) -> StepBundle:
    cfg = variant_for_shape(spec, shape)
    sizes = mesh_axis_sizes(mesh)
    model = Model(cfg, tp=sizes.get("model", 1))
    seq_sharded = shape.name == "long_500k"
    ctx = serve_ctx(mesh, seq_sharded=seq_sharded)
    baxes = serve_batch_axes(mesh)
    bax = (baxes[0] if len(baxes) == 1 else tuple(baxes)) if not seq_sharded else None

    param_specs = _specs(model.defs())
    cdefs = model.cache_defs(shape.global_batch, shape.seq_len,
                             seq_sharded=seq_sharded)
    if not seq_sharded and len(baxes) > 1:
        cdefs = remap_defs(cdefs, {"data": bax})
    cache_specs = _specs(cdefs)
    tok_def = pdefs.ParamDef((shape.global_batch, 1), P(bax, None),
                             dtype="int32")

    def step(params, token, caches, pos):
        return model.decode_step(params, token, caches, pos, ctx,
                                 max_len=shape.seq_len)

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(param_specs, tok_def.spec, cache_specs, P()),
        out_specs=(P(bax, "model"), cache_specs)))
    abstract = (model.abstract_params(mesh),
                pdefs.abstract_params({"t": tok_def}, mesh)["t"],
                pdefs.abstract_params(cdefs, mesh),
                jax.ShapeDtypeStruct((), jnp.int32))
    return StepBundle(fn=fn, abstract_args=abstract, model=model,
                      description="decode" + (" (seq-sharded cache)"
                                              if seq_sharded else ""))


def build_step(spec: ArchSpec, shape: ShapeConfig, mesh, fed: FedConfig,
               train: TrainConfig, **kw) -> StepBundle:
    if shape.kind == "train":
        return build_train_step(spec, shape, mesh, fed, train, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(spec, shape, mesh,
                                  chunk=kw.get("chunk", 2048))
    return build_decode_step(spec, shape, mesh, chunk=kw.get("chunk", 2048))
