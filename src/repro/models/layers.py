"""Shared neural-net building blocks (TP-aware, shard_map style).

Conventions:
  * Activations are replicated over the "model" axis; only weights are sharded.
  * Column-parallel linears produce sharded features (no collective);
    row-parallel linears consume sharded features and finish with psum.
  * All matmuls run in ``cfg.dtype`` (bf16 by default); params live in fp32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import params as pdefs
from repro.sharding.rules import ParallelContext


def cast(x, dtype: str):
    return x.astype(jnp.dtype(dtype))


def rms_norm(scale, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def activation(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {kind!r}")


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float = 10000.0):
    """Apply rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated FFN (column/row parallel)
# ---------------------------------------------------------------------------


def ffn_defs(d_model: int, d_ff: int, act: str = "silu", gated: bool = True):
    defs = {
        "up": pdefs.linear(d_model, d_ff, shard="model"),
        "down": pdefs.linear(d_ff, d_model, shard="model", shard_dim=0),
    }
    if gated:
        defs["gate"] = pdefs.linear(d_model, d_ff, shard="model")
    return defs


def ffn_apply(p, x, ctx: ParallelContext, act: str = "silu", dtype="bfloat16",
              psum: bool = True):
    up = x @ cast(p["up"], dtype)
    if "gate" in p:
        h = activation(x @ cast(p["gate"], dtype), act) * up
    else:
        h = activation(up, act)
    out = h @ cast(p["down"], dtype)
    return ctx.psum_model(out) if psum else out


# ---------------------------------------------------------------------------
# Vocab-sharded embedding + cross entropy
# ---------------------------------------------------------------------------


def embed_defs(vocab_padded: int, d_model: int, scale: float = 1.0):
    return {"table": pdefs.embedding(vocab_padded, d_model, shard="model",
                                     scale=scale)}


def embed_lookup(p, tokens, ctx: ParallelContext, dtype="bfloat16"):
    """Gather rows of a vocab-sharded table: local gather + psum over model."""
    table = p["table"]
    vloc = table.shape[0]
    lo = ctx.model_index() * vloc
    local_ids = tokens - lo
    in_range = (local_ids >= 0) & (local_ids < vloc)
    safe = jnp.clip(local_ids, 0, vloc - 1)
    out = jnp.take(table, safe, axis=0)
    out = jnp.where(in_range[..., None], out, 0.0)
    return ctx.psum_model(out).astype(jnp.dtype(dtype))


def unembed_logits(p, x, dtype="bfloat16"):
    """x @ table.T — logits sharded over vocab (no collective)."""
    return x @ cast(p["table"], dtype).T


def sharded_xent(logits_local, labels, ctx: ParallelContext,
                 true_vocab: Optional[int] = None, mask=None):
    """Cross entropy with vocab-sharded logits.

    logits_local: (..., V/tp) fp32/bf16, labels: (...) int32.
    Padded vocab entries (>= true_vocab) are excluded from the partition sum.
    Returns mean loss (scalar, replicated).
    """
    logits_local = logits_local.astype(jnp.float32)
    vloc = logits_local.shape[-1]
    lo = ctx.model_index() * vloc
    if true_vocab is not None:
        col = lo + jnp.arange(vloc)
        logits_local = jnp.where(col < true_vocab, logits_local, -1e30)
    local_max = jax.lax.stop_gradient(jnp.max(logits_local, axis=-1))
    gmax = ctx.pmax_model(local_max)
    sumexp = jnp.sum(jnp.exp(logits_local - gmax[..., None]), axis=-1)
    lse = jnp.log(ctx.psum_model(sumexp)) + gmax
    local_ids = labels - lo
    in_range = (local_ids >= 0) & (local_ids < vloc)
    safe = jnp.clip(local_ids, 0, vloc - 1)
    lab = jnp.take_along_axis(logits_local, safe[..., None], axis=-1)[..., 0]
    lab = ctx.psum_model(jnp.where(in_range, lab, 0.0))
    nll = lse - lab
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
