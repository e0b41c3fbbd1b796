"""Plain float32 reference of the HuBERT encoder (arXiv:2106.07447) as the
program composes it: frame embeddings through pre-norm bidirectional
multi-head attention and a GELU feed-forward per layer, a final RMSNorm
and a projection onto the cluster targets.

Departures from the published model, shared with the program: the
convolutional feature encoder is a stub (frames arrive as embeddings),
positions are rotary rather than a convolutional embedding, the norms are
RMSNorm rather than LayerNorm, GELU is tanh-approximated, and the
projections have no biases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import (Leaf, embed_and_head_defs, head, layer_kinds,
                              linear, rms_norm, run_stack, stack_defs)


def layer_defs(cfg, kind):
    d, ff = cfg["d_model"], cfg["d_ff"]
    hd = d // cfg["num_heads"]
    width = cfg["num_heads"] * hd
    return {"norm1": Leaf((d,), "ones"),
            "mix": {"wq": linear(d, width), "wk": linear(d, width),
                    "wv": linear(d, width), "wo": linear(width, d)},
            "norm2": Leaf((d,), "ones"),
            "mlp": {"up": linear(d, ff), "down": linear(ff, d)}}


def param_defs(cfg):
    return {**embed_and_head_defs(cfg), "stack": stack_defs(cfg, layer_defs)}


def rope(x, theta):
    """Rotary positions 0..S-1 on (B, S, H, hd), halves rotated as pairs."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, cfg, kind, dot):
    B, S, d = x.shape
    H = cfg["num_heads"]
    hd = d // H
    a = p["mix"]
    h = rms_norm(p["norm1"], x, cfg["norm_eps"])
    q = dot("bsd,de->bse", h, a["wq"]).reshape(B, S, H, hd)
    k = dot("bsd,de->bse", h, a["wk"]).reshape(B, S, H, hd)
    v = dot("bsd,de->bse", h, a["wv"]).reshape(B, S, H, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    w = jax.nn.softmax(dot("bqhd,bkhd->bhqk", q, k) * hd ** -0.5, axis=-1)
    o = dot("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    x = x + dot("bse,ed->bsd", o, a["wo"])
    h2 = rms_norm(p["norm2"], x, cfg["norm_eps"])
    f = p["mlp"]
    u = jax.nn.gelu(dot("bsd,df->bsf", h2, f["up"]), approximate=True)
    return x + dot("bsf,fd->bsd", u, f["down"])


def loss(params, batch, cfg, dot):
    """Mean cross entropy of every frame's cluster target."""
    x = batch["embeddings"].astype(jnp.float32)
    h = run_stack(params["stack"], x, cfg, layer, dot)
    return head(params, h, batch["labels"], cfg, dot)


def forward_flops_per_token(cfg, seq: int) -> int:
    """Model FLOPs of one frame's forward pass: 2 per multiply-add with
    the attention projections, the feed-forward and the target projection
    (the unused token embedding excluded), plus attention's q.k and
    weighted sum over all ``seq`` frames."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    n = len(layer_kinds(cfg))
    matmul = n * (4 * d * d + 2 * d * ff) + cfg["vocab_size"] * d
    return 2 * matmul + 2 * n * 2 * seq * d
