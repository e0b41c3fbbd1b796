"""Plain float32 reference of the xLSTM language model (arXiv:2405.04517)
as the program composes it: pre-norm residual blocks that alternate the
parallel (stabilised, quadratic) mLSTM and the sequential sLSTM with its
gated SiLU feed-forward, a final RMSNorm and an untied unembedding.

Departures from the paper, shared with the program: no causal convolution,
no learnable skip and no group norm inside the blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference.common import (Leaf, embed_and_head_defs, head,
                              layer_kinds, linear, rms_norm, run_stack,
                              stack_defs)

NEG = -1e30


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


def dims(cfg):
    d, nh = cfg["d_model"], cfg["num_heads"]
    x = cfg["xlstm"]
    di = _pad128(int(d * x["mlstm_proj_factor"]))
    dff = _pad128(int(d * x["slstm_proj_factor"]))
    return d, nh, di, dff


def layer_defs(cfg, kind):
    d, nh, di, dff = dims(cfg)
    if kind == "mlstm":
        dh = di // nh
        mix = {"w_q": linear(d, di), "w_k": linear(d, di),
               "w_v": Leaf((d, nh, dh), scale=d ** -0.5),
               "w_i": linear(d, nh), "w_f": linear(d, nh),
               "w_o": Leaf((d, nh, dh), scale=d ** -0.5),
               "w_down": Leaf((nh, dh, d), scale=di ** -0.5)}
    else:
        dh = d // nh
        mix = {"w_in": linear(d, 4 * d),
               "r": Leaf((4, nh, dh, dh), scale=dh ** -0.5),
               "b": Leaf((4 * d,), "zeros"),
               "norm": Leaf((d,), "ones"),
               "ffn": {"up": linear(d, dff), "down": linear(dff, d),
                       "gate": linear(d, dff)}}
    return {"norm1": Leaf((d,), "ones"), "mix": mix}


def param_defs(cfg):
    return {**embed_and_head_defs(cfg), "stack": stack_defs(cfg, layer_defs)}


def mlstm(p, x, nh, dot):
    B, S, _ = x.shape
    di = p["w_q"].shape[1]
    dh = di // nh
    q = dot("bsd,de->bse", x, p["w_q"]).reshape(B, S, nh, dh)
    k = dot("bsd,de->bse", x, p["w_k"]).reshape(B, S, nh, dh)
    v = dot("bsd,dhv->bshv", x, p["w_v"])
    log_i = dot("bsd,dh->bsh", x, p["w_i"])
    log_f = jax.nn.log_sigmoid(dot("bsd,dh->bsh", x, p["w_f"]))
    F = jnp.cumsum(log_f, axis=1)
    # D_ij = F_i - F_j + log i_j for j <= i, stabilised by its row max
    D = F[:, :, None, :] - F[:, None, :, :] + log_i[:, None, :, :]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    D = jnp.where(causal, D, NEG)
    m = jnp.max(D, axis=2)
    w = jnp.exp(D - m[:, :, None, :])
    s = dot("bihd,bjhd->bijh", q, k) * dh ** -0.5
    sw = s * w
    denom = jnp.maximum(jnp.abs(jnp.sum(sw, axis=2)), jnp.exp(-m))
    h = dot("bijh,bjhv->bihv", sw, v) / denom[..., None]
    o = jax.nn.sigmoid(dot("bsd,dhv->bshv", x, p["w_o"]))
    return dot("bshv,hvd->bsd", h * o, p["w_down"])


def slstm(p, x, nh, dot):
    B, S, d = x.shape
    dh = d // nh
    pre = dot("bsd,de->bse", x, p["w_in"]) + p["b"]

    def step(st, pre_t):
        h, c, n, m = st
        rec = dot("bhk,ghkl->bghl", h.reshape(B, nh, dh), p["r"])
        z = pre_t.reshape(B, 4, d) + rec.reshape(B, 4, d)
        i, f, zz, o = z[:, 0], z[:, 1], z[:, 2], z[:, 3]
        lf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(lf + m, i)
        ip, fp = jnp.exp(i - m_new), jnp.exp(lf + m - m_new)
        c = fp * c + ip * jnp.tanh(zz)
        n = fp * n + ip
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1e-6)
        return (h, c, n, m_new), h

    zero = jnp.zeros((B, d), jnp.float32)
    _, hs = lax.scan(step, (zero, zero, zero, jnp.full((B, d), NEG)),
                     pre.transpose(1, 0, 2))
    h = rms_norm(p["norm"], hs.transpose(1, 0, 2), 1e-6)
    f = p["ffn"]
    a = jax.nn.silu(dot("bsd,df->bsf", h, f["gate"])) * dot(
        "bsd,df->bsf", h, f["up"])
    return dot("bsf,fd->bsd", a, f["down"])


def layer(p, x, cfg, kind, dot):
    h = rms_norm(p["norm1"], x, cfg["norm_eps"])
    mix = mlstm if kind == "mlstm" else slstm
    return x + mix(p["mix"], h, cfg["num_heads"], dot)


def loss(params, batch, cfg, dot):
    """Mean next-token cross entropy of one (B, S) batch."""
    x = jnp.take(params["embed"]["table"], batch["tokens"], axis=0)
    h = run_stack(params["stack"], x, cfg, layer, dot)
    return head(params, h, batch["labels"], cfg, dot)


def forward_flops_per_token(cfg, seq: int) -> int:
    """Model FLOPs of one token's forward pass: 2 per multiply-add with
    every parameter that multiplies its activations (the projections, the
    sLSTM's recurrent matrices, the unembedding; not the embedding lookup,
    norms or biases), plus the mLSTM parallel form's q.k and weighted sum
    over the full S x S square that the form evaluates."""
    d, nh, di, dff = dims(cfg)
    dh = d // nh
    per = {"mlstm": 5 * d * di + 2 * d * nh,
           "slstm": 4 * d * d + 4 * nh * dh * dh + 3 * d * dff}
    kinds = layer_kinds(cfg)
    matmul = sum(per[k] for k in kinds) + cfg["vocab_size"] * d
    quadratic = sum(k == "mlstm" for k in kinds) * 2 * seq * di
    return 2 * matmul + 2 * quadratic
