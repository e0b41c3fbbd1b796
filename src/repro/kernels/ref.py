"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth).

Semantics notes:
  * ``topk_ef_ref`` is *threshold* top-k within each block: every element with
    |x| >= (k-th largest |x| in its block) is kept. With ties this keeps more
    than k elements — both kernel and oracle implement the same rule, and the
    contraction bound q = sqrt(1-k/B) only improves when extra elements are
    kept. (The exact-k scatter variant lives in core.compressors for the
    paper-faithful simulation.)
  * ``sign_ef_ref``: scaled sign with the *global* l1 scale (computed outside
    the kernel in one reduction pass) and fused error feedback.
  * ``fedams_update_ref``: the fused server update, Options 1 and 2.
  * ``fedams_ingest_ref``: the one-pass sparse ingest (scatter-mean fused
    into the FedAMS step, with dequant/requant of quantized second-moment
    state) — the bit-identity ground truth for ``kernels.fedams_ingest``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def topk_ef_ref(x, err, k: int, block: int):
    """x, err: (N,) with N % block == 0. Returns (hat, new_err)."""
    tot = (x + err).reshape(-1, block)
    absx = jnp.abs(tot)
    kth = lax.top_k(absx, k)[0][:, -1]
    keep = absx >= kth[:, None]
    hat = jnp.where(keep, tot, 0.0)
    new_err = tot - hat
    return hat.reshape(-1), new_err.reshape(-1)


def sign_ef_ref(x, err):
    """x, err: (N,). Returns (hat, new_err). Scale = mean |x+err| (global);
    sign(0) := +1 (the convention the 1-bit wire format carries)."""
    tot = x + err
    scale = jnp.mean(jnp.abs(tot))
    hat = scale * jnp.where(tot >= 0, 1.0, -1.0)
    return hat, tot - hat


def fedams_update_ref(x, m, v, vhat, delta, *, eta: float, beta1: float,
                      beta2: float, eps: float, option: int = 1):
    """Fused FedAMS server update on flat fp32 vectors."""
    from repro.core.server_opt import fedams_step
    inc, m2, v2, vh2 = fedams_step(m, v, vhat, delta, eta=eta, beta1=beta1,
                                   beta2=beta2, eps=eps, option=option)
    return x + inc, m2, v2, vh2


def fedams_ingest_ref(x, m, v, vhat, vals, idx, v_scale=None, vh_scale=None,
                      *, n_div, eta: float, beta1: float, beta2: float,
                      eps: float, option: int = 1, block: int = 2048,
                      state_dtype: str = "float32"):
    """One-pass sparse ingest oracle, same contract as ``fedams_ingest``.

    The mean delta is summed client by client: each client's entries
    (distinct positions) are set into a fresh dense vector and the vectors
    are added in client order — the kernel's order, and a chain of dense
    adds no compiler reassociates (kernel ≡ ref bitwise). XLA's single
    flat scatter-add may reassociate collided updates, so on coordinates
    several clients selected this oracle may differ from the two-pass
    baseline by that rounding. The elementwise FedAMS step runs in fp32
    with dequant/requant of the stored second moments. Returns
    ``(x, m, v, vhat)`` (+ scales for int8).
    """
    n, nb, k = vals.shape
    N = x.shape[0]
    acc = jnp.zeros(N, jnp.float32)
    for j in range(n):   # client order; within a client indices are unique
        acc = acc + jnp.zeros(N, jnp.float32).at[idx[j].reshape(-1)].set(
            vals[j].reshape(-1))
    d = acc / n_div
    if state_dtype == "int8":
        vv = (v.astype(jnp.float32).reshape(nb, block)
              * v_scale[:, None]).reshape(-1)
        vh = (vhat.astype(jnp.float32).reshape(nb, block)
              * vh_scale[:, None]).reshape(-1)
    else:
        vv = v.astype(jnp.float32)
        vh = vhat.astype(jnp.float32)
    x2, m2, v2, vh2 = fedams_update_ref(x, m, vv, vh, d, eta=eta,
                                        beta1=beta1, beta2=beta2, eps=eps,
                                        option=option)
    if state_dtype == "int8":
        def requant(a):
            ab = a.reshape(nb, block)
            scale = jnp.maximum(jnp.max(jnp.abs(ab), axis=1) / 127.0, 1e-30)
            q = jnp.clip(jnp.round(ab / scale[:, None]), -127,
                         127).astype(jnp.int8)
            return q.reshape(-1), scale
        qv, sv = requant(v2)
        qvh, svh = requant(vh2)
        return x2, m2, qv, qvh, sv, svh
    if state_dtype == "bfloat16":
        return x2, m2, v2.astype(jnp.bfloat16), vh2.astype(jnp.bfloat16)
    return x2, m2, v2, vh2
