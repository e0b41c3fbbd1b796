"""One-pass fused server ingest: sparse scatter-mean + FedAMS update.

The two-pass server round first materializes the dense mean delta
(``server_aggregate_sparse``: one scatter pass writing d words) and then
re-reads it alongside x/m/v/v̂ (``fedams_update``: 5 reads + 4 writes) —
~13 fp32 streams over d per round. This kernel consumes the gathered
``(vals, idx)`` selections from n clients directly: each grid step owns one
selection block of the optimizer state, rebuilds that block's mean delta in
VMEM from the O(n·k) compacted entries, and applies the full FedAMS
m/v/v̂/x update in the same read-modify-write — the dense mean delta never
touches HBM, so the round moves ~9 streams plus the O(n·k) selection
traffic.

Second-moment storage is configurable (``state_dtype``): v/v̂ live in HBM
as fp32, bf16, or int8 with one fp32 absmax scale per selection block;
dequant → fp32 update math → requant is fused into the same pass (bf16
halves, int8 quarters, the v/v̂ residency — the update math itself always
runs in fp32, so the quantization error enters only through the *stored*
state read back next round).

Layout contract (matches ``Compressor.select`` / ``topk_ef_sparse``):
``idx`` are global int32 positions in the zero-padded block domain
(N = nb·block); ``vals``/``idx`` are (n, nb, k) — client-major, one row of
k entries per selection block. A grid step owns :data:`INGEST_ROWS` whole
blocks, one per sublane row (:mod:`repro.kernels.common`).

Numerics contract (tests/test_fused_ingest.py): the jnp blocked-scatter
impl (``server_ingest_leaf(impl="jnp")``) is *bitwise identical* to the
two-pass ``server_aggregate_sparse`` + ``server_update`` baseline at every
state dtype — XLA lowers both to one scatter-add over the same update
sequence. This kernel adds the clients' entries in client order, one
client at a time, which XLA's single scatter may reassociate: the kernel
is bitwise equal to its oracle ``fedams_ingest_ref`` (same client-order
sums), and equal to the baseline on every coordinate that at most one
client selected. Where several clients collide, the mean delta may differ
from the baseline's by the rounding of one reassociated sum.

Implements both paper options:
  option 1:  v̂ = max(v̂, v, ε);  x += η·m·rsqrt(v̂)
  option 2:  v̂ = max(v̂, v);     x += η·m/(√v̂+ε)
with the op sequence of ``server_update``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.server_opt import fedams_step
from repro.kernels.common import interpret_arg, out_struct, row_grid

#: Supported second-moment storage dtypes (mirrors
#: ``configs.base.FED_SERVER_STATE_DTYPES``).
STATE_DTYPES = ("float32", "bfloat16", "int8")

#: Selection blocks per grid step: 32 rows keep int8 state tiles aligned
#: to the TPU's (32, 128) int8 tiling (and bf16's (16, 128)).
INGEST_ROWS = 32


def _ingest_kernel(*refs, n: int, k: int, n_div, eta: float, beta1: float,
                   beta2: float, eps: float, option: int, state_dtype: str):
    if state_dtype == "int8":
        (x_ref, m_ref, v_ref, vh_ref, vals_ref, idx_ref, vs_ref, vhs_ref,
         x_out, m_out, v_out, vh_out, vs_out, vhs_out) = refs
    else:
        (x_ref, m_ref, v_ref, vh_ref, vals_ref, idx_ref,
         x_out, m_out, v_out, vh_out) = refs
    r, block = x_ref.shape
    pos = lax.broadcasted_iota(jnp.int32, (r, block), 1)
    slot = lax.broadcasted_iota(jnp.int32, (r, k), 1)
    row = (pl.program_id(0) * r
           + lax.broadcasted_iota(jnp.int32, (r, 1), 0))

    # -- scatter-mean of this tile's selected entries, entirely in VMEM:
    # client by client, entry by entry, each entry compared against every
    # position of its block row. Within one client the k positions are
    # distinct, so a position receives at most one value per client and
    # the sums run in client order, like the oracle's.
    def add_client(c, acc):
        vals = vals_ref[c]                                   # (r, k)
        local = idx_ref[c] - row * block                     # (r, k)

        def add_entry(j, acc):
            col = slot == j
            p = jnp.sum(jnp.where(col, local, 0), axis=1, keepdims=True)
            v = jnp.sum(jnp.where(col, vals, 0.0), axis=1, keepdims=True)
            return acc + jnp.where(pos == p, v, 0.0)

        return lax.fori_loop(0, k, add_entry, acc)

    acc = lax.fori_loop(0, n, add_client, jnp.zeros((r, block), jnp.float32))
    d = acc / n_div

    # -- dequant stored second moments to fp32 for the update math
    if state_dtype == "int8":
        vv = v_ref[...].astype(jnp.float32) * vs_ref[...]
        vh = vh_ref[...].astype(jnp.float32) * vhs_ref[...]
    else:
        vv = v_ref[...].astype(jnp.float32)
        vh = vh_ref[...].astype(jnp.float32)

    inc, m2, v2, vh2 = fedams_step(m_ref[...], vv, vh, d, eta=eta,
                                   beta1=beta1, beta2=beta2, eps=eps,
                                   option=option)
    x_out[...] = x_ref[...] + inc
    m_out[...] = m2

    # -- requant the refreshed second moments into storage form
    if state_dtype == "int8":
        vs2 = jnp.maximum(jnp.max(jnp.abs(v2), axis=1, keepdims=True)
                          / 127.0, 1e-30)
        vhs2 = jnp.maximum(jnp.max(jnp.abs(vh2), axis=1, keepdims=True)
                           / 127.0, 1e-30)
        v_out[...] = jnp.clip(jnp.round(v2 / vs2), -127, 127).astype(jnp.int8)
        vh_out[...] = jnp.clip(jnp.round(vh2 / vhs2), -127,
                               127).astype(jnp.int8)
        vs_out[...] = vs2
        vhs_out[...] = vhs2
    else:
        v_out[...] = v2.astype(v_out.dtype)
        vh_out[...] = vh2.astype(vh_out.dtype)


@functools.partial(jax.jit, static_argnames=("n_div", "eta", "beta1", "beta2",
                                             "eps", "option", "block",
                                             "state_dtype", "interpret"))
def fedams_ingest(x, m, v, vhat, vals, idx, v_scale=None, vh_scale=None, *,
                  n_div, eta: float, beta1: float, beta2: float, eps: float,
                  option: int = 1, block: int = 2048,
                  state_dtype: str = "float32", interpret=None):
    """Fused scatter-mean + FedAMS step over the padded block domain.

    ``x``/``m``: (N,) fp32 with N = nb·block (block % 128 == 0 to compile
    for TPU);
    ``v``/``vhat``: (N,) in the storage dtype (int8 additionally takes
    ``v_scale``/``vh_scale``: (nb,) fp32 per-block scales);
    ``vals``/``idx``: (n, nb, k) fp32/int32 global selections. ``n_div``
    is the (static) mean divisor — the participating client count.
    Returns ``(x, m, v, vhat)`` with state in storage form, plus
    ``(v_scale, vh_scale)`` when ``state_dtype == 'int8'``.
    """
    assert state_dtype in STATE_DTYPES, state_dtype
    n_clients, nb, k = vals.shape
    N = x.shape[0]
    assert N == nb * block, (N, nb, block)
    grid, r = row_grid(nb, INGEST_ROWS)
    mat = (nb, block)
    svec = pl.BlockSpec((r, block), lambda i: (i, 0))
    ssel = pl.BlockSpec((n_clients, r, k), lambda i: (0, i, 0))
    sscale = pl.BlockSpec((r, 1), lambda i: (i, 0))
    sdt = jnp.dtype(state_dtype)
    ins = [a.reshape(mat) for a in (x, m, v, vhat)] + [vals, idx]
    in_specs = [svec, svec, svec, svec, ssel, ssel]
    out_shape = [out_struct(mat, jnp.float32, x),
                 out_struct(mat, jnp.float32, x),
                 out_struct(mat, sdt, x),
                 out_struct(mat, sdt, x)]
    out_specs = [svec, svec, svec, svec]
    if state_dtype == "int8":
        ins += [v_scale.reshape(nb, 1), vh_scale.reshape(nb, 1)]
        in_specs += [sscale, sscale]
        out_shape += [out_struct((nb, 1), jnp.float32, x)] * 2
        out_specs += [sscale, sscale]
    outs = pl.pallas_call(
        functools.partial(_ingest_kernel, n=n_clients, k=k, n_div=n_div,
                          eta=eta, beta1=beta1, beta2=beta2, eps=eps,
                          option=option, state_dtype=state_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shape),
        interpret=interpret_arg(interpret),
        name="fedams_ingest",
    )(*ins)
    return tuple(o.reshape(-1) for o in outs)
