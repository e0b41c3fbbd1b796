"""Blockwise top-k + fused error feedback as a Pallas TPU kernel.

TPU adaptation of the paper's top-k compressor (DESIGN.md §3): a global
top-k over 10^8-10^9 gradient elements requires a full sort through HBM; the
blockwise variant streams whole selection blocks HBM→VMEM (one block per
sublane row of the tile, :mod:`repro.kernels.common`), selects the top-k
inside each block, and writes both the compressed block and the residual
error in the same pass — the error-feedback update is fused, so the delta is
read exactly once.

Tile height: these kernels take :func:`tile_rows` blocks a grid step, as
many as a fixed VMEM budget holds at the block size, not the package's
8-row :data:`~repro.kernels.common.ROWS`. The selection is a serial chain
of per-block cross-lane reductions (one count per threshold bit and tie
bit, a min and a sum per extracted entry); with 8 rows a step holds one
sublane group, so each reduction's latency is paid alone, about a hundred
times a step. A taller tile issues the same chain for many independent
sublane groups at once, which hides that latency; the bytes moved are the
same either way.

Two output layouts share the selection logic:

* :func:`topk_ef` — dense (hat, new_err), the historical contract used by
  ``KernelImpl.ef_compress_tree`` on the mesh path.
* :func:`topk_ef_sparse` — the compacted ``(vals, idx)`` block the sparse
  uplink keeps end-to-end (DESIGN.md §3), emitted directly from the same
  single HBM pass (plus ``new_err``); ``idx`` are global flat positions.

Selection keeps EXACTLY k entries per block, the k largest ``|value|`` with
ties broken towards the lowest index (``lax.top_k``'s rule) — a pure
threshold ``|x| >= kth`` keeps more than k on ties, which breaks the wire
format's fixed (vals, idx) buffer sizes and the ``bits_per_message``
accounting (tests/test_kernels.py ties regression). It needs no sort: the
k-th largest ``|value|`` is found bit by bit on its float bits (for
non-negative floats the int32 order is the float order), then the lowest
tied positions by the same search over the index. The compacted selection
lists the kept entries in ascending index order, the order
``Compressor.select`` of ``blocktopk`` uses, bit for bit.

The per-block contraction ‖C(x_b)−x_b‖² ≤ (1−k'/B)‖x_b‖² preserves the
paper's Assumption 4.14 with the same q = sqrt(1−r).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import ROWS, interpret_arg, out_struct, row_grid

DEFAULT_BLOCK = 2048

#: VMEM bytes of one (rows, block) f32 tile of a top-k grid step.
TILE_BYTES = 64 * 2048 * 4


def tile_rows(block: int) -> int:
    """Selection blocks per grid step of the top-k kernels: the largest
    multiple of :data:`ROWS` whose ``(rows, block)`` f32 tile fits
    :data:`TILE_BYTES` (at least ``ROWS``). :func:`row_grid` caps it at
    the block count, so a small leaf is one step of all its blocks."""
    return max(ROWS, TILE_BYTES // (4 * block) // ROWS * ROWS)


def _count(mask):
    """Per-row count of a boolean tile: (r, block) -> (r, 1) int32."""
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _keep_mask(tot, k: int):
    """Exactly-k membership per row of the (r, block) tile: the k largest
    ``|tot|``, ties to the lowest index. Returns ``(keep, pos)`` with
    ``pos`` the in-block position of every lane."""
    r, block = tot.shape
    mag = lax.bitcast_convert_type(tot, jnp.int32) & 0x7FFFFFFF
    # largest t with count(|x| >= t) >= k: the k-th largest magnitude
    t = jnp.zeros((r, 1), jnp.int32)
    for bit in range(30, -1, -1):
        cand = t | (1 << bit)
        t = jnp.where(_count(mag >= cand) >= k, cand, t)
    gt = mag > t
    eq = mag == t
    need = k - _count(gt)                 # >= 1 tied entries still to keep
    pos = lax.broadcasted_iota(jnp.int32, (r, block), 1)
    # largest q with fewer than `need` ties before q: the last kept tie
    q = jnp.zeros((r, 1), jnp.int32)
    for bit in range((block - 1).bit_length() - 1, -1, -1):
        cand = q | (1 << bit)
        q = jnp.where(_count(eq & (pos < cand)) < need, cand, q)
    return gt | (eq & (pos <= q)), pos


def _topk_ef_kernel(x_ref, e_ref, hat_ref, err_ref, *, k: int):
    tot = x_ref[...] + e_ref[...]
    keep, _ = _keep_mask(tot, k)
    hat = jnp.where(keep, tot, 0.0)
    hat_ref[...] = hat
    err_ref[...] = tot - hat


def _topk_ef_sparse_kernel(x_ref, e_ref, vals_ref, idx_ref, err_ref, *,
                           k: int):
    tot = x_ref[...] + e_ref[...]
    keep, pos = _keep_mask(tot, k)
    r, block = tot.shape
    bits = lax.bitcast_convert_type(tot, jnp.int32)
    slot = lax.broadcasted_iota(jnp.int32, (r, k), 1)

    def take(j, carry):
        # the j-th kept entry in index order: its position and exact bits;
        # `left` holds the positions not yet taken (`block` once taken)
        left, vbits, idx = carry
        p = jnp.min(left, axis=1, keepdims=True)
        hit = left == p
        b = jnp.sum(jnp.where(hit, bits, 0), axis=1, keepdims=True)
        return (jnp.where(hit, block, left), jnp.where(slot == j, b, vbits),
                jnp.where(slot == j, p, idx))

    zero = jnp.zeros((r, k), jnp.int32)
    _, vbits, idx = lax.fori_loop(0, k, take,
                                  (jnp.where(keep, pos, block), zero, zero))
    row = (pl.program_id(0) * r
           + lax.broadcasted_iota(jnp.int32, (r, 1), 0))
    vals_ref[...] = lax.bitcast_convert_type(vbits, jnp.float32)
    idx_ref[...] = idx + row * block                       # global flat
    err_ref[...] = jnp.where(keep, 0.0, tot)


def _tiles(x, block):
    """The ``(nb, block)`` view's grid of :func:`tile_rows` rows a step
    (the selection's reduction chain is latency-bound, not byte-bound)."""
    n = x.shape[0]
    assert x.ndim == 1 and n % block == 0, (n, block)
    nb = n // block
    grid, r = row_grid(nb, tile_rows(block))
    return nb, grid, r, pl.BlockSpec((r, block), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_ef(x, err, *, k: int, block: int = DEFAULT_BLOCK, interpret=None):
    """x, err: (N,) fp32 with N % block == 0 (block % 128 == 0 to
    compile for TPU).
    Returns (hat, new_err)."""
    assert x.shape == err.shape
    nb, grid, _, spec = _tiles(x, block)
    mat = (nb, block)
    hat, ne = pl.pallas_call(
        functools.partial(_topk_ef_kernel, k=k),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=(out_struct(mat, x.dtype, x), out_struct(mat, x.dtype, x)),
        interpret=interpret_arg(interpret),
        name="topk_ef",
    )(x.reshape(mat), err.reshape(mat))
    return hat.reshape(-1), ne.reshape(-1)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_ef_sparse(x, err, *, k: int, block: int = DEFAULT_BLOCK,
                   interpret=None):
    """x, err: (N,) fp32 with N % block == 0 (block % 128 == 0 to
    compile for TPU). One HBM pass per tile emitting the compacted
    selection directly:

    Returns ``(vals, idx, new_err)`` with ``vals``/``idx`` shaped
    (N // block, k) — per-block kept values and their GLOBAL flat
    positions, in ascending position — and ``new_err`` (N,) the fused EF
    residual (``x + err`` with the selected entries zeroed). The dense
    equivalent ``zeros(N).at[idx].set(vals)`` equals :func:`topk_ef`'s hat
    bit-for-bit (tests/test_kernels.py)."""
    assert x.shape == err.shape
    nb, grid, r, spec = _tiles(x, block)
    mat = (nb, block)
    sel_spec = pl.BlockSpec((r, k), lambda i: (i, 0))
    vals, idx, ne = pl.pallas_call(
        functools.partial(_topk_ef_sparse_kernel, k=k),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[sel_spec, sel_spec, spec],
        out_shape=(out_struct((nb, k), x.dtype, x),
                   out_struct((nb, k), jnp.int32, x),
                   out_struct(mat, x.dtype, x)),
        interpret=interpret_arg(interpret),
        name="topk_ef_sparse",
    )(x.reshape(mat), err.reshape(mat))
    return vals, idx, ne.reshape(-1)
