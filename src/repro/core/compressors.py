"""Biased compressors C: R^d -> R^d (paper §4.2, Assumption 4.14).

All compressors return a *dense* tensor of the same shape (the compressed
message is a sparse/low-bit encoding of it; ``bits_per_message`` accounts for
the wire format, matching the paper's Table 1). ``q_bound`` gives the
contraction constant of Assumption 4.14, property-tested in
``tests/test_compressors.py``.

``blocktopk`` is the TPU-native variant (DESIGN.md §3): exact top-k' inside
fixed-size blocks (ties to the lowest index); its ``select`` lists each
block's kept entries in ascending index order. Per block ‖C(x_b)−x_b‖² ≤ (1−k'/B)‖x_b‖², so the global
contraction bound q = sqrt(1−r) is preserved.

Sparse-friendly compressors (the top-k family) additionally expose
``select(x) -> Selection``: the same selection as ``compress`` but as a
compacted ``(vals, idx)`` pair instead of a dense scatter — the
representation the sparse uplink fast path (DESIGN.md §3) keeps alive from
client to server aggregate. ``selection_to_dense(select(x), d) ==
compress(x)`` bit-for-bit is property-tested.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


class Selection(NamedTuple):
    """A compacted top-k selection of a flat length-d vector.

    ``vals[j]`` is the kept value at flat position ``idx[j]``. For blockwise
    compressors the pairs are grouped per block in block order (``(nb, kb)``
    flattened row-major) and ``idx`` may point into the zero-padded tail of
    the last block (``idx >= d``); those entries carry value 0.0 and are
    dropped by :func:`selection_to_dense` (JAX scatter drops out-of-bounds
    updates), mirroring the dense path's pad-and-slice."""

    vals: jax.Array   # (k,) float32 kept values
    idx: jax.Array    # (k,) int32 flat positions (padded domain for blocks)


def selection_to_dense(sel: Selection, d: int) -> jnp.ndarray:
    """Dense length-``d`` vector carrying the selection (the compressor's
    ``compress`` output, reconstructed from the sparse representation)."""
    return jnp.zeros(d, jnp.float32).at[sel.idx].set(sel.vals)


@dataclass(frozen=True)
class Compressor:
    name: str
    compress: Callable                      # (x, rng=None) -> x_hat (dense)
    bits_per_message: Callable              # d -> wire bits
    q_bound: Callable                       # (x,) -> q (Assumption 4.14)
    ratio: float = 1.0
    # (x, rng=None) -> Selection; None for compressors whose messages are
    # not (value, index) pairs (sign/int8/identity)
    select: Optional[Callable] = None


def _topk_flat(x, k):
    flat = x.reshape(-1)
    vals, idx = lax.top_k(jnp.abs(flat), k)
    out = jnp.zeros_like(flat).at[idx].set(flat[idx])
    return out.reshape(x.shape)


def _argmax_select(xb):
    """Exact top-1 per row of ``xb`` as (vals, idx) — bit-identical to
    ``lax.top_k(|xb|, 1)`` (both keep the lowest index on ties) but a plain
    reduction instead of a sort-based top-k, which is the difference between
    the sparse fast path and the dense baseline at extreme ratios."""
    iidx = jnp.argmax(jnp.abs(xb), axis=-1)
    vals = jnp.take_along_axis(xb, iidx[..., None], axis=-1)[..., 0]
    return vals, iidx.astype(jnp.int32)


def make_topk(ratio: float) -> Compressor:
    def k_of(d: int) -> int:
        return max(1, int(round(ratio * d)))

    def compress(x, rng=None):
        return _topk_flat(x, k_of(x.size))

    def select(x, rng=None):
        flat = x.reshape(-1)
        k = k_of(flat.size)
        if k == 1:
            vals, idx = _argmax_select(flat[None])
            return Selection(vals=vals, idx=idx)
        _, idx = lax.top_k(jnp.abs(flat), k)
        return Selection(vals=flat[idx], idx=idx.astype(jnp.int32))

    return Compressor(
        name=f"topk_{ratio:g}",
        compress=compress,
        # value + index per kept coordinate (paper footnote 8: "roughly double")
        bits_per_message=lambda d: 64 * max(1, int(round(ratio * d))),
        q_bound=lambda x: math.sqrt(max(1.0 - ratio, 0.0)),
        ratio=ratio,
        select=select,
    )


def block_layout(d: int, block: int):
    """Shared block layout for the jnp and Pallas blockwise top-k paths:
    block size is a multiple of 128 (TPU lane width), capped at ``block``."""
    bs = min(block, ((d + 127) // 128) * 128)
    nb = -(-d // bs)
    return bs, nb


def make_blocktopk(ratio: float, block: int = 2048) -> Compressor:
    def compress(x, rng=None):
        flat = x.reshape(-1)
        d = flat.size
        bs, nb = block_layout(d, block)
        pad = nb * bs - d
        xb = jnp.pad(flat, (0, pad)).reshape(nb, bs)
        k = max(1, int(round(ratio * bs)))
        vals, idx = lax.top_k(jnp.abs(xb), k)
        kept = jnp.take_along_axis(xb, idx, axis=1)
        out = jnp.zeros_like(xb).at[
            jnp.arange(nb)[:, None], idx].set(kept)
        return out.reshape(-1)[:d].reshape(x.shape)

    def select(x, rng=None):
        flat = x.reshape(-1)
        d = flat.size
        bs, nb = block_layout(d, block)
        xb = jnp.pad(flat, (0, nb * bs - d)).reshape(nb, bs)
        k = max(1, int(round(ratio * bs)))
        if k == 1:
            kept, idx = _argmax_select(xb)           # (nb,), (nb,)
            kept, idx = kept[:, None], idx[:, None]
        else:
            # the kept entries of each block in ascending index order —
            # the order the Pallas topk_ef_sparse kernel emits them in
            idx = jnp.sort(lax.top_k(jnp.abs(xb), k)[1], axis=1)   # (nb, k)
            kept = jnp.take_along_axis(xb, idx, axis=1)
        gidx = idx.astype(jnp.int32) + (jnp.arange(nb, dtype=jnp.int32)
                                        * bs)[:, None]
        return Selection(vals=kept.reshape(-1), idx=gidx.reshape(-1))

    return Compressor(
        name=f"blocktopk_{ratio:g}",
        compress=compress,
        bits_per_message=lambda d: 64 * max(1, int(round(ratio * d))),
        q_bound=lambda x: math.sqrt(max(1.0 - ratio, 0.0)),
        ratio=ratio,
        select=select,
    )


def make_sign() -> Compressor:
    def compress(x, rng=None):
        scale = jnp.mean(jnp.abs(x))            # ||x||_1 / d
        # sign(0) := +1 — the convention a 1-bit wire format can actually
        # carry (comm.wire sign codec, rounds._packed_sign_leaf); keeps
        # decode(encode(x)) == compress(x) bit-exact including exact zeros.
        return scale * jnp.where(x >= 0, 1.0, -1.0)

    def q_bound(x):
        x = jnp.asarray(x, jnp.float32).reshape(-1)
        l1 = jnp.sum(jnp.abs(x))
        l2sq = jnp.sum(x * x)
        d = x.size
        return float(jnp.sqrt(jnp.maximum(1.0 - l1 * l1 / (d * jnp.maximum(l2sq, 1e-30)), 0.0)))

    return Compressor(
        name="sign",
        compress=compress,
        bits_per_message=lambda d: 32 + d,       # Table 1
        q_bound=q_bound,
    )


def make_randk(ratio: float) -> Compressor:
    def compress(x, rng=None):
        assert rng is not None, "randk needs an rng"
        flat = x.reshape(-1)
        k = max(1, int(round(ratio * flat.size)))
        idx = jax.random.permutation(rng, flat.size)[:k]
        out = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return out.reshape(x.shape)

    return Compressor(
        name=f"randk_{ratio:g}",
        compress=compress,
        bits_per_message=lambda d: 64 * max(1, int(round(ratio * d))),
        q_bound=lambda x: 1.0,   # only contractive in expectation
        ratio=ratio,
    )


def make_int8() -> Compressor:
    def compress(x, rng=None):
        scale = jnp.max(jnp.abs(x)) / 127.0
        scale = jnp.maximum(scale, 1e-30)
        return jnp.round(x / scale) * scale

    return Compressor(
        name="int8",
        compress=compress,
        bits_per_message=lambda d: 32 + 8 * d,
        q_bound=lambda x: 1.0 / 127.0 * math.sqrt(1.0),  # loose: q <= dmax/127·√d/‖x‖
    )


def make_identity() -> Compressor:
    return Compressor(
        name="none",
        compress=lambda x, rng=None: x,
        bits_per_message=lambda d: 32 * d,
        q_bound=lambda x: 0.0,
    )


def make_compressor(name: str, ratio: float = 1 / 64, block: int = 2048) -> Compressor:
    if name in ("none", "identity"):
        return make_identity()
    if name == "topk":
        return make_topk(ratio)
    if name == "blocktopk":
        return make_blocktopk(ratio, block)
    if name in ("sign", "packedsign"):
        c = make_sign()
        if name == "packedsign":
            # identical numerics; packed int8 wire format (DESIGN.md §3)
            return Compressor(name="packedsign", compress=c.compress,
                              bits_per_message=c.bits_per_message,
                              q_bound=c.q_bound)
        return c
    if name == "randk":
        return make_randk(ratio)
    if name == "int8":
        return make_int8()
    raise ValueError(f"unknown compressor {name!r}")
