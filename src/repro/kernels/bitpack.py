"""n-bit pack/unpack, word-wise (the wire formats' hot loop).

The wire formats (comm.wire) carry sub-word payloads: 1 bit per coordinate
for the sign codec, ``ceil(log2(B))`` bits per kept index for blocktopk
(11 bits for B=2048). Packing those into a uint8 stream is a pure
byte-shuffle that on TPU should stream HBM→VMEM once per tile — the naive
formulation (expand every value to an ``(count, nbits)`` bit matrix, then
``packbits``) materializes an 8–32× larger intermediate, which is exactly
the memory traffic the wire format exists to avoid. XLA compiles these
shift/or forms for any backend; no Pallas kernel is needed.

Both directions here are *word-wise shift/or accumulations* with no bit
matrix. MSB-first at ``nbits`` each, value slot ``s`` of the stream spans
stream bits ``[s·nbits, (s+1)·nbits)`` and byte ``k`` spans ``[8k, 8k+8)``;
every overlapping (k, s) pair contributes one contiguous bit run whose
alignment is the *constant* shift ``8k + 8 − (s+1)·nbits``, so

    byte_k = OR_s  shift(value_s, 8k + 8 − (s+1)·nbits)  & 0xFF
    value_s = OR_k shift(byte_k, (s+1)·nbits − 8k − 8)   & (2^nbits − 1)

With ``L = lcm(nbits, 8)`` the stream tiles into groups of ``L/nbits``
values ↔ ``L/8`` bytes, making the (k, s) pairs a small static table
(≤ ``L/8 · (⌈8/nbits⌉+1)`` shift/or ops per group).

``pack_uint_words`` / ``unpack_uint_words`` are the path ``comm.wire``
uses; ``pack_bits_ref`` / ``unpack_bits_ref`` are the 1-bit oracles they
are validated against in tests/test_wire.py.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

_WEIGHTS = (128, 64, 32, 16, 8, 4, 2, 1)  # MSB-first, like jnp.packbits


def group_shape(nbits: int):
    """(values, bytes) per stream group: L/nbits and L/8 for L=lcm(nbits,8)."""
    if not 1 <= nbits <= 32:
        raise ValueError(f"nbits must be in [1, 32], got {nbits}")
    lcm = math.lcm(nbits, 8)
    return lcm // nbits, lcm // 8


def _umask(nbits: int):
    return jnp.uint32(((1 << nbits) - 1) & 0xFFFFFFFF)


def _pack_pairs(nbits: int):
    """Static (byte k) -> [(value slot s, shift)] table for one group."""
    gv, gb = group_shape(nbits)
    return [
        [(s, 8 * k + 8 - (s + 1) * nbits)
         for s in range((8 * k) // nbits,
                        min((8 * k + 7) // nbits, gv - 1) + 1)]
        for k in range(gb)
    ]


def _unpack_pairs(nbits: int):
    """Static (value slot s) -> [(byte k, shift)] table (pack's transpose)."""
    gv, gb = group_shape(nbits)
    return [
        [(k, 8 * k + 8 - (s + 1) * nbits)
         for k in range((s * nbits) // 8,
                        min(((s + 1) * nbits - 1) // 8, gb - 1) + 1)]
        for s in range(gv)
    ]


def _shl(x, sh: int):
    """Shift by a signed static amount (left for positive)."""
    return x << sh if sh >= 0 else x >> -sh


# ---------------------------------------------------------------------------
# jnp word-wise forms (oracle + default wire path)
# ---------------------------------------------------------------------------


def pack_uint_words(vals, nbits: int) -> jnp.ndarray:
    """vals: (count,) uints < 2**nbits. Returns ceil(count*nbits/8) bytes,
    MSB-first — byte-identical to the bit-matrix formulation but without
    ever materializing it (peak intermediate is one uint32 per output byte).
    """
    flat = vals.reshape(-1).astype(jnp.uint32) & _umask(nbits)
    count = flat.size
    gv, gb = group_shape(nbits)
    groups = -(-count // gv)
    v = jnp.pad(flat, (0, groups * gv - count)).reshape(groups, gv)
    cols = []
    for pairs in _pack_pairs(nbits):
        acc = jnp.zeros((groups,), jnp.uint32)
        for s, sh in pairs:
            acc = acc | _shl(v[:, s], sh)
        cols.append(acc & 0xFF)
    out = jnp.stack(cols, axis=1).reshape(-1).astype(jnp.uint8)
    return out[: (count * nbits + 7) // 8]


def unpack_uint_words(buf, nbits: int, count: int) -> jnp.ndarray:
    """Inverse of :func:`pack_uint_words`: read ``count`` values (uint32)."""
    flat = buf.reshape(-1).astype(jnp.uint32)
    gv, gb = group_shape(nbits)
    groups = -(-count // gv)
    b = jnp.pad(flat, (0, max(groups * gb - flat.size, 0))).reshape(-1)
    b = b[: groups * gb].reshape(groups, gb)
    mask = _umask(nbits)
    cols = []
    for pairs in _unpack_pairs(nbits):
        acc = jnp.zeros((groups,), jnp.uint32)
        for k, sh in pairs:
            acc = acc | _shl(b[:, k], -sh)
        cols.append(acc & mask)
    return jnp.stack(cols, axis=1).reshape(-1)[:count]


# 1-bit oracles (kept as an independent reference for the kernels)


def pack_bits_ref(bits):
    """bits: (N,) uint8/bool in {0,1}, N % 8 == 0. Returns (N/8,) uint8."""
    b = bits.reshape(-1, 8).astype(jnp.int32)
    w = jnp.asarray(_WEIGHTS, jnp.int32)
    return jnp.sum(b * w, axis=1).astype(jnp.uint8)


def unpack_bits_ref(packed):
    """packed: (M,) uint8. Returns (8*M,) uint8 in {0,1}."""
    p = packed.astype(jnp.int32)
    shifts = jnp.arange(7, -1, -1, dtype=jnp.int32)
    return ((p[:, None] >> shifts) & 1).reshape(-1).astype(jnp.uint8)
