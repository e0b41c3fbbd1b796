"""Wire formats: serialize a compressed client delta to a flat ``uint8``
buffer and decode it back bit-exactly.

The rest of the repo *accounts* for communication analytically
(``Compressor.bits_per_message``, the paper's Table 1); this module actually
packs the bytes, so the bit counts become *measured* ``wire_bytes``. Every
message is

    [16-byte header][payload]

with the header carrying magic/version/codec/value-dtype plus ``d``, the
per-block keep count and the block size (all native-endian ``uint32`` — the
simulated network never crosses byte orders). Codecs:

``dense32``
    Raw fp32 coordinates — the uncompressed baseline, 32d bits + header.
``topk``
    Exact global top-k: ``k`` uint32 indices + ``k`` values. With fp32
    values this is the paper's "value + index per kept coordinate"
    (64 bits/coord); fp16/bf16 values halve the value bytes.
``blocktopk``
    The TPU-native blockwise top-k: per-block indices packed at
    ``ceil(log2(B))`` bits each (11 bits for B=2048 instead of 32) +
    values. ``value_dtype="int8"`` additionally quantizes values against a
    per-block fp32 scale (max|v|/127).
``sign``
    Scaled sign: one fp32 scale (‖x‖₁/d, or one per block when
    ``block > 0``) + 1 bit per coordinate — Table 1's 32 + d bits.

Bit-exactness: with the default ``value_dtype="float32"``,
``decode(encode(x)) == compressor.compress(x)`` bit-for-bit (same
``lax.top_k`` selection, same scatter; the sign codec and ``make_sign``
share the sign(0) := +1 convention). Narrower value dtypes round the kept
values through fp16/bf16/int8; error feedback stays exact because the
integration tracks the *decoded* value (core.sim wire mode).

Everything here is jit-safe: shapes depend only on ``d`` and the codec
config, so encode/decode trace into fixed-size byte-shuffling that runs
inside the federated round.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.compressors import (Compressor, Selection, block_layout,
                                    make_blocktopk, make_compressor,
                                    make_identity, make_sign, make_topk)

HEADER_BYTES = 16
MAGIC = 0xFC
VERSION = 1

CODEC_IDS = {"dense32": 1, "topk": 2, "blocktopk": 3, "sign": 4}
_VALUE_DTYPES = {
    "float32": (0, jnp.float32, 4),
    "float16": (1, jnp.float16, 2),
    "bfloat16": (2, jnp.bfloat16, 2),
    "int8": (3, jnp.int8, 1),
}


# ---------------------------------------------------------------------------
# byte-level helpers (all jit-safe)
# ---------------------------------------------------------------------------


def _to_bytes(x) -> jnp.ndarray:
    """Bitcast any array to a flat uint8 view (native byte order)."""
    if x.dtype == jnp.uint8:
        return x.reshape(-1)
    return lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _from_bytes(buf, dtype, count: int):
    """Inverse of ``_to_bytes``: read ``count`` items of ``dtype``."""
    if jnp.dtype(dtype) == jnp.uint8:
        return buf[:count]
    width = jnp.dtype(dtype).itemsize
    return lax.bitcast_convert_type(
        buf[: count * width].reshape(count, width), dtype)


def pack_uint(vals, nbits: int) -> jnp.ndarray:
    """Pack unsigned ints (< 2**nbits) at ``nbits`` bits each, MSB-first,
    into a uint8 stream (zero-padded to a whole byte).

    Word-wise shift/or accumulation (kernels.bitpack): never materializes
    the ``(count, nbits)`` bit matrix the naive formulation needs — for
    blocktopk's 11-bit indices that intermediate is a 32× blowup over the
    packed bytes."""
    from repro.kernels.bitpack import pack_uint_words
    return pack_uint_words(vals, nbits)


def unpack_uint(buf, nbits: int, count: int) -> jnp.ndarray:
    """Inverse of ``pack_uint``."""
    from repro.kernels.bitpack import unpack_uint_words
    return unpack_uint_words(buf, nbits, count)


def _header(codec: str, vdtype: str, d: int, k: int, block: int):
    h = np.zeros(HEADER_BYTES, np.uint8)
    h[0], h[1] = MAGIC, VERSION
    h[2] = CODEC_IDS[codec]
    h[3] = _VALUE_DTYPES[vdtype][0]
    h[4:8] = np.frombuffer(np.uint32(d).tobytes(), np.uint8)
    h[8:12] = np.frombuffer(np.uint32(k).tobytes(), np.uint8)
    h[12:16] = np.frombuffer(np.uint32(block).tobytes(), np.uint8)
    return jnp.asarray(h)


def parse_header(buf) -> dict:
    """Host-side header validation/introspection (numpy, not jittable)."""
    h = np.asarray(buf[:HEADER_BYTES], np.uint8)
    if h[0] != MAGIC or h[1] != VERSION:
        raise ValueError(f"bad wire header: magic={h[0]:#x} version={h[1]}")
    names = {v: k for k, v in CODEC_IDS.items()}
    vnames = {v[0]: k for k, v in _VALUE_DTYPES.items()}
    return {
        "codec": names[int(h[2])],
        "value_dtype": vnames[int(h[3])],
        "d": int(h[4:8].view(np.uint32)[0]),
        "k": int(h[8:12].view(np.uint32)[0]),
        "block": int(h[12:16].view(np.uint32)[0]),
    }


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WireCodec:
    """A serializer for compressed deltas.

    ``encode(x, rng=None)`` maps a flat fp32 vector to a packed uint8
    buffer; ``decode(buf, d)`` maps it back to the dense fp32
    representation (``d`` must be the original length — it is static under
    jit). ``rng`` is the per-client key the integration threads through
    (core.stages) — today's codecs are deterministic and ignore it, but the
    plumbing keeps stochastic codecs (e.g. randomized rounding) from
    desyncing client streams later. ``nbytes(d)`` is the exact buffer size,
    so measured wire bytes are available without encoding. ``compressor``
    is the dense-path :class:`Compressor` this codec is the wire format of;
    ``exact`` states whether ``decode(encode(x)) == compressor.compress(x)``
    bit-for-bit.

    Selection fast path (sparse uplink, DESIGN.md §3): codecs whose payload
    is (value, index) pairs also provide

    * ``encode_from_selection(sel, d)`` — pack an already-computed
      :class:`Selection` (byte-identical to ``encode(x)`` when ``sel`` is
      the compressor's own selection of ``x``), so wire mode never re-runs
      ``lax.top_k`` on the dense vector;
    * ``decode_to_selection(buf, d)`` — unpack straight back to a
      :class:`Selection` without materializing the dense vector;
    * ``roundtrip_selection(sel, d)`` — what the server receives:
      bit-identical shortcut for
      ``decode_to_selection(encode_from_selection(sel, d), d)`` that skips
      the byte shuffling (the identity for ``exact`` codecs; the pure
      value-narrowing for fp16/bf16/int8 values — indices always survive
      the wire exactly). Property-tested against the full byte roundtrip.
    """

    name: str
    encode: Callable
    decode: Callable
    nbytes: Callable
    compressor: Compressor
    exact: bool = True
    header_bytes: int = field(default=HEADER_BYTES)
    encode_from_selection: Optional[Callable] = None
    decode_to_selection: Optional[Callable] = None
    roundtrip_selection: Optional[Callable] = None


def make_dense32_codec() -> WireCodec:
    def encode(x, rng=None):
        flat = x.reshape(-1).astype(jnp.float32)
        return jnp.concatenate(
            [_header("dense32", "float32", flat.size, 0, 0), _to_bytes(flat)])

    def decode(buf, d: int):
        return _from_bytes(buf[HEADER_BYTES:], jnp.float32, d)

    return WireCodec(name="dense32", encode=encode, decode=decode,
                     nbytes=lambda d: HEADER_BYTES + 4 * d,
                     compressor=make_identity())


def make_topk_codec(ratio: float, value_dtype: str = "float32") -> WireCodec:
    if value_dtype not in ("float32", "float16", "bfloat16"):
        raise ValueError(f"topk codec: unsupported value_dtype {value_dtype!r}")
    _, vdt, vb = _VALUE_DTYPES[value_dtype]

    def k_of(d: int) -> int:
        return max(1, int(round(ratio * d)))

    def encode_from_selection(sel: Selection, d: int):
        vals = sel.vals.astype(vdt)
        return jnp.concatenate([
            _header("topk", value_dtype, d, k_of(d), 0),
            _to_bytes(sel.idx.astype(jnp.uint32)), _to_bytes(vals)])

    def encode(x, rng=None):
        flat = x.reshape(-1).astype(jnp.float32)
        d = flat.size
        k = k_of(d)
        _, idx = lax.top_k(jnp.abs(flat), k)
        return encode_from_selection(
            Selection(vals=flat[idx], idx=idx.astype(jnp.int32)), d)

    def decode_to_selection(buf, d: int) -> Selection:
        k = k_of(d)
        off = HEADER_BYTES
        idx = _from_bytes(buf[off:], jnp.uint32, k)
        vals = _from_bytes(buf[off + 4 * k:], vdt, k).astype(jnp.float32)
        return Selection(vals=vals, idx=idx.astype(jnp.int32))

    def decode(buf, d: int):
        sel = decode_to_selection(buf, d)
        return jnp.zeros(d, jnp.float32).at[sel.idx].set(sel.vals)

    def roundtrip_selection(sel: Selection, d: int) -> Selection:
        if value_dtype == "float32":
            return sel
        return Selection(vals=sel.vals.astype(vdt).astype(jnp.float32),
                         idx=sel.idx)

    return WireCodec(
        name=f"topk_{ratio:g}_{value_dtype}", encode=encode, decode=decode,
        nbytes=lambda d: HEADER_BYTES + k_of(d) * (4 + vb),
        compressor=make_topk(ratio), exact=value_dtype == "float32",
        encode_from_selection=encode_from_selection,
        decode_to_selection=decode_to_selection,
        roundtrip_selection=roundtrip_selection)


def make_blocktopk_codec(ratio: float, block: int = 2048,
                         value_dtype: str = "float32") -> WireCodec:
    _, vdt, vb = _VALUE_DTYPES[value_dtype]
    int8 = value_dtype == "int8"

    def layout(d: int):
        bs, nb = block_layout(d, block)
        kb = max(1, int(round(ratio * bs)))
        ib = max(1, math.ceil(math.log2(bs)))
        return bs, nb, kb, ib

    def _quantize(vals):
        """Per-block int8 quantization of (nb, kb) kept values; returns
        (scale (nb,), q (nb, kb) int8)."""
        scale = jnp.maximum(jnp.max(jnp.abs(vals), axis=1), 1e-30) / 127.0
        return scale, jnp.round(vals / scale[:, None]).astype(jnp.int8)

    def encode_from_selection(sel: Selection, d: int):
        bs, nb, kb, ib = layout(d)
        # Selection carries padded-domain global positions in block order;
        # the wire packs block-local offsets at ib bits each.
        gidx = sel.idx.reshape(nb, kb)
        idx = gidx - (jnp.arange(nb, dtype=jnp.int32) * bs)[:, None]
        vals = sel.vals.reshape(nb, kb)
        parts = [_header("blocktopk", value_dtype, d, kb, bs),
                 pack_uint(idx.astype(jnp.uint32), ib)]
        if int8:
            scale, q = _quantize(vals)
            parts += [_to_bytes(scale.astype(jnp.float32)),
                      lax.bitcast_convert_type(q, jnp.uint8).reshape(-1)]
        else:
            parts.append(_to_bytes(vals.astype(vdt)))
        return jnp.concatenate(parts)

    comp = make_blocktopk(ratio, block)

    def encode(x, rng=None):
        flat = x.reshape(-1).astype(jnp.float32)
        return encode_from_selection(comp.select(flat), flat.size)

    def decode_to_selection(buf, d: int) -> Selection:
        bs, nb, kb, ib = layout(d)
        off = HEADER_BYTES
        nidx = (nb * kb * ib + 7) // 8
        idx = unpack_uint(buf[off:off + nidx], ib, nb * kb).reshape(nb, kb)
        off += nidx
        if int8:
            scale = _from_bytes(buf[off:], jnp.float32, nb)
            off += 4 * nb
            q = lax.bitcast_convert_type(buf[off:off + nb * kb], jnp.int8)
            vals = q.reshape(nb, kb).astype(jnp.float32) * scale[:, None]
        else:
            vals = _from_bytes(buf[off:], vdt, nb * kb)
            vals = vals.reshape(nb, kb).astype(jnp.float32)
        gidx = idx.astype(jnp.int32) + (jnp.arange(nb, dtype=jnp.int32)
                                        * bs)[:, None]
        return Selection(vals=vals.reshape(-1), idx=gidx.reshape(-1))

    def decode(buf, d: int):
        bs, nb, kb, ib = layout(d)
        sel = decode_to_selection(buf, d)
        out = jnp.zeros(nb * bs, jnp.float32).at[sel.idx].set(sel.vals)
        return out[:d]

    def roundtrip_selection(sel: Selection, d: int) -> Selection:
        if value_dtype == "float32":
            return sel
        bs, nb, kb, ib = layout(d)
        vals = sel.vals.reshape(nb, kb)
        if int8:
            scale, q = _quantize(vals)
            vals = q.astype(jnp.float32) * scale.astype(
                jnp.float32)[:, None]
        else:
            vals = vals.astype(vdt).astype(jnp.float32)
        return Selection(vals=vals.reshape(-1), idx=sel.idx)

    def nbytes(d: int) -> int:
        bs, nb, kb, ib = layout(d)
        n = HEADER_BYTES + (nb * kb * ib + 7) // 8
        return n + (4 * nb + nb * kb if int8 else nb * kb * vb)

    return WireCodec(
        name=f"blocktopk_{ratio:g}_{value_dtype}", encode=encode,
        decode=decode, nbytes=nbytes,
        compressor=comp,
        exact=value_dtype == "float32",
        encode_from_selection=encode_from_selection,
        decode_to_selection=decode_to_selection,
        roundtrip_selection=roundtrip_selection)


def make_sign_codec(block: int = 0) -> WireCodec:
    """1 bit/coordinate + fp32 scale(s). ``block=0``: one global ‖x‖₁/d
    scale — the paper's Table 1 format and bit-exact vs ``make_sign``.
    ``block>0``: one scale per block of that size (beyond-paper; tighter
    local scales at 32 bits/block extra)."""

    def nb_of(d: int) -> int:
        return 1 if block <= 0 else -(-d // block)

    def scales_of(flat, d: int):
        if block <= 0:
            return jnp.mean(jnp.abs(flat)).reshape(1)
        nb = nb_of(d)
        xb = jnp.pad(jnp.abs(flat), (0, nb * block - d)).reshape(nb, block)
        # per-block mean of |x| over the *real* (unpadded) elements
        counts = jnp.clip(d - jnp.arange(nb) * block, 0, block)
        return jnp.sum(xb, axis=1) / counts

    def encode(x, rng=None):
        flat = x.reshape(-1).astype(jnp.float32)
        d = flat.size
        return jnp.concatenate([
            _header("sign", "float32", d, 0, max(block, 0)),
            _to_bytes(scales_of(flat, d)),
            jnp.packbits((flat >= 0).astype(jnp.uint8))])

    def decode(buf, d: int):
        nb = nb_of(d)
        scales = _from_bytes(buf[HEADER_BYTES:], jnp.float32, nb)
        bits = jnp.unpackbits(buf[HEADER_BYTES + 4 * nb:], count=d)
        sgn = bits.astype(jnp.float32) * 2.0 - 1.0
        if block <= 0:
            return scales[0] * sgn
        per_coord = jnp.repeat(scales, block)[:d]
        return per_coord * sgn

    def dense_compress(x, rng=None):
        flat = x.reshape(-1).astype(jnp.float32)
        return decode(encode(flat), flat.size).reshape(x.shape)

    base = make_sign()
    comp = base if block <= 0 else Compressor(
        name=f"sign_b{block}", compress=dense_compress,
        bits_per_message=lambda d: 32 * nb_of(d) + d, q_bound=base.q_bound)

    return WireCodec(
        name="sign" if block <= 0 else f"sign_b{block}",
        encode=encode, decode=decode,
        nbytes=lambda d: HEADER_BYTES + 4 * nb_of(d) + (d + 7) // 8,
        compressor=comp)


def make_wire_codec(name: str, ratio: float = 1 / 64, block: int = 2048,
                    value_dtype: str = "float32") -> WireCodec:
    """Registry mirroring :func:`repro.core.compressors.make_compressor`."""
    if name in ("none", "identity", "dense32"):
        return make_dense32_codec()
    if name == "topk":
        return make_topk_codec(ratio, value_dtype)
    if name == "blocktopk":
        return make_blocktopk_codec(ratio, block, value_dtype)
    if name in ("sign", "packedsign"):
        return make_sign_codec()
    raise ValueError(
        f"no wire codec for compressor {name!r} (randk/int8 deltas have no "
        f"packed format yet — run them with wire=False)")


def measured_vs_analytic(codec: WireCodec, d: int) -> dict:
    """Measured wire size against the Table-1 analytic bit count."""
    analytic_bits = codec.compressor.bits_per_message(d)
    measured_bits = 8 * codec.nbytes(d)
    return {
        "codec": codec.name, "d": d,
        "measured_bytes": codec.nbytes(d),
        "measured_bits": measured_bits,
        "analytic_bits": analytic_bits,
        "header_bits": 8 * codec.header_bytes,
        "overhead_bits": measured_bits - analytic_bits,
    }
