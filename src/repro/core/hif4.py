"""HiFloat4 (HiF4) block floating-point format — the paper's contribution.

A HiF4 unit = 64 S1P2 elements + 32-bit metadata:
    [ E6M2 scale : 8b | E1_8 micro-exps : 8b | E1_16 micro-exps : 16b ]
Value of element i (1-based):
    V_i = E6M2 * 2^(E1_8[ceil(i/8)] + E1_16[ceil(i/4)]) * S1P2_i

This module implements Algorithm 1 (BF16 -> HiF4) with explicit bf16
emulation of every step the paper executes in bf16 hardware, plus
dequantization, bit-packing (4.5 bits/value storage), and the integer
"absorbed shift" representation used by the fixed-point dot product
(paper SS III.B).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core import rounding as R

GROUP_SIZE = 64
N_E1_8 = 8    # level-2 micro-exponents: one per 8 elements
N_E1_16 = 16  # level-3 micro-exponents: one per 4 elements
BITS_PER_VALUE = 4.5
# E6M2 code 0xFF decodes to NaN on every path (_meta_fields below,
# rounding.decode_e6m2). Algorithm 1 NEVER produces it, so its presence in
# packed metadata is definitionally corruption — the health sentinel the
# serving guard (repro.runtime.guard) counts on packed KV pages.
META_NAN = 0xFF
MAX_POS = (2.0 ** 15 * 1.5) * 4.0 * 1.75   # = 2^18 * 1.3125  (Table II)
MIN_POS = 2.0 ** -48 * 0.25                # = 2^-50           (Table II)
INTRA_MAX = 7.0                            # 2^(1+1) * 1.75 (Alg. 1 line 8)

_RECIP7_BF16 = float(np.asarray(1.0 / 7.0, jnp.bfloat16))  # (1/7)_BF16


class HiF4Groups(NamedTuple):
    """Value-level (unpacked) HiF4 representation of shape (..., 64) data."""

    e6m2: jnp.ndarray    # (...,)     f32, value on the E6M2 grid
    e1_8: jnp.ndarray    # (..., 8)   int32 in {0, 1}
    e1_16: jnp.ndarray   # (..., 16)  int32 in {0, 1}
    s1p2: jnp.ndarray    # (..., 64)  f32, value on the S1P2 grid


class HiF4Packed(NamedTuple):
    """Bit-packed HiF4: 4.5 bits/value storage (deployment artifact)."""

    codes: jnp.ndarray   # (..., 32) uint8 — two 4-bit S1P2 codes per byte
    meta: jnp.ndarray    # (...,)    uint32 — e6m2<<24 | e1_8<<16 | e1_16


def quantize_groups(v: jnp.ndarray) -> HiF4Groups:
    """Algorithm 1: convert (..., 64) bf16/f32 values to HiF4 components.

    Runs in f32 with every bf16 hardware rounding made explicit
    (``round_bf16``), for bf16 inputs too: plain bf16 arithmetic would
    round the same way op by op, but under ``jit`` XLA may fuse bf16 ops
    in excess precision and skip those roundings.
    """
    v = v.astype(jnp.float32)
    av = jnp.abs(v)
    lead = v.shape[:-1]

    # Stage 1: three-level tree max reduction (lines 1-7).
    v16 = jnp.max(av.reshape(lead + (16, 4)), axis=-1)          # (..., 16)
    v8 = jnp.max(v16.reshape(lead + (8, 2)), axis=-1)           # (..., 8)
    vmax = jnp.max(v8, axis=-1)                                 # (...,)

    # Stage 2: hierarchical scaling metadata (lines 8-14).
    sf = R.round_bf16(R.round_bf16(vmax) * _RECIP7_BF16)        # line 8
    e6m2 = R.round_e6m2(sf)                                     # line 9
    rec = R.e6m2_reciprocal_bf16(e6m2)                          # line 10
    e1_8 = (R.round_bf16(v8 * rec[..., None]) > 4.0)            # line 11
    e1_8 = e1_8.astype(jnp.int32)
    shift2 = jnp.repeat(e1_8, 2, axis=-1)                       # (..., 16)
    t16 = R.round_bf16(v16 * rec[..., None]) * jnp.ldexp(jnp.float32(1.0), -shift2)
    e1_16 = (t16 >= 2.0).astype(jnp.int32)                      # line 13

    # Stage 3: scale and round the 64 elements (lines 15-18).
    shift8 = jnp.repeat(e1_8, 8, axis=-1)                       # (..., 64)
    shift4 = jnp.repeat(e1_16, 4, axis=-1)                      # (..., 64)
    scaled = R.round_bf16(v * rec[..., None]) * jnp.ldexp(
        jnp.float32(1.0), -(shift8 + shift4)
    )
    s1p2 = R.quantize_s1p2(scaled)                              # line 18
    return HiF4Groups(e6m2=e6m2, e1_8=e1_8, e1_16=e1_16, s1p2=s1p2)


def dequantize_groups(g: HiF4Groups) -> jnp.ndarray:
    """Equation 2: reconstruct (..., 64) values.

    Computes in the s1p2 dtype: the product E6M2 * 2^shift * S1P2 carries
    at most 2+3 significant bits, so it is EXACT in bf16 as well as f32.
    """
    dt = g.s1p2.dtype
    shift = jnp.repeat(g.e1_8, 8, axis=-1) + jnp.repeat(g.e1_16, 4, axis=-1)
    scale = g.e6m2.astype(dt)[..., None] * jnp.ldexp(
        jnp.ones((), dt), shift)
    return scale * g.s1p2


def meta_nan_mask(meta: jnp.ndarray) -> jnp.ndarray:
    """Elementwise True where a packed meta word carries the E6M2 NaN
    sentinel (scale byte == :data:`META_NAN`). Any True is corruption:
    Algorithm 1 never emits 0xFF, and every decode path turns it into NaN
    (:func:`_meta_fields`), so this mask is the cheap integrity probe
    health audits reduce over."""
    return (meta >> 24) == jnp.uint32(META_NAN)


# ---------------------------------------------------------------------------
# Fixed-point ("absorbed shift") view — paper SS III.B
# ---------------------------------------------------------------------------


def to_absorbed_int(g: HiF4Groups) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Absorb micro-exponents into integer elements (S2P2-and-wider view).

    Returns ``(ints, scale)`` where ``ints`` is (..., 64) int8 holding
    S1P2-quarters shifted left by (E1_8 + E1_16) — |q| <= 7*4 = 28 — and
    ``scale`` is (...,) f32 = E6M2 / 4 (the 1/4 is the quarter-LSB of
    S1P2). Reconstruction ``scale * ints`` and the dot product
    ``scale_A*scale_B*sum(intA*intB)`` are *exact* (verified in tests).
    """
    quarters = R.s1p2_to_int(g.s1p2).astype(jnp.int32)
    shift = jnp.repeat(g.e1_8, 8, axis=-1) + jnp.repeat(g.e1_16, 4, axis=-1)
    ints = (quarters << shift).astype(jnp.int8)
    scale = g.e6m2 * 0.25  # each operand contributes sqrt(1/16) = 1/4
    return ints, scale


# ---------------------------------------------------------------------------
# K-major ("kernel-tile") bit-layout helpers — usable from inside a kernel
# ---------------------------------------------------------------------------
#
# The packed artifact stores a weight output-major: codes (N, K/64, 32),
# meta (N, K/64) (see docs/FORMATS.md).  A matmul kernel consumes the
# CONTRACTION axis innermost, so the serving re-layout transposes the
# payload once into K-major 2-D buffers
#
#     codes_km (K/2, N) uint8    row k2 holds elements 2*k2 (low nibble)
#                                and 2*k2+1 (high nibble) of column n
#     meta_km  (K/64, N) uint32  one group record per 64 contraction rows
#
# and the helpers below expand a (bk/2, bn) / (bk/64, bn) VMEM tile of
# those buffers to the absorbed-shift int8 operand of paper §III.B.  They
# are pure jnp on whatever tile they are given — the same code runs inside
# a Pallas kernel on VMEM refs and in the XLA twin of the fused matmul.
# They are written in the forms the TPU kernel compiler (Mosaic) accepts:
# sub-word and unsigned integers widen to int32 before any arithmetic,
# unsigned words are bitcast to int32 and shifted logically, index
# vectors are 2-D+ iotas, and per-group values broadcast over a new
# sublane axis instead of ``jnp.repeat``.


def expand_codes_km(codes_km: jnp.ndarray) -> jnp.ndarray:
    """(bk/2, bn) uint8 K-major code bytes -> (bk, bn) int32 S1P2 quarters.

    Low nibble is the even contraction row, high nibble the odd one; the
    4-bit code is sign<<3 | quarters (rounding.encode_s1p2)."""
    c = codes_km.astype(jnp.int32)
    half, bn = codes_km.shape
    c4 = jnp.stack([c & 0xF, c >> 4], axis=1).reshape(half * 2, bn)
    return _signed_quarters(c4)


def _signed_quarters(c4: jnp.ndarray) -> jnp.ndarray:
    """4-bit S1P2 codes (int32) -> signed quarters."""
    mag = c4 & 0x7
    return jnp.where((c4 >> 3) & 1 == 1, -mag, mag)


def _micro_shift(w8, w16, r):
    """E1_8 + E1_16 micro-exponent sum of in-group rows ``r`` (bg, R, bn)."""
    return (((w8[:, None, :] >> (r // 8)) & 1)
            + ((w16[:, None, :] >> (r // 4)) & 1))


def _meta_shift_scale(meta_km: jnp.ndarray):
    """(bg, bn) uint32 metadata -> (shift (bg, 64, bn) int32, scale
    (bg, bn) f32): the per-element micro-exponent sum E1_8 + E1_16 and
    the group scale E6M2 / 4."""
    bg, bn = meta_km.shape
    w8, w16, scale = _meta_fields(meta_km)
    r = jax.lax.broadcasted_iota(jnp.int32, (bg, GROUP_SIZE, bn), 1)
    return _micro_shift(w8, w16, r), scale


def _meta_fields(meta_km: jnp.ndarray):
    """(bg, bn) uint32 metadata -> (E1_8 bits, E1_16 bits, scale f32)."""
    m = jax.lax.bitcast_convert_type(meta_km, jnp.int32)
    w8 = jax.lax.shift_right_logical(m, 16) & 0xFF   # E1_8 bits
    w16 = m & 0xFFFF                                  # E1_16 bits
    code = jax.lax.shift_right_logical(m, 24)
    # 2^eb built by exponent-field bitcast: jnp.exp2 is a polynomial
    # approximation that is NOT exact across the E6M2 range (observed
    # exp2(15) != 32768 on CPU), and the scale must stay on the exact
    # power-of-two grid. eb in [-48, 15] is always a normal f32.
    eb = (code >> 2) - 48
    pow2 = jax.lax.bitcast_convert_type((eb + 127) << 23, jnp.float32)
    m2 = (code & 0x3).astype(jnp.float32)
    scale = pow2 * (1.0 + m2 * 0.25) * 0.25
    # E6M2 0xFF is NaN (never produced by Algorithm 1, but corrupted bits
    # must decode identically on every path — decode_e6m2 parity)
    scale = jnp.where(code == 0xFF, jnp.nan, scale)
    return w8, w16, scale


# Word-level ("SWAR") decode for the fused matmul. A 16-bit view of a
# code tile (``pltpu.bitcast``: code rows 2r and 2r+1, low byte first)
# holds contraction rows 4r..4r+3, one nibble each. Widened to int32 and
# spread one nibble per byte, each word decodes with a handful of integer
# ops for its four values, and its bytes, viewed as int8, are those rows
# in contraction order: no value is widened alone, no row is shuffled.
# The four rows of a word share one E1_16 bit (r) and one E1_8 bit (r/2),
# so one shift serves the whole word; |q| <= 7 << 2 = 28 never carries
# into the next byte.
_WORD_ROWS = GROUP_SIZE // 4        # int32 word rows of one group
_MSB = 0x80808080 - (1 << 32)       # bit 7 of each byte, as an int32


def _bitcast_rows(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """``pltpu.bitcast``: consecutive rows of ``x`` packed into (or
    unpacked from) wider words, the first row in the low bits. Mosaic
    lowers it inside a kernel and XLA under ``jit``; it has no eager
    rule, so a concrete array (``jax.disable_jit``) takes the reshape and
    ``bitcast_convert_type`` it lowers to."""
    if isinstance(x, jax.core.Tracer):
        return pltpu.bitcast(x, dtype)
    ratio = jnp.dtype(dtype).itemsize / x.dtype.itemsize
    *lead, m, n = x.shape
    if ratio > 1:
        x = x.reshape(*lead, m // int(ratio), int(ratio), n)
        return jax.lax.bitcast_convert_type(jnp.swapaxes(x, -1, -2), dtype)
    y = jnp.swapaxes(jax.lax.bitcast_convert_type(x, dtype), -1, -2)
    return y.reshape(*lead, int(m / ratio), n)


def absorbed_int_km(codes_km: jnp.ndarray, meta_km: jnp.ndarray):
    """K-major packed tile -> (ints (bk, bn) int8, scale (bk/64, bn) f32).

    The §III.B absorbed-shift operand (micro-exponents folded in as left
    shifts, |q| <= 28), produced directly from the 4.5-bit payload four
    values per int32 op: bitwise identical to
    ``to_absorbed_int(unpack_groups(...))`` re-laid out K-major, with
    ``scale`` = E6M2 / 4 (NaN for the 0xFF record)."""
    bg, bn = meta_km.shape
    half = _bitcast_rows(codes_km, jnp.int16)              # (bk/4, bn)
    x = half.astype(jnp.int32).reshape(bg, _WORD_ROWS, bn) & 0xFFFF
    x = (x | (x << 8)) & 0x00FF00FF     # code row 2r in byte 0, 2r+1 in 2
    x = x | (x << 4)                    # row 4r+b's code: low nibble, byte b
    w8, w16, scale = _meta_fields(meta_km)
    r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = (((w8[:, None, :] >> (r >> 1)) & 1)
             + ((w16[:, None, :] >> r) & 1))
    mag = (x & 0x07070707) << shift
    sign = (x >> 3) & 0x01010101
    # per byte: q for sign 0, 256 - q (0 for q = 0) for sign 1, no borrow
    ints = ((mag | _MSB) - sign) ^ (_MSB - sign)
    return _bitcast_rows(ints.reshape(bg * _WORD_ROWS, bn), jnp.int8), scale


def dequantize_km(codes_km: jnp.ndarray, meta_km: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """K-major packed buffers -> (K, N) dense values.

    ``scale * ints`` carries <= 6 significant bits, so the reconstruction
    is exact in bf16 as well as f32 — and unlike the output-major
    dequantize it needs no final (N, K) -> (K, N) transpose and no
    per-element exp2 (shifts are integer left-shifts)."""
    shift, scale = _meta_shift_scale(meta_km)
    bg, _, bn = shift.shape
    quarters = expand_codes_km(codes_km).reshape(bg, GROUP_SIZE, bn)
    vals = scale[:, None, :] * (quarters << shift).astype(jnp.float32)
    return vals.reshape(bg * GROUP_SIZE, bn).astype(dtype)


def dequantize_km_split(codes_km: jnp.ndarray, meta_km: jnp.ndarray):
    """K-major packed buffers -> (even rows, odd rows), each (K/2, N) f32.

    The values :func:`dequantize_km` gives (before its cast), with the
    even and odd contraction rows — a code byte's low and high nibble —
    left apart: interleaving them as arrays is a costly sublane shuffle
    in a TPU kernel, where two stride-2 row stores into VMEM do it."""
    w8, w16, scale = _meta_fields(meta_km)
    bg, bn = meta_km.shape
    half = codes_km.shape[0]
    c = codes_km.astype(jnp.int32).reshape(bg, GROUP_SIZE // 2, bn)
    r = 2 * jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    rows = []
    for parity, nibble in enumerate((c & 0xF, c >> 4)):
        quarters = _signed_quarters(nibble)
        shift = _micro_shift(w8, w16, r + parity)
        vals = scale[:, None, :] * (quarters << shift).astype(jnp.float32)
        rows.append(vals.reshape(half, bn))
    return tuple(rows)


# ---------------------------------------------------------------------------
# Bit packing (storage at 4.5 bits/value)
# ---------------------------------------------------------------------------


def pack_groups(g: HiF4Groups) -> HiF4Packed:
    codes4 = R.encode_s1p2(g.s1p2)                               # (..., 64) uint8
    lo = codes4[..., 0::2]
    hi = codes4[..., 1::2]
    codes = (lo | (hi << 4)).astype(jnp.uint8)                   # (..., 32)

    e6_bits = R.encode_e6m2(g.e6m2).astype(jnp.uint32)           # (...,)
    w8 = jnp.sum(
        g.e1_8.astype(jnp.uint32) << jnp.arange(N_E1_8, dtype=jnp.uint32), axis=-1
    )
    w16 = jnp.sum(
        g.e1_16.astype(jnp.uint32) << jnp.arange(N_E1_16, dtype=jnp.uint32), axis=-1
    )
    meta = (e6_bits << 24) | (w8 << 16) | w16
    return HiF4Packed(codes=codes, meta=meta)


def quantize_packed(v: jnp.ndarray) -> HiF4Packed:
    """Algorithm 1 + bit packing in one step: (..., 64) values -> 4.5-bit
    storage. This is the unit every packed artifact is built from — weights
    (:class:`repro.core.qlinear.PackedW`) and the KV cache
    (:mod:`repro.core.kvcache`) share it, so their bits always agree with
    the QDQ grid (see docs/FORMATS.md for the layout)."""
    return pack_groups(quantize_groups(v))


def dequantize_packed(p: HiF4Packed) -> jnp.ndarray:
    """Inverse of :func:`quantize_packed` up to the value grid: unpack the
    bits and reconstruct the (..., 64) values (exact, also in bf16)."""
    return dequantize_groups(unpack_groups(p))


def unpack_groups(p: HiF4Packed) -> HiF4Groups:
    lo = p.codes & 0xF
    hi = p.codes >> 4
    codes4 = jnp.stack([lo, hi], axis=-1).reshape(p.codes.shape[:-1] + (GROUP_SIZE,))
    s1p2 = R.decode_s1p2(codes4)

    e6m2 = R.decode_e6m2((p.meta >> 24).astype(jnp.uint8))
    w8 = (p.meta >> 16) & 0xFF
    w16 = p.meta & 0xFFFF
    e1_8 = ((w8[..., None] >> jnp.arange(N_E1_8, dtype=jnp.uint32)) & 1).astype(jnp.int32)
    e1_16 = ((w16[..., None] >> jnp.arange(N_E1_16, dtype=jnp.uint32)) & 1).astype(
        jnp.int32
    )
    return HiF4Groups(e6m2=e6m2, e1_8=e1_8, e1_16=e1_16, s1p2=s1p2)


# ---------------------------------------------------------------------------
# Tensor-level QDQ entry point (axis -> groups of 64)
# ---------------------------------------------------------------------------


def qdq(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Quantize-dequantize ("fake quant") along ``axis`` in groups of 64."""
    from repro.core.grouping import apply_grouped  # local import, no cycle

    return apply_grouped(
        lambda v: dequantize_groups(quantize_groups(v)), x, axis, GROUP_SIZE
    )
