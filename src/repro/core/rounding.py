"""Low-level rounding/encoding primitives for BFP formats.

Everything here is pure jnp, jit-able, and uses round-to-nearest-even (RNE)
as the paper prescribes ("round-half-to-even or round-half-away-from-zero";
we standardize on RNE, which is what ``jnp.round`` implements).

Value-level convention: quantizers take float32 arrays and return float32
arrays holding the *exact representable value* of the target format
("fake quant" / QDQ). Separate encode/decode helpers map values <-> bit
patterns for the packed-storage path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Generic helpers
# ---------------------------------------------------------------------------


def round_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """Round float32 -> nearest bfloat16 (RNE), returned as float32.

    An explicit ``reduce_precision``: under ``jit`` XLA may keep a value in
    excess precision across a bf16 round trip or between fused bf16 ops
    (``xla_allow_excess_precision``), which moves Algorithm-1 decisions;
    a precision reduction is never elided."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)


def _binade_exponent(ax: jnp.ndarray) -> jnp.ndarray:
    """floor(log2(ax)) computed exactly via frexp; ax must be > 0 where used."""
    _, e = jnp.frexp(ax)  # ax = m * 2**e, m in [0.5, 1)
    return e - 1


def _rne_on_quantum(ax: jnp.ndarray, quantum: jnp.ndarray) -> jnp.ndarray:
    """Round |x| to the nearest multiple of ``quantum`` (RNE)."""
    return jnp.round(ax / quantum) * quantum


# ---------------------------------------------------------------------------
# S1P2  (HiF4 in-group element: sign-magnitude, 1 integer + 2 fraction bits)
# grid: +-{0.00, 0.25, ..., 1.75}
# ---------------------------------------------------------------------------

S1P2_MAX = 1.75
S1P2_STEP = 0.25


def quantize_s1p2(x: jnp.ndarray) -> jnp.ndarray:
    q = jnp.round(x / S1P2_STEP) * S1P2_STEP
    return jnp.clip(q, -S1P2_MAX, S1P2_MAX)


def encode_s1p2(v: jnp.ndarray) -> jnp.ndarray:
    """Value on the S1P2 grid -> 4-bit code (uint8): sign<<3 | quarters."""
    sign = (v < 0) | ((v == 0) & (jnp.signbit(v)))
    mag = jnp.round(jnp.abs(v) / S1P2_STEP).astype(jnp.uint8)
    return (sign.astype(jnp.uint8) << 3) | mag


def decode_s1p2(code: jnp.ndarray) -> jnp.ndarray:
    sign = jnp.where((code >> 3) & 1, -1.0, 1.0)
    mag = (code & 0x7).astype(jnp.float32) * S1P2_STEP
    return sign * mag


def s1p2_to_int(v: jnp.ndarray) -> jnp.ndarray:
    """Value on the S1P2 grid -> signed integer quarters in [-7, 7]."""
    return jnp.round(v / S1P2_STEP).astype(jnp.int8)


# ---------------------------------------------------------------------------
# E2M1  (MXFP4 / NVFP4 in-group element)
# grid: +-{0, 0.5, 1, 1.5, 2, 3, 4, 6}
# ---------------------------------------------------------------------------

E2M1_MAX = 6.0
E2M1_VALUES = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)


def quantize_e2m1(x: jnp.ndarray) -> jnp.ndarray:
    ax = jnp.abs(x)
    safe = jnp.maximum(ax, 2.0 ** -20)  # avoid frexp(0); result unaffected
    eb = jnp.clip(_binade_exponent(safe), 0, 2)
    quantum = jnp.ldexp(jnp.float32(1.0), eb - 1)
    q = jnp.minimum(_rne_on_quantum(ax, quantum), E2M1_MAX)
    return jnp.where(x < 0, -q, q)


def encode_e2m1(v: jnp.ndarray) -> jnp.ndarray:
    """Value on E2M1 grid -> 4-bit code: sign<<3 | 3-bit (e,m) code 0..7."""
    av = jnp.abs(v)
    idx = jnp.zeros(v.shape, jnp.uint8)
    for i, val in enumerate(E2M1_VALUES):
        idx = jnp.where(av == val, jnp.uint8(i), idx)
    sign = (v < 0).astype(jnp.uint8)
    return (sign << 3) | idx


def decode_e2m1(code: jnp.ndarray) -> jnp.ndarray:
    table = jnp.asarray(E2M1_VALUES, jnp.float32)
    mag = table[(code & 0x7).astype(jnp.int32)]
    return jnp.where((code >> 3) & 1, -mag, mag)


def e2m1_to_int(v: jnp.ndarray) -> jnp.ndarray:
    """Value on E2M1 grid -> signed integer halves in [-12, 12] (S3P1 flow)."""
    return jnp.round(v / 0.5).astype(jnp.int8)


# ---------------------------------------------------------------------------
# FP8 E4M3 (OCP "FN" variant used by NVFP4 scales)
# bias 7, normals 2^-6..448, subnormals down to 2^-9, no inf, NaN = S.1111.111
# ---------------------------------------------------------------------------

E4M3_MAX = 448.0
E4M3_MIN_NORMAL = 2.0 ** -6
E4M3_MIN_SUBNORMAL = 2.0 ** -9


def round_e4m3(x: jnp.ndarray, saturate: bool = True) -> jnp.ndarray:
    ax = jnp.abs(x)
    safe = jnp.maximum(ax, 2.0 ** -40)
    eb = jnp.clip(_binade_exponent(safe), -6, 8)
    quantum = jnp.ldexp(jnp.float32(1.0), eb - 3)
    q = _rne_on_quantum(ax, quantum)
    q = jnp.minimum(q, E4M3_MAX) if saturate else q
    return jnp.where(x < 0, -q, q)


# ---------------------------------------------------------------------------
# Unsigned FP8 E6M2 (HiF4 level-1 scale)
# bias 48, exponent in [-48, 15], hidden bit 1, no zero/inf/subnormals.
# Encoding 0b111111_11 is NaN, so the max *value* is 2^15 * 1.50.
# ---------------------------------------------------------------------------

E6M2_BIAS = 48
E6M2_MIN = 2.0 ** -48            # 000000_00
E6M2_MAX = (2.0 ** 15) * 1.50    # 111111_10 (111111_11 is NaN)
E6M2_NAN_BITS = 0xFF


def round_e6m2(x: jnp.ndarray) -> jnp.ndarray:
    """Round positive float32 -> nearest representable E6M2 value.

    Values below the minimum clamp to 2^-48 (format has no zero); values
    above the max clamp to 2^15*1.5 (the all-ones pattern is NaN, never
    produced here).
    """
    ax = jnp.maximum(jnp.abs(x), E6M2_MIN)
    eb = jnp.clip(_binade_exponent(ax), -E6M2_BIAS, 15)
    quantum = jnp.ldexp(jnp.float32(1.0), eb - 2)
    q = _rne_on_quantum(ax, quantum)
    return jnp.clip(q, E6M2_MIN, E6M2_MAX)


def encode_e6m2(v: jnp.ndarray) -> jnp.ndarray:
    """Value on the E6M2 grid -> 8-bit code (uint8): (e+48)<<2 | m."""
    eb = _binade_exponent(v)
    m = jnp.round((v / jnp.ldexp(jnp.float32(1.0), eb) - 1.0) * 4.0)
    return ((eb + E6M2_BIAS).astype(jnp.uint8) << 2) | m.astype(jnp.uint8)


def decode_e6m2(code: jnp.ndarray) -> jnp.ndarray:
    eb = (code >> 2).astype(jnp.int32) - E6M2_BIAS
    m = (code & 0x3).astype(jnp.float32)
    val = jnp.ldexp(jnp.float32(1.0), eb) * (1.0 + m * 0.25)
    return jnp.where(code == E6M2_NAN_BITS, jnp.nan, val)


def e6m2_reciprocal_bf16(v: jnp.ndarray) -> jnp.ndarray:
    """The paper's E6M2_REC_to_BF16 instruction.

    Hardware realizes it as a 4-entry LUT on the mantissa plus exponent
    subtraction; numerically identical to RNE(1/v) in bf16 because 1/1.M
    has the same bf16 rounding for all four mantissas (verified in tests).
    """
    return round_bf16(1.0 / v)


# ---------------------------------------------------------------------------
# E8M0 power-of-two scale (MXFP4 shared exponent, OCP MX spec)
# ---------------------------------------------------------------------------

E8M0_EXP_MIN = -127
E8M0_EXP_MAX = 127


def e8m0_scale_from_amax(amax: jnp.ndarray, element_emax: int = 2) -> jnp.ndarray:
    """OCP MX shared scale: 2^(floor(log2(amax)) - emax_elem), clamped.

    ``element_emax`` is the exponent of the element format's max value
    (E2M1 max = 6 -> emax 2). amax == 0 maps to scale 1.
    """
    safe = jnp.maximum(amax, 2.0 ** -126)
    e = _binade_exponent(safe) - element_emax
    e = jnp.clip(e, E8M0_EXP_MIN, E8M0_EXP_MAX)
    scale = jnp.ldexp(jnp.float32(1.0), e)
    return jnp.where(amax > 0, scale, 1.0)
