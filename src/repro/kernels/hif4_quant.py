"""Pallas TPU kernel: tiled BF16 -> HiF4 conversion (paper Algorithm 1).

Hardware adaptation (DESIGN.md §3): the paper's bespoke scalar instructions
(BF16->E6M2, E6M2 reciprocal LUT, multiply-compare) become VPU vector ops on
VMEM tiles. The kernel works GROUP-MAJOR: the wrapper lays the (M, K)
source out as (64, M*K/64) — one HiF4 group per lane, its 64 elements down
the sublanes — so a grid step holds ``block_groups`` whole groups and
every reduction of Algorithm 1 is a max over tile-aligned row slabs:

    row  b*16 + d*8 + c  of a group column holds element  8c + 4d + b

(c: the E1_8 sub-group, d: which half of it, b: position in the E1_16
sub-group). The three-level tree max is then a max over four 16-row
slabs (the 16 E1_16 maxima), over two 8-row slabs (the 8 E1_8 maxima),
and a sublane reduce (the group max); the shifts broadcast back by
concatenating those slabs. No lane-splitting reshape, strided access or
scalar bitcast reaches the TPU compiler. The kernel writes

  ints   (M, K)    int8 — S1P2 quarters shifted by the two micro-exponent
                          levels (|q| <= 28)
  scales (M, K/64) f32  — E6M2 / 4 per 64-group

(the wrapper undoes the row order). ``scales[m, g] * ints[m, 64g:64g+64]``
reconstructs Eq. 2 exactly, bitwise equal to ``repro.kernels.ref`` /
``repro.core.hif4`` (tested).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import rounding as R

GROUP = 64
_LANE = 128
_BLOCK_GROUPS = 1024          # groups (lanes) per grid step
_RECIP7_BF16 = float(np.asarray(1.0 / 7.0, jnp.bfloat16))
# bf16(1 / 1.M) for the four E6M2 mantissas: the paper's E6M2_REC LUT
_REC_LUT = tuple(float(np.asarray(1.0 / (1.0 + m / 4), jnp.bfloat16))
                 for m in range(4))


def _fit(dim: int, want: int, quantum: int) -> int:
    """Largest block <= want that divides dim and is a multiple of quantum."""
    b = (want // quantum) * quantum
    while b > quantum and dim % b != 0:
        b -= quantum
    b = max(b, quantum)
    assert dim % b == 0, (dim, want, quantum)
    return b


# -- exact power-of-two arithmetic on vectors (normal f32 range only) -------


def _pow2(e):
    """int32 exponent -> exactly 2^e as f32, by exponent-field bitcast."""
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def _exponent(x):
    """floor(log2 x) of a positive normal f32."""
    return (jax.lax.bitcast_convert_type(x, jnp.int32) >> 23) - 127


def _round_e6m2(x):
    """``rounding.round_e6m2`` for x >= 0, bitwise: the binade comes from
    the exponent field and the quantum scaling is an exact power-of-two
    multiply, instead of frexp/ldexp on scalars."""
    ax = jnp.maximum(x, R.E6M2_MIN)
    eb = jnp.clip(_exponent(ax), -R.E6M2_BIAS, 15)
    q = jnp.round(ax * _pow2(2 - eb)) * _pow2(eb - 2)
    return jnp.clip(q, R.E6M2_MIN, R.E6M2_MAX)


def _e6m2_reciprocal_bf16(v):
    """``rounding.e6m2_reciprocal_bf16`` as the hardware does it: a
    4-entry mantissa LUT times 2^-exponent (exact for the whole E6M2
    range, so bitwise equal to bf16(1/v))."""
    e = _exponent(v)
    m = (jax.lax.bitcast_convert_type(v, jnp.int32) >> 21) & 0x3
    lut = jnp.where(m == 0, _REC_LUT[0],
                    jnp.where(m == 1, _REC_LUT[1],
                              jnp.where(m == 2, _REC_LUT[2], _REC_LUT[3])))
    return lut * _pow2(-e)


def _round_bf16(x):
    """RNE to bf16, kept in f32: the convert round trip, which the kernel
    compiler lowers as written (it has no ``reduce_precision``, the form
    ``rounding.round_bf16`` takes for XLA)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _neg_pow2(s):
    """2^-s for a micro-exponent sum s in {0, 1, 2}."""
    return jnp.where(s == 0, 1.0, jnp.where(s == 1, 0.5, 0.25))


def _quant_kernel(x_ref, ints_ref, scale_ref):
    v = x_ref[...].astype(jnp.float32)                 # (64, bg) group-major
    av = jnp.abs(v)

    # Stage 1: three-level tree max (Alg. 1 lines 1-7)
    v16 = jnp.maximum(jnp.maximum(av[0:16], av[16:32]),
                      jnp.maximum(av[32:48], av[48:64]))    # row d*8 + c
    v8 = jnp.maximum(v16[0:8], v16[8:16])              # row c
    vmax = jnp.max(v8, axis=0, keepdims=True)          # (1, bg)

    # Stage 2: hierarchical scaling metadata (lines 8-14)
    sf = _round_bf16(_round_bf16(vmax) * _RECIP7_BF16)
    e6m2 = _round_e6m2(sf)
    rec = _e6m2_reciprocal_bf16(e6m2)
    e1_8 = (_round_bf16(v8 * rec) > 4.0).astype(jnp.int32)
    shift2 = jnp.concatenate([e1_8, e1_8], axis=0)     # E1_8 per E1_16 row
    t16 = _round_bf16(v16 * rec) * _neg_pow2(shift2)
    e1_16 = (t16 >= 2.0).astype(jnp.int32)

    # Stage 3: scale, round to S1P2 quarters, absorb shifts (lines 15-18)
    shift = jnp.concatenate([shift2 + e1_16] * 4, axis=0)     # (64, bg)
    scaled = _round_bf16(v * rec) * _neg_pow2(shift)
    q = jnp.clip(jnp.round(scaled * 4.0), -7, 7).astype(jnp.int32)
    ints_ref[...] = (q << shift).astype(jnp.int8)      # |q| <= 28
    scale_ref[...] = e6m2 * 0.25


def _group_block(n: int, block_groups: Optional[int]) -> int:
    """Lanes per grid step: all n groups when they fit one step, else a
    multiple of 128 (the wrapper pads n up to a whole number of steps)."""
    want = block_groups or _BLOCK_GROUPS
    if n <= want:
        return n
    return max(_LANE, want // _LANE * _LANE)


@functools.partial(jax.jit, static_argnames=("block_groups", "interpret"))
def hif4_quantize(
    x: jax.Array,
    *,
    block_groups: Optional[int] = None,
    interpret: bool = False,
):
    """x (M, K) bf16/f32 -> (ints (M, K) int8, scales (M, K/64) f32)."""
    M, K = x.shape
    assert K % GROUP == 0, f"K={K} must be a multiple of {GROUP}"
    n = M * K // GROUP
    bg = _group_block(n, block_groups)
    pad = -n % bg
    # element 8c + 4d + b of group j -> row b*16 + d*8 + c of column j
    xt = x.reshape(n, 8, 2, 4).transpose(3, 2, 1, 0).reshape(GROUP, n)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad)))           # zero groups: inert

    ints, scales = pl.pallas_call(
        _quant_kernel,
        grid=((n + pad) // bg,),
        in_specs=[pl.BlockSpec((GROUP, bg), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((GROUP, bg), lambda j: (0, j)),
            pl.BlockSpec((1, bg), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((GROUP, n + pad), jnp.int8),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
        ],
        interpret=interpret,
    )(xt)
    ints = ints[:, :n].reshape(4, 2, 8, n).transpose(3, 2, 1, 0)
    return ints.reshape(M, K), scales[0, :n].reshape(M, K // GROUP)
