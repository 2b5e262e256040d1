"""Fused dequantize-in-kernel packed matmul: the 4.5-bit serving hot path.

The serving deployment stores weights as :class:`repro.core.qlinear.PackedW`
(HiF4, 0.5625 B/value). Before this kernel, every matmul on the decode hot
path re-materialized a (K, N) bf16 or int8 weight in HBM from those buffers
— so the packed path was 3.56x smaller but paid MORE memory traffic per
token than bf16 serving. Here the kernel consumes the K-major packed
buffers (``codes_km`` (K/2, N) uint8, ``meta_km`` (K/64, N) uint32 — see
docs/FORMATS.md "kernel-tile layout") **directly**: each grid step DMAs a
4.5-bit tile into VMEM, expands two-codes-per-byte + metadata to the
absorbed-shift int8 operand of paper §III.B *inside* VMEM
(:func:`repro.core.hif4.absorbed_int_km`), and contracts all 64-groups of
the tile in one batched MXU ``dot_general``. HBM reads per output tile are
the packed payload plus the activation tile — no (K, N)-sized intermediate
ever exists in HBM.

How a tile decodes: the (bk/2, bn) code bytes are viewed as (bk/4, bn)
16-bit words (``pltpu.bitcast``: code rows 2r and 2r+1, low byte first),
each widened to one int32 word that holds the four codes of contraction
rows 4r..4r+3. Spread one code per byte, the word decodes with integer
ops that each serve four values: magnitude, one left shift by the
micro-exponent sum the four rows share (E1_16 bit r plus E1_8 bit r/2),
and two's complement per byte. Viewed as int8, the words are the tile's
rows in contraction order, so the activation meets the weight in its own
order and no row is shuffled. The stored bytes are the same as ever.

Decode tiles (M <= 32) take a K tile of up to 1024 — it fixes how the
f32 sum over 64-groups associates — and widen N up to a VMEM budget of
2 M values a step, so the per-step cost does not dominate at
Qwen3-4B's widths (``bfp_matmul.select_block_sizes``).

Two executions of the same contraction:

* :func:`fused_packed_matmul` — the Pallas kernel (TPU; ``interpret=True``
  runs it anywhere for tests).
* :func:`fused_packed_matmul_xla` — the identical math as straight-line
  XLA ops, used by the engine off-TPU where interpret-mode Pallas is a
  correctness vehicle, not a serving path. The integer group dots are
  computed in f32 (every |product| <= 28*28 and every 64-term group sum
  < 2^24, so f32 is exact) which hits the fast batched-GEMM path on CPU.

Both are bit-exact against each other and against expanding the packed
buffer first (``tests/test_fused_matmul.py``): in-kernel dequantization
changes WHERE the bits expand, never what is computed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hif4
from repro.kernels.bfp_matmul import (
    GROUP,
    K_GRID_AXIS,
    _tile_group_dot,
    group_major,
    matmul_call,
    resolve_blocks,
)


def _fused_packed_kernel(a_ref, as_ref, codes_ref, meta_ref, o_ref):
    k_step = pl.program_id(K_GRID_AXIS)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # unpack the 4.5-bit tile to absorbed int8 + group scales IN VMEM
    b_ints, b_scales = hif4.absorbed_int_km(codes_ref[...], meta_ref[...])
    o_ref[...] += _tile_group_dot(a_ref[...], as_ref[...], b_ints, b_scales)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def fused_packed_matmul(
    a_ints: jax.Array,     # (M, K)    int8   absorbed activation
    a_scales: jax.Array,   # (M, K/64) f32
    codes_km: jax.Array,   # (K/2, N)  uint8  K-major packed weight payload
    meta_km: jax.Array,    # (K/64, N) uint32
    *,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Packed-operand group-scaled matmul -> (M, N) f32.

    Block sizes default to :func:`select_block_sizes` (decode vs prefill
    regime). The codes/meta BlockSpecs tile the SAME logical (bk, bn)
    window at 1/2 and 1/64 granularity along K, so ``bk`` stays a multiple
    of 64 and every VMEM tile holds whole HiF4 groups.
    """
    M, K = a_ints.shape
    half, N = codes_km.shape
    assert 2 * half == K and K % GROUP == 0, (a_ints.shape, codes_km.shape)
    assert meta_km.shape == (K // GROUP, N), meta_km.shape
    bm, bn, bk = resolve_blocks(M, N, K, block_m, block_n, block_k)
    a3, asc3 = group_major(a_ints, a_scales)
    return matmul_call(_fused_packed_kernel, (a3, asc3, codes_km, meta_km),
                       M=M, N=N, K=K, bm=bm, bn=bn, bk=bk,
                       b_rows=lambda bk: bk // 2, interpret=interpret)


def fused_packed_matmul_xla(a_ints, a_scales, codes_km, meta_km):
    """The fused contraction as straight-line XLA: bit-for-bit the single-
    K-step kernel, without a Pallas lowering requirement.

    Unpack (integer shifts, no exp2 over (K, N)), ONE group-batched f32
    GEMM of the exact integer values, then the per-(row, col, group)
    rescale summed over groups — the same op sequence the kernel runs on a
    full-K tile, so outputs match the interpret-mode kernel bitwise.
    """
    M, K = a_ints.shape
    b_ints, b_scales = hif4.absorbed_int_km(codes_km, meta_km)
    g = K // GROUP
    a3 = a_ints.reshape(M, g, GROUP).astype(jnp.float32)
    b3 = b_ints.reshape(g, GROUP, -1).astype(jnp.float32)
    part = jax.lax.dot_general(
        a3, b3,
        dimension_numbers=(((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                   # (g, M, N) exact ints
    scaled = part * jnp.transpose(a_scales)[:, :, None] * b_scales[:, None, :]
    return jnp.sum(scaled, axis=0)


def absorbed_activation(x2d: jax.Array):
    """Dynamic activation quantization for the XLA twin: (M, K) bf16/f32 ->
    (ints (M, K) int8, scales (M, K/64) f32), bitwise identical to the
    Algorithm-1 Pallas kernel (``repro.kernels.hif4_quant.hif4_quantize``,
    property-tested) but as plain jnp ops."""
    M, K = x2d.shape
    assert K % GROUP == 0, x2d.shape
    g = hif4.quantize_groups(x2d.reshape(M, K // GROUP, GROUP))
    ints, scales = hif4.to_absorbed_int(g)
    return ints.reshape(M, K), scales
