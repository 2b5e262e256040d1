"""Pallas TPU kernel: HiF4 group-scaled fixed-point matmul (paper §III.B).

The paper's core hardware insight: micro-exponents are left shifts, so a
64-length HiF4 dot is pure integer work with ONE float multiply at the end
(Eq. 3). TPU mapping (DESIGN.md §3): contract each 64-group on the MXU in
int8 (absorbed-shift elements, |q| <= 28; int8 x int8 -> int32 runs at 2x
the bf16 rate on v5e — the same 2x the paper claims for 4-bit PEs), then
apply the single f32 ``a_scale * b_scale`` rescale per (row, col, group)
while accumulating. All 64-groups of a VMEM tile contract in ONE
``dot_general`` with the group axis batched (``_tile_group_dot``) — not a
per-group Python loop of 64-wide dots.

The kernels take the activation GROUP-MAJOR: ``group_major`` lays the
(M, K) int8 operand out as (K/64, M, 64) and its (M, K/64) scales as
(K/64, M, 1), so the group axis is the leading batch axis of the MXU
contraction and of the rescale — the TPU compiler accepts neither a
lane-splitting (M, K) -> (M, K/64, 64) reshape nor a lane-to-sublane
scales relayout inside a kernel.

Grid (M/bm, N/bn, K/bk); each VMEM tile holds whole 64-groups (bk % 64 ==
0). The f32 accumulator lives in VMEM across the K-steps of one (i, j)
tile (standard revisiting-output pattern). That revisit pattern silently
relies on K being the INNERMOST grid axis — consecutive grid steps must
revisit the same out_ref block — so the K position is a named module
invariant (``K_GRID_AXIS``) asserted by every host wrapper, not a
convention.

Block sizes default to a per-regime selection (``select_block_sizes``):
decode calls have tiny M (a batch of single tokens) and want all of M with
deep K / wide N tiles; prefill calls have large M and want square-ish MXU
tiles. Every block is one the TPU compiler tiles: each lane extent a
multiple of 128 or the full dimension, each sublane extent a multiple of
the dtype's tile rows (8 for 32-bit, 32 for int8/uint8) or the full
dimension — and the (bk/64, bn) scales/meta blocks obey the same rule,
so bk is a multiple of 512 or all of K. Pass explicit ``block_*`` to
override (interpret-mode tests tile finer than the chip allows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hif4_quant import _fit

GROUP = 64

# The output-revisit accumulator requires the K grid axis to be LAST
# (innermost): pallas iterates the grid in row-major order, so only the
# last axis advances between consecutive steps of one (i, j) output tile.
K_GRID_AXIS = 2

# Decode M (a batch of single-token rows) vs prefill M (batch x seq)
# regime boundary for block selection.
_DECODE_M_MAX = 32

_LANE = 128                     # lane tile of every dtype
_INT8_ROWS = 32                 # sublane tile of int8/uint8
_META_ROWS = 8                  # sublane tile of the 32-bit scales/meta
# Cap on bk*bn: the unpacked weight tile and its temporaries live in
# VMEM. The fused matmul's word-level unpack keeps four values in each
# int32 temporary (``hif4.absorbed_int_km``): 2 M values take the VMEM
# of 512 K int32s.
_TILE_ELEMS_MAX = 2048 * 1024
# Scoped VMEM the matmul kernels may use (v5e has 128 MiB per core).
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _aligned_block(dim: int, want: int, quantum: int) -> int:
    """Largest multiple of ``quantum`` <= want that divides dim, or the
    whole dim (a full-extent block is always legal) when none does."""
    b = min(want, dim) // quantum * quantum
    while b >= quantum:
        if dim % b == 0:
            return b
        b -= quantum
    return dim


def select_block_sizes(M: int, N: int, K: int) -> tuple[int, int, int]:
    """(bm, bn, bk) per execution regime.

    decode (M <= 32): M doesn't tile — take all of it — and the weight is
    the whole HBM traffic, so a deep-K tile as wide in N as the VMEM cap
    allows maximizes payload per grid step: the fixed cost of a step
    (about 0.35 us) stays small against its unpack and DMA.
    prefill (M large): square-ish 256/256/512 MXU tiles, the classic
    compute-bound shape.

    ``bk`` sets how the f32 sum over a row's 64-groups associates (one
    partial sum per K step), so it stays as chosen here; ``bn`` only
    splits independent output columns.
    """
    if M <= _DECODE_M_MAX:
        bm, want_n, want_k = M, N, 1024
    else:
        bm, want_n, want_k = _aligned_block(M, 256, _INT8_ROWS), 256, 512
    bk = _aligned_block(K, want_k, GROUP * _META_ROWS)
    bn = _aligned_block(N, want_n, _LANE)
    if bk * bn > _TILE_ELEMS_MAX:
        bn = _aligned_block(N, max(_LANE, _TILE_ELEMS_MAX // bk), _LANE)
    return bm, bn, bk


def resolve_blocks(M: int, N: int, K: int, block_m, block_n, block_k):
    """Explicit overrides (fitted to divide) over the regime defaults."""
    abm, abn, abk = select_block_sizes(M, N, K)
    bm = _fit(M, min(block_m, M), 1) if block_m else abm
    bn = _fit(N, min(block_n, N), 1) if block_n else abn
    bk = _fit(K, min(block_k, K), GROUP) if block_k else abk
    return bm, bn, bk


def group_major(a_ints: jax.Array, a_scales: jax.Array):
    """(M, K) int8 + (M, K/64) f32 -> ((K/64, M, 64), (K/64, M, 1))."""
    M, K = a_ints.shape
    a3 = jnp.transpose(a_ints.reshape(M, K // GROUP, GROUP), (1, 0, 2))
    return a3, jnp.transpose(a_scales)[:, :, None]


def _tile_group_dot(a3, asc3, b, bsc):
    """All 64-groups of one VMEM tile in a single batched MXU contraction.

    a3 (g, bm, 64) int8, asc3 (g, bm, 1) f32, b (g*64, bn) int8,
    bsc (g, bn) f32 -> (bm, bn) f32: integer dot per group batched over
    the group axis, then the ONE f32 ``a_scale * b_scale`` rescale per
    (row, col, group) while summing groups (Eq. 3 flow).
    """
    g = a3.shape[0]
    bn = b.shape[1]
    part = jax.lax.dot_general(
        a3, b.reshape(g, GROUP, bn),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )                                                   # (g, bm, bn)
    scaled = part.astype(jnp.float32) * asc3 * bsc[:, None, :]
    return jnp.sum(scaled, axis=0)


def matmul_call(kernel, operands, *, M, N, K, bm, bn, bk, b_rows,
                interpret):
    """The shared pallas_call of the group-scaled matmuls: group-major
    activation operands, a (b_rows(bk), bn) weight tile and its
    (bk/64, bn) scales/meta tile, f32 output accumulated over K."""
    grid = (M // bm, N // bn, K // bk)
    # documented invariant: the accumulator revisit pattern needs K innermost
    assert K_GRID_AXIS == len(grid) - 1 and grid[K_GRID_AXIS] == K // bk
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk // GROUP, bm, GROUP), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((bk // GROUP, bm, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((b_rows(bk), bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // GROUP, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)


def _bfp_matmul_kernel(a_ref, as_ref, b_ref, bs_ref, o_ref):
    k_step = pl.program_id(K_GRID_AXIS)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _tile_group_dot(a_ref[...], as_ref[...],
                                  b_ref[...], bs_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def bfp_matmul_quantized(
    a_ints: jax.Array,     # (M, K) int8
    a_scales: jax.Array,   # (M, K/64) f32
    b_ints: jax.Array,     # (K, N) int8
    b_scales: jax.Array,   # (K/64, N) f32
    *,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Group-scaled integer matmul on pre-quantized HiF4 operands -> f32."""
    M, K = a_ints.shape
    K2, N = b_ints.shape
    assert K == K2 and K % GROUP == 0
    bm, bn, bk = resolve_blocks(M, N, K, block_m, block_n, block_k)
    a3, asc3 = group_major(a_ints, a_scales)
    return matmul_call(_bfp_matmul_kernel, (a3, asc3, b_ints, b_scales),
                       M=M, N=N, K=K, bm=bm, bn=bn, bk=bk,
                       b_rows=lambda bk: bk, interpret=interpret)
