"""Fused HiF4 flash decode-attention: stream the 4.5-bit KV cache into MXU.

The serving KV cache is resident as HiF4 packed leaves (4.5 bits/value,
``repro.core.kvcache``). Before this kernel, every decode step dequantized
the ENTIRE per-layer cache to a (B, S, Hkv, Dh) bf16 array in HBM
(``repro.models.attention.decode_attention_packed`` before its bounded
rewrite), so the packed cache bought residency but paid bf16 HBM traffic on
the decode hot path. Here the kernel consumes the KERNEL-TILE cache layout
(``codes`` (B, F/2, S) uint8, ``meta`` (B, G, S) uint32 — see
docs/FORMATS.md "Packed KV-cache layout") **directly**: each grid step DMAs
one 4.5-bit KV tile into VMEM, expands codes+meta to bf16 K/V columns
*inside* VMEM with the same K-major bit helpers the fused matmul uses
(``repro.core.hif4.dequantize_km``), and folds the tile into an online-
softmax recurrence. HBM reads per decode step are the packed payload — the
bf16 working set is one (features, kv-tile) block, never the cache.

Grid: (batch-slot, kv-head block, KV tile), KV innermost so the softmax
state (m, l, normalized accumulator) lives in VMEM scratch across the
tiles of one (slot, head) cell. A head block covers
``lcm(d_head, 64) // d_head`` heads so every codes/meta block holds whole
HiF4 groups even when a 64-group spans heads (d_head < 64). Per-slot
``length`` masks the cache tail exactly like
``repro.models.attention.decode_attention``.

Two executions of the same contraction:

* :func:`fused_decode_attention` — the Pallas kernel (TPU;
  ``interpret=True`` runs it anywhere for tests).
* :func:`fused_decode_attention_xla` — the identical recurrence as
  straight-line XLA (a tightened Sq=1 form of the
  ``repro.models.attention.flash_mha_vec_packed`` chunked-loader
  recurrence), used by the engine off-TPU and for cache layouts the kernel
  cannot tile (artifact layout, partial-group staging tail).

The recurrence keeps the accumulator NORMALIZED at every step
(``acc <- acc * (l*corr/l_new) + (e/l_new)_bf16 @ V``), so with a single
KV tile it degenerates to exactly the flat masked softmax of
``decode_attention`` — max, exp, sum, divide, bf16 probabilities, f32 PV
dot, in that order — and the three paths are BITWISE equal there
(``tests/test_fused_attention.py``; multi-tile runs reassociate the f32
sums and are float-close, mirroring the single-K-step anchor of
``tests/test_fused_matmul.py``). NaN metadata (E6M2 0xFF) propagates
identically on every path.

PAGED variant: when the KV cache lives in the fixed-size page pool of
``repro.core.kvcache`` (leaves (n_pages, F, P), per-slot page table — see
docs/FORMATS.md "Paged KV-cache pool"), the same recurrence runs with the
KV-tile grid axis walking the page table instead of a contiguous token
axis. :func:`fused_paged_decode_attention` prefetches the (B, max_pages)
table as a scalar-prefetch operand and gathers each tile's pool page in
the BlockSpec index map; :func:`fused_paged_decode_attention_xla` is its
bitwise twin (a scan whose tile loader is a page gather instead of a
token slice). Because a fully masked tile is an exact no-op of the
recurrence (``exp(NEG_INF - m)`` underflows to f32 zero and the
correction factor is exactly 1.0), paged attention over pages of P
tokens is BITWISE equal to the contiguous kernel/twin run with
``block_kv=P`` on a capacity padded to a page multiple — the parity
``tests/test_paged_kv.py`` pins.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hif4, kvcache
from repro.kernels.hif4_quant import _fit

NEG_INF = -1e30   # matches repro.models.attention.NEG_INF (masked-score value)

# The softmax-state revisit pattern requires the KV-tile grid axis to be
# LAST (innermost): scratch carries (m, l, acc) across consecutive grid
# steps of one (slot, head-block) cell.
KV_GRID_AXIS = 2

# Decode KV tiles: deep tiles maximize packed payload per grid step; small
# caches take a single tile (the regime where the recurrence IS the flat
# softmax, bitwise).
_KV_TILE = 256

_LANE = 128        # lane tile: token-axis blocks are multiples or all of S
_META_ROWS = 8     # sublane tile of the uint32 meta block (feature groups)


def select_kv_block(seq: int, block_kv: Optional[int] = None) -> int:
    """Per-regime KV tile size: whole cache when it fits one tile
    (<= ``_KV_TILE`` slots), else a divisor of ``seq`` near the tile
    target — every tile holds whole token slots, groups never split
    (grouping is per token).

    Awkward capacities (e.g. a prime 509 = prompt 381 + budget 128) have
    no useful divisor below the target; the largest one can be 1, which
    would silently turn decode attention into an S-step scan per layer.
    When the best divisor below the target is degenerate (< 1/4 of it),
    take the SMALLEST divisor at or above the target instead — at worst
    one tile spanning the whole cache, never a 1-token tile storm.
    """
    want = min(block_kv or _KV_TILE, seq)
    best = _fit(seq, want, 1)
    if best * 4 < want:
        best = next(d for d in range(want, seq + 1) if seq % d == 0)
    return best


def heads_per_block(d_head: int, n_kv_heads: Optional[int] = None) -> int:
    """KV heads per grid step so head blocks hold whole 64-groups.

    d_head % 64 == 0 -> 1; d_head = 32 -> 2; etc. (lcm(d_head, 64)/d_head).
    With ``n_kv_heads`` given, the block the TPU compiler can tile: the
    smallest multiple of that unit dividing the head count whose
    (hb*d_head/64)-row meta block is a whole 8-row sublane tile, or all
    heads (a full-extent block) when no such multiple exists.
    """
    unit = math.lcm(d_head, 64) // d_head
    if n_kv_heads is None:
        return unit
    for hb in range(unit, n_kv_heads + 1, unit):
        if n_kv_heads % hb == 0 and (hb * d_head // 64) % _META_ROWS == 0:
            return hb
    return n_kv_heads


def kernel_compatible(k_cache: dict, n_kv_heads: int, d_head: int) -> bool:
    """Can the Pallas kernel tile this cache?  Needs the kernel-tile layout,
    no partial-group staging tail (the tail is bf16 prose the kernel has no
    bit helper for), and head blocks that divide the head count. The last
    condition is implied by a tail-free F (64 | Hkv*Dh forces
    64/gcd(Dh, 64) | Hkv) — kept as a cheap structural guard."""
    return (
        kvcache.is_kernel_layout(k_cache)
        and k_cache["tail"].shape[-2] == 0
        and n_kv_heads % heads_per_block(d_head) == 0
    )


def _dma_width(seq: int, ck: int) -> int:
    """Token columns one grid step DMAs: the smallest multiple of the
    recurrence tile ``ck`` that is a whole number of 128-lane tiles and
    divides ``seq``, else the whole cache (a full-extent block). The
    kernel folds the ``width / ck`` recurrence tiles of a step one by one,
    so the arithmetic is the twin's at ``block_kv=ck`` whatever the DMA
    width."""
    for w in range(ck, seq + 1, ck):
        if w % _LANE == 0 and seq % w == 0:
            return w
    return seq


def _online_softmax_tile(q, kc, km, vc, vm, length, first_pos, m_ref, l_ref,
                         acc_ref, *, d_head: int):
    """Fold one KV tile into the normalized online-softmax state.

    q (hb, rep, D) bf16; kc/vc (hb*D/2, ck) uint8 and km/vm (hb*D/64, ck)
    uint32 packed columns of tokens first_pos .. first_pos+ck-1.
    """
    hb = q.shape[0]
    ck = kc.shape[-1]
    # expand the 4.5-bit tile to bf16 K/V columns IN VMEM (K-major helpers)
    kT = hif4.dequantize_km(kc, km).reshape(hb, d_head, ck)
    vT = hif4.dequantize_km(vc, vm).reshape(hb, d_head, ck)
    s = jax.lax.dot_general(
        q, kT, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) / (d_head ** 0.5)                                  # (hb, rep, ck)
    kp = first_pos + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ck), 2)
    s = jnp.where(kp < length, s, NEG_INF)

    m_prev = m_ref[..., :1]
    l_prev = l_ref[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    e = jnp.exp(s - m_new)
    l_new = l_prev * corr + jnp.sum(e, axis=-1, keepdims=True)
    p = (e / l_new).astype(vT.dtype)                     # normalized, bf16
    pv = jax.lax.dot_general(
        p, vT, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                    # (hb, rep, D)
    acc_ref[...] = acc_ref[...] * (l_prev * corr / l_new) + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _fused_decode_kernel(len_ref, q_ref, kc_ref, km_ref, vc_ref, vm_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, d_head: int,
                         n_steps: int, block_kv: int):
    b = pl.program_id(0)
    ki = pl.program_id(KV_GRID_AXIS)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                         # (hb, rep, D) bf16
    width = kc_ref.shape[-1]
    for t in range(width // block_kv):                   # static sub-tiles
        cols = slice(t * block_kv, (t + 1) * block_kv)
        _online_softmax_tile(
            q, kc_ref[0, :, cols], km_ref[0, :, cols], vc_ref[0, :, cols],
            vm_ref[0, :, cols], len_ref[b], ki * width + t * block_kv,
            m_ref, l_ref, acc_ref, d_head=d_head)

    @pl.when(ki == n_steps - 1)
    def _fin():
        o_ref[0] = acc_ref[...]


def _softmax_scratch(hb: int, rep: int, d_head: int):
    return [
        pltpu.VMEM((hb, rep, _LANE), jnp.float32),       # running max
        pltpu.VMEM((hb, rep, _LANE), jnp.float32),       # running denom
        pltpu.VMEM((hb, rep, d_head), jnp.float32),      # normalized acc
    ]


@functools.partial(
    jax.jit,
    static_argnames=("n_kv_heads", "d_head", "block_kv", "interpret"),
)
def fused_decode_attention(
    q: jax.Array,            # (B, H, D) bf16 — the single query token
    k_cache: dict,           # kernel-tile packed leaves {codes, meta, tail}
    v_cache: dict,
    length: jax.Array,       # (B,) valid cache prefix per slot
    *,
    n_kv_heads: int,
    d_head: int,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode-attention straight off the 4.5-bit KV cache -> (B, H, D).

    Requires :func:`kernel_compatible` geometry (the engine routes
    everything else to :func:`fused_decode_attention_xla`). ``length``
    rides in SMEM as a scalar-prefetch operand.
    """
    B, H, D = q.shape
    assert D == d_head and kernel_compatible(k_cache, n_kv_heads, d_head)
    S = kvcache.seq_capacity(k_cache)
    rep = H // n_kv_heads
    hb = heads_per_block(d_head, n_kv_heads)
    ck = select_kv_block(S, block_kv)
    width = _dma_width(S, ck)
    n_steps = S // width
    grid = (B, n_kv_heads // hb, n_steps)
    assert KV_GRID_AXIS == len(grid) - 1 and grid[KV_GRID_AXIS] == n_steps

    qf = q.reshape(B, n_kv_heads, rep, D)
    kernel = functools.partial(_fused_decode_kernel, d_head=d_head,
                               n_steps=n_steps, block_kv=ck)
    codes = pl.BlockSpec((1, hb * D // 2, width), lambda b, h, k, ln: (b, h, k))
    meta = pl.BlockSpec((1, hb * D // 64, width), lambda b, h, k, ln: (b, h, k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, rep, D), lambda b, h, k, ln: (b, h, 0, 0)),
            codes, meta, codes, meta,
        ],
        out_specs=pl.BlockSpec((1, hb, rep, D),
                               lambda b, h, k, ln: (b, h, 0, 0)),
        scratch_shapes=_softmax_scratch(hb, rep, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_heads, rep, D), jnp.float32),
        interpret=interpret,
    )(length.astype(jnp.int32), qf, k_cache["codes"], k_cache["meta"],
      v_cache["codes"], v_cache["meta"])
    return out.reshape(B, H, D).astype(q.dtype)


def fused_decode_attention_xla(
    q: jax.Array,            # (B, H, D)
    k_cache: dict,           # packed leaves, either layout
    v_cache: dict,
    length: jax.Array,       # (B,)
    n_kv_heads: int,
    d_head: int,
    *,
    block_kv: Optional[int] = None,
) -> jax.Array:
    """The kernel's recurrence as straight-line XLA: the off-TPU serving
    twin, and the executable form for artifact-layout / staging-tail caches.

    A ``lax.scan`` over KV tiles; each tile is sliced from the packed
    leaves, dequantized through the shared K-major decode
    (``repro.core.kvcache.dequantize_kv``), masked, and folded into the
    normalized online-softmax state. The bf16 working set is one
    (B, block_kv, Hkv, Dh) tile — never the whole cache — and the per-tile
    ops mirror the kernel blocks exactly, so interpret-mode kernel and twin
    agree bitwise at every tiling.
    """
    B, H, D = q.shape
    assert D == d_head
    S = kvcache.seq_capacity(k_cache)
    rep = H // n_kv_heads
    ck = select_kv_block(S, block_kv)
    n_tiles = S // ck
    qf = q.reshape(B, n_kv_heads, rep, D)
    positions = jnp.arange(ck)

    def tile(carry, ki):
        m, l, acc = carry
        kblk = kvcache.dequantize_kv(
            kvcache.slice_tokens(k_cache, ki * ck, ck), n_kv_heads, d_head)
        vblk = kvcache.dequantize_kv(
            kvcache.slice_tokens(v_cache, ki * ck, ck), n_kv_heads, d_head)
        s = jnp.einsum("bgrd,bkgd->bgrk", qf, kblk,
                       preferred_element_type=jnp.float32) / (d_head ** 0.5)
        valid = (ki * ck + positions)[None, :] < length[:, None]     # (B, ck)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        p = (e / l_new).astype(vblk.dtype)
        pv = jnp.einsum("bgrk,bkgd->bgrd", p, vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * (l * corr / l_new) + pv
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, n_kv_heads, rep, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, n_kv_heads, rep, 1), jnp.float32),
        jnp.zeros((B, n_kv_heads, rep, D), jnp.float32),
    )
    if n_tiles == 1:
        (_, _, acc), _ = tile(init, 0)
    else:
        (_, _, acc), _ = jax.lax.scan(tile, init, jnp.arange(n_tiles))
    return acc.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged variant: the KV-tile grid axis walks a per-slot page table
# ---------------------------------------------------------------------------


def _fused_paged_kernel(pt_ref, len_ref, q_ref, kc_ref, km_ref, vc_ref,
                        vm_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        d_head: int, n_tiles: int):
    # Scalar-prefetch kernels receive the prefetched operands first; the
    # page-table gather happened in the BlockSpec index maps, so the body
    # is EXACTLY one contiguous-kernel tile (same ops, same order ->
    # bitwise).
    del pt_ref
    _fused_decode_kernel(len_ref, q_ref, kc_ref, km_ref, vc_ref, vm_ref,
                         o_ref, m_ref, l_ref, acc_ref, d_head=d_head,
                         n_steps=n_tiles, block_kv=kc_ref.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("n_kv_heads", "d_head", "interpret"),
)
def fused_paged_decode_attention(
    q: jax.Array,            # (B, H, D) bf16 — the single query token
    k_pool: dict,            # page-pool packed leaves (n_pages, F, P)
    v_pool: dict,
    pages: jax.Array,        # (B, max_pages) int32 per-slot page table
    length: jax.Array,       # (B,) valid cache prefix per slot
    *,
    n_kv_heads: int,
    d_head: int,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode-attention off the PAGED 4.5-bit pool -> (B, H, D).

    Grid (slot, head block, logical page): the page table (flattened) and
    the lengths ride in as scalar-prefetch operands and the KV BlockSpec
    index maps read ``pages[b, k]`` to pick tile k's pool page, so each
    grid step DMAs one page's packed payload — a gather walk over the
    table instead of a contiguous token axis. The tile width IS the page
    size, logical page index k supplies the positions for the length
    mask, and unused trailing table entries (zeros -> the scratch page)
    are fully masked exact no-ops, so the result is bitwise equal to the
    contiguous kernel at ``block_kv=P`` on a page-multiple capacity.
    """
    B, H, D = q.shape
    assert D == d_head and kernel_compatible(k_pool, n_kv_heads, d_head)
    P = kvcache.pool_page_tokens(k_pool)
    n_tiles = pages.shape[1]
    rep = H // n_kv_heads
    hb = heads_per_block(d_head, n_kv_heads)
    grid = (B, n_kv_heads // hb, n_tiles)
    assert KV_GRID_AXIS == len(grid) - 1

    qf = q.reshape(B, n_kv_heads, rep, D)
    kernel = functools.partial(_fused_paged_kernel, d_head=d_head,
                               n_tiles=n_tiles)

    def page(b, h, k, pt, ln):
        return (pt[b * n_tiles + k], h, 0)

    codes = pl.BlockSpec((1, hb * D // 2, P), page)
    meta = pl.BlockSpec((1, hb * D // 64, P), page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, rep, D), lambda b, h, k, pt, ln: (b, h, 0, 0)),
            codes, meta, codes, meta,
        ],
        out_specs=pl.BlockSpec((1, hb, rep, D),
                               lambda b, h, k, pt, ln: (b, h, 0, 0)),
        scratch_shapes=_softmax_scratch(hb, rep, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_heads, rep, D), jnp.float32),
        interpret=interpret,
    )(pages.astype(jnp.int32).reshape(-1), length.astype(jnp.int32), qf,
      k_pool["codes"], k_pool["meta"], v_pool["codes"], v_pool["meta"])
    return out.reshape(B, H, D).astype(q.dtype)


def fused_paged_decode_attention_xla(
    q: jax.Array,            # (B, H, D)
    k_pool: dict,            # page-pool packed leaves (n_pages, F, P)
    v_pool: dict,
    pages: jax.Array,        # (B, max_pages) int32 per-slot page table
    length: jax.Array,       # (B,)
    n_kv_heads: int,
    d_head: int,
) -> jax.Array:
    """The paged kernel's recurrence as straight-line XLA: the off-TPU
    serving twin, and the executable form for staging-tail pools.

    Identical to :func:`fused_decode_attention_xla` except the tile
    loader: each scan step GATHERS tile k's pool page per slot
    (``pool[pages[:, k]]``) instead of slicing a contiguous token axis.
    The gathered bytes feed the same shared K-major decode and the same
    per-tile ops, so kernel (interpret) and twin agree bitwise, and both
    agree bitwise with the contiguous paths at ``block_kv=P``.
    """
    B, H, D = q.shape
    assert D == d_head
    P = kvcache.pool_page_tokens(k_pool)
    n_tiles = pages.shape[1]
    rep = H // n_kv_heads
    qf = q.reshape(B, n_kv_heads, rep, D)
    positions = jnp.arange(P)

    def gather(pool_t, pids):
        return {key: jnp.take(a, pids, axis=0) for key, a in pool_t.items()}

    def tile(carry, ki):
        m, l, acc = carry
        pids = jax.lax.dynamic_index_in_dim(pages, ki, axis=1,
                                            keepdims=False)       # (B,)
        kblk = kvcache.dequantize_kv(gather(k_pool, pids),
                                     n_kv_heads, d_head)
        vblk = kvcache.dequantize_kv(gather(v_pool, pids),
                                     n_kv_heads, d_head)
        s = jnp.einsum("bgrd,bkgd->bgrk", qf, kblk,
                       preferred_element_type=jnp.float32) / (d_head ** 0.5)
        valid = (ki * P + positions)[None, :] < length[:, None]    # (B, P)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        p = (e / l_new).astype(vblk.dtype)
        pv = jnp.einsum("bgrk,bkgd->bgrd", p, vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * (l * corr / l_new) + pv
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, n_kv_heads, rep, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, n_kv_heads, rep, 1), jnp.float32),
        jnp.zeros((B, n_kv_heads, rep, D), jnp.float32),
    )
    if n_tiles == 1:
        (_, _, acc), _ = tile(init, 0)
    else:
        (_, _, acc), _ = jax.lax.scan(tile, init, jnp.arange(n_tiles))
    return acc.reshape(B, H, D).astype(q.dtype)
