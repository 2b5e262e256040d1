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
docs/FORMATS.md "Paged KV-cache pool"), the same recurrence walks the
page table instead of a contiguous token axis, and only its live part.
:func:`fused_paged_decode_attention` prefetches the (B, max_pages) table
and the lengths as scalar-prefetch operands. Its grid is (slot, block of
table entries): each step covers a 128-lane tile's worth of pages (two of
64 tokens) with all KV heads, each page its own pipelined operand whose
index map reads the table, so each page is one DMA, fetched while the
previous block is folded. (Mosaic refuses a hand-written DMA of one page:
the page's 64-token lane axis is half a lane tile.) The block's pages are
dequantized and scored side by side at full lane width, then folded one
page at a time. Entries at or past ``ceil(length / P)`` get no DMA — the
operand keeps the page it holds — and their pages no compute.
:func:`fused_paged_decode_attention_xla` is its bitwise twin (a scan
whose tile loader is a page gather instead of a token slice, which drops
the p·V of a slot's tiles past its live pages). A fully masked tile is a
no-op of the recurrence up to one rounding (``exp(NEG_INF - m)``
underflows to f32 zero and the correction factor is exactly 1.0, but the
rescale l·corr/l_new goes through the chip's f32 division, which can
land one ulp below 1), so the kernel still applies that rescale once per
entry past the length, and paged attention over pages of P tokens stays
BITWISE equal to the contiguous kernel/twin run with ``block_kv=P`` on a
capacity padded to a page multiple — the parity ``tests/test_paged_kv.py``
pins.
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
    _fold_tile(_scores(q, kT, d_head), vT, length, first_pos, m_ref, l_ref,
               acc_ref)


def _scores(q, kT, d_head: int):
    """q (hb, rep, D) against K columns (hb, D, ck) -> (hb, rep, ck) f32."""
    return jax.lax.dot_general(
        q, kT, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) / (d_head ** 0.5)


def _fold_tile(s, vT, length, first_pos, m_ref, l_ref, acc_ref):
    """Fold one KV tile — its scores s (hb, rep, ck) and values vT
    (hb, D, ck) of tokens first_pos .. first_pos+ck-1 — into the state."""
    ck = s.shape[-1]
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


def _fold_masked_tile(m_ref, l_ref, acc_ref):
    """What :func:`_fold_tile` does with a tile whose every token is past
    ``length`` (scores all NEG_INF, so p = 0 and pv = 0), without its
    scores or values: m and l stay, and acc is scaled by l*corr/l_new —
    exactly 1 in IEEE arithmetic, but the chip's f32 division can round
    it one ulp below, so the tile still moves acc's last bits."""
    m_prev = m_ref[..., :1]
    l_prev = l_ref[..., :1]
    m_new = jnp.maximum(m_prev, NEG_INF)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr
    acc_ref[...] = acc_ref[...] * (l_prev * corr / l_new)
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


def _live_pages(len_ref, b, P: int, n_tiles: int):
    """Table entries of slot ``b`` that hold a token: ``ceil(length/P)``,
    at most the table (a finished slot's length runs past it)."""
    return jnp.minimum(pl.cdiv(len_ref[b], P), n_tiles)


def _fused_paged_kernel(pt_ref, len_ref, q_ref, *refs, d_head: int,
                        n_tiles: int, pages_per_block: int):
    """Grid step (slot b, block j): fold the live pages of block j.

    ``refs`` holds, per page i of the block, that page's K codes, K meta,
    V codes and V meta blocks, then the output, the softmax scratch and
    the f32 K/V scratch. The block's pages are dequantized and scored side
    by side, a lane tile's worth at once, then folded one by one, in table
    order. The table's entries past the live pages are fully masked
    tiles: they are read and scored not at all, and folded by
    :func:`_fold_masked_tile`, so the state ends as a walk over the whole
    table leaves it, bit for bit.
    """
    pb = pages_per_block
    pages, (o_ref, m_ref, l_ref, acc_ref, kv_ref) = refs[:4 * pb], refs[4 * pb:]
    b, j = pl.program_id(0), pl.program_id(1)
    P = pages[0].shape[-1]
    length = len_ref[b]
    n_live = _live_pages(len_ref, b, P, n_tiles)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * pb < n_live)
    def _fold_block():
        q = q_ref[0]                                     # (Hkv, rep, D) bf16
        leaves = [jnp.concatenate([pages[4 * i + f][0] for i in range(pb)],
                                  axis=-1) for f in range(4)]
        kT, vT = _dequantize_block(*leaves, kv_ref, q.shape[0], d_head)
        s = _scores(q, kT, d_head)                       # (Hkv, rep, pb*P)
        for i in range(pb):
            cols = slice(i * P, (i + 1) * P)
            pl.when(j * pb + i < n_live)(functools.partial(
                _fold_tile, s[..., cols], vT[..., cols], length,
                (j * pb + i) * P, m_ref, l_ref, acc_ref))

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        @pl.loop(0, jnp.where(n_live > 0, n_tiles - n_live, 0))
        def _masked(_):
            _fold_masked_tile(m_ref, l_ref, acc_ref)

        o_ref[0] = acc_ref[...]


def _dequantize_block(kc, km, vc, vm, kv_ref, hb: int, d_head: int):
    """Expand a 4.5-bit K/V block to bf16 (hb, D, ck) columns through
    VMEM: each of K and V lands in kv_ref[0] / kv_ref[1] (F, ck) f32 as
    its even and odd feature rows, two stride-2 row stores, and is read
    back whole — the values ``hif4.dequantize_km`` gives, without the
    sublane shuffle that interleaving them as arrays costs."""
    half = kc.shape[0]
    out = []
    for i, (codes, meta) in enumerate(((kc, km), (vc, vm))):
        even, odd = hif4.dequantize_km_split(codes, meta)
        kv_ref[i, pl.ds(0, half, stride=2), :] = even
        kv_ref[i, pl.ds(1, half, stride=2), :] = odd
        out.append(kv_ref[i].astype(jnp.bfloat16).reshape(hb, d_head, -1))
    return out


def _pages_per_block(page_tokens: int, n_tiles: int) -> int:
    """Pages one grid step of the paged walk holds: a 128-lane tile's worth
    (2 pages of 64 tokens), so the block is dequantized and scored at
    full lane width; one page where a page fills the lanes or does not
    divide them; never more than the table holds."""
    pb = _LANE // page_tokens if _LANE % page_tokens == 0 else 1
    return max(1, min(pb, n_tiles))


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

    Grid (slot, block of table entries): the page table (flattened) and
    the lengths ride in as scalar-prefetch operands; a block is
    :func:`_pages_per_block` pages x all KV heads, one pipelined
    DMA per page. Only a slot's live entries — those below
    ``ceil(length / P)`` — are read: past them no DMA is issued and no
    page is dequantized or scored. Each page is folded as one recurrence
    tile, in table order, and each entry past the length as a fully
    masked tile, so the result is bitwise equal to the contiguous kernel
    at ``block_kv=P`` on a page-multiple capacity.
    """
    B, H, D = q.shape
    assert D == d_head and kernel_compatible(k_pool, n_kv_heads, d_head)
    P = kvcache.pool_page_tokens(k_pool)
    n_tiles = pages.shape[1]
    rep = H // n_kv_heads
    pb = _pages_per_block(P, n_tiles)
    qf = q.reshape(B, n_kv_heads, rep, D)
    kernel = functools.partial(_fused_paged_kernel, d_head=d_head,
                               n_tiles=n_tiles, pages_per_block=pb)

    def page_spec(a, i):
        """Page i of block j: table entry j*pb + i while it is live. Past
        the slot's last live page the operand holds the last live entry it
        fetched (the slot's last live entry if it fetched none), so the
        pipeline sees an unchanged block and issues no DMA."""
        def index(b, j, pt, ln):
            n_live = _live_pages(ln, b, P, n_tiles)
            k = j * pb + i
            own = i + (n_live - 1 - i) // pb * pb
            last = jnp.where(n_live > i, own, jnp.maximum(n_live - 1, 0))
            return (pt[b * n_tiles + jnp.where(k < n_live, k, last)], 0, 0)
        return pl.BlockSpec((1,) + a.shape[1:], index)

    leaves = (k_pool["codes"], k_pool["meta"], v_pool["codes"],
              v_pool["meta"])
    slot = pl.BlockSpec((1, n_kv_heads, rep, D),
                        lambda b, j, pt, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(n_tiles, pb)),
        in_specs=[slot] + [page_spec(a, i) for i in range(pb)
                           for a in leaves],
        out_specs=slot,
        scratch_shapes=_softmax_scratch(n_kv_heads, rep, D) + [
            pltpu.VMEM((2, n_kv_heads * D, pb * P), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv_heads, rep, D), jnp.float32),
        interpret=interpret,
    )(pages.astype(jnp.int32).reshape(-1), length.astype(jnp.int32), qf,
      *(leaves * pb))
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
    agree bitwise with the contiguous paths at ``block_kv=P``. Like the
    kernel, a slot's entries at or past ``ceil(length / P)`` contribute no
    values (their p·V is dropped): whatever bytes they point at never
    reach the result.
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
        live = (ki * P < length)[:, None, None, None]               # (B,)
        acc_new = acc * (l * corr / l_new) + jnp.where(live, pv, 0.0)
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
