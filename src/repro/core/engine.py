"""Execution engine: the single dispatch point for quantized matmuls.

``QuantConfig.impl`` selects how a quantized contraction actually executes;
every model-side linear layer funnels through :func:`matmul`, so the three
paths advertised by the config are now real dispatch instead of
documentation:

  qdq    — fake-quant the operands, matmul in bf16/f32. Lowers on any
           backend and is differentiable (STE); the training and accuracy-
           experiment path.
  packed — the weight is resident as a :class:`~repro.core.qlinear.PackedW`
           (HiF4 bit-packed buffers, 0.5625 bytes/value) and is contracted
           by the FUSED packed-operand matmul: the kernel reads the 4.5-bit
           payload tiles directly and expands them to absorbed int8 inside
           VMEM (``repro.kernels.fused_matmul``), so serving HBM traffic is
           the packed payload — no (K, N) bf16/int8 intermediate. Off-TPU
           the identical contraction runs as straight-line XLA
           (``fused_packed_matmul_xla``); activations are quantized
           dynamically either way. The serving deployment path.
  pallas — the paper's §III.B fixed-point flow. On a PackedW it IS the
           fused packed kernel (same dispatch as ``packed``); on a dense
           weight it ``hif4_quantize``s both operands (Algorithm 1 kernel)
           and contracts with ``bfp_matmul_quantized``. Runs in interpret
           mode off-TPU.

Dispatch is **total**: a combination an impl cannot execute falls back to
the closest executable path instead of erroring, so model code never guards
call sites. The fallbacks (see docs/EXECUTION.md for the full matrix):

  * non-HiF4 formats on ``pallas``          -> qdq (kernels are HiF4-only)
  * ``weights_only`` on ``pallas``          -> qdq (the integer dot
                                               inherently quantizes both)
  * dense (unpacked) weight under ``packed``-> qdq (nothing resident to
                                               contract against)
  * PackedW under ``qdq``                   -> dequantize-then-dot (a
                                               4.5-bit buffer can only be
                                               dequantized)
  * PackedW × ``weights_only`` / non-HiF4
    fmt / non-innermost contraction         -> dequantize-then-dot (the
                                               fused kernel quantizes
                                               activations and tiles K)
  * contraction not a whole number of
    64-groups                               -> qdq

The engine context also carries the :class:`ShardCtx` that packed-weight
dequantization needs (gather the 4.5-bit payload, not the dequantized bf16
weight) — previously a module-level mutable (``_PACKED_SHARD``), now
threaded explicitly from the model context.

Decode attention over an HiF4-packed KV cache dispatches here too
(:func:`attention_decode`): impl packed/pallas on a kernel-tileable cache
on TPU runs the fused Pallas flash kernel
(``repro.kernels.fused_attention`` — the 4.5-bit payload expands per KV
tile inside VMEM); every other combination runs its bit-exact XLA twin,
whose bf16 working set is still one KV tile. The bf16 cache path never
enters the engine. See docs/EXECUTION.md for the attention matrix.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hif4, kvcache
from repro.core import tap as site_tap
from repro.core.qlinear import (
    NO_QUANT,
    PackedW,
    QuantConfig,
    quantize_activation,
    quantize_weight,
)
# Imported at module scope deliberately: the kernel modules concretize
# bf16-rounded constants at import time, so a first import from inside a
# traced scan body would see tracers and fail.
from repro.kernels.bfp_matmul import bfp_matmul_quantized, select_block_sizes
from repro.kernels.fused_attention import (
    fused_decode_attention,
    fused_decode_attention_xla,
    fused_paged_decode_attention,
    fused_paged_decode_attention_xla,
    kernel_compatible,
    select_kv_block,
)
from repro.kernels.fused_matmul import (
    absorbed_activation,
    fused_packed_matmul,
    fused_packed_matmul_xla,
)
from repro.kernels.hif4_quant import hif4_quantize
from repro.sharding.rules import NO_SHARD, ShardCtx


@functools.lru_cache(maxsize=None)
def _default_backend() -> str:
    """Backend detection, resolved once per process.

    ``jax.default_backend()`` walks the backend registry; un-cached it ran
    on EVERY matmul dispatch inside the decode scan body (trace time, but
    per call site per retrace).
    """
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class EngineCtx:
    """Everything a quantized contraction needs besides its operands."""

    quant: QuantConfig = NO_QUANT
    shard: ShardCtx = dataclasses.field(default_factory=lambda: NO_SHARD)
    # Pallas interpret mode: None = auto (interpret everywhere but TPU).
    interpret: Optional[bool] = None

    def resolved_interpret(self) -> bool:
        if self.interpret is None:
            return _default_backend() != "tpu"
        return self.interpret


DEFAULT_ENGINE = EngineCtx()


def matmul(
    x: jnp.ndarray,
    w,
    ectx: EngineCtx = DEFAULT_ENGINE,
    *,
    contract_x: int = -1,
    contract_w: int = 0,
    precision=None,
    accum_dtype=None,
) -> jnp.ndarray:
    """``x @ w`` through the configured execution path.

    ``w`` is a dense array or a :class:`PackedW`. ``accum_dtype`` is the dot
    OUTPUT dtype on the qdq/packed-fallback paths (default x.dtype; see
    qmatmul for the TP wire rationale); the fused/pallas kernels always
    accumulate f32 and cast once at the end.
    """
    cfg = ectx.quant
    # calibration probe: record this contraction's activation operand under
    # the site path ModelCtx.site_quant marked (no-op without an installed
    # tap — see repro.core.tap)
    site_tap.consume_pending(x, contract_x)
    if isinstance(w, PackedW):
        if _fused_packed_ok(cfg, x, contract_x, w):
            return _fused_packed_matmul(x, w, ectx)
        return _packed_matmul(x, w, ectx, contract_x=contract_x,
                              accum_dtype=accum_dtype)
    if (
        cfg.enabled
        and cfg.impl == "pallas"
        and _pallas_activation_ok(cfg, x, contract_x)
        and _pallas_weight_ok(w, contract_w)
    ):
        return _pallas_dense_matmul(x, w, ectx)
    return _qdq_matmul(x, w, cfg, contract_x=contract_x, contract_w=contract_w,
                       precision=precision, accum_dtype=accum_dtype)


def qdq_einsum(eq: str, a: jnp.ndarray, w: jnp.ndarray, ectx: EngineCtx,
               *, a_axis: int = -1, w_axis: int = 1) -> jnp.ndarray:
    """Batched-contraction einsum (MoE expert matmuls) on the qdq path.

    Batched-expert weights have no packed/pallas dispatch yet (the (E, C)
    dispatch buffer re-tiles per step, so there is no static packed operand
    to contract against); they always execute fake-quant regardless of
    ``impl`` — documented in the docs/EXECUTION.md matrix.
    """
    cfg = ectx.quant
    site_tap.consume_pending(a, a_axis)
    if cfg.enabled:
        a = quantize_activation(a, cfg, axis=a_axis)
        w = quantize_weight(w, cfg, axis=w_axis)
    return jnp.einsum(eq, a, w)


# ---------------------------------------------------------------------------
# qdq path
# ---------------------------------------------------------------------------


def _qdq_matmul(x, w, cfg, *, contract_x, contract_w, precision, accum_dtype):
    out_dtype = x.dtype
    if cfg.enabled:
        x = quantize_activation(x, cfg, axis=contract_x)
        w = quantize_weight(w, cfg, axis=contract_w)
    cx = contract_x % x.ndim
    cw = contract_w % w.ndim
    y = jax.lax.dot_general(
        x,
        w,
        dimension_numbers=(((cx,), (cw,)), ((), ())),
        precision=precision,
        preferred_element_type=accum_dtype or out_dtype,
    )
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# fused packed path: the kernel consumes the 4.5-bit payload directly
# ---------------------------------------------------------------------------


def _fused_packed_ok(cfg: QuantConfig, x, contract_x: int, w: PackedW) -> bool:
    """The fused kernel dynamically quantizes activations and tiles K, so it
    needs: a packed/pallas impl on the HiF4 format, both-operand
    quantization, and an innermost-axis contraction of exactly K."""
    return (
        cfg.impl in ("packed", "pallas")
        and cfg.fmt == "hif4"
        and not cfg.weights_only
        and contract_x % x.ndim == x.ndim - 1
        and x.shape[-1] == w.shape2d[0]
    )


# The XLA twin's group-batched dot materializes a (K/64, M, N) f32
# intermediate (the Pallas kernel keeps it tile-sized in VMEM). Fine for
# decode (tiny M) and smoke prefill; at large-M off-TPU prefill it would be
# K/64 times the output — cap it and take the dequantize fallback instead.
_XLA_FUSED_PART_BYTES_MAX = 128 * 2 ** 20


def _fused_packed_matmul(x, w: PackedW, ectx: EngineCtx):
    """Serving hot path: dynamic activation quant × packed resident weight,
    dequantized inside the contraction — never a (K, N) HBM intermediate."""
    out_dtype = x.dtype
    k, n = w.shape2d
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    if ectx.resolved_interpret():
        # Off-TPU there is no Pallas lowering; interpret mode is a test
        # vehicle, not a serving path. Run the SAME fused contraction as
        # straight-line XLA (bit-exact vs the kernel; see fused_matmul) —
        # unless its batched-dot intermediate would dwarf the output.
        part_bytes = (k // hif4.GROUP_SIZE) * x2.shape[0] * n * 4
        if part_bytes > _XLA_FUSED_PART_BYTES_MAX:
            return _packed_matmul(x, w, ectx, contract_x=-1, accum_dtype=None)
        codes_km, meta_km = w.kernel_operands(shard=ectx.shard)
        ai, asc = absorbed_activation(x2)
        y = fused_packed_matmul_xla(ai, asc, codes_km, meta_km)
    else:
        codes_km, meta_km = w.kernel_operands(shard=ectx.shard)
        ai, asc = hif4_quantize(x2, interpret=False)
        y = fused_packed_matmul(ai, asc, codes_km, meta_km, interpret=False)
    return y.reshape(lead + (n,)).astype(out_dtype)


def packed_dispatch_info(quant: QuantConfig, w: PackedW, *, decode_m: int,
                         prefill_m: int, interpret: Optional[bool] = None):
    """What the engine will actually run for ``w`` under ``quant`` — the
    launcher prints this next to the residency lines.

    Returns a dict with ``fused`` (bool), ``execution`` (human string), and
    per-regime kernel block sizes (None on the XLA twin, which doesn't
    tile).
    """
    ectx = EngineCtx(quant=quant, interpret=interpret)
    k, n = w.shape2d
    probe = jax.ShapeDtypeStruct((decode_m, k), jnp.bfloat16)
    fused = _fused_packed_ok(quant, probe, -1, w)
    if not fused:
        return {"fused": False, "execution": "dequantize-then-dot fallback",
                "decode_blocks": None, "prefill_blocks": None}
    if ectx.resolved_interpret():
        return {"fused": True,
                "execution": "XLA fused contraction (off-TPU twin)",
                "decode_blocks": None, "prefill_blocks": None}
    return {"fused": True, "execution": "Pallas fused kernel",
            "decode_blocks": select_block_sizes(decode_m, n, k),
            "prefill_blocks": select_block_sizes(prefill_m, n, k)}


# ---------------------------------------------------------------------------
# fused decode-attention path: the kernel consumes the packed KV cache
# ---------------------------------------------------------------------------


def _fused_attn_ok(cfg: QuantConfig, k_cache: dict, n_kv_heads: int,
                   d_head: int) -> bool:
    """The Pallas decode-attention kernel needs a packed/pallas impl and a
    kernel-tileable cache (kernel-tile layout, no staging tail, head blocks
    dividing the head count)."""
    return (
        cfg.impl in ("packed", "pallas")
        and kernel_compatible(k_cache, n_kv_heads, d_head)
    )


def attention_decode(
    q: jnp.ndarray,          # (B, H, D) single query token
    k_cache: dict,           # HiF4-packed leaves {codes, meta, tail}
    v_cache: dict,
    length: jnp.ndarray,     # (B,) valid cache prefix per slot
    n_kv_heads: int,
    d_head: int,
    ectx: EngineCtx = DEFAULT_ENGINE,
    *,
    pages: Optional[jnp.ndarray] = None,   # (B, max_pages) page table
    block_kv: Optional[int] = None,        # contiguous KV tile override
) -> jnp.ndarray:
    """Decode attention against a PACKED KV cache, dispatched like matmul.

    impl packed/pallas x a kernel-tileable cache x TPU runs the fused
    Pallas kernel (``repro.kernels.fused_attention``): the 4.5-bit payload
    streams into VMEM and expands per KV tile. Every other combination —
    off-TPU, qdq impl, artifact layout, staging tail — runs the bit-exact
    XLA twin, whose bf16 working set is still ONE KV tile, never the cache.
    bf16 caches never reach this function (``attn_decode`` keeps the dense
    path untouched). See docs/EXECUTION.md for the full matrix.

    With ``pages`` set, ``k_cache``/``v_cache`` are page-POOL leaves
    ((n_pages, F, P), ``repro.core.kvcache.init_page_pool``) and the same
    dispatch picks the paged kernel / paged XLA twin — they walk each
    slot's live page-table entries instead of a contiguous token axis.
    ``block_kv`` overrides the contiguous tile size (the paged tile IS
    the page size); serving threads it from ``ModelCtx.attn_kv_block`` so
    a solo reference run can align its tile partition with a paged run
    for bitwise comparison.
    """
    fused = (_fused_attn_ok(ectx.quant, k_cache, n_kv_heads, d_head)
             and not ectx.resolved_interpret())
    if pages is not None:
        if fused:
            return fused_paged_decode_attention(
                q, k_cache, v_cache, pages, length,
                n_kv_heads=n_kv_heads, d_head=d_head, interpret=False)
        return fused_paged_decode_attention_xla(
            q, k_cache, v_cache, pages, length, n_kv_heads, d_head)
    if fused:
        return fused_decode_attention(
            q, k_cache, v_cache, length,
            n_kv_heads=n_kv_heads, d_head=d_head, block_kv=block_kv,
            interpret=False)
    return fused_decode_attention_xla(
        q, k_cache, v_cache, length, n_kv_heads, d_head, block_kv=block_kv)


def attention_dispatch_info(quant: QuantConfig, k_cache: dict, *,
                            n_kv_heads: int, d_head: int,
                            interpret: Optional[bool] = None,
                            paged: bool = False):
    """What :func:`attention_decode` will run for this cache under
    ``quant`` — the launcher prints it next to the fused-matmul line,
    and the scenario matrix asserts it per cell.

    Returns ``fused`` (bool: the Pallas kernel), ``execution`` (human
    string), ``block_kv`` (the KV tile both executions stream),
    ``kernel_eligible`` (backend-NEUTRAL: the cache/impl combination the
    fused kernel accepts — True still runs the bit-exact twin off-TPU),
    and ``route`` (the exact function dispatch picks on THIS backend).
    ``paged=True`` answers for a page-pool cache (``pages`` passed to
    :func:`attention_decode`): the same eligibility picks the paged
    kernel / paged twin pair instead.
    """
    ectx = EngineCtx(quant=quant, interpret=interpret)
    block = (kvcache.pool_page_tokens(k_cache) if paged
             else select_kv_block(kvcache.seq_capacity(k_cache)))
    eligible = _fused_attn_ok(quant, k_cache, n_kv_heads, d_head)
    routes = (("fused_paged_decode_attention", "fused_paged_decode_attention_xla")
              if paged else
              ("fused_decode_attention", "fused_decode_attention_xla"))
    if not eligible:
        if quant.impl not in ("packed", "pallas"):
            why = f"impl={quant.impl}"
        elif not kvcache.is_kernel_layout(k_cache):
            why = "artifact layout"
        else:
            # the only remaining kernel_compatible failure: F % 64 != 0
            # (a tail-free F always makes Hkv divisible by the head block)
            why = "staging tail"
        return {"fused": False, "block_kv": block, "kernel_eligible": False,
                "route": routes[1],
                "execution": f"XLA twin (chunked dequantize; {why})"}
    if ectx.resolved_interpret():
        return {"fused": False, "block_kv": block, "kernel_eligible": True,
                "route": routes[1],
                "execution": "XLA twin (chunked dequantize; off-TPU)"}
    return {"fused": True, "block_kv": block, "kernel_eligible": True,
            "route": routes[0], "execution": "Pallas fused kernel"}


# ---------------------------------------------------------------------------
# packed fallback: dequantize the PackedW in-graph, then a dense dot.
# Taken when the fused kernel cannot run (qdq impl, weights_only, non-HiF4
# activation format, non-innermost contraction) — see docs/EXECUTION.md.
# ---------------------------------------------------------------------------


def _packed_matmul(x, w: PackedW, ectx: EngineCtx, *, contract_x, accum_dtype):
    out_dtype = x.dtype
    wd = w.dequantize(shard=ectx.shard)                 # (K, N) dense
    x = quantize_activation(x, ectx.quant, axis=contract_x)
    cx = contract_x % x.ndim
    y = jax.lax.dot_general(
        x,
        wd,
        dimension_numbers=(((cx,), (0,)), ((), ())),
        preferred_element_type=accum_dtype or out_dtype,
    )
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# pallas path: Algorithm-1 quantize kernel + §III.B fixed-point matmul
# ---------------------------------------------------------------------------


def _pallas_activation_ok(cfg: QuantConfig, x, contract_x: int) -> bool:
    return (
        cfg.fmt == "hif4"
        and not cfg.weights_only
        and contract_x % x.ndim == x.ndim - 1
        and x.shape[-1] % hif4.GROUP_SIZE == 0
    )


def _pallas_weight_ok(w, contract_w: int) -> bool:
    return (
        w.ndim == 2
        and contract_w % w.ndim == 0
        and w.shape[0] % hif4.GROUP_SIZE == 0
    )


def _pallas_dense_matmul(x, w, ectx: EngineCtx):
    """Both operands quantized by the Algorithm-1 kernel each call (A-W
    dynamic quantization; the offline-weights variant is the fused packed
    path)."""
    interp = ectx.resolved_interpret()
    out_dtype = x.dtype
    lead, K = x.shape[:-1], x.shape[-1]
    N = w.shape[1]
    ai, asc = hif4_quantize(x.reshape(-1, K), interpret=interp)
    wi, wsc = hif4_quantize(w.T, interpret=interp)       # rows along K-groups
    y = bfp_matmul_quantized(ai, asc, wi.T, wsc.T, interpret=interp)
    return y.reshape(lead + (N,)).astype(out_dtype)


def packed_to_absorbed(w: PackedW) -> tuple[jnp.ndarray, jnp.ndarray]:
    """PackedW -> (ints (K, N) int8, scales (K/64, N) f32) for the kernel.

    The 4-bit codes + 32-bit meta expand to the absorbed-shift integers of
    §III.B (micro-exponents become left shifts, |q| <= 28) without ever
    materializing the bf16 weight. The fused kernel performs exactly this
    expansion per VMEM tile; this host-level version exists as the
    materialized reference the fused path is tested bit-exact against.
    """
    return hif4.absorbed_int_km(*w.kernel_operands())
