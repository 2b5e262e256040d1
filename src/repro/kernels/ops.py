"""Jit'd user-facing wrappers over the Pallas kernels.

On this CPU container the kernels run with interpret=True (the kernel body
executes as python/jnp, validating the exact tiling + compute flow the TPU
would run). On a real TPU backend set interpret=False (the default picks
automatically).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bfp_matmul import bfp_matmul_quantized
from repro.kernels.hif4_quant import hif4_quantize


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def quantize(x: jax.Array, *, interpret=None):
    """BF16/FP32 (M, K) -> HiF4 absorbed layout (ints int8, scales f32)."""
    if interpret is None:
        interpret = _interpret_default()
    return hif4_quantize(x, interpret=interpret)


def matmul(x: jax.Array, w: jax.Array, *, block_m: int = 256,
           block_n: int = 256, block_k: int = 512, interpret=None) -> jax.Array:
    """HiF4 A-W quantized matmul: quantize both operands (Alg. 1 kernel),
    contract with the fixed-point kernel (§III.B). x (M, K) @ w (K, N)."""
    if interpret is None:
        interpret = _interpret_default()
    ai, ascale = hif4_quantize(x, interpret=interpret)
    wi, wscale = hif4_quantize(w.T, interpret=interpret)
    return bfp_matmul_quantized(
        ai, ascale, wi.T, wscale.T,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


def matmul_prequantized(x: jax.Array, wi: jax.Array, wscale: jax.Array,
                        **kw) -> jax.Array:
    """Serving path: dynamic activation quant x offline-quantized weight."""
    interpret = kw.pop("interpret", None)
    if interpret is None:
        interpret = _interpret_default()
    ai, ascale = hif4_quantize(x, interpret=interpret)
    return bfp_matmul_quantized(ai, ascale, wi, wscale, interpret=interpret, **kw)
