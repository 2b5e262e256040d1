"""Shared model building blocks: norms, RoPE, initializers, activations."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import tap as site_tap
from repro.core.policy import QuantPlan, uniform_site_config
from repro.core.qlinear import NO_QUANT, QuantConfig
from repro.sharding.rules import NO_SHARD, ShardCtx


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Everything a model forward needs besides params and inputs.

    Quantization placement is PER SITE: every linear call site asks
    :meth:`site_quant` for its config. With a resolved :class:`QuantPlan`
    attached (``plan``), the answer comes from the policy; without one,
    from the uniform shim over the global ``quant`` — which reproduces
    the legacy behavior (body quantized, embed/lm_head/router excluded)
    through the same rule machinery instead of hardcoded NO_QUANT calls.
    ``scope`` is the param-tree prefix the current block runs under
    ("blocks", "shared", "enc_blocks") — set via :meth:`scoped` by the
    family forwards, so shared block code resolves the right sites.
    """

    quant: QuantConfig = NO_QUANT
    plan: Optional[QuantPlan] = None
    scope: str = ""
    shard: ShardCtx = dataclasses.field(default_factory=lambda: NO_SHARD)
    param_dtype: jnp.dtype = jnp.bfloat16
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = True
    attn_q_chunk: int = 512
    attn_k_chunk: int = 1024
    # "scan_q": sequential q-chunk loop with causal early-exit (default).
    # "vec_q" : q-chunk axis is a shardable data axis — use when the head
    #           count does not divide the TP axis (see attention.py §vec_q).
    attn_impl: str = "scan_q"
    # Decode KV-tile override for the packed attention paths (None = the
    # kernel's own select_kv_block). Bitwise parity between a paged run
    # (tiles = pages) and a contiguous reference depends on the PARTITION
    # of tokens into tiles, so solo references set this to the page size.
    attn_kv_block: Optional[int] = None

    def __post_init__(self):
        # A plan-carrying ctx left at the default quant derives it from the
        # plan's attention-site config: KV-format resolution and packed-KV
        # attention dispatch read ctx.quant, and silently running them off
        # NO_QUANT while the sites follow the plan would drop the policy's
        # kv/impl (ModelCtx(plan=plan) is the natural spelling).
        if self.plan is not None and self.quant == NO_QUANT:
            object.__setattr__(self, "quant", self.plan.base)

    def scoped(self, prefix: str) -> "ModelCtx":
        return dataclasses.replace(self, scope=prefix)

    def site_quant(self, site: str) -> QuantConfig:
        """The QuantConfig the linear layer at ``site`` executes under
        (``site`` is relative to :attr:`scope`, e.g. "attn.wq")."""
        path = f"{self.scope}.{site}" if self.scope else site
        # calibration probe: mark the activation tap with the site path the
        # next engine contraction executes under (no-op without a tap —
        # see repro.core.tap)
        site_tap.mark_site(path)
        if self.plan is not None:
            return self.plan.at(path)
        return uniform_site_config(self.quant, path)


DEFAULT_CTX = ModelCtx()


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, std=0.02, dtype=jnp.bfloat16):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(
        dtype
    )


def zeros(shape, dtype=jnp.bfloat16):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.bfloat16):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back)
# ---------------------------------------------------------------------------


def pin_bf16(x: jax.Array) -> jax.Array:
    """A bf16 ``x`` rounded to bf16 as written; other dtypes pass.

    XLA may keep a fused bf16 value in excess precision, and how much it
    keeps depends on how each program fused. HiF4's discontinuous
    activation quantization turns those last bits into different codes,
    so the residual stream and the norm inputs and outputs — what the
    quantization sites see — are pinned: ``reduce_precision`` on f32 is
    never elided, and where XLA already rounded it is a no-op."""
    if x.dtype != jnp.bfloat16:
        return x
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7).astype(x.dtype)


def residual_add(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x + y`` on the residual stream, rounded as written."""
    return pin_bf16(x + y)


def row_mean(x: jax.Array) -> jax.Array:
    """Mean over the last axis (keepdims), summed in one fixed order.

    An XLA reduce adds in the order of the layout it picked for its
    operand, and that layout follows the consumers: on a TPU v5e the same
    norm reduced along lanes where it fed the packed kernels and along the
    major axis where it fed the qdq fake-quant, so the two programs' norm
    outputs differed in the last bf16 bit. Halving the axis and adding the
    halves elementwise (zero-padded to a power of two) is one order for
    every program, layout and backend. The mean is a multiply by the f32
    reciprocal, which XLA already makes of a division by a constant under
    ``jit`` but not eagerly."""
    n = x.shape[-1]
    width = 1 << (n - 1).bit_length()
    if width != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x * (1.0 / n)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = pin_bf16(x).astype(jnp.float32)
    var = row_mean(jnp.square(xf))
    y = xf * jax.lax.rsqrt(var + eps)
    return pin_bf16((y * weight.astype(jnp.float32)).astype(x.dtype))


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: Optional[jax.Array], eps: float = 1e-5
) -> jax.Array:
    xf = pin_bf16(x).astype(jnp.float32)
    mu = row_mean(xf)
    var = row_mean(jnp.square(xf - mu))
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return pin_bf16(y.astype(x.dtype))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, d_head); positions: (..., seq) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs     # (..., seq, d/2)
    cos = jnp.cos(angles)[..., None, :]                           # (..., seq, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation_fn(name: str):
    if name == "swiglu":  # handled in mlp (two matmuls); gate act is silu
        return jax.nn.silu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name!r}")


# ---------------------------------------------------------------------------
# Quantized dense helper
# ---------------------------------------------------------------------------


def dense(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    quant: QuantConfig = NO_QUANT,
    shard: Optional[ShardCtx] = None,
    accum_dtype=None,
) -> jax.Array:
    """y = x @ w (+ b), executed by the engine ``quant.impl`` selects.

    ``w`` is (d_in, ...) dense, or a :class:`PackedW` (HiF4 bit-packed
    serving weight, dequantized in-graph — 4.5 bits/value of residency and
    FSDP-gather wire) — call sites accept either transparently. ``quant``
    is the PER-SITE config (callers pass ``ctx.site_quant("attn.wq")``
    etc.); the §IV exclusions (embed/lm_head/router) are default policy
    rules, not hardcoded NO_QUANT arguments (repro.core.policy). ``shard``
    (usually ctx.shard) reaches packed dequantization so the gather moves
    the 4.5-bit payload.
    """
    ectx = engine.EngineCtx(quant=quant, shard=shard if shard is not None
                            else NO_SHARD)
    y = engine.matmul(x, w, ectx, contract_x=-1, contract_w=0,
                      accum_dtype=accum_dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean CE over tokens; logits (..., V) f32-upcast, labels (...) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
