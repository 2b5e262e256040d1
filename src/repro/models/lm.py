"""Top-level language models for all assigned architecture families.

One functional API over five families:

  dense / vlm    — GQA transformer (qk-norm, QKV-bias, swiglu/squared-relu)
  moe            — GQA transformer with top-k MoE FFN (EP-sharded)
  ssm            — Mamba2 (SSD) stack, attention-free
  hybrid         — Mamba2 backbone + ONE shared attention+FFN block invoked
                   every ``hybrid_attn_every`` layers (Zamba2 scheme)
  audio          — encoder-decoder (Whisper backbone; stub conv frontend)

Entry points (all pure functions of pytrees — pjit-able directly):

  abstract_params(cfg)                 -> PSpec tree (no allocation)
  init_params(cfg, key)                -> materialized params
  train_loss(params, batch, cfg, ctx)  -> scalar CE loss
  prefill(params, batch, cfg, ctx)     -> (last-token logits, decode cache)
  decode_step(params, token, cache, cfg, ctx) -> (logits, new cache)
  abstract_cache(cfg, batch, seq)      -> PSpec tree for the decode cache

Layers are stacked and iterated with lax.scan (O(1) compile scaling to 96
layers); the residual stream is sequence-sharded over the TP axis at layer
boundaries (Megatron-style SP) so remat-saved activations fit HBM at 340B.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import mamba2, moe as moe_mod, transformer as tf
from repro.models.common import ModelCtx, cross_entropy, dense, residual_add
from repro.models.params import PSpec, stack_specs, init_from_specs


# ---------------------------------------------------------------------------
# Positional (sinusoidal, for the audio enc-dec family)
# ---------------------------------------------------------------------------


def sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """(...,) int positions -> (..., d) f32 sinusoidal embeddings."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Per-family block specs
# ---------------------------------------------------------------------------


def _tblock_specs(cfg: ArchConfig) -> dict:
    """Transformer block: norm+attn+norm+ffn (ffn = mlp or moe)."""
    specs = {
        "norm1": tf.norm_specs(cfg),
        "attn": tf.attn_specs(cfg),
        "norm2": tf.norm_specs(cfg),
    }
    if cfg.family == "moe":
        specs["moe"] = moe_mod.moe_specs(cfg)
    else:
        specs["mlp"] = tf.mlp_specs(cfg)
    return specs


def _dec_block_specs(cfg: ArchConfig) -> dict:
    """Decoder block for enc-dec: self-attn + cross-attn + mlp."""
    return {
        "norm1": tf.norm_specs(cfg),
        "attn": tf.attn_specs(cfg),
        "norm_x": tf.norm_specs(cfg),
        "xattn": tf.attn_specs(cfg),
        "norm2": tf.norm_specs(cfg),
        "mlp": tf.mlp_specs(cfg),
    }


def _enc_block_specs(cfg: ArchConfig) -> dict:
    return {
        "norm1": tf.norm_specs(cfg),
        "attn": tf.attn_specs(cfg),
        "norm2": tf.norm_specs(cfg),
        "mlp": tf.mlp_specs(cfg),
    }


def _hybrid_layout(cfg: ArchConfig) -> tuple[int, int]:
    """(n_super_blocks, mamba_layers_per_super)."""
    per = cfg.hybrid_attn_every
    assert cfg.n_layers % per == 0, (cfg.n_layers, per)
    return cfg.n_layers // per, per


def abstract_params(cfg: ArchConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab
    specs: dict = {
        "embed": PSpec((v, d), ("vocab", "fsdp"), std=0.02),
        "final_norm": tf.norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = PSpec((d, v), ("fsdp", "vocab"), std=0.02)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        specs["blocks"] = stack_specs(_tblock_specs(cfg), cfg.n_layers)
    elif fam == "ssm":
        specs["blocks"] = stack_specs(mamba2.mamba_specs(cfg), cfg.n_layers)
    elif fam == "hybrid":
        ns, per = _hybrid_layout(cfg)
        specs["blocks"] = stack_specs(
            stack_specs(mamba2.mamba_specs(cfg), per), ns
        )
        specs["shared"] = {
            "norm1": tf.norm_specs(cfg),
            "attn": tf.attn_specs(cfg),
            "norm2": tf.norm_specs(cfg),
            "mlp": tf.mlp_specs(cfg),
        }
    elif fam == "audio":
        specs["enc_blocks"] = stack_specs(_enc_block_specs(cfg), cfg.enc_layers)
        specs["enc_norm"] = tf.norm_specs(cfg)
        specs["blocks"] = stack_specs(_dec_block_specs(cfg), cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam!r}")
    return specs


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    return init_from_specs(abstract_params(cfg), key)


# ---------------------------------------------------------------------------
# Decode-cache specs
# ---------------------------------------------------------------------------

ENC_FRAMES_DECODE = 1536  # nominal encoder length backing a decode step (audio)


def abstract_cache(cfg: ArchConfig, batch: int, seq: int,
                   kv_format: str = "bf16") -> dict:
    """Cache pytree spec for a decode step with capacity ``seq``.

    kv_format="hif4" packs the attention KV caches at 4.5 bits/value
    (repro.core.kvcache) for the transformer families and the audio
    decoder (both the growing "self" cache and the read-only encoder
    "cross" cache); SSM state and the hybrid caches stay bf16
    (documented fallback, docs/EXECUTION.md).
    """
    fam = cfg.family
    pos = PSpec((), (), dtype=jnp.int32, init="zeros")
    if fam in ("dense", "vlm", "moe"):
        return {
            "kv": stack_specs(
                tf.attn_cache_specs(cfg, batch, seq, kv_format), cfg.n_layers
            ),
            "pos": pos,
        }
    if fam == "ssm":
        return {
            "layers": stack_specs(mamba2.mamba_cache_specs(cfg, batch), cfg.n_layers),
            "pos": pos,
        }
    if fam == "hybrid":
        ns, per = _hybrid_layout(cfg)
        return {
            "layers": stack_specs(
                stack_specs(mamba2.mamba_cache_specs(cfg, batch), per), ns
            ),
            "kv": stack_specs(tf.attn_cache_specs(cfg, batch, seq), ns),
            "pos": pos,
        }
    if fam == "audio":
        return {
            "self": stack_specs(
                tf.attn_cache_specs(cfg, batch, seq, kv_format), cfg.n_layers
            ),
            "cross": stack_specs(
                tf.attn_cache_specs(cfg, batch, ENC_FRAMES_DECODE, kv_format),
                cfg.n_layers,
            ),
            "pos": pos,
        }
    raise ValueError(fam)


def init_cache(cfg: ArchConfig, batch: int, seq: int,
               kv_format: str = "bf16") -> dict:
    """Zero-initialized decode cache (for real serving, not the dry-run)."""
    return init_from_specs(abstract_cache(cfg, batch, seq, kv_format),
                           jax.random.PRNGKey(0))


def init_paged_cache(cfg: ArchConfig, batch: int, n_pages: int,
                     page_tokens: int, max_pages_per_slot: int) -> dict:
    """Zero-initialized PAGED decode cache for the page-pool scheduler.

    ``kv`` holds the fixed-size HiF4 page pool shared by all slots
    (repro.core.kvcache.init_page_pool — leaves (L, n_pages, F, P));
    ``pages`` (B, max_pages_per_slot) int32 is the per-slot page table
    (all-zero rows point at the reserved scratch page) and ``pos`` (B,)
    the per-slot token counts. Transformer families only — the pool IS
    the self-attention KV cache.
    """
    from repro.core import kvcache

    assert cfg.family in ("dense", "vlm", "moe"), cfg.family
    a = cfg.attn
    return {
        "kv": kvcache.init_page_pool(cfg.n_layers, a.n_kv_heads, a.d_head,
                                     n_pages, page_tokens),
        "pages": jnp.zeros((batch, max_pages_per_slot), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params: dict, tokens: jax.Array, cfg: ArchConfig, ctx: ModelCtx):
    # the lookup is a gather, not a matmul: policy resolution clamps the
    # "embed" site to fmt='none' (and §IV keeps it high-precision anyway)
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(ctx.compute_dtype)


def lm_logits(params: dict, x: jax.Array, cfg: ArchConfig, ctx: ModelCtx):
    x = tf.norm_apply(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        w = params["embed"].T                            # (d, V)
    else:
        w = params["lm_head"]
    # per-site config ("lm_head"): fmt='none' under the default §IV rules,
    # quantizable by an explicit policy rule; f32 accumulation either way
    # (loss-critical logits)
    y = dense(x, w, quant=ctx.site_quant("lm_head"), accum_dtype=jnp.float32)
    axes = ("batch", "act_seq", "vocab") if y.ndim == 3 else ("batch", "vocab")
    return ctx.shard.constrain(y.astype(jnp.float32), *axes)


# ---------------------------------------------------------------------------
# Transformer-family forward (dense / vlm / moe)
# ---------------------------------------------------------------------------


def _tblock_apply(p, x, cfg, ctx, *, mode, cache=None, pos=None,
                  causal=True, use_rope=True, pages=None):
    h = tf.norm_apply(p["norm1"], x, cfg)
    if mode == "decode":
        a, new_cache = tf.attn_decode(p["attn"], h, cache, pos, cfg, ctx,
                                      use_rope=use_rope, pages=pages)
    else:
        a, new_cache = tf.attn_full(
            p["attn"], h, cfg, ctx, causal=causal, use_rope=use_rope,
            return_cache=(mode == "prefill"),
        )
    x = residual_add(x, a)
    h2 = tf.norm_apply(p["norm2"], x, cfg)
    if "moe" in p:
        f = moe_mod.moe_apply(p["moe"], h2, cfg, ctx)
    else:
        f = tf.mlp_apply(p["mlp"], h2, cfg, ctx)
    return residual_add(x, f), new_cache


def _scan_layers(body, x0, xs, remat: bool):
    if remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, x0, xs)


def _transformer_forward(params, x, cfg, ctx, *, mode, caches=None, pos=None,
                         pages=None):
    """x (B,S,d). Returns (x, caches-or-None). mode: train|prefill|decode."""
    sp = ("batch", "act_seq", None) if x.shape[1] > 1 else ("batch", None, None)
    bctx = ctx.scoped("blocks")

    if mode == "train":
        def body(h, p_layer):
            h = ctx.shard.constrain(h, *sp)
            h, _ = _tblock_apply(p_layer, h, cfg, bctx, mode="train")
            return h, None
        x, _ = _scan_layers(body, x, params["blocks"], ctx.remat)
        return ctx.shard.constrain(x, *sp), None

    if mode == "prefill":
        def body(h, p_layer):
            h = ctx.shard.constrain(h, *sp)
            h, cache = _tblock_apply(p_layer, h, cfg, bctx, mode="prefill")
            return h, cache
        x, caches = _scan_layers(body, x, params["blocks"], False)
        return ctx.shard.constrain(x, *sp), caches

    # decode (``pages`` is loop-invariant: the page table is closure-
    # captured while the per-layer pool leaves ride the scan xs)
    def body(h, layer):
        p_layer, cache = layer
        h, new_cache = _tblock_apply(p_layer, h, cfg, bctx, mode="decode",
                                     cache=cache, pos=pos, pages=pages)
        return h, new_cache
    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# SSM-family forward (mamba2)
# ---------------------------------------------------------------------------


def _ssm_forward(params, x, cfg, ctx, *, mode, caches=None):
    sp = ("batch", "act_seq", None) if x.shape[1] > 1 else ("batch", None, None)
    bctx = ctx.scoped("blocks")
    if mode in ("train", "prefill"):
        want_cache = mode == "prefill"

        def body(h, p_layer):
            h = ctx.shard.constrain(h, *sp)
            out, cache = mamba2.mamba_full(p_layer, h, cfg, bctx,
                                           return_cache=want_cache)
            return residual_add(h, out), cache
        remat = ctx.remat and mode == "train"
        x, caches = _scan_layers(body, x, params["blocks"], remat)
        return ctx.shard.constrain(x, *sp), (caches if want_cache else None)

    def body(h, layer):
        p_layer, cache = layer
        out, new_cache = mamba2.mamba_step(p_layer, h, cache, cfg, bctx)
        return residual_add(h, out), new_cache
    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Hybrid-family forward (zamba2: shared attention block + mamba groups)
# ---------------------------------------------------------------------------


def _hybrid_forward(params, x, cfg, ctx, *, mode, caches=None, pos=None):
    shared = params["shared"]
    sp = ("batch", "act_seq", None) if x.shape[1] > 1 else ("batch", None, None)
    sctx = ctx.scoped("shared")
    bctx = ctx.scoped("blocks")

    def shared_apply(h, kv_cache):
        hn = tf.norm_apply(shared["norm1"], h, cfg)
        if mode == "decode":
            a, new_kv = tf.attn_decode(shared["attn"], hn, kv_cache, pos, cfg,
                                       sctx)
        else:
            a, new_kv = tf.attn_full(shared["attn"], hn, cfg, sctx, causal=True,
                                     return_cache=(mode == "prefill"))
        h = residual_add(h, a)
        h2 = tf.norm_apply(shared["norm2"], h, cfg)
        f = tf.mlp_apply(shared["mlp"], h2, cfg, sctx)
        return residual_add(h, f), new_kv

    if mode in ("train", "prefill"):
        want_cache = mode == "prefill"

        def super_body(h, p_super):
            h = ctx.shard.constrain(h, *sp)
            h, kv = shared_apply(h, None)

            def inner(hh, p_layer):
                out, mc = mamba2.mamba_full(p_layer, hh, cfg, bctx,
                                            return_cache=want_cache)
                return residual_add(hh, out), mc
            h, mcaches = jax.lax.scan(inner, h, p_super)
            return h, (mcaches, kv)
        remat = ctx.remat and mode == "train"
        x, ys = _scan_layers(super_body, x, params["blocks"], remat)
        x = ctx.shard.constrain(x, *sp)
        if want_cache:
            mcaches, kvs = ys
            return x, {"layers": mcaches, "kv": kvs}
        return x, None

    def super_body(h, xs):
        p_super, mcache, kv_cache = xs
        h, new_kv = shared_apply(h, kv_cache)

        def inner(hh, layer):
            p_layer, mc = layer
            out, new_mc = mamba2.mamba_step(p_layer, hh, mc, cfg, bctx)
            return residual_add(hh, out), new_mc
        h, new_mc = jax.lax.scan(inner, h, (p_super, mcache))
        return h, (new_mc, new_kv)

    x, (new_layers, new_kvs) = jax.lax.scan(
        super_body, x, (params["blocks"], caches["layers"], caches["kv"])
    )
    return x, {"layers": new_layers, "kv": new_kvs}


# ---------------------------------------------------------------------------
# Audio enc-dec forward (whisper)
# ---------------------------------------------------------------------------


def _encode(params, frames, cfg, ctx):
    """frames (B, S_enc, d): precomputed frame embeddings (stub frontend)."""
    B, S, d = frames.shape
    x = frames.astype(ctx.compute_dtype) + sinusoid(jnp.arange(S), d).astype(
        ctx.compute_dtype
    )
    sp = ("batch", "act_seq", None)
    ectx = ctx.scoped("enc_blocks")

    def body(h, p_layer):
        h = ctx.shard.constrain(h, *sp)
        hn = tf.norm_apply(p_layer["norm1"], h, cfg)
        a, _ = tf.attn_full(p_layer["attn"], hn, cfg, ectx, causal=False,
                            use_rope=False)
        h = residual_add(h, a)
        h2 = tf.norm_apply(p_layer["norm2"], h, cfg)
        f = tf.mlp_apply(p_layer["mlp"], h2, cfg, ectx)
        return residual_add(h, f), None

    x, _ = _scan_layers(body, x, params["enc_blocks"], ctx.remat)
    return tf.norm_apply(params["enc_norm"], x, cfg)


def _cross_kv(params, enc, cfg, ctx):
    """Project encoder output into per-decoder-layer cross KV caches."""
    a = cfg.attn
    B, S, d = enc.shape

    bctx = ctx.scoped("blocks")

    def body(_, p_layer):
        pa = p_layer["xattn"]
        k = dense(enc, pa["wk"].reshape(d, -1),
                  quant=bctx.site_quant("xattn.wk"), shard=ctx.shard).reshape(
            B, S, a.n_kv_heads, a.d_head
        )
        v = dense(enc, pa["wv"].reshape(d, -1),
                  quant=bctx.site_quant("xattn.wv"), shard=ctx.shard).reshape(
            B, S, a.n_kv_heads, a.d_head
        )
        if a.qkv_bias:
            k = k + pa["bk"].astype(k.dtype)
            v = v + pa["bv"].astype(v.dtype)
        return None, {"k": k, "v": v}

    _, kv = jax.lax.scan(body, None, params["blocks"])
    return kv


def _dec_block_apply(p, x, cfg, ctx, *, mode, self_cache, cross_kv, pos):
    h = tf.norm_apply(p["norm1"], x, cfg)
    if mode == "decode":
        a, new_self = tf.attn_decode(p["attn"], h, self_cache, pos, cfg, ctx,
                                     use_rope=False)
    else:
        a, new_self = tf.attn_full(p["attn"], h, cfg, ctx, causal=True,
                                   use_rope=False,
                                   return_cache=(mode == "prefill"))
    x = residual_add(x, a)

    hx = tf.norm_apply(p["norm_x"], x, cfg)
    if mode == "decode":
        a, _ = tf.attn_decode(p["xattn"], hx, cross_kv, pos, cfg, ctx,
                              use_rope=False, cross=True, site="xattn")
    else:
        # full-sequence cross attention against the encoder output KV
        B, S, d = hx.shape
        aa = cfg.attn
        q = dense(hx, p["xattn"]["wq"].reshape(d, -1),
                  quant=ctx.site_quant("xattn.wq"), shard=ctx.shard).reshape(
            B, S, aa.n_heads, aa.d_head
        )
        if aa.qkv_bias:
            q = q + p["xattn"]["bq"].astype(q.dtype)
        from repro.models.attention import flash_attention, AttnChunking
        o = flash_attention(
            q, cross_kv["k"], cross_kv["v"], causal=False,
            chunking=AttnChunking(q_chunk=min(ctx.attn_q_chunk, S),
                                  k_chunk=min(ctx.attn_k_chunk, cross_kv["k"].shape[1])),
        )
        a = dense(o.reshape(B, S, -1), p["xattn"]["wo"].reshape(-1, d),
                  quant=ctx.site_quant("xattn.wo"), shard=ctx.shard)
    x = residual_add(x, a)

    h2 = tf.norm_apply(p["norm2"], x, cfg)
    return residual_add(x, tf.mlp_apply(p["mlp"], h2, cfg, ctx)), new_self


def _audio_forward(params, dec_x, cfg, ctx, *, mode, frames=None, caches=None,
                   pos=None):
    """dec_x (B, S_dec, d) embedded decoder input."""
    sp = ("batch", "act_seq", None) if dec_x.shape[1] > 1 else ("batch", None, None)
    bctx = ctx.scoped("blocks")
    if mode in ("train", "prefill"):
        enc = _encode(params, frames, cfg, ctx)
        cross = _cross_kv(params, enc, cfg, ctx)        # (L, B, S_enc, Hkv, Dh)

        def body(h, layer):
            p_layer, ckv = layer
            h = ctx.shard.constrain(h, *sp)
            h, self_cache = _dec_block_apply(p_layer, h, cfg, bctx, mode=mode,
                                             self_cache=None, cross_kv=ckv,
                                             pos=None)
            return h, self_cache
        remat = ctx.remat and mode == "train"
        x, self_caches = _scan_layers(body, dec_x, (params["blocks"], cross), remat)
        x = ctx.shard.constrain(x, *sp)
        if mode == "prefill":
            return x, {"self": self_caches, "cross": cross}
        return x, None

    def body(h, layer):
        p_layer, self_cache, ckv = layer
        h, new_self = _dec_block_apply(p_layer, h, cfg, bctx, mode="decode",
                                       self_cache=self_cache, cross_kv=ckv,
                                       pos=pos)
        return h, new_self
    x, new_self = jax.lax.scan(
        body, dec_x, (params["blocks"], caches["self"], caches["cross"])
    )
    return x, {"self": new_self, "cross": caches["cross"]}


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _backbone(params, x, cfg, ctx, *, mode, caches=None, pos=None,
              frames=None, pages=None):
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        return _transformer_forward(params, x, cfg, ctx, mode=mode,
                                    caches=caches, pos=pos, pages=pages)
    assert pages is None, f"paged KV pool is transformer-only, got {fam!r}"
    if fam == "ssm":
        return _ssm_forward(params, x, cfg, ctx, mode=mode,
                            caches=caches)
    if fam == "hybrid":
        return _hybrid_forward(params, x, cfg, ctx, mode=mode, caches=caches,
                               pos=pos)
    if fam == "audio":
        return _audio_forward(params, x, cfg, ctx, mode=mode, frames=frames,
                              caches=caches, pos=pos)
    raise ValueError(fam)


def train_loss(params: dict, batch: dict, cfg: ArchConfig, ctx: ModelCtx):
    """Next-token CE loss. batch: {"tokens"} | {"embeds","labels"} |
    {"frames","tokens"} (audio)."""
    if cfg.family == "audio":
        x = embed_tokens(params, batch["tokens"], cfg, ctx)
        x = x + sinusoid(jnp.arange(x.shape[1]), cfg.d_model).astype(x.dtype)
        h, _ = _backbone(params, x, cfg, ctx, mode="train",
                         frames=batch["frames"])
        logits = lm_logits(params, h, cfg, ctx)
        return cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])
    if cfg.embeds_input:
        x = batch["embeds"].astype(ctx.compute_dtype)
        labels = batch["labels"]
        h, _ = _backbone(params, x, cfg, ctx, mode="train")
        logits = lm_logits(params, h, cfg, ctx)
        return cross_entropy(logits[:, :-1], labels[:, 1:])
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg, ctx)
    h, _ = _backbone(params, x, cfg, ctx, mode="train")
    logits = lm_logits(params, h, cfg, ctx)
    return cross_entropy(logits[:, :-1], tokens[:, 1:])


def prefill(params: dict, batch: dict, cfg: ArchConfig, ctx: ModelCtx):
    """Process the prompt; return (last-token logits (B,V), decode cache)."""
    if cfg.family == "audio":
        # encode the frames; decoder consumes BOS (token 0)
        B = batch["frames"].shape[0]
        bos = jnp.zeros((B, 1), jnp.int32)
        x = embed_tokens(params, bos, cfg, ctx)
        x = x + sinusoid(jnp.arange(1), cfg.d_model).astype(x.dtype)
        h, caches = _backbone(params, x, cfg, ctx, mode="prefill",
                              frames=batch["frames"])
        seq_pos = jnp.asarray(1, jnp.int32)
    elif cfg.embeds_input:
        x = batch["embeds"].astype(ctx.compute_dtype)
        h, caches = _backbone(params, x, cfg, ctx, mode="prefill")
        seq_pos = jnp.asarray(x.shape[1], jnp.int32)
    else:
        x = embed_tokens(params, batch["tokens"], cfg, ctx)
        h, caches = _backbone(params, x, cfg, ctx, mode="prefill")
        seq_pos = jnp.asarray(x.shape[1], jnp.int32)
    logits = lm_logits(params, h[:, -1:], cfg, ctx)[:, 0]       # (B, V)

    if cfg.family in ("dense", "vlm", "moe"):
        cache = {"kv": caches, "pos": seq_pos}
    elif cfg.family == "ssm":
        cache = {"layers": caches, "pos": seq_pos}
    elif cfg.family == "hybrid":
        cache = {"layers": caches["layers"], "kv": caches["kv"], "pos": seq_pos}
    else:  # audio
        cache = {"self": caches["self"], "cross": caches["cross"], "pos": seq_pos}
    return logits, cache


def pad_cache(cache: dict, cfg: ArchConfig, capacity: int) -> dict:
    """Grow prefill KV caches along the token axis to ``capacity`` slots.

    Dense leaves (L, B, S, Hkv, Dh) pad axis 2; HiF4-packed tensors pad
    their own layout's token axis (``repro.core.kvcache.pad_tokens`` —
    the kernel-tile layout keeps tokens LAST). Zero padding is inert
    under the length mask either way.
    """
    from repro.core import kvcache

    def pad_dense(x):
        s = x.shape[2]  # (L, B, S, Hkv, Dh)
        if s >= capacity:
            return x
        pads = [(0, 0)] * x.ndim
        pads[2] = (0, capacity - s)
        return jnp.pad(x, pads)

    def grow(kv):
        return {
            name: (kvcache.pad_tokens(t, capacity) if kvcache.is_packed_kv(t)
                   else pad_dense(t))
            for name, t in kv.items()
        }

    out = dict(cache)
    for key in ("kv", "self"):
        if key in out:
            out[key] = grow(out[key])
    return out


def quantize_kv_cache(cache: dict, cfg: ArchConfig) -> dict:
    """Convert a prefill KV cache to the HiF4-packed layout (one-time).

    KV leaves (L, B, S, Hkv, Dh) become packed {codes, meta, tail} leaves
    (4.5 bits/value + bf16 partial-group tail) in the KERNEL-TILE layout
    (token axis last) the fused decode-attention kernel streams — the
    analogue of ``PackedW.to_kernel_layout`` in
    ``prepare_params_for_serving``, applied once at cache build. Grouping
    is per token and the re-layout is a pure bit move, so this bulk
    conversion is bit-identical to appending the same tokens one at a
    time — the invariant continuous-batching parity rests on. The
    transformer families convert their self-attention cache ("kv"); the
    audio family converts both the decoder "self" cache and the
    read-only encoder "cross" cache (the cross cache never grows, so it
    is packed once here and only ever dequantized on read). Call before
    :func:`pad_cache` (zero padding after packing stays inert).
    """
    from repro.core import kvcache

    assert cfg.family in ("dense", "vlm", "moe", "audio"), cfg.family

    def pack(kv):
        return {
            "k": kvcache.to_kernel_layout(kvcache.quantize_kv(kv["k"])),
            "v": kvcache.to_kernel_layout(kvcache.quantize_kv(kv["v"])),
        }

    out = dict(cache)
    if cfg.family == "audio":
        out["self"] = pack(cache["self"])
        out["cross"] = pack(cache["cross"])
    else:
        out["kv"] = pack(cache["kv"])
    return out


def decode_step(params: dict, token: jax.Array, cache: dict, cfg: ArchConfig,
                ctx: ModelCtx):
    """token (B,) int32 -> (logits (B, V), updated cache)."""
    pos = cache["pos"]
    x = embed_tokens(params, token[:, None], cfg, ctx)          # (B, 1, d)
    if cfg.family == "audio":
        x = x + sinusoid(pos + jnp.arange(1), cfg.d_model).astype(x.dtype)
        h, new = _backbone(params, x, cfg, ctx, mode="decode", caches=cache,
                           pos=pos)
        new_cache = {"self": new["self"], "cross": new["cross"], "pos": pos + 1}
    elif cfg.family == "ssm":
        h, new = _backbone(params, x, cfg, ctx, mode="decode",
                           caches=cache["layers"])
        new_cache = {"layers": new, "pos": pos + 1}
    elif cfg.family == "hybrid":
        h, new = _backbone(params, x, cfg, ctx, mode="decode", caches=cache,
                           pos=pos)
        new_cache = {"layers": new["layers"], "kv": new["kv"], "pos": pos + 1}
    else:
        pages = cache.get("pages")
        h, new = _backbone(params, x, cfg, ctx, mode="decode",
                           caches=cache["kv"], pos=pos, pages=pages)
        new_cache = {"kv": new, "pos": pos + 1}
        if pages is not None:
            new_cache["pages"] = pages
    logits = lm_logits(params, h[:, -1:], cfg, ctx)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Packed-weight serving overlay (HiF4 4.5-bit deployment artifact)
# ---------------------------------------------------------------------------

from repro.core.policy import STACKED_COLLECTIONS, QuantPlan, QuantPolicy
from repro.core.qlinear import QuantConfig


def quant_plan(cfg: ArchConfig, policy) -> QuantPlan:
    """Resolve a policy (or a legacy global QuantConfig, via the uniform
    shim) against this architecture's param specs — the explicit
    site -> QuantConfig plan everything serving-side packs and QDQs from."""
    if isinstance(policy, QuantPlan):
        return policy
    if isinstance(policy, QuantConfig):
        policy = QuantPolicy.uniform(policy)
    return policy.resolve(abstract_params(cfg), family=cfg.family)


def _default_packed_plan(cfg: ArchConfig) -> QuantPlan:
    """The historical packing set: uniform hif4/packed over the default
    packable sites (used when callers pack without an explicit policy)."""
    return quant_plan(cfg, QuantConfig(fmt="hif4", impl="packed"))


def _marker_geometry(site, axes: tuple):
    """(k, n, L, out_name, c_name) of a packed STACKED site spec."""
    import numpy as np

    ca = site.contract_axes
    nd = len(site.shape)
    out_axes = tuple(a for a in range(1, nd) if a not in ca)
    k = int(np.prod([site.shape[a] for a in ca]))
    n = int(np.prod([site.shape[a] for a in out_axes])) if out_axes else 1
    out_name = next((axes[a] for a in out_axes if axes[a] is not None), None)
    c_name = next((axes[a] for a in ca if axes[a] is not None), None)
    return k, n, site.shape[0], out_name, c_name


def packed_overlay(specs: dict, plan: QuantPlan) -> dict:
    """Replace the block-weight PSpecs the PLAN marks packed with packed
    codes/meta PSpecs — the overlay packs exactly the policy's site set,
    nothing else.

    Returned leaves for a packed weight: a dict
        {"__packed__": True, "codes": PSpec, "meta": PSpec,
         "shape2d": (K, N), "dtype": ...}
    which launch/runtime code converts into :class:`PackedW` nodes (with
    ShapeDtypeStructs for the dry-run, real buffers for serving).
    """

    def walk(node, parts):
        if isinstance(node, PSpec):
            site = plan.get(".".join(parts))
            if site is None or not site.packed:
                return node
            k, n, L, out_name, c_name = _marker_geometry(site, node.axes)
            return {
                "__packed__": True,
                "codes": PSpec((L, n, k // 64, 32),
                               ("layers", out_name, c_name, None),
                               dtype=jnp.uint8, init="zeros"),
                "meta": PSpec((L, n, k // 64),
                              ("layers", out_name, c_name),
                              dtype=jnp.uint32, init="zeros"),
                "shape2d": (k, n),
                "dtype": jnp.bfloat16,
                "axes2d": (out_name, c_name),
            }
        if isinstance(node, dict):
            return {kk: walk(vv, parts + (kk,)) for kk, vv in node.items()}
        return node

    out = dict(specs)
    for blk in STACKED_COLLECTIONS:
        if blk in out:
            out[blk] = walk(out[blk], (blk,))
    return out


def is_packed_marker(node) -> bool:
    return isinstance(node, dict) and node.get("__packed__") is True


def realize_packed(tree, leaf_fn):
    """Convert packed markers into PackedW nodes; other PSpecs via leaf_fn.

    ``leaf_fn(pspec)`` -> array-like (ShapeDtypeStruct or real buffer).
    """
    from repro.core.qlinear import PackedW

    def walk(node):
        if is_packed_marker(node):
            return PackedW(leaf_fn(node["codes"]), leaf_fn(node["meta"]),
                           tuple(node["shape2d"]), node["dtype"],
                           tuple(node["axes2d"]))
        if isinstance(node, PSpec):
            return leaf_fn(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(tree)


def pack_params_for_serving(params: dict, cfg: ArchConfig,
                            plan: Optional[QuantPlan] = None) -> dict:
    """Offline conversion of real trained weights into PackedW nodes.

    Packs EXACTLY the sites ``plan`` marks packed (default: the uniform
    hif4/packed plan — the historical behavior). A policy rule flipping
    one site to bf16/qdq leaves that site's weight dense here, and the
    engine serves it through the matching non-packed path.
    """
    from repro.core.qlinear import PackedW

    if plan is None:
        plan = _default_packed_plan(cfg)
    specs = abstract_params(cfg)

    def walk(p_node, s_node, parts):
        if isinstance(s_node, PSpec):
            site = plan.get(".".join(parts))
            if site is None or not site.packed:
                return p_node
            ca = site.contract_axes
            _, _, _, out_name, c_name = _marker_geometry(site, s_node.axes)
            # per-layer pack, stacked along L
            stacked = [
                PackedW.from_dense(p_node[i], tuple(a - 1 for a in ca))
                for i in range(p_node.shape[0])
            ]
            codes = jnp.stack([s.codes for s in stacked])
            meta = jnp.stack([s.meta for s in stacked])
            return PackedW(codes, meta, stacked[0].shape2d,
                           p_node.dtype, (out_name, c_name))
        if isinstance(s_node, dict):
            return {kk: walk(p_node[kk], vv, parts + (kk,))
                    for kk, vv in s_node.items()}
        return p_node

    out = dict(params)
    for blk in STACKED_COLLECTIONS:
        if blk in out:
            out[blk] = walk(params[blk], specs[blk], (blk,))
    return out
