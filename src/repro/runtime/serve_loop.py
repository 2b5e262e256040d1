"""Batched serving loop: offline weight PTQ/packing -> prefill -> scan decode.

Weights are converted ONCE into the deployment artifact the configured
execution path consumes (``QuantConfig.impl``):

  qdq            -> fake-quant (QDQ) bf16 weights (accuracy-experiment shape)
  packed/pallas  -> :class:`PackedW` 4.5-bit buffers in the K-major kernel
                    layout (real 0.5625 B/value HBM residency) consumed
                    directly by the fused dequantize-in-kernel matmul
                    (repro.kernels.fused_matmul)

Decode runs as a ``jax.lax.scan`` over a static token budget — ONE jitted
dispatch per chunk instead of one per token — with per-request done masks.
:func:`serve_requests` adds a slot-based continuous-batching scheduler on
top: a fixed number of decode slots, per-slot cache positions, and admission
of queued requests into slots as earlier requests finish.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import kvcache
from repro.core.policy import STACKED_COLLECTIONS, QuantPlan
from repro.core.qlinear import QuantConfig, quantize_params_offline
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime import guard as guard_mod
from repro.runtime.guard import (ArtifactLayoutError, ArtifactNotFoundError,
                                 GuardConfig, PoolExhaustedError)


class KVFallbackWarning(UserWarning):
    """``kv_format=hif4`` was narrowed to bf16 for a family whose recurrent
    state has no packed layout. A real warning (not a print) so callers and
    tests capture and assert on it; records carry ``kv_format_fallback``."""


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    cache_capacity: Optional[int] = None   # default: prompt + max_new
    decode_chunk: int = 0                  # tokens per jitted scan segment;
    #                                        0 = the whole budget in one scan
    eos_id: Optional[int] = None           # stop a request at this token
    kv_format: Optional[str] = None        # 'bf16' | 'hif4' KV cache storage;
    #                                        None = ctx.quant.kv.kv_format
    kv_pages: int = 0                      # > 0: page-pool scheduler with this
    #                                        many pool pages (hif4 KV only)
    kv_page_tokens: int = 64               # tokens per pool page
    prefix_sharing: bool = True            # hash-share prompt-prefix pages
    guard: Optional[GuardConfig] = None    # health sentinels + fault domains
    #                                        (None = unguarded; failures raise)
    journal_dir: Optional[str] = None      # write-ahead request journal +
    #                                        pool checkpoints live here
    #                                        (None = no crash safety)
    checkpoint_every: int = 0              # pool checkpoint cadence in decode
    #                                        chunks (paged scheduler; 0 = off)


def resolve_kv_format(cfg: ArchConfig, quant: QuantConfig,
                      serve_cfg: ServeConfig, *, verbose: bool = False,
                      warned: Optional[set] = None) -> str:
    """The KV storage this serve actually runs: ServeConfig overrides the
    QuantConfig KVCacheConfig; SSM-state families fall back to bf16 (the
    recurrent state has no packed layout — see the docs/EXECUTION.md
    matrix). Attention caches — including the audio self + read-only
    cross (encoder) caches — pack. ``verbose=True`` (the serve/launch
    entry points) emits a :class:`KVFallbackWarning` instead of narrowing
    silently; benchmark and dryrun records carry it as
    ``kv_format_fallback``. ``warned`` is a per-serve-call dedup set:
    the fallback warns once per (arch, requested format) per serve call,
    not once per admission/re-prefill that re-resolves the format."""
    from repro.core import kvcache

    fmt = serve_cfg.kv_format or quant.kv.kv_format
    assert fmt in kvcache.KV_FORMATS, fmt
    if fmt == "hif4" and cfg.family not in ("dense", "vlm", "moe", "audio"):
        key = (cfg.name, fmt)
        if verbose and (warned is None or key not in warned):
            if warned is not None:
                warned.add(key)
            warnings.warn(
                f"kv_format=hif4 has no packed layout for family "
                f"{cfg.family!r} (SSM recurrent state) — serving falls "
                f"back to bf16 KV", KVFallbackWarning, stacklevel=2)
        return "bf16"
    return fmt


def kv_format_fallback(cfg: ArchConfig, quant: QuantConfig,
                       serve_cfg: ServeConfig) -> bool:
    """True when the requested KV format was narrowed by family fallback —
    the flag benchmark/dryrun records carry so a silently-bf16 run is
    visible in artifacts, not just stdout."""
    requested = serve_cfg.kv_format or quant.kv.kv_format
    return resolve_kv_format(cfg, quant, serve_cfg) != requested


def _to_kernel_layout(params):
    """Re-layout every PackedW leaf K-major ONCE (same 4.5-bit payload,
    transposed) so the fused matmul tiles resident buffers per step instead
    of re-laying-out inside the decode scan body."""
    from repro.core.qlinear import PackedW

    return jax.tree_util.tree_map(
        lambda leaf: leaf.to_kernel_layout()
        if isinstance(leaf, PackedW) else leaf,
        params, is_leaf=lambda x: isinstance(x, PackedW),
    )


def prepare_params_for_serving(params: dict, cfg: ArchConfig,
                               quant, *, kernel_layout: bool = True) -> dict:
    """One-time offline conversion of block weights into the serving artifact.

    ``quant`` is a legacy global :class:`QuantConfig` (converted via the
    uniform-policy shim), a :class:`~repro.core.policy.QuantPolicy`, or an
    already-resolved :class:`~repro.core.policy.QuantPlan`. Per site, the
    resolved plan decides the artifact — there is no other packing
    predicate:

    * sites the plan marks ``packed`` become 4.5-bit PackedW buffers in
      the K-major kernel layout the fused matmul consumes
      (docs/FORMATS.md);
    * quantized-but-not-packed sites (qdq impl, non-HiF4 formats, or
      sites a rule flipped away from the packed path) get the offline
      fake-quant QDQ artifact along their true contraction axes;
    * everything else (embed/head/router under the default §IV rules,
      fmt='none' sites) stays full precision.

    ``kernel_layout=False`` keeps PackedW leaves in the artifact
    (output-major, on-disk) layout — what :func:`save_serving_artifact`
    checkpoints; serving always re-lays-out K-major once.
    """
    plan = lm.quant_plan(cfg, quant)
    if not plan.enabled:
        return params
    if packed_weight_bytes(params)[1]:
        # already packed (idempotent); honor the layout request — there is
        # no kernel->artifact inverse, so callers needing the artifact
        # layout must start from raw weights (save_serving_artifact asserts)
        return _to_kernel_layout(params) if kernel_layout else params
    out = dict(params)
    if plan.packed_paths:
        out = lm.pack_params_for_serving(out, cfg, plan)
    for key in STACKED_COLLECTIONS:
        if key in out:
            out[key] = quantize_params_offline(out[key], plan.base,
                                               plan=plan, prefix=key)
    # top-level untied head: a policy that quantizes it gets a real
    # offline artifact too (the uniform shim resolves it to fmt='none')
    site = plan.get("lm_head")
    if (site is not None and "lm_head" in out and site.quantize_offline
            and site.cfg.format() is not None):
        from repro.core.qlinear import _qdq_along

        out["lm_head"] = _qdq_along(out["lm_head"], site.cfg.format(),
                                    site.contract_axes)
    if plan.packed_paths and kernel_layout:
        return _to_kernel_layout(out)
    return out


def serving_ctx(ctx: ModelCtx) -> ModelCtx:
    """The model context decode runs under: weights already quantized
    offline (skip in-graph weight QDQ), no remat. With a policy plan
    attached, every site config gets the same offline flip."""
    qcfg = dataclasses.replace(ctx.quant, offline_weights=True)
    plan = ctx.plan.with_offline_weights() if ctx.plan is not None else None
    return dataclasses.replace(ctx, quant=qcfg, plan=plan, remat=False)


def save_serving_artifact(directory: str, params: dict, cfg: ArchConfig,
                          policy) -> str:
    """Write the deployment artifact: the policy-converted weights (PackedW
    leaves in the on-disk artifact layout, QDQ'd bf16 elsewhere) PLUS the
    policy itself, serialized into the checkpoint's ``extra.json`` — so an
    artifact can never be served under a different placement than it was
    packed with. ``params`` are the RAW trained weights; ``policy`` is a
    QuantPolicy/QuantPlan (or a legacy QuantConfig via the uniform shim).

    The checkpoint's ``extra.json`` also records an integrity block —
    per-PackedW-leaf sha256 over the codes and meta payloads plus the
    HiF4 format invariants (:mod:`repro.runtime.guard`) — which
    :func:`load_serving_artifact` re-verifies, so a bit-rotted artifact
    fails loudly at load instead of serving silently wrong tokens.
    """
    from repro.checkpoint import save_checkpoint

    if packed_weight_bytes(params)[1]:
        raise ArtifactLayoutError(
            f"save_serving_artifact({directory!r}) was handed an "
            "already-packed tree. Expected RAW (unpacked) trained weights: "
            "packed PackedW leaves may be in the K-major kernel layout, "
            "which has no inverse back to the on-disk artifact layout. "
            "To re-export, load the raw training weights and call "
            "save_serving_artifact(directory, raw_params, cfg, policy) — "
            "the policy conversion happens inside.")
    plan = lm.quant_plan(cfg, policy)
    artifact = prepare_params_for_serving(params, cfg, plan,
                                          kernel_layout=False)
    extra = {"family": cfg.family,
             "quant_policy": plan.policy.to_json_dict(),
             "integrity": guard_mod.artifact_integrity(artifact)}
    return save_checkpoint(directory, 0, artifact, extra)


def load_serving_artifact(directory: str, cfg: ArchConfig):
    """Restore (serving_params, policy) written by
    :func:`save_serving_artifact`. The policy is read FIRST and its
    resolved plan rebuilds the packed/dense tree structure the arrays load
    into; pass the params straight to :func:`serve` with a plan-carrying
    ModelCtx (prepare is idempotent on the packed tree and only re-lays-out
    K-major).

    Artifacts written with an integrity block (see
    :func:`save_serving_artifact`) are verified leaf-by-leaf after load;
    corruption raises :class:`repro.runtime.guard.ArtifactIntegrityError`
    naming the failing leaf. Older artifacts without the block load
    unverified.
    """
    import json
    import os

    from repro.checkpoint import latest_step, load_checkpoint
    from repro.core.policy import QuantPolicy

    step = latest_step(directory)
    if step is None:
        raise ArtifactNotFoundError(
            f"no serving artifact under {directory!r}: expected a "
            "step_<NNNNNNNN>/ directory holding manifest.json, the packed "
            "arrays, and extra.json with the serialized quant_policy. "
            "Re-export with repro.runtime.serve_loop.save_serving_artifact("
            "directory, raw_params, cfg, policy).")
    with open(os.path.join(directory, f"step_{step:08d}", "extra.json")) as f:
        extra = json.load(f)
    policy = QuantPolicy.from_json_dict(extra["quant_policy"])
    plan = lm.quant_plan(cfg, policy)
    specs = lm.packed_overlay(lm.abstract_params(cfg), plan)
    target = lm.realize_packed(
        specs, lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype))
    params, _ = load_checkpoint(directory, step, target)
    integrity = extra.get("integrity")
    if integrity is not None:
        guard_mod.verify_artifact_integrity(params, integrity, directory)
    return params, policy


def packed_weight_bytes(params) -> tuple[int, int]:
    """(packed payload bytes, packed value count) over all PackedW leaves."""
    from repro.core.qlinear import PackedW

    total = values = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, PackedW)
    ):
        if isinstance(leaf, PackedW):
            total += leaf.nbytes_packed
            values += leaf.n_values
    return total, values


def kv_cache_bytes(cache: dict) -> tuple[int, int]:
    """(resident KV-cache bytes, token slots) of a decode cache.

    Counts every attention KV entry: "kv" (transformer/hybrid families) or
    "self" + "cross" (audio). Token slots = B * capacity of the decode
    self-attention cache (one slot holds a token's K/V across ALL layers,
    so bytes/token = bytes / slots); the read-only cross cache contributes
    bytes but no slots. Works on bf16 and HiF4-packed caches alike.
    """
    from repro.core import kvcache

    total = 0
    slots = 0
    for entry, counts_slots in (("kv", True), ("self", True),
                                ("cross", False)):
        kv = cache.get(entry)
        if kv is None:
            continue
        for tensor in (kv["k"], kv["v"]):
            if kvcache.is_packed_kv(tensor):
                total += kvcache.packed_kv_nbytes(tensor)
                b = tensor["meta"].shape[1]          # (L, B, ...) stacked
                s = kvcache.seq_capacity(tensor)
            else:
                total += int(tensor.nbytes)
                _, b, s = tensor.shape[:3]
            if counts_slots:
                slots = b * s
    return total, slots


# ---------------------------------------------------------------------------
# Scan decode
# ---------------------------------------------------------------------------


def _decode_scan(params, token, cache, done, n_tokens: int, cfg: ArchConfig,
                 sctx: ModelCtx, eos_id: Optional[int]):
    """Greedy-decode ``n_tokens`` steps inside one lax.scan.

    token (B,) int32 is the last emitted token; done (B,) bool masks
    finished requests (their slots keep emitting eos/pad, and their cache
    writes are inert because outputs are masked). Returns
    (tokens (B, n_tokens), token, cache, done).
    """

    def body(carry, _):
        token, cache, done = carry
        logits, cache = lm.decode_step(params, token, cache, cfg, sctx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, done), nxt

    (token, cache, done), toks = jax.lax.scan(
        body, (token, cache, done), None, length=n_tokens
    )
    return jnp.swapaxes(toks, 0, 1), token, cache, done


def _decode_scan_guarded(params, token, cache, done, bad, n_tokens: int,
                         cfg: ArchConfig, sctx: ModelCtx,
                         eos_id: Optional[int]):
    """:func:`_decode_scan` with the health sentinels fused in.

    ``bad`` (B,) bool OR-accumulates a per-slot ``~isfinite(logits)``
    reduction every step (:func:`repro.runtime.guard.bad_logits`) —
    one extra (B, V) reduction carried in the scan state. After the scan,
    the SAME jitted program reduces the 0xFF E6M2 sentinel count over the
    packed KV leaves (per slot for the contiguous cache, per pool page
    for the paged pool; zeros for bf16 KV): corruption persists in the
    cache, so one end-of-chunk reduction sees everything a per-step one
    would, without a second dispatch or host sync. Both sentinels come
    back as ONE ``flags`` int32 vector — ``flags[:B]`` the NaN flags,
    ``flags[B:]`` the 0xFF counts — so the scheduler's existing per-chunk
    token pull grows by a single small leaf (host-transfer calls carry a
    large fixed cost; the guard_overhead gate holds because of this).
    The token stream is computed by exactly the same ops in the same
    order, so guarded outputs are bitwise identical to the unguarded
    scan. Returns (tokens (B, n_tokens), token, cache, done, flags).
    """

    def body(carry, _):
        token, cache, done, bad = carry
        logits, cache = lm.decode_step(params, token, cache, cfg, sctx)
        bad = bad | guard_mod.bad_logits(logits)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, done, bad), nxt

    (token, cache, done, bad), toks = jax.lax.scan(
        body, (token, cache, done, bad), None, length=n_tokens
    )
    kv = cache.get("kv") if isinstance(cache, dict) else None
    if isinstance(kv, dict) and isinstance(kv.get("k"), dict) \
            and "meta" in kv["k"]:
        meta_nan = guard_mod.slot_meta_nan_counts(kv)
    else:
        meta_nan = jnp.zeros(token.shape, jnp.int32)
    flags = jnp.concatenate([bad.astype(jnp.int32), meta_nan])
    return jnp.swapaxes(toks, 0, 1), token, cache, done, flags


# jax.jit caches compiled executables per wrapper OBJECT, so building a
# fresh wrapper inside every serve() call would retrace+recompile the whole
# model per call. Key the wrappers on the values that change the traced
# graph (ArchConfig and QuantConfig are frozen/hashable; ShardCtx is not —
# its mesh identity + rules stand in for it). Bounded in practice: a
# handful of (arch, ctx, budget) combinations per process.
_JIT_CACHE: dict = {}


def _ctx_cache_key(ctx: ModelCtx):
    shard = ctx.shard
    mesh_key = None if shard.mesh is None else (
        tuple(shard.mesh.shape.items()), id(shard.mesh)
    )
    return (ctx.quant, ctx.plan, ctx.scope, mesh_key,
            tuple(sorted((k, tuple(v)) for k, v in shard.rules.items())),
            str(ctx.param_dtype), str(ctx.compute_dtype), ctx.remat,
            ctx.attn_q_chunk, ctx.attn_k_chunk, ctx.attn_impl,
            ctx.attn_kv_block)


# Each wrapper jits a function with its own name, so the program and its
# device ops read as ``jit_serve_prefill``, ``jit_serve_decode`` etc. in a
# profiler trace.
def _jit_prefill(cfg: ArchConfig, sctx: ModelCtx):
    key = ("prefill", cfg, _ctx_cache_key(sctx))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def serve_prefill(params, batch):
            return lm.prefill(params, batch, cfg, sctx)

        fn = _JIT_CACHE[key] = jax.jit(serve_prefill)
    return fn


def _jit_quantize_kv(cfg: ArchConfig):
    key = ("quantize_kv", cfg)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def serve_quantize_kv(cache):
            return lm.quantize_kv_cache(cache, cfg)

        fn = _JIT_CACHE[key] = jax.jit(serve_quantize_kv)
    return fn


def _jit_decode_scan(cfg: ArchConfig, sctx: ModelCtx, n_tokens: int,
                     eos_id: Optional[int]):
    key = ("decode", cfg, _ctx_cache_key(sctx), n_tokens, eos_id)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def serve_decode(params, token, cache, done):
            return _decode_scan(params, token, cache, done, n_tokens, cfg,
                                sctx, eos_id)

        fn = _JIT_CACHE[key] = jax.jit(
            serve_decode, donate_argnums=(2,))   # cache updates in place
    return fn


def _jit_decode_scan_guarded(cfg: ArchConfig, sctx: ModelCtx, n_tokens: int,
                             eos_id: Optional[int]):
    key = ("decode-guarded", cfg, _ctx_cache_key(sctx), n_tokens, eos_id)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def serve_decode_guarded(params, token, cache, done, bad):
            return _decode_scan_guarded(params, token, cache, done, bad,
                                        n_tokens, cfg, sctx, eos_id)

        fn = _JIT_CACHE[key] = jax.jit(
            serve_decode_guarded, donate_argnums=(2,))  # cache in place
    return fn


def build_decode_cache(cfg: ArchConfig, serving_params: dict, batch: dict,
                       sctx: ModelCtx, serve_cfg: ServeConfig, *,
                       quant=None, verbose: bool = False,
                       warned: Optional[set] = None):
    """Prefill and return (last-token logits, THE decode cache serve runs).

    The exact cache-build sequence :func:`serve` decodes against: prefill,
    then — when :func:`resolve_kv_format` says the serve really runs hif4 —
    pack the prefix ONCE (per-token groups: bit-identical to having
    appended the same tokens one at a time), then pad to capacity (zero
    padding of packed leaves is inert under the length mask). Exposed so
    tests and the scenario matrix can assert the format actually served —
    the ``kv_format_fallback`` flag must agree with these leaves.
    """
    quant = quant or sctx.quant
    kv_fmt = resolve_kv_format(cfg, quant, serve_cfg, verbose=verbose,
                               warned=warned)
    logits, cache = _jit_prefill(cfg, sctx)(serving_params, batch)
    if kv_fmt == "hif4":
        cache = _jit_quantize_kv(cfg)(cache)
    if cfg.family in ("dense", "vlm", "moe", "audio", "hybrid"):
        prompt_len = int(cache["pos"])
        cap = serve_cfg.cache_capacity or prompt_len + serve_cfg.max_new_tokens
        cache = lm.pad_cache(cache, cfg, cap)
    return logits, cache


def serve(
    cfg: ArchConfig,
    params: dict,
    batch: dict,                       # prefill inputs (tokens/embeds/frames)
    ctx: ModelCtx,
    serve_cfg: ServeConfig = ServeConfig(),
):
    """Greedy-decode ``max_new_tokens``; returns (B, T) int32 tokens.

    All requests advance in lockstep (shared position clock); decode is a
    single jitted scan per ``decode_chunk`` segment, not a dispatch per
    token. For heterogeneous request streams use :func:`serve_requests`.
    """
    sctx = serving_ctx(ctx)
    params = prepare_params_for_serving(params, cfg, ctx.plan or ctx.quant)
    logits, cache = build_decode_cache(cfg, params, batch, sctx, serve_cfg,
                                       quant=ctx.quant, verbose=True)

    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    done = jnp.zeros(token.shape, bool)
    if serve_cfg.eos_id is not None:
        done = done | (token == serve_cfg.eos_id)
    out = [token[:, None]]

    budget = serve_cfg.max_new_tokens - 1
    chunk = serve_cfg.decode_chunk or budget
    emitted = 0
    while emitted < budget:
        n = min(chunk, budget - emitted)
        step = _jit_decode_scan(cfg, sctx, n, serve_cfg.eos_id)
        toks, token, cache, done = step(params, token, cache, done)
        out.append(toks)
        emitted += n
        if serve_cfg.eos_id is not None and bool(jnp.all(done)):
            break
    toks = jnp.concatenate(out, axis=1)
    if toks.shape[1] < serve_cfg.max_new_tokens and serve_cfg.eos_id is not None:
        pad = jnp.full(
            (toks.shape[0], serve_cfg.max_new_tokens - toks.shape[1]),
            serve_cfg.eos_id, jnp.int32,
        )
        toks = jnp.concatenate([toks, pad], axis=1)
    return toks


# ---------------------------------------------------------------------------
# Continuous batching: slot-based admission over a shared decode batch
# ---------------------------------------------------------------------------


def _insert_slot(cache, slot_cache, token, slot_token, b: int):
    """Write a freshly prefilled request (batch 1) into batch slot ``b``.

    KV leaves are (L, B, S, Hkv, Dh) — insert along axis 1; the per-slot
    ``pos`` vector and last-token vector update at index ``b``.
    """

    def put(full, one):
        idx = (0, b) + (0,) * (full.ndim - 2)
        return jax.lax.dynamic_update_slice(full, one.astype(full.dtype), idx)

    new_kv = jax.tree_util.tree_map(put, cache["kv"], slot_cache["kv"])
    pos = cache["pos"].at[b].set(slot_cache["pos"].astype(jnp.int32))
    return (
        {"kv": new_kv, "pos": pos},
        token.at[b].set(slot_token),
    )


_insert_slot_jit = jax.jit(_insert_slot, static_argnums=(4,),
                           donate_argnums=(0,))


def _finalize_result(toks: list, budget: int, eos_id: Optional[int]):
    """Trim a slot's emitted tokens to the request's (budget,) result: drop
    over-emission past the budget, and past eos replace everything with eos
    padding (a finished request keeps emitting eos inside the chunked scan).
    """
    toks = toks[:budget]
    if eos_id is not None and eos_id in toks:
        stop = toks.index(eos_id) + 1
        toks = toks + [eos_id] * (budget - len(toks))
        toks = toks[:stop] + [eos_id] * (budget - stop)
    return jnp.asarray(toks, jnp.int32)


def _failed_result(budget: int, eos_id: Optional[int]) -> jnp.ndarray:
    """The (budget,) placeholder a rejected/quarantined request returns:
    eos fill when an eos is configured, else -1 (never a valid token)."""
    return jnp.full((budget,), eos_id if eos_id is not None else -1,
                    jnp.int32)


def _finalize_partial(toks: list, budget: int,
                      eos_id: Optional[int]) -> jnp.ndarray:
    """A timed-out request's partial tokens, padded to (budget,)."""
    fill = eos_id if eos_id is not None else -1
    toks = list(toks[:budget])
    return jnp.asarray(toks + [fill] * (budget - len(toks)), jnp.int32)


def _retry_fallback(cfg: ArchConfig, params: dict, prompt, ctx: ModelCtx,
                    serve_cfg: ServeConfig):
    """Quarantine retry: re-serve ONE request solo on the degradation
    path — qdq impl (dequantize-then-dot on the packed leaves) + bf16 KV —
    with the NaN sentinel carried through prefill and decode.

    Returns ((budget,) int32 tokens, healthy bool). The fallback path
    avoids both fused kernels and the packed cache, so a fault rooted in
    packed payloads or kernel dispatch cannot recur; a still-unhealthy
    retry means the fault is upstream (weights/inputs) and the request is
    quarantined for good.
    """
    fb_quant = dataclasses.replace(ctx.quant, impl="qdq", kv=kvcache.KV_BF16)
    fb_ctx = dataclasses.replace(ctx, quant=fb_quant, plan=None)
    fb_serve = dataclasses.replace(serve_cfg, kv_format="bf16", kv_pages=0,
                                   guard=None)
    sctx = serving_ctx(fb_ctx)
    params = prepare_params_for_serving(params, cfg, fb_quant)
    batch = {"tokens": jnp.asarray(prompt, jnp.int32).reshape(1, -1)}
    logits, cache = build_decode_cache(cfg, params, batch, sctx, fb_serve,
                                       quant=fb_quant)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    bad = guard_mod.bad_logits(logits)
    done = jnp.zeros(token.shape, bool)
    if fb_serve.eos_id is not None:
        done = done | (token == fb_serve.eos_id)
    out = [token[:, None]]
    budget = fb_serve.max_new_tokens - 1
    if budget > 0:
        gstep = _jit_decode_scan_guarded(cfg, sctx, budget, fb_serve.eos_id)
        toks, token, cache, done, flags = gstep(params, token, cache, done,
                                                bad)
        out.append(toks)
        bad = flags[:1]                    # B=1; meta part is zeros (bf16)
    toks = [int(t) for t in jax.device_get(jnp.concatenate(out, axis=1))[0]]
    healthy = not bool(jax.device_get(bad)[0])
    return (_finalize_result(toks, fb_serve.max_new_tokens, fb_serve.eos_id),
            healthy)


def _open_journal(serve_cfg: ServeConfig, requests, *, resume: bool,
                  kind: str, chunk: int, **geometry):
    """(journal, recovery plan) for a serve call — (None, None) without a
    ``journal_dir``. On resume the OLD journal is replayed into the plan
    first; the new journal then stages at ``.tmp``, records its start
    event plus a ``done`` event per already-completed request (so a
    second crash still recovers them without re-serving), and only then
    atomically replaces the old file."""
    if serve_cfg.journal_dir is None:
        if resume:
            raise guard_mod.RecoveryError(
                "resume=True needs serve_cfg.journal_dir pointing at the "
                "crashed serve's journal")
        return None, None
    from repro.runtime import journal as journal_mod

    plan = None
    if resume:
        plan = journal_mod.recover(
            serve_cfg.journal_dir, requests,
            budget=serve_cfg.max_new_tokens, eos=serve_cfg.eos_id)
    journal = journal_mod.RequestJournal(serve_cfg.journal_dir)
    journal.append(
        "start", v=journal_mod.JOURNAL_VERSION, kind=kind,
        n_requests=len(requests), budget=serve_cfg.max_new_tokens,
        eos=serve_cfg.eos_id, chunk=chunk,
        prompts=[journal_mod.prompt_sha256(r) for r in requests],
        **geometry)
    if plan is not None:
        for rid in sorted(plan.completed):
            ent = plan.completed[rid]
            journal.append("done", rid=rid, status=ent["status"],
                           detail=ent["detail"], retries=ent["retries"],
                           toks=ent["toks"])
    journal.activate()
    return journal, plan


def _inject_completed(plan, queue, results, reports):
    """Feed a recovery plan's journaled terminal results straight into the
    result/report tables — completed work is never re-served."""
    for rid in sorted(plan.completed):
        ent = plan.completed[rid]
        queue.remove(rid)
        results[rid] = jnp.asarray(ent["toks"], jnp.int32)
        reports[rid].update(status=ent["status"], detail=ent["detail"])
        reports[rid]["retries"] = ent["retries"]


def _verify_recovery(plan, results, reports) -> int:
    """Recovered state is checked, not trusted: every re-served request
    that finished cleanly must reproduce its journaled token prefix
    bitwise (greedy decode + per-token-deterministic packed bits make the
    replay exact by construction — a mismatch means recovery restored the
    wrong bytes). Returns the number of verified prefixes."""
    verified = 0
    for rid in sorted(plan.emitted):
        if rid in plan.completed or reports[rid]["status"] != "ok":
            continue
        exp = plan.expected_prefix(rid)
        if not exp:
            continue
        got = [int(t) for t in jax.device_get(results[rid])][: len(exp)]
        if got != exp:
            raise guard_mod.RecoveryError(
                f"request {rid}: re-served output {got} contradicts its "
                f"journaled token prefix {exp} — recovered state failed "
                "replay verification")
        verified += 1
    return verified


def serve_requests(
    cfg: ArchConfig,
    params: dict,
    requests: Sequence[jnp.ndarray],   # per-request prompt token arrays (T,)
    ctx: ModelCtx,
    serve_cfg: ServeConfig = ServeConfig(),
    *,
    slots: int = 4,
    stats: Optional[dict] = None,      # filled with scheduler counters
    injector=None,                     # repro.runtime.faults.FaultInjector
    resume: bool = False,              # recover from serve_cfg.journal_dir
) -> list:
    """Continuous-batching scheduler: serve ``requests`` through a fixed
    number of decode ``slots``.

    Each request is prefilled individually (its true prompt length — no
    cross-request padding) and admitted into a free slot with its own cache
    position; the shared decode batch advances via the scan body with
    per-slot positions and done masks. When a request exhausts its budget
    (or hits eos) its slot is freed and the next queued request admitted.
    Per-request results are bit-identical to serving each request alone:
    batch elements never mix, and invalid cache tail slots are masked by
    the per-slot length.

    With ``serve_cfg.kv_pages > 0`` (hif4 KV only) the whole-slot cache is
    replaced by the paged pool scheduler (:func:`_serve_requests_paged`):
    admission is by page availability instead of slot count, identical
    prompt-prefix pages are shared copy-on-write, and pool exhaustion
    preempts the youngest sequence instead of rejecting the queue — see the
    docs/EXECUTION.md admission matrix.

    Transformer families only (the per-slot position clock lives in the KV
    cache); returns a list of (max_new_tokens,) int32 arrays, one per
    request, in submission order.

    With ``serve_cfg.guard`` set (:class:`repro.runtime.guard.GuardConfig`)
    each request becomes its own fault domain: the decode scan carries the
    NaN/Inf sentinel, packed KV is audited per chunk, and a faulty slot is
    quarantined — evicted, retried once on the qdq/bf16 fallback path —
    while the rest of the batch continues bitwise-unaffected. Per-request
    outcomes land in ``stats["reports"]`` (status vocabulary in
    docs/EXECUTION.md §Failure semantics). ``injector`` is the
    fault-injection hook (:class:`repro.runtime.faults.FaultInjector`);
    tests and ``--inject-fault`` use it to prove every guard fires.

    With ``serve_cfg.journal_dir`` set, every request lifecycle event is
    written through a crc32-framed write-ahead journal (fsync-batched per
    decode chunk) and — on the paged scheduler — the pool is periodically
    checkpointed (``serve_cfg.checkpoint_every`` chunks). After a process
    crash, calling again with ``resume=True`` rebuilds state from
    checkpoint-plus-journal-tail (:mod:`repro.runtime.journal`): finished
    requests' results are injected, checkpoint-covered residents restore
    their page bytes, everything else re-prefills from its prompt — and
    the resumed greedy outputs are verified bitwise against the journaled
    token prefixes (docs/EXECUTION.md §Crash recovery).

    The paged scheduler marks its phases with profiler annotations
    (``serve.setup``, ``serve.admit``, ``serve.prefill``, ``serve.pages``,
    ``serve.decode``, ``serve.account``, ``serve.finish``; about a
    microsecond of host time each while no profiler trace records)
    and adds to ``stats`` the counters ``prefills``, ``prefill_tokens``,
    ``decode_chunks`` and ``decode_steps``, ``attn_pages_walked`` (the
    page-table entries decode attention reads: per slot and decode step,
    those below ``ceil(length / page_tokens)``) and ``attn_pages_table``
    (the table entries there are), ``decode_slot_steps_live`` (the
    slot-steps of decode in which a slot holds a request still inside its
    token budget) and ``decode_kv_tokens`` (the cached tokens those
    slot-steps attend over), and, per request id,
    ``request_times``: ``admitted``, ``first_token`` and ``finished`` in
    seconds since this call began (``time.perf_counter``), and the
    ``tokens`` its result holds.
    """
    t_enter = time.perf_counter()
    assert cfg.family in ("dense", "vlm", "moe"), (
        f"continuous batching supports KV-cache families, got {cfg.family!r}"
    )
    sctx = serving_ctx(ctx)
    params = prepare_params_for_serving(params, cfg, ctx.plan or ctx.quant)
    warned: set = set()                # KVFallbackWarning dedup, per call
    kv_fmt = resolve_kv_format(cfg, ctx.quant, serve_cfg, verbose=True,
                               warned=warned)
    # Resolve the jitted entry points ONCE per serve call — admission runs
    # between every decode chunk, and a dict probe per admitted request
    # (plus the jit wrapper construction on a miss) is avoidable
    # scheduler overhead.
    prefill = _jit_prefill(cfg, sctx)
    quantize = _jit_quantize_kv(cfg) if kv_fmt == "hif4" else None

    if serve_cfg.kv_pages:
        assert kv_fmt == "hif4", (
            "the paged KV pool stores packed HiF4 pages; bf16 serving (or a "
            "family fallback) must use the whole-slot scheduler")
        return _serve_requests_paged(
            cfg, params, requests, sctx, serve_cfg, ctx=ctx,
            slots=slots, prefill=prefill, quantize=quantize, stats=stats,
            injector=injector, resume=resume, t_enter=t_enter)

    guard = serve_cfg.guard
    budget = serve_cfg.max_new_tokens
    max_prompt = max(int(r.shape[-1]) for r in requests)
    cap = serve_cfg.cache_capacity or max_prompt + budget
    B = min(slots, len(requests))
    chunk = serve_cfg.decode_chunk or max(1, budget // 4)
    journal, plan = _open_journal(serve_cfg, requests, resume=resume,
                                  kind="slots", chunk=chunk)

    # Shared decode state: zero cache at full capacity, per-slot positions.
    cache = lm.init_cache(cfg, B, cap, kv_format=kv_fmt)
    cache["pos"] = jnp.zeros((B,), jnp.int32)
    token = jnp.zeros((B,), jnp.int32)
    done = jnp.ones((B,), bool)                  # empty slots count as done

    queue = list(range(len(requests)))
    slot_req = [None] * B                        # request id per slot
    slot_toks: list[list] = [[] for _ in range(B)]
    admit_time = [0.0] * B
    results: list = [None] * len(requests)
    reports = {rid: guard_mod.new_report() for rid in range(len(requests))}
    if plan is not None:
        _inject_completed(plan, queue, results, reports)
    max_concurrent = 0
    chunk_idx = 0

    def jlog_done(rid):
        if journal is not None:
            rep = reports[rid]
            journal.append("done", rid=rid, status=rep["status"],
                           detail=rep["detail"], retries=rep["retries"],
                           toks=[int(t) for t in jax.device_get(results[rid])])

    def admit(b: int, cache, token):
        rid = queue.pop(0)
        prompt = jnp.asarray(requests[rid], jnp.int32).reshape(1, -1)
        logits, slot_cache = prefill(params, {"tokens": prompt})
        if quantize is not None:
            slot_cache = quantize(slot_cache)
        slot_cache = lm.pad_cache(slot_cache, cfg, cap)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
        cache, token = _insert_slot_jit(cache, slot_cache, token, first, b)
        slot_req[b] = rid
        slot_toks[b] = [int(first)]
        admit_time[b] = time.monotonic()
        if journal is not None:
            journal.append("admitted", rid=rid, src="prefill",
                           toks=slot_toks[b])
        return cache, token

    guarded = guard is not None and guard.nan_sentinel
    if guarded:
        gstep = _jit_decode_scan_guarded(cfg, sctx, chunk, serve_cfg.eos_id)
        zeros_bad = jnp.zeros((B,), bool)     # fresh carry, hoisted: the
        #                                       scan never donates it
    else:
        step = _jit_decode_scan(cfg, sctx, chunk, serve_cfg.eos_id)

    def retire(b: int):
        rid = slot_req[b]
        results[rid] = _finalize_result(slot_toks[b], budget,
                                        serve_cfg.eos_id)
        slot_req[b] = None
        jlog_done(rid)

    def quarantine(b: int, reason: str):
        """Evict the poisoned slot only; its neighbours' state is
        untouched (batch rows never mix), so the rest of the batch
        continues bitwise-unaffected. The slot's cache region needs no
        scrub: admission overwrites the full capacity slab."""
        rid = slot_req[b]
        slot_req[b] = None
        slot_toks[b] = []
        if guard.retry_fallback:
            res, healthy = _retry_fallback(cfg, params, requests[rid], ctx,
                                           serve_cfg)
            reports[rid]["retries"] += 1
            if healthy:
                results[rid] = res
                reports[rid].update(
                    status="retried",
                    detail=f"{reason}; re-served solo on the qdq/bf16 "
                           "fallback path")
                jlog_done(rid)
                return
        results[rid] = _failed_result(budget, serve_cfg.eos_id)
        reports[rid].update(status="quarantined", detail=reason)
        jlog_done(rid)

    while queue or any(r is not None for r in slot_req):
        # Admission: fill every free slot before the next decode segment.
        for b in range(B):
            if slot_req[b] is None and queue:
                cache, token = admit(b, cache, token)
                done = done.at[b].set(
                    serve_cfg.eos_id is not None
                    and slot_toks[b][0] == serve_cfg.eos_id
                )
                if injector is not None:
                    injector.crash_point("after_admit", chunk_idx=chunk_idx,
                                         rid=slot_req[b], journal=journal)
        max_concurrent = max(max_concurrent,
                             sum(r is not None for r in slot_req))
        if injector is not None:
            cache["kv"] = injector.poison_cache(cache["kv"], slot_req,
                                                chunk_idx)
        active = jnp.asarray([r is not None for r in slot_req])
        metav = None
        if guarded:
            toks, token, cache, done, flags = gstep(
                params, token, cache, done | ~active, zeros_bad)
            host_toks, flagsv = jax.device_get((toks, flags))
            badv = flagsv[:B].astype(bool)
            if guard.meta_audit and kv_fmt == "hif4":
                metav = flagsv[B:]
        else:
            toks, token, cache, done = step(params, token, cache,
                                            done | ~active)
            badv = None
            if (guard is not None and guard.meta_audit
                    and kv_fmt == "hif4"):
                metav = jax.device_get(
                    guard_mod.slot_meta_nan_jit(cache["kv"]))
            host_toks = jax.device_get(toks)
        chunk_idx += 1
        if journal is not None:
            journal.append("chunk", idx=chunk_idx - 1, emitted={
                slot_req[b]: [int(t) for t in host_toks[b]]
                for b in range(B) if slot_req[b] is not None})
        for b in range(B):
            if slot_req[b] is None:
                continue
            reason = None
            if badv is not None and bool(badv[b]):
                reason = "nan_logits: non-finite logits in the decode scan"
            if metav is not None and int(metav[b]):
                reason = (f"meta_nan: {int(metav[b])} E6M2 NaN sentinel(s) "
                          "in the slot's packed KV")
            if reason is not None:
                done = done.at[b].set(True)
                quarantine(b, reason)
                continue
            slot_toks[b].extend(int(t) for t in host_toks[b])
            if (guard is not None and guard.deadline_s is not None
                    and time.monotonic() - admit_time[b] > guard.deadline_s):
                rid = slot_req[b]
                results[rid] = _finalize_partial(slot_toks[b], budget,
                                                 serve_cfg.eos_id)
                reports[rid].update(
                    status="timeout",
                    detail=f"deadline: exceeded {guard.deadline_s}s")
                slot_req[b] = None
                slot_toks[b] = []
                done = done.at[b].set(True)
                jlog_done(rid)
                continue
            finished = len(slot_toks[b]) >= budget or (
                serve_cfg.eos_id is not None
                and serve_cfg.eos_id in slot_toks[b]
            )
            if finished:
                retire(b)
        if journal is not None:
            journal.commit()
        if injector is not None:
            injector.crash_point("mid_decode", chunk_idx=chunk_idx - 1,
                                 journal=journal)
    if journal is not None:
        journal.close()
    if plan is not None:
        verified = _verify_recovery(plan, results, reports)
        if stats is not None:
            stats["recovery"] = dict(plan.report(), verified=verified)
    if stats is not None:
        stats.update(scheduler="slots", max_concurrent=max_concurrent,
                     preemptions=0, shared_page_hits=0, evictions=0,
                     reports=reports,
                     **_report_counts(reports))
    return results


def _report_counts(reports: dict) -> dict:
    counts = {status: 0 for status in guard_mod.STATUS_NAMES}
    for rep in reports.values():
        counts[rep["status"]] += 1
    return {"quarantined": counts["quarantined"],
            "retried": counts["retried"],
            "rejected": counts["rejected"],
            "timeouts": counts["timeout"]}


# ---------------------------------------------------------------------------
# Paged continuous batching: page-pool admission + COW prefix sharing
# ---------------------------------------------------------------------------


def _pool_gather(pool, ids):
    return {"k": kvcache.gather_pages(pool["k"], ids),
            "v": kvcache.gather_pages(pool["v"], ids)}


_pool_gather_jit = jax.jit(_pool_gather)


def _pool_scatter(pool, pages_k, pages_v, src, dst):
    """Write logical pages ``src`` of the (L, n, F, P) blocks into pool
    pages ``dst`` (K and V together, pool donated)."""

    def sel(t):
        return {key: jnp.take(a, src, axis=1) for key, a in t.items()}

    return {"k": kvcache.scatter_pages(pool["k"], sel(pages_k), dst),
            "v": kvcache.scatter_pages(pool["v"], sel(pages_v), dst)}


_pool_scatter_jit = jax.jit(_pool_scatter, donate_argnums=(0,))


def _pool_copy(pool, src, dst):
    return {"k": kvcache.copy_page(pool["k"], src, dst),
            "v": kvcache.copy_page(pool["v"], src, dst)}


_pool_copy_jit = jax.jit(_pool_copy, donate_argnums=(0,))


def _pool_scrub(pool, ids):
    """Zero the freed pages of a quarantined slot so stale corruption
    cannot leak into the page's next owner."""
    return {"k": kvcache.scrub_pages(pool["k"], ids),
            "v": kvcache.scrub_pages(pool["v"], ids)}


_pool_scrub_jit = jax.jit(_pool_scrub, donate_argnums=(0,))


def _page_prefix_equal(pool, pid, page_k, page_v, count):
    """True iff pool page ``pid`` matches the candidate page blocks
    (L, F, P) byte-for-byte on the first ``count`` token columns — the
    share-time verification that makes prefix sharing exact by
    construction rather than by trust in the hash."""
    cols = jnp.arange(page_k["meta"].shape[-1]) < count

    def eq(pool_t, page):
        oks = [jnp.all(jnp.where(cols, pool_t[key][:, pid] == page[key],
                                 True))
               for key in ("codes", "meta", "tail")]
        return jnp.all(jnp.stack(oks))

    return jnp.logical_and(eq(pool["k"], page_k), eq(pool["v"], page_v))


_page_equal_jit = jax.jit(_page_prefix_equal)


def _serve_requests_paged(
    cfg: ArchConfig,
    params: dict,
    requests: Sequence[jnp.ndarray],
    sctx: ModelCtx,
    serve_cfg: ServeConfig,
    *,
    ctx: ModelCtx,
    slots: int,
    prefill,
    quantize,
    stats: Optional[dict] = None,
    injector=None,
    resume: bool = False,
    t_enter: float,
) -> list:
    """Page-pool continuous batching (the :func:`serve_requests` backend
    for ``serve_cfg.kv_pages > 0``).

    The whole-slot contiguous cache is replaced by a fixed pool of
    ``kv_pages`` HiF4 pages of ``kv_page_tokens`` tokens each
    (repro.core.kvcache); per-slot page tables map logical page indices to
    pool pages and the decode step streams KV tiles through the table
    (repro.kernels.fused_attention paged grid). Scheduling:

    * **admission** — a queued request is admitted when its PROMPT pages
      fit (prompt pages shared with resident requests do not count), not
      when a whole max-capacity slot is free: memory is committed
      page-by-page as sequences actually grow;
    * **prefix sharing** — prompt pages whose cumulative token key hits
      the full-page hash (or whose tail matches a live partial page) are
      shared by refcount after byte-for-byte verification; a sharer that
      must append into a shared page copies it first (copy-on-write), so
      sharing never changes any request's bytes;
    * **eviction / preemption** — retired requests' full pages park in an
      LRU cache (free prefix hits for followers) and are evicted when the
      pool runs dry; if the pool is dry with no evictable page, the
      YOUNGEST resident request is preempted: its page bytes are
      snapshotted to host, its pages freed, and it re-enters the queue
      front to be restored verbatim later (decode-token KV cannot be
      re-prefilled, so bytes — not tokens — are what's saved).

    Per-request outputs remain bit-identical to solo serving with the same
    page-size KV tiling: pages partition the token axis exactly like the
    kernel's KV tiles, appends land in exclusively-owned pages, and fully
    masked tiles are exact no-ops in the online softmax.

    **Fault domains.** Preemption snapshots always carry an integrity
    fingerprint, verified before re-admission ever scatters bytes back
    into the pool; a corrupt snapshot is dropped and the request re-queued
    from its prompt (greedy decode is deterministic, so the recomputed
    result is exact — status ``retried``). With ``serve_cfg.guard`` set,
    the scan carries the NaN sentinel, every chunk audits live pages
    (0xFF meta counts always; per-page byte-sum checksums against the
    values recorded after the previous chunk, skipping pages the
    scheduler legitimately wrote in between), faulty slots are
    quarantined with their freed pages scrubbed, and pool starvation
    becomes a bounded-retry ``rejected`` status instead of an exception.
    The one audit blind spot: corruption landing in a page during the
    same chunk the scheduler wrote it is invisible to the checksum until
    the next chunk — the 0xFF meta and NaN sentinels still cover it.
    """
    P = serve_cfg.kv_page_tokens
    budget = serve_cfg.max_new_tokens
    eos = serve_cfg.eos_id
    n_req = len(requests)
    with jax.profiler.TraceAnnotation("serve.setup"):
        prompts = [jax.device_get(jnp.asarray(r, jnp.int32)).ravel().tolist()
                   for r in requests]
        max_prompt = max(len(p) for p in prompts)
        cap = serve_cfg.cache_capacity or max_prompt + budget
        for p_toks in prompts:
            assert len(p_toks) + budget <= cap, (
                f"prompt {len(p_toks)} + budget {budget} exceeds capacity "
                f"{cap}")
        maxp = kvcache.pages_for_tokens(cap, P)
        pool = kvcache.PagePool(serve_cfg.kv_pages, P)
        assert maxp <= pool.usable_pages, (
            f"one max-length sequence needs {maxp} pages but the pool has "
            f"only {pool.usable_pages} usable (kv_pages={serve_cfg.kv_pages} "
            f"minus the scratch page)")
        B = min(slots, n_req)

        cache = lm.init_paged_cache(cfg, B, serve_cfg.kv_pages, P, maxp)
        token = jnp.zeros((B,), jnp.int32)
        done = jnp.ones((B,), bool)

        guard = serve_cfg.guard
        chunk = serve_cfg.decode_chunk or max(1, budget // 4)
        guarded = guard is not None and guard.nan_sentinel
        if guarded:
            gstep = _jit_decode_scan_guarded(cfg, sctx, chunk, eos)
            zeros_bad = jnp.zeros((B,), bool)  # fresh carry, hoisted: the
            #                                    scan never donates it
        else:
            step = _jit_decode_scan(cfg, sctx, chunk, eos)
        if injector is not None:
            injector.steal_pages(pool)

        journal, plan = _open_journal(
            serve_cfg, requests, resume=resume, kind="paged", chunk=chunk,
            kv_pages=serve_cfg.kv_pages, page_tokens=P)

    queue = list(range(n_req))
    suspended: dict = {}               # rid -> preemption byte snapshot
    slot_req = [None] * B
    slot_toks: list[list] = [[] for _ in range(B)]
    slot_written: list[list] = [[] for _ in range(B)]  # tokens whose KV is
    #                                                    resident, in order
    slot_pages: list[list] = [[] for _ in range(B)]    # pool ids, logical
    admit_clock = [0] * B
    admit_time = [0.0] * B             # time.perf_counter after admission
    request_times: dict = {}           # rid -> seconds since t_enter
    t_host = 0.0                       # when the last chunk's tokens landed
    results: list = [None] * n_req
    reports = {rid: guard_mod.new_report() for rid in range(n_req)}
    if plan is not None:
        _inject_completed(plan, queue, results, reports)
        for rid, snap in plan.suspended.items():
            # checkpointed residents re-enter through the preemption
            # snapshot path; written is derived from the scheduler
            # invariant written == prompt + toks[:-1]
            suspended[rid] = dict(
                snap, toks=list(snap["toks"]),
                written=prompts[rid] + list(snap["toks"])[:-1])

    def jlog_done(rid):
        if journal is not None:
            rep = reports[rid]
            journal.append("done", rid=rid, status=rep["status"],
                           detail=rep["detail"], retries=rep["retries"],
                           toks=[int(t) for t in jax.device_get(results[rid])])
    admission_attempts: dict = {}      # rid -> failed empty-pool admissions
    clock = 0
    preempt_count = 0
    max_concurrent = 0
    peak_live = 0
    snapshot_drops = 0
    chunk_idx = 0
    prefills = prefill_tokens = 0
    slot_pos = [0] * B                 # host copy of cache["pos"]
    pages_walked = 0                   # table entries decode attention read
    slot_steps_live = kv_tokens = 0    # decode slot-steps a request keeps
    # Page-checksum audit state: ``recorded`` maps pool page id -> the
    # byte-sum observed after the last chunk; ``dirty`` collects pages the
    # scheduler itself wrote since then (admission scatters, COW copies,
    # horizon allocs, chunk appends) — those are re-recorded, not compared.
    recorded: dict = {}
    dirty: set = set()

    def set_table_row(b, pids):
        row = jnp.zeros((maxp,), jnp.int32)
        if pids:
            row = row.at[: len(pids)].set(jnp.asarray(pids, jnp.int32))
        cache["pages"] = cache["pages"].at[b].set(row)

    def refresh_metadata(b):
        """Index slot ``b``'s OWNED pages for sharing: completed pages by
        their cumulative token key, the live tail page in the partial
        registry. The last table entry (logical page maxp-1) is never
        indexed: over-emission inside a request's final chunk clamps into
        it (masked, discarded tokens), so its bytes are not trusted."""
        rid = slot_req[b]
        written = slot_written[b]
        for j, pid in enumerate(slot_pages[b]):
            if j == maxp - 1 or pool.owner.get(pid) != rid:
                continue
            seg = written[j * P:(j + 1) * P]
            if len(seg) == P:
                pool.register_full(pid, tuple(written[: (j + 1) * P]))
            elif seg:
                pool.register_partial(pid, tuple(written[: j * P]), seg)

    def pick_victim():
        live = [b for b in range(B) if slot_req[b] is not None]
        if not live:
            return None
        return max(live, key=lambda b: admit_clock[b])

    def preempt(b):
        nonlocal preempt_count
        rid = slot_req[b]
        ids = jnp.asarray(slot_pages[b], jnp.int32)
        snap = jax.device_get(_pool_gather_jit(cache["kv"], ids))
        # fingerprint BEFORE the injector hook: the stamp models the bytes
        # as they left the device; host-side corruption after that is what
        # re-admission must catch
        crc = guard_mod.snapshot_fingerprint(snap)
        if injector is not None:
            snap = injector.poison_snapshot(snap, rid)
        suspended[rid] = {
            "pages": snap,                      # page BYTES, not tokens
            "crc32": crc,
            "token": int(jax.device_get(token[b])),
            "toks": slot_toks[b],
            "written": slot_written[b],
        }
        for pid in slot_pages[b]:
            pool.release(pid)
        slot_pages[b] = []
        slot_req[b] = None
        slot_toks[b] = []
        slot_written[b] = []
        set_table_row(b, [])                    # writes -> scratch page 0
        queue.insert(0, rid)
        preempt_count += 1
        if journal is not None:
            # no replay state: the snapshot lives only in process memory
            journal.append("preempted", rid=rid)

    def alloc_page(rid, requester_slot):
        """Allocate, preempting youngest-first when the pool is dry.
        Returns None when the requester itself was the victim."""
        while True:
            pid = pool.alloc(owner=rid)
            if pid is not None:
                return pid
            victim = pick_victim()
            if victim is None:
                raise PoolExhaustedError(
                    f"KV page pool exhausted: {pool.usable_pages} usable "
                    f"pages cannot hold even one resident sequence")
            preempt(victim)
            if victim == requester_slot:
                return None

    def try_admit(b, rid):
        nonlocal token, done, clock, snapshot_drops, prefills, prefill_tokens
        t_try = time.perf_counter()
        snap = suspended.get(rid)
        if snap is not None and not guard_mod.verify_snapshot(snap):
            # a truncated/flipped snapshot must never reach the pool:
            # drop it and fall through to the fresh-prompt path — greedy
            # decode is deterministic, so recomputing from the prompt
            # reproduces the request's exact result
            del suspended[rid]
            snapshot_drops += 1
            reports[rid]["retries"] += 1
            reports[rid].update(
                status="retried",
                detail="snapshot_integrity: preemption snapshot failed its "
                       "fingerprint at re-admission; re-queued from the "
                       "prompt")
            snap = None
        if snap is not None:
            n = snap["pages"]["k"]["meta"].shape[1]
            if pool.available() < n:
                return False
            pids = [pool.alloc(owner=rid) for _ in range(n)]
            cache["kv"] = _pool_scatter_jit(
                cache["kv"], snap["pages"]["k"], snap["pages"]["v"],
                jnp.arange(n, dtype=jnp.int32),
                jnp.asarray(pids, jnp.int32))
            dirty.update(pids)
            del suspended[rid]
            token = token.at[b].set(snap["token"])
            cache["pos"] = cache["pos"].at[b].set(len(snap["written"]))
            slot_pos[b] = len(snap["written"])
            done = done.at[b].set(False)
            slot_toks[b] = snap["toks"]
            slot_written[b] = snap["written"]
        else:
            toks = prompts[rid]
            n_tok = len(toks)
            prefills += 1
            prefill_tokens += n_tok
            with jax.profiler.TraceAnnotation("serve.prefill", rid=rid,
                                              tokens=n_tok):
                logits, slot_cache = prefill(params, {
                    "tokens": jnp.asarray(toks, jnp.int32).reshape(1, -1)})
                slot_cache = quantize(slot_cache)
                kp = kvcache.split_pages(slot_cache["kv"]["k"], P)
                vp = kvcache.split_pages(slot_cache["kv"]["v"], P)
                n_pg = kvcache.pages_for_tokens(n_tok, P)
                share = [None] * n_pg
                if serve_cfg.prefix_sharing:
                    for j in range(n_pg):
                        seg = toks[j * P:(j + 1) * P]
                        if len(seg) == P:
                            cand = pool.lookup_full(
                                tuple(toks[: (j + 1) * P]))
                        else:
                            cand = pool.lookup_partial(
                                tuple(toks[: j * P]), seg)
                        if cand is None:
                            continue
                        page_k = {key: a[:, j] for key, a in kp.items()}
                        page_v = {key: a[:, j] for key, a in vp.items()}
                        if bool(jax.device_get(_page_equal_jit(
                                cache["kv"], cand, page_k, page_v, len(seg)))):
                            share[j] = cand
                n_new = sum(1 for s in share if s is None)
                n_revive = sum(1 for s in share
                               if s is not None and s in pool.cached)
                if pool.available() < n_new + n_revive:
                    return False
                # retain every shared page BEFORE allocating: alloc may evict
                # from the LRU cache, and a not-yet-retained candidate must
                # not be its victim
                for s in share:
                    if s is not None:
                        pool.retain(s)
                        pool.shared_hits += 1
                pids = []
                own_src, own_dst = [], []
                for j in range(n_pg):
                    if share[j] is not None:
                        pids.append(share[j])
                    else:
                        pid = pool.alloc(owner=rid)
                        own_src.append(j)
                        own_dst.append(pid)
                        pids.append(pid)
                if own_dst:
                    cache["kv"] = _pool_scatter_jit(
                        cache["kv"], kp, vp,
                        jnp.asarray(own_src, jnp.int32),
                        jnp.asarray(own_dst, jnp.int32))
                    dirty.update(own_dst)
                first = int(jax.device_get(jnp.argmax(logits, axis=-1))[0])
            token = token.at[b].set(first)
            cache["pos"] = cache["pos"].at[b].set(n_tok)
            slot_pos[b] = n_tok
            done = done.at[b].set(eos is not None and first == eos)
            slot_toks[b] = [first]
            slot_written[b] = list(toks)
        slot_req[b] = rid
        slot_pages[b] = pids
        set_table_row(b, pids)
        clock += 1
        admit_clock[b] = clock
        admit_time[b] = time.perf_counter()
        times = request_times.setdefault(rid, {"admitted": t_try - t_enter})
        times.setdefault("first_token", admit_time[b] - t_enter)
        refresh_metadata(b)
        if journal is not None:
            # an admitted record RESETS the rid's journaled emission to
            # its cumulative toks — uniform for fresh prefills ([first]),
            # snapshot restores, and checkpoint-recovered residents
            journal.append("admitted", rid=rid,
                           src="snapshot" if snap is not None else "prefill",
                           toks=[int(t) for t in slot_toks[b]])
        return True

    def provision(b):
        """Pre-chunk page work for slot ``b``: copy-on-write the page its
        next append lands in if it is shared, then allocate pages through
        the chunk horizon. Returns False if ``b`` itself got preempted."""
        rid = slot_req[b]
        pos_b = len(slot_written[b])
        cur = pos_b // P
        if cur < len(slot_pages[b]):
            pid = slot_pages[b][cur]
            if pool.owner.get(pid) != rid:
                if pool.ref.get(pid, 0) > 1:
                    new = alloc_page(rid, b)
                    if new is None:
                        return False
                    cache["kv"] = _pool_copy_jit(cache["kv"], pid, new)
                    dirty.add(new)
                    pool.release(pid)
                    slot_pages[b][cur] = new
                    cache["pages"] = cache["pages"].at[b, cur].set(new)
                else:
                    pool.owner[pid] = rid      # sole holder adopts in place
        last = min((pos_b + chunk - 1) // P, maxp - 1)
        for j in range(len(slot_pages[b]), last + 1):
            pid = alloc_page(rid, b)
            if pid is None:
                return False
            dirty.add(pid)
            slot_pages[b].append(pid)
            cache["pages"] = cache["pages"].at[b, j].set(pid)
        return True

    def release_slot(b):
        for pid in slot_pages[b]:
            pool.release(pid)                  # hashed full pages park LRU
        slot_pages[b] = []
        slot_req[b] = None
        slot_toks[b] = []
        slot_written[b] = []
        set_table_row(b, [])

    def finished_at(rid, tokens):
        request_times[rid].update(finished=t_host - t_enter, tokens=tokens)

    def retire(b):
        rid = slot_req[b]
        results[rid] = _finalize_result(slot_toks[b], budget, eos)
        toks = slot_toks[b][:budget]
        finished_at(rid, toks.index(eos) + 1 if eos in toks else len(toks))
        release_slot(b)
        jlog_done(rid)

    def quarantine(b, reason):
        """Evict the poisoned slot only: drop its pool refs, scrub the
        pages that actually freed (shared pages survive for their other
        holders, whose own audits will catch them if THEY are the
        corrupted bytes), and retry the request once on the qdq/bf16
        fallback path. Neighbouring slots' pages and scan state are
        untouched — they continue bitwise-unaffected."""
        nonlocal done
        rid = slot_req[b]
        freed = []
        for pid in slot_pages[b]:
            pool.release(pid, keep_cached=False)
            if pid not in pool.ref:
                freed.append(pid)
                recorded.pop(pid, None)
        if freed:
            cache["kv"] = _pool_scrub_jit(cache["kv"],
                                          jnp.asarray(freed, jnp.int32))
            dirty.update(freed)
        slot_pages[b] = []
        slot_req[b] = None
        slot_toks[b] = []
        slot_written[b] = []
        set_table_row(b, [])
        done = done.at[b].set(True)
        if guard.retry_fallback:
            res, healthy = _retry_fallback(cfg, params, requests[rid], ctx,
                                           serve_cfg)
            reports[rid]["retries"] += 1
            if healthy:
                results[rid] = res
                reports[rid].update(
                    status="retried",
                    detail=f"{reason}; re-served solo on the qdq/bf16 "
                           "fallback path")
                jlog_done(rid)
                return
        results[rid] = _failed_result(budget, eos)
        reports[rid].update(status="quarantined", detail=reason)
        jlog_done(rid)

    def reject(rid, detail):
        queue.remove(rid)
        suspended.pop(rid, None)
        results[rid] = _failed_result(budget, eos)
        reports[rid].update(status="rejected", detail=detail)
        jlog_done(rid)

    while queue or any(r is not None for r in slot_req):
        # Admission: FIFO, page-fit driven — stop at the first request
        # whose prompt pages do not fit (no skip-ahead; completion order
        # stays deterministic).
        while queue:
            free_b = next((b for b in range(B) if slot_req[b] is None), None)
            if free_b is None:
                break
            head = queue[0]
            with jax.profiler.TraceAnnotation(
                    "serve.admit", rid=head,
                    src="snapshot" if head in suspended else "prefill"):
                admitted = try_admit(free_b, head)
            if not admitted:
                break
            queue.pop(0)
            if injector is not None:
                injector.crash_point("after_admit", chunk_idx=chunk_idx,
                                     rid=head, journal=journal)
        if not any(r is not None for r in slot_req):
            # nothing resident AND the queue head still does not fit: with
            # no guard this is fatal; with one it becomes bounded
            # retry+backoff and then a per-request ``rejected`` status
            rid = queue[0]
            msg = (f"request {rid!r} cannot be admitted into an empty "
                   f"pool ({pool.usable_pages} usable pages, "
                   f"{pool.available()} allocatable)")
            if guard is None:
                raise PoolExhaustedError(msg)
            attempts = admission_attempts.get(rid, 0) + 1
            admission_attempts[rid] = attempts
            if attempts <= guard.max_admission_retries:
                reports[rid]["retries"] += 1
                if guard.admission_backoff_s:
                    time.sleep(guard.admission_backoff_s
                               * 2 ** (attempts - 1))
                continue
            reject(rid, "pool_exhausted: " + msg + " after "
                   f"{attempts - 1} retries")
            continue
        with jax.profiler.TraceAnnotation(
                "serve.pages", slots=sum(r is not None for r in slot_req)):
            for b in range(B):
                if slot_req[b] is not None:    # provision may preempt
                    provision(b)
        # counted AFTER provisioning: sequences actually decoding this
        # chunk, not admissions that provisioning preempted right back out
        max_concurrent = max(max_concurrent,
                             sum(r is not None for r in slot_req))
        peak_live = max(peak_live, pool.live_pages())
        if injector is not None:
            cache["kv"] = injector.poison_pool(cache["kv"], pool, slot_req,
                                               slot_pages, chunk_idx)
        with jax.profiler.TraceAnnotation("serve.decode", chunk=chunk_idx):
            active = jnp.asarray([r is not None for r in slot_req])
            if guarded:
                toks, token, cache, done, flags = gstep(
                    params, token, cache, done | ~active, zeros_bad)
                host_toks, flagsv = jax.device_get((toks, flags))
                badv = flagsv[:B].astype(bool)
                pagemeta = flagsv[B:]          # per-pool-page 0xFF counts
            else:
                toks, token, cache, done = step(params, token, cache,
                                                done | ~active)
                badv = pagemeta = None
                host_toks = jax.device_get(toks)
        t_host = time.perf_counter()
        chunk_idx += 1
        with jax.profiler.TraceAnnotation("serve.account"):
            # 0) the pages decode attention walked: every slot's position
            #    advanced once a step, attending over pos + 1 tokens; and
            #    the steps of a request still inside its token budget,
            #    with the tokens those steps attend over
            for b in range(B):
                pages_walked += sum(
                    min(kvcache.pages_for_tokens(slot_pos[b] + s + 1, P),
                        maxp) for s in range(chunk))
                if slot_req[b] is not None:
                    live = max(0, min(chunk, budget - len(slot_toks[b])))
                    slot_steps_live += live
                    kv_tokens += live * (slot_pos[b] + 1) + live * (live - 1) // 2
                slot_pos[b] += chunk
            # 1) account this chunk's KV writes (and mark their pages dirty)
            chunk_emitted = {}
            for b in range(B):
                if slot_req[b] is None:
                    continue
                new = [int(t) for t in host_toks[b]]
                chunk_emitted[slot_req[b]] = new
                # this chunk wrote KV for the previously pending token plus
                # every emission except the newest (still pending)
                pending = slot_toks[b][-1]
                n0 = len(slot_written[b])
                slot_written[b].extend([pending] + new[:-1])
                slot_toks[b].extend(new)
                n1 = len(slot_written[b])
                for j in range(n0 // P, (n1 - 1) // P + 1):
                    # over-emission past the table clamps into the last entry
                    dirty.add(slot_pages[b][min(j, len(slot_pages[b]) - 1)])
            if journal is not None:
                journal.append("chunk", idx=chunk_idx - 1,
                               emitted=chunk_emitted)
            # 2) audit live pages BEFORE retiring anything, so a final-chunk
            #    fault cannot slip out with the request. The per-page 0xFF
            #    counts come fused out of the guarded scan; only the checksum
            #    audit needs a second (sums-only) reduction.
            faulty = {}
            if (guard is not None and guard.meta_audit and pagemeta is None):
                pagemeta = jax.device_get(
                    guard_mod.slot_meta_nan_jit(cache["kv"]))
            sums = None
            if guard is not None and guard.page_checksums:
                sums = jax.device_get(
                    guard_mod.pool_page_sums_jit(cache["kv"]))
            if guard is not None:
                for b in range(B):
                    if slot_req[b] is None:
                        continue
                    for pid in slot_pages[b]:
                        if (guard.meta_audit and pagemeta is not None
                                and int(pagemeta[pid])):
                            faulty[b] = (f"meta_nan: page {pid} carries "
                                         f"{int(pagemeta[pid])} E6M2 "
                                         "NaN sentinel(s)")
                            break
                        if (sums is not None and pid in recorded
                                and pid not in dirty
                                and int(sums[pid]) != recorded[pid]):
                            faulty[b] = (f"page_checksum: settled page {pid} "
                                         "changed outside the scheduler")
                            break
            for b in range(B):
                if (slot_req[b] is not None and b not in faulty
                        and badv is not None and bool(badv[b])):
                    faulty[b] = ("nan_logits: non-finite logits in the "
                                 "decode scan")
            for b, reason in faulty.items():
                quarantine(b, reason)
            # 3) re-record checksums for the pages still live, then settle
            if sums is not None:
                for b in range(B):
                    if slot_req[b] is None:
                        continue
                    for pid in slot_pages[b]:
                        recorded[pid] = int(sums[pid])
            dirty.clear()
            # 4) sharing metadata, deadlines, retirement
            for b in range(B):
                if slot_req[b] is None:
                    continue
                refresh_metadata(b)
                if (guard is not None and guard.deadline_s is not None
                        and time.perf_counter() - admit_time[b]
                        > guard.deadline_s):
                    rid = slot_req[b]
                    results[rid] = _finalize_partial(slot_toks[b], budget,
                                                     eos)
                    finished_at(rid, len(slot_toks[b][:budget]))
                    reports[rid].update(
                        status="timeout",
                        detail=f"deadline: exceeded {guard.deadline_s}s")
                    release_slot(b)
                    done = done.at[b].set(True)
                    jlog_done(rid)
                    continue
                finished = len(slot_toks[b]) >= budget or (
                    eos is not None and eos in slot_toks[b])
                if finished:
                    retire(b)
            # 5) durability: periodic pool checkpoint, then ONE fsync for the
            #    whole chunk's records
            if journal is not None:
                if (serve_cfg.checkpoint_every > 0
                        and chunk_idx % serve_cfg.checkpoint_every == 0
                        and any(r is not None for r in slot_req)):
                    from repro.runtime import journal as journal_mod
                    residents = {}
                    for b in range(B):
                        rid = slot_req[b]
                        if rid is None:
                            continue
                        ids = jnp.asarray(slot_pages[b], jnp.int32)
                        residents[rid] = {
                            "pages": jax.device_get(
                                _pool_gather_jit(cache["kv"], ids)),
                            "token": int(jax.device_get(token[b])),
                            "toks": [int(t) for t in slot_toks[b]],
                        }
                    fname, digest = journal_mod.save_pool_checkpoint(
                        serve_cfg.journal_dir, chunk_idx, residents)
                    if injector is not None:
                        # the .npz is on disk but its journal record is not:
                        # crash_during_checkpoint leaves an orphan recovery
                        # must ignore
                        injector.crash_point("during_checkpoint",
                                             chunk_idx=chunk_idx - 1,
                                             journal=journal)
                    journal.append(
                        "checkpoint", chunk=chunk_idx, file=fname,
                        sha256=digest,
                        residents={rid: {"token": ent["token"],
                                         "toks": ent["toks"]}
                                   for rid, ent in residents.items()})
                journal.commit()
        if injector is not None:
            injector.crash_point("mid_decode", chunk_idx=chunk_idx - 1,
                                 journal=journal)
    with jax.profiler.TraceAnnotation("serve.finish"):
        if journal is not None:
            journal.close()
        holders = {f"slot{b}": slot_pages[b] for b in range(B)
                   if slot_pages[b]}
        if injector is not None and injector.held_pages:
            holders["__fault_injector__"] = list(injector.held_pages)
        audit = pool.audit(holders=holders)
        if plan is not None:
            verified = _verify_recovery(plan, results, reports)
            if stats is not None:
                stats["recovery"] = dict(plan.report(), verified=verified)
        if stats is not None:
            stats.update(
                scheduler="paged", max_concurrent=max_concurrent,
                preemptions=preempt_count, evictions=pool.evictions,
                shared_page_hits=pool.shared_hits,
                peak_live_pages=peak_live,
                snapshot_drops=snapshot_drops, pool_audit=audit,
                reports=reports,
                prefills=prefills, prefill_tokens=prefill_tokens,
                decode_chunks=chunk_idx, decode_steps=chunk_idx * chunk,
                attn_pages_walked=pages_walked,
                attn_pages_table=chunk_idx * chunk * B * maxp,
                decode_slot_steps_live=slot_steps_live,
                decode_kv_tokens=kv_tokens,
                request_times=request_times,
                **_report_counts(reports))
    return results
