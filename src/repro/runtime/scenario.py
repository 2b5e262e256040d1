"""The probes of a serve cell: each family's prefill inputs, the analytic
dispatch probe, and a decode step's exact byte count. The serve launcher
and the calibrator take their prefill inputs from :func:`prefill_batch`;
``chip_smoke.py`` and ``tests/test_kv_format_matrix.py`` check routes with
:func:`probe_dispatch` (``tests/test_scenario.py`` pins it to the cache a
cell actually serves, since the serve jit cache rules out spying on a
run); ``bench/tests`` checks the benchmark's byte counts against
:func:`decode_step_bytes`.
"""
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine as qengine
from repro.core import kvcache
from repro.core.qlinear import PackedW
from repro.runtime.serve_loop import (
    ServeConfig,
    kv_format_fallback,
    packed_weight_bytes,
    resolve_kv_format,
)


def prefill_batch(cfg, batch: int, prompt_len: int, seed: int = 1) -> dict:
    """The prefill inputs each family's serve entry takes."""
    key = jax.random.PRNGKey(seed)
    if cfg.family == "audio":
        return {"frames": jax.random.normal(
            key, (batch, prompt_len, cfg.d_model), jnp.float32)}
    if cfg.embeds_input:
        return {"embeds": jax.random.normal(
            key, (batch, prompt_len, cfg.d_model), jnp.float32)}
    return {"tokens": jax.random.randint(
        key, (batch, prompt_len), 0, cfg.vocab)}


def _first_packed(params) -> Optional[PackedW]:
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedW)):
        if isinstance(leaf, PackedW):
            return leaf
    return None


def probe_dispatch(cfg, quant, serve_cfg: ServeConfig, serving_params,
                   *, paged: bool = False, batch: int = 1,
                   prompt_len: int = 16) -> dict:
    """Analytically resolve every dispatch decision this cell will hit.

    Pure probes — no serving, no tracing: ``resolve_kv_format`` for the
    cache format, :func:`repro.core.engine.attention_dispatch_info` on a
    geometry-exact packed probe cache (page-pool shaped for paged cells),
    and :func:`repro.core.engine.packed_dispatch_info` on the first real
    ``PackedW`` of the serving params (all block matmuls share the
    eligibility rule, which depends on impl/format, not shape).
    """
    a = cfg.attn
    resolved = resolve_kv_format(cfg, quant, serve_cfg)
    d = {
        "kv_format_resolved": resolved,
        "kv_format_fallback": kv_format_fallback(cfg, quant, serve_cfg),
        "paged": paged,
    }
    if cfg.family == "ssm" or a is None:
        d["attn"] = {"route": "none"}
    elif resolved != "hif4":
        d["attn"] = {"route": "dense"}
    elif paged:
        pool = kvcache.init_page_pool(cfg.n_layers, a.n_kv_heads, a.d_head,
                                      2, serve_cfg.kv_page_tokens)
        d["attn"] = qengine.attention_dispatch_info(
            quant, pool["k"], n_kv_heads=a.n_kv_heads, d_head=a.d_head,
            paged=True)
    else:
        probe = kvcache.to_kernel_layout(kvcache.quantize_kv(
            jnp.zeros((1, 8, a.n_kv_heads, a.d_head), jnp.bfloat16)))
        d["attn"] = qengine.attention_dispatch_info(
            quant, probe, n_kv_heads=a.n_kv_heads, d_head=a.d_head)
    w = _first_packed(serving_params)
    if w is None:
        # nothing packed (qdq plan / hybrid artifact): fake-quant dense dots
        d["matmul"] = {"route": "qdq", "execution": "qdq dense dot"}
    else:
        info = qengine.packed_dispatch_info(
            quant, w, decode_m=batch, prefill_m=batch * prompt_len)
        info["route"] = "fused" if info["fused"] else "dequant-dot"
        d["matmul"] = info
    return d


def _params_nbytes(params) -> int:
    """Resident weight bytes, PackedW-aware (exact 4.5-bit payload)."""
    packed_b, _ = packed_weight_bytes(params)
    dense_b = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedW))
        if not isinstance(leaf, PackedW))
    return packed_b + int(dense_b)


def decode_step_bytes(cfg, serving_params, cache, valid_len: int) -> dict:
    """EXACT HBM traffic floor of one decode step, from payload sizes.

    A decode step must stream every resident weight byte once (batch
    reuses them) plus the valid prefix of every attention cache entry:
    packed entries at their true 4.5-bit + meta + tail payload
    (``kvcache.packed_kv_nbytes``), dense entries at 2 B/value. The
    read-only cross cache is fully valid; recurrent ("layers") state is
    read AND written every step. This is the roofline numerator —
    dividing by measured stream bandwidth gives the predicted step time.
    """
    weight_bytes = _params_nbytes(serving_params)
    kv_bytes = 0
    for entry, frac_valid in (("kv", None), ("self", None), ("cross", 1.0)):
        kv = cache.get(entry)
        if kv is None:
            continue
        for tensor in (kv["k"], kv["v"]):
            if kvcache.is_packed_kv(tensor):
                total = kvcache.packed_kv_nbytes(tensor)
                cap = kvcache.seq_capacity(tensor)
            else:
                total = int(tensor.nbytes)
                cap = tensor.shape[2]          # (L, B, S, Hkv, Dh)
            frac = 1.0 if frac_valid else min(valid_len / cap, 1.0)
            kv_bytes += int(total * frac)
    state_bytes = 0
    if "layers" in cache:
        state_bytes = 2 * int(sum(                 # read + write
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache["layers"])))
    return {
        "weight_bytes": weight_bytes,
        "kv_bytes": kv_bytes,
        "state_bytes": state_bytes,
        "bytes_per_step": weight_bytes + kv_bytes + state_bytes,
    }
