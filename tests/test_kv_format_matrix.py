"""The family matrix: KV-format fallback loudness and the dispatch table.

Every registry arch x every requested KV format, asserting that the
``kv_format_fallback`` flag agrees with (a) the ``KVFallbackWarning``
the verbose resolve emits and (b) the format of the cache leaves
ACTUALLY served — built through ``serve_loop.build_decode_cache``, the
exact sequence ``serve`` decodes against. The enc-dec families
(audio/vlm) must serve packed HiF4 — including the whisper cross
(encoder) cache — with no fallback; only the SSM-state families
(ssm/hybrid) may narrow, and must say so.

The dispatch table pins, per family x impl x KV format x policy, which
attention route and which matmul route ``scenario.probe_dispatch``
resolves; ``tests/test_scenario.py`` pins the probe to what a cell
actually serves.
"""
import functools
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core import kvcache
from repro.core.policy import get_policy
from repro.core.qlinear import QuantConfig
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime import serve_loop
from repro.runtime.scenario import prefill_batch, probe_dispatch
from repro.runtime.serve_loop import (
    KVFallbackWarning,
    ServeConfig,
    build_decode_cache,
    kv_format_fallback,
    resolve_kv_format,
)

ARCHS = ("qwen1.5-0.5b", "granite-moe-1b-a400m", "mamba2-1.3b",
         "zamba2-2.7b", "whisper-tiny", "llava-next-34b")
FALLBACK_FAMILIES = ("ssm", "hybrid")     # recurrent state: no packed layout


def _served_formats(cache):
    """kv format per attention entry actually present in a decode cache."""
    return {
        entry: "hif4" if kvcache.is_packed_kv(cache[entry]["k"]) else "bf16"
        for entry in ("kv", "self", "cross") if entry in cache
    }


@pytest.mark.slow
@pytest.mark.parametrize("requested", ["bf16", "hif4"])
@pytest.mark.parametrize("arch", ARCHS)
def test_fallback_flag_agrees_with_served_cache(arch, requested):
    cfg = get_arch(arch).reduced()
    quant = QuantConfig(fmt="hif4", impl="packed",
                        kv=kvcache.KVCacheConfig(requested))
    ctx = ModelCtx(quant=quant, remat=False, attn_q_chunk=8, attn_k_chunk=8)
    sc = ServeConfig(max_new_tokens=4, kv_format=requested)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        resolved = resolve_kv_format(cfg, quant, sc, verbose=True)
    fallback = kv_format_fallback(cfg, quant, sc)
    expected_fallback = (requested == "hif4"
                         and cfg.family in FALLBACK_FAMILIES)
    assert fallback == expected_fallback
    assert fallback == (resolved != requested)
    # loudness: narrowing must warn (a catchable KVFallbackWarning, not a
    # print); silence means no narrowing
    fb_warns = [w for w in caught
                if issubclass(w.category, KVFallbackWarning)]
    assert bool(fb_warns) == fallback
    if fallback:
        assert "falls back to bf16" in str(fb_warns[0].message)

    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sp = serve_loop.prepare_params_for_serving(params, cfg, quant)
    sctx = serve_loop.serving_ctx(ctx)
    _, cache = build_decode_cache(cfg, sp, prefill_batch(cfg, 2, 16), sctx,
                                  sc, quant=quant)
    fmts = _served_formats(cache)
    if cfg.family == "ssm":
        assert fmts == {}                  # no attention cache at all
    else:
        # every attention entry served carries exactly the resolved format
        assert set(fmts.values()) == {resolved}, fmts
    if cfg.family == "audio":
        # the read-only cross (encoder) cache packs too — the former
        # permanent-fallback cell is gone
        assert fmts["cross"] == resolved
        assert set(fmts) == {"self", "cross"}


# short name -> registry arch, in ARCHS order
SHORT = dict(zip(("qwen", "moe", "mamba", "hybrid", "whisper", "llava"),
                 ARCHS))

# (arch, impl, requested kv, policy, paged) -> (attn route, matmul route).
# Routes are backend-neutral: "fused_decode_attention" means the cache is
# kernel-eligible (the Pallas kernel on TPU, its bitwise twin off-TPU),
# "twin" means the chunked-dequantize twin is the only execution. qdq
# always takes the twin; so does the reduced vlm arch, whose 1 kv-head x
# 32 d_head is below one 64-value HiF4 group (a tail-only cache). ssm has
# no attention; hybrid falls back to a bf16 cache and its doubly-stacked
# blocks never pack.
DISPATCH = (
    ("qwen", "qdq", "hif4", "uniform:hif4", False, "twin", "qdq"),
    ("qwen", "packed", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("qwen", "pallas", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("moe", "qdq", "hif4", "uniform:hif4", False, "twin", "qdq"),
    ("moe", "packed", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("moe", "pallas", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("mamba", "qdq", "hif4", "uniform:hif4", False, "none", "qdq"),
    ("mamba", "packed", "hif4", "uniform:hif4", False, "none", "fused"),
    ("mamba", "pallas", "hif4", "uniform:hif4", False, "none", "fused"),
    ("hybrid", "qdq", "hif4", "uniform:hif4", False, "dense", "qdq"),
    ("hybrid", "packed", "hif4", "uniform:hif4", False, "dense", "qdq"),
    ("hybrid", "pallas", "hif4", "uniform:hif4", False, "dense", "qdq"),
    ("whisper", "qdq", "hif4", "uniform:hif4", False, "twin", "qdq"),
    ("whisper", "packed", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("whisper", "pallas", "hif4", "uniform:hif4", False,
     "fused_decode_attention", "fused"),
    ("llava", "qdq", "hif4", "uniform:hif4", False, "twin", "qdq"),
    ("llava", "packed", "hif4", "uniform:hif4", False, "twin", "fused"),
    ("llava", "pallas", "hif4", "uniform:hif4", False, "twin", "fused"),
    ("qwen", "packed", "bf16", "uniform:hif4", False, "dense", "fused"),
    ("moe", "packed", "bf16", "uniform:hif4", False, "dense", "fused"),
    ("mamba", "packed", "bf16", "uniform:hif4", False, "none", "fused"),
    ("hybrid", "packed", "bf16", "uniform:hif4", False, "dense", "qdq"),
    ("whisper", "packed", "bf16", "uniform:hif4", False, "dense", "fused"),
    ("llava", "packed", "bf16", "uniform:hif4", False, "dense", "fused"),
    ("qwen", "qdq", "bf16", "uniform:hif4", False, "dense", "qdq"),
    ("qwen", "packed", "hif4", "paper-iv", False,
     "fused_decode_attention", "fused"),
    ("qwen", "packed", "hif4", "sensitive-fallback", False,
     "fused_decode_attention", "fused"),
    ("moe", "packed", "hif4", "paper-iv", False,
     "fused_decode_attention", "fused"),
    ("moe", "packed", "hif4", "sensitive-fallback", False,
     "fused_decode_attention", "fused"),
    ("qwen", "packed", "hif4", "uniform:hif4", True,
     "fused_paged_decode_attention", "fused"),
    ("moe", "packed", "hif4", "uniform:hif4", True,
     "fused_paged_decode_attention", "fused"),
)


def _case_id(case):
    short, impl, kv, policy, paged = case[:5]
    tail = "" if policy == "uniform:hif4" else f"-{policy}"
    return f"{short}-{impl}-{kv}{tail}{'-paged' if paged else ''}"


@functools.lru_cache(maxsize=None)
def _serving_params(arch, impl, policy):
    cfg = get_arch(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    plan = lm.quant_plan(cfg, get_policy(policy, impl=impl))
    return serve_loop.prepare_params_for_serving(params, cfg, plan)


def _attn_route(d):
    a = d["attn"]
    if a["route"] in ("none", "dense"):
        return a["route"]
    if not a["kernel_eligible"]:
        return "twin"
    return ("fused_paged_decode_attention" if d["paged"]
            else "fused_decode_attention")


@pytest.mark.parametrize("case", DISPATCH, ids=_case_id)
def test_dispatch_routes_per_family(case):
    short, impl, kv, policy, paged, attn, matmul = case
    cfg = get_arch(SHORT[short]).reduced()
    plan = lm.quant_plan(cfg, get_policy(
        policy, impl=impl, kv=kvcache.KVCacheConfig(kv)))
    sc = ServeConfig(max_new_tokens=8, kv_format=kv, kv_page_tokens=16)
    d = probe_dispatch(cfg, plan.base, sc,
                       _serving_params(SHORT[short], impl, policy),
                       paged=paged, batch=2, prompt_len=16)
    assert (_attn_route(d), d["matmul"]["route"]) == (attn, matmul), d
