"""Bring-up smoke test: serve qwen1.5-0.5b at full width on one TPU.

    python chip_smoke.py          # from the repository root, one TPU chip

The quickest proof that the serving stack still starts on the chip. One
process, one chip, no child processes. It builds qwen1.5-0.5b at its
published widths (24 layers, d_model 1024, 16 heads x 64, d_ff 2816,
vocab 151936) from seeded random weights, packs it with the ``paper-iv``
policy (``impl=packed``, HiF4 KV cache) through the same library calls
as ``python -m repro.launch.serve``, and then:

1. asserts that dispatch picks the Pallas kernels — the fused packed
   matmul, the contiguous and the paged decode-attention kernels — and
   that the lowered decode step really contains TPU kernel calls;
2. checks each kernel against its XLA twin on the chip, at those widths;
3. serves 8 requests (prompt 128, 32 new tokens) through ``serve()``;
4. compares the first decode step's logits with a reference that runs no
   Pallas (the same weights under ``impl=qdq``: XLA fake-quant and the
   attention twin) within the decode tolerance of docs/FORMATS.md
   (|dlogit| <= 0.1 + 0.05 |logit|), and reports greedy-token agreement;
5. serves the same 8 requests through ``serve_requests`` on the paged
   HiF4 pool (64-token pages) and requires them to equal, token for
   token, each request served alone at the page's KV tile.

It prints the device first and compile/run seconds of one cold run along
the way (a smoke reading, not a metric). Any failed check or raised error
exits non-zero without a result line; on success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU — or outside a checkout of the repository — it fails before
any phase.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen1.5-0.5b"
POLICY = "paper-iv"
BATCH = 8
PROMPT = 128
NEW_TOKENS = 32
PAGE = 64
SEED = 0
# docs/FORMATS.md decode tolerance: |dlogit| <= ATOL + RTOL * |logit|
LOGIT_ATOL, LOGIT_RTOL = 0.1, 0.05


def die(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class Checks:
    """Named pass/fail checks; the run fails at the end if any failed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = ""):
        print(f"[{'pass' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


class Timer:
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        return dt


def _ready(x):
    import jax
    return jax.block_until_ready(x)


def build(cfg, impl: str, params):
    """ModelCtx + serving params exactly as the serve launcher builds them."""
    from repro.core import kvcache
    from repro.core.policy import get_policy
    from repro.models import lm
    from repro.models.common import ModelCtx
    from repro.runtime.serve_loop import prepare_params_for_serving
    from repro.sharding.rules import ShardCtx

    policy = get_policy(POLICY, impl=impl, kv=kvcache.KVCacheConfig("hif4"))
    plan = lm.quant_plan(cfg, policy)
    ctx = ModelCtx(quant=plan.base, plan=plan, shard=ShardCtx(mesh=None),
                   remat=False, attn_q_chunk=32, attn_k_chunk=32)
    return ctx, prepare_params_for_serving(params, cfg, ctx.plan)


def check_dispatch(check, cfg, ctx, serving, decode_step_text: str):
    from repro.runtime.scenario import probe_dispatch
    from repro.runtime.serve_loop import ServeConfig

    sc = ServeConfig(max_new_tokens=NEW_TOKENS, kv_format="hif4",
                     kv_page_tokens=PAGE)
    for paged, route in ((False, "fused_decode_attention"),
                         (True, "fused_paged_decode_attention")):
        d = probe_dispatch(cfg, ctx.quant, sc, serving, paged=paged,
                           batch=BATCH, prompt_len=PROMPT)
        check(f"kv format hif4 (paged={paged})",
              d["kv_format_resolved"] == "hif4" and not d["kv_format_fallback"])
        check(f"matmul dispatch (paged={paged})",
              d["matmul"]["execution"] == "Pallas fused kernel",
              f"{d['matmul']['execution']}; blocks decode "
              f"{d['matmul']['decode_blocks']} prefill "
              f"{d['matmul']['prefill_blocks']}")
        check(f"attention dispatch (paged={paged})",
              d["attn"]["route"] == route
              and d["attn"]["execution"] == "Pallas fused kernel",
              f"{d['attn']['route']} [{d['attn']['execution']}] "
              f"kv tile {d['attn']['block_kv']}")
    n = decode_step_text.count("tpu_custom_call")
    check("lowered decode step holds TPU kernel calls", n > 0,
          f"{n} tpu_custom_call sites")


def _decode_in_kernel(codes, meta):
    """``hif4.absorbed_int_km`` of whole (K/2, N) / (K/64, N) buffers, run
    as one Pallas kernel on the chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.core import hif4

    def kernel(c_ref, m_ref, i_ref, s_ref):
        i_ref[...], s_ref[...] = hif4.absorbed_int_km(c_ref[...], m_ref[...])

    half, n = codes.shape
    return pl.pallas_call(kernel, out_shape=(
        jax.ShapeDtypeStruct((2 * half, n), jnp.int8),
        jax.ShapeDtypeStruct(meta.shape, jnp.float32)))(codes, meta)


def check_kernels(check, cfg, serving, key):
    """Each serve-path kernel against its XLA twin, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hif4, kvcache
    from repro.core.qlinear import PackedW
    from repro.kernels import fused_attention as fa
    from repro.kernels.fused_matmul import (absorbed_activation,
                                            fused_packed_matmul,
                                            fused_packed_matmul_xla)
    from repro.kernels.hif4_quant import hif4_quantize

    k1, k2, k3, k4 = jax.random.split(key, 4)
    # Algorithm-1 activation quantization: bitwise
    for m, k in ((BATCH, cfg.d_ff), (BATCH * PROMPT, cfg.d_model)):
        x = jax.random.normal(k1, (m, k), jnp.float32).astype(jnp.bfloat16)
        ints, sc = _ready(hif4_quantize(x))
        ints_t, sc_t = absorbed_activation(x)
        same = (np.array_equal(np.asarray(ints), np.asarray(ints_t))
                and np.array_equal(np.asarray(sc), np.asarray(sc_t)))
        check(f"hif4_quantize == absorbed_activation ({m}x{k})", same)

    # fused packed matmul on real packed weights (layer 0): both widths
    layer = jax.tree_util.tree_map(lambda b: b[0], serving["blocks"]["mlp"])
    # its word-level unpack reads code rows in the order pltpu.bitcast
    # packs them: the chip's kernel must decode the XLA twin's bits
    codes, meta = layer["wu"].kernel_operands()
    codes, meta = codes[:256, :1024], meta[:8, :1024]
    in_kernel = _decode_in_kernel(codes, meta)
    in_xla = hif4.absorbed_int_km(codes, meta)
    check("absorbed_int_km in a kernel == in XLA (bitwise)",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(in_kernel, in_xla)))
    for name in ("wu", "wo"):
        w = layer[name]
        assert isinstance(w, PackedW), (name, type(w))
        kk, n = w.shape2d
        codes, meta = w.kernel_operands()
        for m in (BATCH, BATCH * PROMPT):
            x = (jax.random.normal(k2, (m, kk)) * 0.5).astype(jnp.bfloat16)
            ai, asc = absorbed_activation(x)
            got = np.asarray(_ready(fused_packed_matmul(ai, asc, codes, meta)))
            want = np.asarray(fused_packed_matmul_xla(ai, asc, codes, meta))
            rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            check(f"fused_packed_matmul ~ twin ({name} M={m} K={kk} N={n})",
                  rel <= 1e-5, f"max |diff| / max |twin| = {rel:.3e}")

    # decode attention, contiguous and paged, at full KV width
    a = cfg.attn
    cap = kvcache.pages_for_tokens(PROMPT + NEW_TOKENS, PAGE) * PAGE
    kv = (jax.random.normal(k3, (2, BATCH, cap, a.n_kv_heads, a.d_head))
          * 0.5).astype(jnp.bfloat16)
    q = (jax.random.normal(k4, (BATCH, a.n_heads, a.d_head))
         * 0.5).astype(jnp.bfloat16)
    kc, vc = (kvcache.to_kernel_layout(kvcache.quantize_kv(t)) for t in kv)
    length = jnp.asarray([PROMPT + 1 + 4 * i for i in range(BATCH)],
                         jnp.int32)
    hkv, dh = a.n_kv_heads, a.d_head
    for block in (None, PAGE):
        got = np.asarray(_ready(fa.fused_decode_attention(
            q, kc, vc, length, n_kv_heads=hkv, d_head=dh, block_kv=block)),
            np.float32)
        want = np.asarray(fa.fused_decode_attention_xla(
            q, kc, vc, length, hkv, dh, block_kv=block), np.float32)
        err = float(np.max(np.abs(got - want)))
        check(f"fused_decode_attention ~ twin (block_kv={block})",
              err <= 2e-2, f"max |diff| = {err:.3e}")
        if block == PAGE:
            contiguous_at_page = got
    maxp = cap // PAGE
    pool = kvcache.init_page_pool(1, hkv, dh, BATCH * maxp + 1, PAGE)
    table = jnp.arange(1, BATCH * maxp + 1, dtype=jnp.int32).reshape(
        BATCH, maxp)
    for name, cache in (("k", kc), ("v", vc)):
        for b in range(BATCH):
            pages = kvcache.split_pages(
                {key: t[b][None, None] for key, t in cache.items()}, PAGE)
            pool[name] = kvcache.scatter_pages(pool[name], pages, table[b])
    kp = {key: t[0] for key, t in pool["k"].items()}
    vp = {key: t[0] for key, t in pool["v"].items()}
    got = np.asarray(_ready(fa.fused_paged_decode_attention(
        q, kp, vp, table, length, n_kv_heads=hkv, d_head=dh)), np.float32)
    want = np.asarray(fa.fused_paged_decode_attention_xla(
        q, kp, vp, table, length, hkv, dh), np.float32)
    err = float(np.max(np.abs(got - want)))
    check("fused_paged_decode_attention ~ twin", err <= 2e-2,
          f"max |diff| = {err:.3e}")
    check("paged kernel == contiguous kernel at block_kv=P (bitwise)",
          np.array_equal(got, contiguous_at_page))


def main():
    if os.environ.get("JAX_PLATFORMS") and "tpu" not in os.environ[
            "JAX_PLATFORMS"].split(","):
        die(f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']} holds JAX off "
            "the TPU; this smoke test needs one TPU chip")
    if not (SRC / "repro").is_dir():
        die(f"no repro package under {SRC}: run chip_smoke.py from a "
            "checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.jax_setup import configure_jax

    cache_dir = configure_jax()
    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if dev.platform != "tpu":
        die(f"no TPU: JAX found {dev.platform!r} devices only")
    print(f"compilation cache: {cache_dir}; XLA_FLAGS="
          f"{os.environ.get('XLA_FLAGS', '')}", flush=True)

    from repro.configs import get_arch
    from repro.models import lm
    from repro.runtime.scenario import prefill_batch
    from repro.runtime.serve_loop import (ServeConfig, build_decode_cache,
                                          packed_weight_bytes, serve,
                                          serve_requests, serving_ctx)

    check = Checks()
    timer = Timer()
    cfg = get_arch(ARCH)
    a = cfg.attn
    print(f"model: {cfg.name} {cfg.n_layers}L d_model={cfg.d_model} "
          f"heads={a.n_heads}/{a.n_kv_heads}x{a.d_head} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} ({cfg.n_params() / 1e9:.3f} B params), "
          f"policy {POLICY}, impl=packed, kv_format=hif4", flush=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(SEED))
    ctx, serving = build(cfg, "packed", params)
    ref_ctx, ref_serving = build(cfg, "qdq", params)
    nbytes, nvals = packed_weight_bytes(serving)
    check("weights packed", nvals > 0,
          f"{nbytes / 2**20:.1f} MiB for {nvals} values "
          f"({nbytes / max(nvals, 1):.4f} B/value)")
    batch = prefill_batch(cfg, BATCH, PROMPT, seed=SEED + 1)
    tokens = batch["tokens"]
    _ready((serving, ref_serving, tokens))
    print(f"build: {timer.lap():.1f} s (init + pack, one cold run)",
          flush=True)

    # -- first decode step, packed vs the no-Pallas reference --------------
    sc = ServeConfig(max_new_tokens=NEW_TOKENS, kv_format="hif4")
    logits = {}
    for name, c, p in (("packed", ctx, serving), ("qdq", ref_ctx, ref_serving)):
        sctx = serving_ctx(c)
        pre, cache = build_decode_cache(cfg, p, batch, sctx, sc,
                                        quant=c.quant)
        if name == "packed":
            first = jnp.argmax(pre, axis=-1).astype(jnp.int32)
        step = jax.jit(lambda p_, t_, c_, s=sctx: lm.decode_step(
            p_, t_, c_, cfg, s))
        if name == "packed":
            check_dispatch(check, cfg, ctx, serving,
                           step.lower(p, first, cache).as_text())
        logits[name] = (np.asarray(pre, np.float32),
                        np.asarray(_ready(step(p, first, cache)[0]),
                                   np.float32))
    print(f"first decode step, both paths: {timer.lap():.1f} s "
          "(compile + run, one cold run)", flush=True)
    for i, what in enumerate(("prefill (last token)", "first decode step")):
        got, ref = logits["packed"][i], logits["qdq"][i]
        dev_ = np.abs(got - ref)
        excess = float(np.max(dev_ - (LOGIT_ATOL + LOGIT_RTOL * np.abs(ref))))
        agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
        print(f"logits {what} vs qdq/XLA reference: max |dlogit| = "
              f"{float(np.max(dev_)):.4f} (max |logit| "
              f"{float(np.max(np.abs(ref))):.3f}), greedy agreement "
              f"{agree:.3f}", flush=True)
        check(f"logits {what} within FORMATS.md decode tolerance",
              bool(np.all(np.isfinite(got))) and excess <= 0.0,
              f"worst margin {excess:+.4f}")

    # -- kernels vs twins --------------------------------------------------
    check_kernels(check, cfg, serving, jax.random.PRNGKey(SEED + 2))
    print(f"kernel checks: {timer.lap():.1f} s (compile + run)", flush=True)

    # -- contiguous serve ----------------------------------------------------
    out = _ready(serve(cfg, serving, batch, ctx, sc))
    cold = timer.lap()
    out = np.asarray(_ready(serve(cfg, serving, batch, ctx, sc)))
    warm = timer.lap()
    print(f"serve() {BATCH} requests x {NEW_TOKENS} tokens: cold "
          f"{cold:.1f} s, warm {warm:.2f} s -> ~{cold - warm:.1f} s compile "
          "(one cold run, not a metric)", flush=True)
    check("serve() output", out.shape == (BATCH, NEW_TOKENS)
          and bool(np.all((out >= 0) & (out < cfg.vocab))), str(out.shape))
    ref_out = np.asarray(_ready(serve(cfg, params, batch, ref_ctx, sc)))
    timer.lap()
    print(f"greedy tokens vs qdq/XLA reference: first token "
          f"{float(np.mean(out[:, 0] == ref_out[:, 0])):.3f}, all "
          f"{float(np.mean(out == ref_out)):.3f} agree", flush=True)

    # -- paged serve vs solo at the page tile --------------------------------
    cap = -(-(PROMPT + NEW_TOKENS) // PAGE) * PAGE
    n_pages = BATCH * cap // PAGE + 1
    psc = dataclasses.replace(sc, cache_capacity=cap, kv_pages=n_pages,
                              kv_page_tokens=PAGE)
    stats: dict = {}
    paged = np.stack([np.asarray(r) for r in serve_requests(
        cfg, serving, list(tokens), ctx, psc, slots=BATCH, stats=stats)])
    print(f"serve_requests paged ({n_pages} pages x {PAGE} tokens): "
          f"{timer.lap():.1f} s cold; max {stats['max_concurrent']} "
          f"concurrent, peak {stats['peak_live_pages']} pages live",
          flush=True)
    check("paged scheduler ran", stats.get("scheduler") == "paged")
    solo_ctx = dataclasses.replace(ctx, attn_kv_block=PAGE)
    solo_sc = dataclasses.replace(sc, cache_capacity=cap)
    solo = np.stack([np.asarray(serve(
        cfg, serving, {"tokens": tokens[i:i + 1]}, solo_ctx, solo_sc)[0])
        for i in range(BATCH)])
    print(f"solo serve() at block_kv={PAGE}: {timer.lap():.1f} s", flush=True)
    check("paged == solo contiguous at the page tile, token for token",
          np.array_equal(paged, solo),
          f"{int(np.sum(paged != solo))} of {paged.size} tokens differ")
    print(f"paged vs batched serve() (default tile): "
          f"{float(np.mean(paged == out)):.3f} of tokens agree", flush=True)

    if check.failed:
        die(f"{len(check.failed)} check(s) failed: {', '.join(check.failed)}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
