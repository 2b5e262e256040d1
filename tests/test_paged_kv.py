"""Paged HiF4 KV cache: pool primitives, paged attention parity, and the
page-pool continuous-batching scheduler.

The load-bearing claim (docs/FORMATS.md "Paged KV-cache pool"): pages
partition the token axis exactly like the kernel's KV tiles and fully
masked tiles are exact no-ops of the online-softmax recurrence, so paged
serving is BITWISE equal to contiguous/solo serving at ``block_kv = P`` on
a page-multiple capacity — paging buys admission, never bits. These tests
pin that parity at the kernel level (interpret kernel + XLA twin against
the contiguous paths), through the scheduler (shared prefixes, COW
divergence, forced preemption), and at the host allocator (PagePool
refcounts / LRU / sharing indexes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import kvcache
from repro.core.qlinear import QuantConfig
from repro.kernels import fused_attention as fa
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime.serve_loop import (
    ServeConfig,
    kv_format_fallback,
    resolve_kv_format,
    serve,
    serve_requests,
)

CFG = get_arch("qwen1.5-0.5b").reduced()


def _ctx(impl="packed", **kw):
    return ModelCtx(quant=QuantConfig(fmt="hif4", impl=impl,
                                      kv=kvcache.KVCacheConfig("hif4")),
                    remat=False, attn_q_chunk=2, attn_k_chunk=2, **kw)


# ---------------------------------------------------------------------------
# Pool primitives (device-side array ops)
# ---------------------------------------------------------------------------


def test_split_pages_roundtrip_bitwise():
    """split_pages is a pure bit move: gathering the pages back in order
    reassembles the contiguous kernel-layout cache exactly."""
    Hkv, Dh, S, P = 4, 32, 40, 16
    kv = (jax.random.normal(jax.random.PRNGKey(0), (1, 1, S, Hkv, Dh))
          * 0.3).astype(jnp.bfloat16)
    pk = kvcache.to_kernel_layout(kvcache.quantize_kv(kv))   # (1, 1, F, S)
    pages = kvcache.split_pages(pk, P)                       # (1, 3, F, P)
    n = kvcache.pages_for_tokens(S, P)
    assert pages["meta"].shape[1] == n
    back = {key: jnp.moveaxis(a, 1, 2).reshape(
        a.shape[0], 1, a.shape[2], n * P)[..., :S]
        for key, a in pages.items()}
    for key in ("codes", "meta", "tail"):
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(pk[key]))


def test_append_token_paged_matches_contiguous_append():
    """One decode append through the page table writes exactly the bytes a
    contiguous kernel-layout append would — including a slot mid-page and
    a slot exactly on a page boundary."""
    Hkv, Dh, P, maxp = 4, 32, 8, 3
    B = 2
    pos = jnp.asarray([13, 16], jnp.int32)       # mid-page / page boundary
    kv_new = (jax.random.normal(jax.random.PRNGKey(1), (B, 1, Hkv, Dh))
              * 0.3).astype(jnp.bfloat16)

    pool = lm.init_paged_cache(CFG, B, 8, P, maxp)["kv"]["k"]
    layer0 = {key: a[0] for key, a in pool.items()}          # (NP, F, P)
    table = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    out = kvcache.append_token_paged(layer0, kv_new, pos, table)

    one = kvcache.to_kernel_layout(kvcache.quantize_kv(kv_new))
    for b, (p, row) in enumerate([(13, 1), (16, 5)]):
        pid = int(table[b, p // P])
        for key in ("codes", "meta", "tail"):
            np.testing.assert_array_equal(
                np.asarray(out[key][pid, :, p % P]),
                np.asarray(one[key][b, :, 0]))


# ---------------------------------------------------------------------------
# Paged attention parity: kernel (interpret), XLA twin, contiguous paths
# ---------------------------------------------------------------------------


def _build_paged_case(seed=0, B=2, Hkv=4, Dh=32, P=16, maxp=3, rep=3,
                      lengths=None):
    """Random per-slot KV prefixes scattered into a shuffled page pool,
    plus the equivalent contiguous kernel-layout cache. Every table entry
    points at a page of quantized bytes, those past ``length`` too."""
    cap = maxp * P
    lengths = jnp.asarray(lengths or [cap - 5, P + 3][:B], jnp.int32)
    kv_k = (jax.random.normal(jax.random.PRNGKey(seed), (B, cap, Hkv, Dh))
            * 0.3).astype(jnp.bfloat16)
    kv_v = (jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (B, cap, Hkv, Dh)) * 0.3).astype(jnp.bfloat16)
    q = (jax.random.normal(jax.random.PRNGKey(seed + 2), (B, Hkv * rep, Dh))
         * 0.3).astype(jnp.bfloat16)

    def contiguous(kv):
        return kvcache.to_kernel_layout(kvcache.quantize_kv(kv))

    kc, vc = contiguous(kv_k), contiguous(kv_v)              # (B, F, cap)

    # scatter each slot's pages into the pool at shuffled, non-contiguous
    # ids (page 0 = scratch stays zero)
    n_pages = B * maxp + 1
    pool = kvcache.init_page_pool(1, Hkv, Dh, n_pages, P)
    ids = (np.random.default_rng(seed).permutation(n_pages - 1) + 1
           ).reshape(B, maxp)
    for b in range(B):
        pk = kvcache.split_pages(
            {key: a[b][None, None] for key, a in kc.items()}, P)
        pv = kvcache.split_pages(
            {key: a[b][None, None] for key, a in vc.items()}, P)
        row = jnp.asarray(ids[b], jnp.int32)
        pool["k"] = kvcache.scatter_pages(pool["k"], pk, row)
        pool["v"] = kvcache.scatter_pages(pool["v"], pv, row)
    table = jnp.asarray(ids, jnp.int32)
    k_pool = {key: a[0] for key, a in pool["k"].items()}     # (NP, F, P)
    v_pool = {key: a[0] for key, a in pool["v"].items()}
    return q, (kc, vc), (k_pool, v_pool), table, lengths, (Hkv, Dh, P)


# maxp 3 is one block of 3 pages; maxp 10 walks blocks of 8 pages, and a
# 10-entry table is not a multiple of the block
@pytest.mark.parametrize("case", [
    dict(),                                       # GQA rep 3, d_head 32
    dict(maxp=10, lengths=[8 * 16, 3 * 16]),      # block / page boundary
    dict(maxp=10, lengths=[1, 10 * 16]),          # one token / full table
    dict(maxp=10, Hkv=2, Dh=64, rep=1, lengths=[5 * 16 + 7, 9 * 16 + 1]),
    dict(maxp=4, Hkv=8, Dh=128, rep=4, lengths=[3 * 16 + 5, 4 * 16]),  # qwen3
], ids=["gqa-dh32", "block-and-page-boundary", "one-token-full-table",
        "mha-dh64", "gqa-hkv8-rep4-dh128"])
def test_paged_attention_bitwise_vs_contiguous(case):
    """All four executions — paged kernel (interpret), paged XLA twin,
    contiguous kernel at block_kv=P, contiguous XLA twin — produce the SAME
    bits: the page gather and the block walk only reorder DMA, never
    arithmetic."""
    q, (kc, vc), (kp, vp), table, lengths, (Hkv, Dh, P) = _build_paged_case(
        **case)

    cont_kernel = fa.fused_decode_attention(
        q, kc, vc, lengths, n_kv_heads=Hkv, d_head=Dh, block_kv=P,
        interpret=True)
    cont_xla = fa.fused_decode_attention_xla(
        q, kc, vc, lengths, Hkv, Dh, block_kv=P)
    paged_kernel = fa.fused_paged_decode_attention(
        q, kp, vp, table, lengths, n_kv_heads=Hkv, d_head=Dh, interpret=True)
    paged_xla = fa.fused_paged_decode_attention_xla(
        q, kp, vp, table, lengths, Hkv, Dh)

    ref = np.asarray(cont_kernel)
    for got in (cont_xla, paged_kernel, paged_xla):
        np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("dead_page", ["scratch", "nan_meta"])
def test_paged_attention_trailing_scratch_pages_are_noops(dead_page):
    """Table entries past a slot's live pages (``ceil(length / P)``) never
    change a bit, whatever page they point at: the zero scratch page, or a
    page whose meta is E6M2 0xFF (NaN), which would turn the result NaN
    if it were read."""
    q, _, (kp, vp), table, lengths, (Hkv, Dh, P) = _build_paged_case(
        maxp=10, lengths=[8 * 16 + 5, 16 + 3])
    if dead_page == "scratch":
        page = 0
    else:                           # one more pool page, every scale NaN
        def grow(pool):
            extra = {key: jnp.zeros_like(a[:1]) for key, a in pool.items()}
            extra["meta"] = jnp.full_like(extra["meta"], 0xFF000000)
            return {key: jnp.concatenate([a, extra[key]])
                    for key, a in pool.items()}
        kp, vp = grow(kp), grow(vp)
        page = kp["meta"].shape[0] - 1
    live = -(-np.asarray(lengths) // P)
    dead = np.arange(table.shape[1])[None, :] >= live[:, None]
    alt = jnp.where(jnp.asarray(dead), page, table)
    for run in (
            lambda t: fa.fused_paged_decode_attention_xla(
                q, kp, vp, t, lengths, Hkv, Dh),
            lambda t: fa.fused_paged_decode_attention(
                q, kp, vp, t, lengths, n_kv_heads=Hkv, d_head=Dh,
                interpret=True)):
        np.testing.assert_array_equal(np.asarray(run(alt)),
                                      np.asarray(run(table)))


# ---------------------------------------------------------------------------
# Host-side allocator: refcounts, LRU cache, sharing indexes
# ---------------------------------------------------------------------------


def test_page_pool_alloc_release_scratch_reserved():
    pool = kvcache.PagePool(4, 8)
    assert pool.usable_pages == 3
    got = [pool.alloc(owner="a") for _ in range(3)]
    assert 0 not in got and None not in got
    assert pool.alloc() is None                  # dry, nothing evictable
    pool.release(got[0])                         # unhashed -> frees
    assert pool.available() == 1
    assert pool.alloc(owner="b") == got[0]
    pool.audit()


def test_page_pool_refcount_and_cow_ownership():
    pool = kvcache.PagePool(4, 8)
    pid = pool.alloc(owner="a")
    pool.retain(pid)                             # sharer
    assert pool.ref[pid] == 2 and pool.owner[pid] == "a"
    pool.release(pid)                            # owner drops out
    assert pool.ref[pid] == 1                    # sharer keeps it live
    pool.audit(holders={"sharer": [pid]})


def test_page_pool_lru_cache_revive_and_evict():
    pool = kvcache.PagePool(4, 8)
    a, b, c = (pool.alloc(owner="r") for _ in range(3))
    pool.register_full(a, (1, 2))
    pool.register_full(b, (1, 2, 3, 4))
    for pid in (a, b, c):
        pool.release(pid)
    # a, b park in the LRU cache (hashed); c frees (unhashed)
    assert list(pool.cached) == [a, b] and pool.free == [c]
    # a prefix hit revives b out of the cache
    assert pool.lookup_full((1, 2, 3, 4)) == b
    pool.retain(b)
    assert b not in pool.cached and pool.ref[b] == 1
    # pool dry -> alloc evicts the LRU cached page (a) and drops its hash
    pool.alloc(owner="x")                        # takes the free page c
    got = pool.alloc(owner="x")
    assert got == a and pool.evictions == 1
    assert pool.lookup_full((1, 2)) is None
    pool.audit()


def test_page_pool_partial_registry_prefix_match():
    pool = kvcache.PagePool(4, 8)
    pid = pool.alloc(owner="a")
    pool.register_partial(pid, (7, 8), [1, 2, 3])
    assert pool.lookup_partial((7, 8), [1, 2]) == pid
    assert pool.lookup_partial((7, 8), [1, 9]) is None       # diverges
    assert pool.lookup_partial((0,), [1, 2]) is None         # wrong prefix
    assert pool.lookup_partial((7, 8), [1, 2, 3, 4]) is None  # too long
    # promoting the page to a hashed full drops it from the registry
    pool.register_full(pid, (7, 8, 1, 2, 3))
    assert pool.lookup_partial((7, 8), [1, 2]) is None
    pool.audit(holders={"a": [pid]})


def test_page_pool_register_full_first_writer_wins():
    pool = kvcache.PagePool(4, 8)
    a, b = pool.alloc(), pool.alloc()
    pool.register_full(a, (1,))
    pool.register_full(b, (1,))                  # duplicate: stays unshared
    assert pool.lookup_full((1,)) == a
    assert b not in pool.key_of
    pool.audit(holders={"x": [a], "y": [b]})


# ---------------------------------------------------------------------------
# Paged scheduler vs solo serving (bitwise)
# ---------------------------------------------------------------------------


def _solo(params, r, ctx, P, cap, budget, eos=None):
    solo_ctx = dataclasses.replace(ctx, attn_kv_block=P)
    sc = ServeConfig(max_new_tokens=budget, cache_capacity=cap,
                     kv_format="hif4", eos_id=eos)
    return serve(CFG, params, {"tokens": r[None, :]}, solo_ctx, sc)[0]


@pytest.mark.slow
def test_paged_scheduler_matches_solo_shared_prefix():
    """Mixed prompt lengths with a common 12-token prefix through the page
    pool: per-request outputs bitwise equal solo serving, and the prefix
    pages are actually shared."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    prefix = jax.random.randint(jax.random.PRNGKey(5), (12,), 0, CFG.vocab)
    reqs = [jnp.concatenate([prefix, jax.random.randint(
        jax.random.PRNGKey(30 + i), (4 + 2 * i,), 0, CFG.vocab)])
        for i in range(3)]                       # prompts 16, 18, 20
    ctx = _ctx()
    P, budget = 8, 6
    cap = 32                                     # page multiple >= 20 + 6
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=cap,
                     kv_format="hif4", kv_pages=9, kv_page_tokens=P)
    stats: dict = {}
    res = serve_requests(CFG, params, reqs, ctx, sc, slots=3, stats=stats)
    assert stats["scheduler"] == "paged"
    assert stats["shared_page_hits"] >= 1        # the shared prefix page
    assert stats["pool_audit"]["live"] == 0      # serve-end invariant audit
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            np.asarray(res[i]), np.asarray(_solo(params, r, ctx, P, cap,
                                                 budget)))


@pytest.mark.slow
def test_paged_scheduler_prompt_on_page_boundary():
    """A prompt filling its pages EXACTLY (16 = 2 x P) must admit cleanly
    and put its first decode token at offset 0 of a fresh page."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    r = jax.random.randint(jax.random.PRNGKey(9), (16,), 0, CFG.vocab)
    ctx = _ctx()
    P, budget, cap = 8, 4, 24
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=cap,
                     kv_format="hif4", kv_pages=6, kv_page_tokens=P)
    res = serve_requests(CFG, params, [r], ctx, sc, slots=1)
    np.testing.assert_array_equal(
        np.asarray(res[0]), np.asarray(_solo(params, r, ctx, P, cap, budget)))


@pytest.mark.slow
def test_paged_scheduler_single_token_pages():
    """P=1 is the degenerate page size: every token its own page, the table
    IS the token order. Still bitwise vs solo at block_kv=1."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    r = jax.random.randint(jax.random.PRNGKey(11), (4,), 0, CFG.vocab)
    ctx = _ctx()
    P, budget, cap = 1, 3, 7
    sc = ServeConfig(max_new_tokens=budget, cache_capacity=cap,
                     kv_format="hif4", kv_pages=8, kv_page_tokens=P)
    res = serve_requests(CFG, params, [r], ctx, sc, slots=1)
    np.testing.assert_array_equal(
        np.asarray(res[0]), np.asarray(_solo(params, r, ctx, P, cap, budget)))


@pytest.mark.slow
def test_paged_scheduler_cow_divergence():
    """B's prompt is a strict prefix of A's that ends INSIDE A's live tail
    page: B shares the page via the partial registry, then its first
    append lands there and must copy-on-write — A's bytes never change and
    both stay bitwise vs solo."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    a = jax.random.randint(jax.random.PRNGKey(13), (20,), 0, CFG.vocab)
    reqs = [a, a[:18]]
    ctx = _ctx()
    P, budget, cap = 8, 6, 32
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=cap,
                     kv_format="hif4", kv_pages=10, kv_page_tokens=P)
    stats: dict = {}
    res = serve_requests(CFG, params, reqs, ctx, sc, slots=2, stats=stats)
    # 2 full prefix pages + the live partial tail page
    assert stats["shared_page_hits"] >= 3
    assert stats["pool_audit"]["live"] == 0
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            np.asarray(res[i]), np.asarray(_solo(params, r, ctx, P, cap,
                                                 budget)))


@pytest.mark.slow
def test_paged_scheduler_preemption_bitwise():
    """A pool too small for both sequences' decode growth: the younger slot
    is preempted mid-admission (its page BYTES snapshotted), restored after
    the older retires, and still finishes bitwise equal to solo serving."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    reqs = [jax.random.randint(jax.random.PRNGKey(15 + i), (8,), 0,
                               CFG.vocab) for i in range(2)]
    ctx = _ctx()
    P, budget, cap = 4, 8, 16
    # 5 usable pages; each sequence needs 4 -> they cannot both finish
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=cap,
                     kv_format="hif4", kv_pages=6, kv_page_tokens=P)
    stats: dict = {}
    res = serve_requests(CFG, params, reqs, ctx, sc, slots=2, stats=stats)
    assert stats["preemptions"] >= 1
    assert stats["pool_audit"]["live"] == 0
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            np.asarray(res[i]), np.asarray(_solo(params, r, ctx, P, cap,
                                                 budget)))


@pytest.mark.slow
def test_paged_scheduler_eos_matches_solo():
    """eos handling through the paged retire path: a request stopping early
    returns exactly solo's eos-padded result."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    r = jax.random.randint(jax.random.PRNGKey(21), (12,), 0, CFG.vocab)
    ctx = _ctx()
    P, budget, cap = 8, 6, 24
    solo_free = _solo(params, r, ctx, P, cap, budget)
    eos = int(solo_free[2])                      # stop after the 3rd token
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=cap,
                     kv_format="hif4", kv_pages=8, kv_page_tokens=P,
                     eos_id=eos)
    res = serve_requests(CFG, params, [r], ctx, sc, slots=1)
    np.testing.assert_array_equal(
        np.asarray(res[0]),
        np.asarray(_solo(params, r, ctx, P, cap, budget, eos=eos)))


# ---------------------------------------------------------------------------
# Legacy slot scheduler: retire() eos regressions (satellite)
# ---------------------------------------------------------------------------


def _eos_case(eos_pick):
    """Serve 3 mixed-length requests through 2 slots with an eos chosen
    from one request's solo output; every request must match its own solo
    serve under the same eos."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    reqs = [jax.random.randint(jax.random.PRNGKey(50 + i), (8 + 4 * i,), 0,
                               CFG.vocab) for i in range(3)]
    ctx = _ctx()
    budget = 6
    solo_free = serve(CFG, params, {"tokens": reqs[0][None, :]}, ctx,
                      ServeConfig(max_new_tokens=budget, kv_format="hif4"))
    eos = eos_pick(np.asarray(solo_free[0]))
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2,
                     kv_format="hif4", eos_id=eos)
    res = serve_requests(CFG, params, reqs, ctx, sc, slots=2)
    for i, r in enumerate(reqs):
        solo = serve(CFG, params, {"tokens": r[None, :]}, ctx,
                     ServeConfig(max_new_tokens=budget, kv_format="hif4",
                                 eos_id=eos))
        np.testing.assert_array_equal(np.asarray(res[i]), np.asarray(solo[0]))


@pytest.mark.slow
def test_retire_eos_at_first_token():
    """eos emitted by prefill itself: the slot retires before any decode
    chunk ran for it, and the result is budget-length eos padding."""
    _eos_case(lambda toks: int(toks[0]))


@pytest.mark.slow
def test_retire_eos_near_budget():
    """eos on the LAST budgeted token: the trim-to-budget and pad-past-eos
    paths of retire() compose without off-by-one."""
    _eos_case(lambda toks: int(toks[-1]))


@pytest.mark.slow
def test_retire_no_eos_token_matches_eos_free():
    """An eos id that never appears must serve exactly like eos_id=None."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    reqs = [jax.random.randint(jax.random.PRNGKey(60 + i), (8 + 4 * i,), 0,
                               CFG.vocab) for i in range(3)]
    ctx = _ctx()
    sc_free = ServeConfig(max_new_tokens=6, decode_chunk=2, kv_format="hif4")
    res_free = serve_requests(CFG, params, reqs, ctx, sc_free, slots=2)
    emitted = {int(t) for r in res_free for t in np.asarray(r)}
    eos = next(t for t in range(CFG.vocab) if t not in emitted)
    res_eos = serve_requests(CFG, params, reqs, ctx,
                             dataclasses.replace(sc_free, eos_id=eos),
                             slots=2)
    for a, b in zip(res_eos, res_free):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# KV-format fallback loudness (satellite)
# ---------------------------------------------------------------------------


def test_kv_fallback_loud_and_recorded():
    """A family without a packed KV layout must fall back to bf16 LOUDLY
    (verbose resolve emits a catchable KVFallbackWarning) and visibly
    (kv_format_fallback=True for the records benchmark/dryrun carry) —
    never silently."""
    import warnings

    from repro.runtime.serve_loop import KVFallbackWarning

    ssm = get_arch("mamba2-1.3b").reduced()
    quant = QuantConfig(fmt="hif4", impl="qdq",
                        kv=kvcache.KVCacheConfig("hif4"))
    sc = ServeConfig()
    assert resolve_kv_format(ssm, quant, sc) == "bf16"
    with pytest.warns(KVFallbackWarning, match="falls back to bf16"):
        assert resolve_kv_format(ssm, quant, sc, verbose=True) == "bf16"
    assert kv_format_fallback(ssm, quant, sc) is True
    # a KV-cache family narrows nothing and warns nothing
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_kv_format(CFG, quant, sc, verbose=True) == "hif4"
    assert not [w for w in caught
                if issubclass(w.category, KVFallbackWarning)]
    assert kv_format_fallback(CFG, quant, sc) is False


def test_paged_pool_requires_hif4():
    """kv_pages on a bf16 cache (or a fallen-back family) must refuse, not
    silently serve unpaged."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    r = jax.random.randint(jax.random.PRNGKey(3), (8,), 0, CFG.vocab)
    sc = ServeConfig(max_new_tokens=2, kv_format="bf16", kv_pages=4,
                     kv_page_tokens=8)
    with pytest.raises(AssertionError, match="paged KV pool"):
        serve_requests(CFG, params, [r], _ctx(), sc, slots=1)
