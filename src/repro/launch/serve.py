"""Serving launcher: offline HiF4 packing/PTQ + batched scan decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 16 --policy paper-iv \
        --impl packed --kv-format hif4

``--policy`` selects the per-site quantization placement (see
docs/EXECUTION.md §Policy resolution): a preset (``paper-iv``,
``uniform:hif4``, ``nvfp4-baseline``, ``sensitive-fallback``) or a policy
JSON file; the launcher prints the resolved plan — one line per site with
its format, impl, and resident artifact — next to the fused-kernel and
residency lines. Without ``--policy`` the legacy ``--quant``/``--impl``
global config applies (identical to ``uniform:<fmt>``).

``--impl`` picks the execution path (see docs/EXECUTION.md): ``packed``
(default) serves real 4.5-bit resident weights through the fused
dequantize-in-kernel matmul (Pallas on TPU, its XLA twin elsewhere);
``qdq`` is the fake-quant accuracy shape; ``pallas`` adds the fixed-point
kernels for dense weights too (interpret mode off TPU — slow on CPU, use
tiny shapes). ``--kv-format hif4`` additionally stores the decode KV cache
at 4.5 bits/value (docs/FORMATS.md) — KV storage stays cache-global.

``--kv-pages N`` (requires ``--kv-format hif4``) swaps the whole-slot
decode cache for the fixed page pool: requests are served through the
paged continuous-batching scheduler (page-granular admission, COW prefix
sharing, LRU eviction / preemption — docs/EXECUTION.md) and the launcher
prints pool residency and scheduler counters instead of the dense
slots x capacity line. ``--kv-page-tokens`` sets the page size.

``--guard`` arms the health sentinels (docs/EXECUTION.md §Failure
semantics): NaN/Inf logits detection fused into the decode scan, per-chunk
0xFF-meta and page-checksum audits over packed KV, quarantine + qdq/bf16
fallback retry, and per-request status reporting (printed per request).
``--inject-fault kind[:key=value,...]`` drives one deterministic fault
through :mod:`repro.runtime.faults` to demonstrate detection/containment,
e.g. ``--inject-fault meta_flip:seed=3,target_request=1,after_chunk=1``.
Both flags route serving through the request scheduler (transformer
families only).

``--journal-dir DIR`` makes the serve crash-safe (docs/EXECUTION.md
§Crash recovery): a write-ahead request journal under DIR records every
admission, per-chunk emission, and terminal status (fsynced once per
decode chunk), and ``--checkpoint-every N`` adds a durable page-pool
checkpoint every N chunks. After a crash — including an injected
``crash_*`` fault — rerunning with ``--resume`` replays the journal:
finished requests' results are injected verbatim, checkpointed residents
restored byte-for-byte, the rest re-prefilled, and every re-served
output is verified bitwise against its journaled token prefix. The
launcher prints journal/checkpoint residency and, on resume, the
recovery report.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core import kvcache
from repro.core.policy import get_policy
from repro.core.qlinear import PackedW, QuantConfig
from repro.jax_setup import configure_jax
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime import GuardConfig, ServeConfig, serve
from repro.runtime import faults
from repro.runtime.serve_loop import (
    packed_weight_bytes,
    prepare_params_for_serving,
    resolve_kv_format,
    serve_requests,
)
from repro.sharding.rules import ShardCtx


def _leaf_at(tree, path: str):
    node = tree
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def _print_plan(plan, serving_params):
    """The resolved policy plan, one line per site: what each weight site
    quantizes to and what is actually resident for it."""
    print(f"policy plan [{plan.policy.name}] "
          f"({len(plan.packed_paths)}/{len(plan.sites)} sites packed):")
    print(f"  {'site':<18} {'fmt':<10} {'impl':<7} {'resident artifact':<34} "
          f"{'bytes':>12}")
    for site in plan.sites:
        leaf = _leaf_at(serving_params, site.path)
        if isinstance(leaf, PackedW):
            nbytes = leaf.nbytes_packed
            art = f"PackedW 4.5-bit ({nbytes / leaf.n_values:.4f} B/value)"
        elif leaf is None:
            nbytes = 0
            art = "(tied -> embed)" if site.path == "lm_head" else "(absent)"
        else:
            nbytes = int(leaf.nbytes)
            art = (f"qdq {leaf.dtype} (offline PTQ)"
                   if site.cfg.enabled and site.quantize_offline
                   else str(leaf.dtype))
        print(f"  {site.path:<18} {site.cfg.fmt:<10} {site.cfg.impl:<7} "
              f"{art:<34} {nbytes:>12,}")


def _print_kernel_dispatch(serving_params, ctx, args):
    """One line per serving regime: is the fused dequantize-in-kernel matmul
    active for the resident PackedW weights, and with which tile sizes."""
    from repro.core.engine import packed_dispatch_info
    from repro.core.qlinear import PackedW

    pws = [leaf for leaf in jax.tree_util.tree_leaves(
        serving_params, is_leaf=lambda x: isinstance(x, PackedW))
        if isinstance(leaf, PackedW)]
    if not pws:
        return
    # representative weight: a per-layer slice of the first (stacked) leaf
    pw = pws[0]
    if pw.codes.ndim > (2 if pw.kernel_layout else 3):
        pw = jax.tree_util.tree_map(lambda b: b[0], pw)
    info = packed_dispatch_info(ctx.quant, pw, decode_m=args.batch,
                                prefill_m=args.batch * args.prompt_len)
    if not info["fused"]:
        print("packed matmul: dequantize-then-dot fallback "
              "(fused kernel needs impl=packed|pallas, fmt=hif4, "
              "both-operand quantization)")
        return
    k, n = pw.shape2d
    line = f"packed matmul: fused [{info['execution']}] on e.g. (K={k}, N={n})"
    if info["decode_blocks"] is not None:
        line += (f"; blocks decode(bm,bn,bk)={info['decode_blocks']} "
                 f"prefill={info['prefill_blocks']}")
    print(line)


def _print_attention_dispatch(cfg, ctx, capacity):
    """One line for the packed-KV decode hot path: fused Pallas kernel vs
    XLA twin, and the KV tile size either execution streams — next to the
    fused-matmul and residency prints, the whole packed story at a glance."""
    from repro.core.engine import attention_dispatch_info

    a = cfg.attn
    g, t = kvcache.split_features(a.n_kv_heads, a.d_head)
    # shape probe only: dispatch reads ranks/shapes, never the bytes
    probe = {
        "codes": jax.ShapeDtypeStruct((1, g * 32, capacity), jnp.uint8),
        "meta": jax.ShapeDtypeStruct((1, g, capacity), jnp.uint32),
        "tail": jax.ShapeDtypeStruct((1, t, capacity), jnp.bfloat16),
    }
    info = attention_dispatch_info(ctx.quant, probe,
                                   n_kv_heads=a.n_kv_heads, d_head=a.d_head)
    print(f"packed attention: {'fused' if info['fused'] else 'twin'} "
          f"[{info['execution']}] kv tile {info['block_kv']} of "
          f"{capacity} slots")


def _print_journal_residency(directory):
    from repro.runtime.journal import journal_residency

    res = journal_residency(directory)
    print(f"journal residency [{directory}]: "
          f"{res['journal_bytes']} B journal, "
          f"{res['checkpoints']} checkpoint(s) = "
          f"{res['checkpoint_bytes']} B")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--quant", default="hif4")
    ap.add_argument("--impl", default="packed",
                    choices=["qdq", "packed", "pallas"])
    ap.add_argument("--decode-chunk", type=int, default=0,
                    help="tokens per jitted decode scan (0 = whole budget)")
    ap.add_argument("--kv-format", default="bf16",
                    choices=list(kvcache.KV_FORMATS),
                    help="decode KV-cache storage (hif4 = 4.5 bits/value)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="> 0: paged KV pool with this many pages "
                         "(page-granular admission + COW prefix sharing; "
                         "requires --kv-format hif4)")
    ap.add_argument("--kv-page-tokens", type=int,
                    default=kvcache.DEFAULT_PAGE_TOKENS,
                    help="tokens per KV pool page")
    ap.add_argument("--guard", action="store_true",
                    help="arm the serving health sentinels: NaN scan flag, "
                         "packed-KV audits, quarantine + fallback retry, "
                         "per-request status reports")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline (implies --guard)")
    ap.add_argument("--inject-fault", default=None, metavar="SPEC",
                    help="deterministic fault injection, "
                         "kind[:key=value,...] with kinds "
                         + "/".join(faults.FAULT_CLASSES)
                         + " (implies --guard)")
    ap.add_argument("--policy", default=None,
                    help="per-site quantization policy: a preset name "
                         "(paper-iv, uniform:<fmt>, nvfp4-baseline, "
                         "sensitive-fallback) or a policy JSON file; "
                         "overrides --quant")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="crash-safe serving: write-ahead request journal "
                         "(+ pool checkpoints) under DIR; routes through "
                         "the request scheduler")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="durable pool checkpoint every N decode chunks "
                         "(0 = journal only; paged scheduler)")
    ap.add_argument("--resume", action="store_true",
                    help="recover from the journal in --journal-dir: "
                         "journaled terminal results are injected, "
                         "checkpointed residents restored, the rest "
                         "re-prefilled — outputs bitwise identical to an "
                         "uninterrupted run")
    args = ap.parse_args()
    configure_jax()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh() if len(jax.devices()) > 1 else None
    kv = kvcache.KVCacheConfig(args.kv_format)
    plan = None
    if args.policy is not None:
        policy = get_policy(args.policy, impl=args.impl, kv=kv)
        plan = lm.quant_plan(cfg, policy)
        quant = plan.base
    else:
        quant = QuantConfig(fmt=args.quant, impl=args.impl, kv=kv)
    ctx = ModelCtx(quant=quant, plan=plan,
                   shard=ShardCtx(mesh=mesh), remat=False,
                   attn_q_chunk=32, attn_k_chunk=32)

    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    serving_params = prepare_params_for_serving(params, cfg,
                                                ctx.plan or ctx.quant)
    if plan is not None:
        _print_plan(plan, serving_params)
    nbytes, nvals = packed_weight_bytes(serving_params)
    if nvals:
        print(f"packed weight residency: {nbytes / 2**20:.2f} MiB for "
              f"{nvals} values = {nbytes / nvals:.4f} B/value "
              f"(bf16 would be {2 * nvals / 2**20:.2f} MiB)")
        _print_kernel_dispatch(serving_params, ctx, args)
    else:
        print(f"impl={args.impl}: no packed weights resident "
              f"(fake-quant bf16 artifact)")

    guard = None
    if args.guard or args.deadline_s is not None or args.inject_fault:
        guard = GuardConfig(deadline_s=args.deadline_s)
    injector = (faults.FaultInjector(faults.parse_fault(args.inject_fault))
                if args.inject_fault else None)
    sc = ServeConfig(max_new_tokens=args.new_tokens,
                     decode_chunk=args.decode_chunk,
                     kv_pages=args.kv_pages,
                     kv_page_tokens=args.kv_page_tokens,
                     guard=guard,
                     journal_dir=args.journal_dir,
                     checkpoint_every=args.checkpoint_every)
    a = cfg.attn
    kv_fmt = None
    if a is None:
        print("kv cache residency: n/a (attention-free family)")
    else:
        # verbose: the hybrid/audio bf16 fallback prints loudly here
        kv_fmt = resolve_kv_format(cfg, ctx.quant, sc, verbose=True)
        cap = args.prompt_len + args.new_tokens
        per_tok = kvcache.kv_bytes_per_token(
            a.n_kv_heads, a.d_head, kv_fmt) * cfg.n_layers
        bf16_tok = kvcache.kv_bytes_per_token(
            a.n_kv_heads, a.d_head, "bf16") * cfg.n_layers
        if args.kv_pages:
            pg = kvcache.page_nbytes(a.n_kv_heads, a.d_head,
                                     args.kv_page_tokens, cfg.n_layers)
            print(f"kv page pool [{kv_fmt}]: {args.kv_pages} pages x "
                  f"{args.kv_page_tokens} tokens ({pg} B/page) = "
                  f"{args.kv_pages * pg / 2**20:.2f} MiB "
                  f"(whole-slot equivalent: "
                  f"{per_tok * cap * args.batch / 2**20:.2f} MiB for "
                  f"{args.batch} slots x {cap} capacity)")
        else:
            total = per_tok * cap * args.batch
            print(f"kv cache residency [{kv_fmt}]: {per_tok} B/token "
                  f"(bf16: {bf16_tok}) x {cap} capacity x {args.batch} slots "
                  f"= {total / 2**20:.2f} MiB"
                  + (f"  [{bf16_tok / per_tok:.2f}x more slots per byte]"
                     if kv_fmt == "hif4" else ""))
        if kv_fmt == "hif4":
            _print_attention_dispatch(cfg, ctx, cap)

    # family-correct prefill inputs: audio takes encoder frames, vlm
    # takes projected embeds, everything else token ids
    from repro.runtime.scenario import prefill_batch

    batch = prefill_batch(cfg, args.batch, args.prompt_len)
    tokens = batch.get("tokens")
    # packed impls reuse the converted tree (prepare is idempotent on it);
    # the qdq artifact is re-derived inside serve from the raw weights
    sparams = serving_params if nvals else params
    try:
        if args.kv_pages:
            assert tokens is not None, (
                "--kv-pages serves token requests (dense/vlm-embeds not "
                "supported by the paged scheduler entry)")
            assert kv_fmt == "hif4", (
                "--kv-pages requires --kv-format hif4 on a KV-cache family "
                "(the page pool stores packed HiF4 pages)")
            stats: dict = {}
            res = serve_requests(cfg, sparams, list(tokens), ctx, sc,
                                 slots=args.batch, stats=stats,
                                 injector=injector, resume=args.resume)
            print(f"paged scheduler: max {stats['max_concurrent']} "
                  f"concurrent, {stats['shared_page_hits']} shared-page "
                  f"hits, {stats['preemptions']} preemptions, "
                  f"{stats['evictions']} LRU evictions, peak "
                  f"{stats['peak_live_pages']}/{args.kv_pages} pages live")
            toks = jnp.stack(res)
        elif guard is not None or args.journal_dir is not None:
            # guarded/journaled serving is per-request — route through the
            # request scheduler even without the page pool
            assert tokens is not None, (
                "--guard/--inject-fault/--journal-dir serve token requests "
                "through the request scheduler (dense/vlm-embeds not "
                "supported)")
            stats = {}
            res = serve_requests(cfg, sparams, list(tokens), ctx, sc,
                                 slots=args.batch, stats=stats,
                                 injector=injector, resume=args.resume)
            toks = jnp.stack(res)
        else:
            stats = None
            toks = serve(cfg, sparams, batch, ctx, sc)
    except faults.SimulatedCrash as crash:
        # the injected process kill: report what the journal holds and
        # exit cleanly so CI smoke runs can chain a --resume invocation
        print(f"simulated crash: {crash}")
        if args.journal_dir is not None:
            _print_journal_residency(args.journal_dir)
        print("resume with: --journal-dir", args.journal_dir, "--resume")
        return
    if args.journal_dir is not None:
        _print_journal_residency(args.journal_dir)
        if args.resume and stats is not None and "recovery" in stats:
            rec = stats["recovery"]
            print(f"recovery report: {rec['completed']} journaled results "
                  f"injected, {rec['replayed']} residents restored from "
                  f"checkpoint, {rec['re_prefilled']} re-prefilled, "
                  f"{rec['dropped_bytes']} torn journal bytes dropped, "
                  f"{rec['verified']} replay prefixes verified bitwise "
                  f"({rec['recovery_ms']:.1f} ms plan build)")
    if injector is not None:
        for kind, detail in injector.events:
            print(f"injected fault: {kind} {detail}")
    if guard is not None and stats is not None:
        counts = {k: stats[k] for k in
                  ("quarantined", "retried", "rejected", "timeouts")}
        print(f"guarded serving: {counts}")
        for rid in sorted(stats["reports"]):
            rep = stats["reports"][rid]
            line = f"request {rid}: status={rep['status']}"
            if rep["detail"]:
                line += f" ({rep['detail']})"
            print(line)
    for i in range(args.batch):
        print(f"request {i}: {toks[i].tolist()}")


if __name__ == "__main__":
    main()
