"""Model step: a decode step's share of the HBM roofline.

The least bytes one decode step of the whole model moves, over the HBM
bandwidth (``peaks.json``) times the step's time. The bytes, counted by
the functions of ``bench/work/``: every packed matmul of the body
(``fused_matmul.layer_shapes``) at its bytes per value, the tied head in
bfloat16 (2 bytes a value, read once a step), and the packed K and V of
the cached tokens the step's live slots attend over
(``paged_attention.kv_bytes`` of ``decode_kv_tokens / decode_steps`` in
``stats``, in every layer). The step's time is the summed time of the
scheduler's ``serve.decode`` spans in the traced window over the steps
those chunks ran (``decode_steps``). Activations, norms and embedding
rows are left out: small beside these at decode. Moves ``tokens_per_s``:
a decode step streams the weights once for every slot."""

SPAN = "serve.decode"
HEAD_BYTES_PER_VALUE = 2.0


def least_bytes(finder, sizes, kv_tokens: float) -> float:
    """Least bytes of one decode step attending over ``kv_tokens`` cached
    tokens in all, summed over the slots."""
    mm = finder.module("work", "fused_matmul")
    attn = finder.module("work", "paged_attention")
    body = sum(k * n for k, n in mm.layer_shapes(sizes)) * sizes.layers
    return (mm.WEIGHT_BYTES_PER_VALUE * body
            + HEAD_BYTES_PER_VALUE * sizes.d * sizes.vocab
            + attn.kv_bytes(sizes, kv_tokens) * sizes.layers)


def read(run):
    t = run.trace
    if run.peaks is None or t is None:      # no chip: no share of its peak
        return None
    calls = [c.stats for c in run.traced_calls() if "decode_kv_tokens" in c.stats]
    steps = sum(s["decode_steps"] for s in calls)
    secs = sum(e - s for s, e, name in t.host
               if name == SPAN and s >= t.lo and e <= t.hi)
    if not steps or not secs:
        return None
    kv_tokens = sum(s["decode_kv_tokens"] for s in calls) / steps
    nbytes = least_bytes(run.finder, run.sizes, kv_tokens)
    return 100.0 * nbytes / run.peaks.hbm_bw / (secs / steps)
