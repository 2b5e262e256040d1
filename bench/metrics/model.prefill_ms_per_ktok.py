"""Model step: prefill time per thousand prompt tokens. The summed time of
the scheduler's ``serve.prefill`` spans in the traced window (each runs
from the prefill's dispatch through quantization, page split and scatter
to the first token's read, so it holds the device work it launched) over
the prompt tokens those prefills took (``prefill_tokens`` in ``stats``)."""

SPAN = "serve.prefill"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = sum(e - s for s, e, name in t.host
               if name == SPAN and s >= t.lo and e <= t.hi)
    tokens = sum(c.stats.get("prefill_tokens", 0) for c in run.traced_calls())
    if not secs or not tokens:
        return None
    return secs * 1e3 / tokens * 1e3
