"""Model step: time per decode step. The summed time of the scheduler's
``serve.decode`` spans in the traced window (each from a chunk's dispatch
through the read of its tokens) over the steps those chunks ran
(``decode_steps`` in ``stats``: chunks times chunk length, counting the
steps a scan runs past a request's budget)."""

SPAN = "serve.decode"


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = sum(e - s for s, e, name in t.host
               if name == SPAN and s >= t.lo and e <= t.hi)
    steps = sum(c.stats.get("decode_steps", 0) for c in run.traced_calls())
    if not secs or not steps:
        return None
    return secs * 1e3 / steps
