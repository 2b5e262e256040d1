"""Scheduler: the share of the traced calls' wall time the scheduler spends
on its own host work between decode chunks: the summed time of its
``serve.pages`` spans (copy-on-write, pages for the chunk's horizon,
page-table writes) and ``serve.account`` spans (token accounting, audits,
sharing metadata, retirement, the journal) in the traced window."""

SPANS = ("serve.pages", "serve.account")


def read(run):
    t = run.trace
    if t is None:
        return None
    secs = sum(e - s for s, e, name in t.host
               if name in SPANS and s >= t.lo and e <= t.hi)
    wall = sum(c.seconds for c in run.traced_calls())
    if not secs or not wall:
        return None
    return 100.0 * secs / wall
