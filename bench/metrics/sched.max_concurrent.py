"""Scheduler: the most requests decoding together in one chunk, over the
traced calls (the ``max_concurrent`` counter ``serve_requests`` fills in
``stats``). Moves ``tokens_per_s``: fewer concurrent sequences, fewer
tokens per decode step."""


def read(run):
    seen = [c.stats["max_concurrent"] for c in run.traced_calls()
            if "max_concurrent" in c.stats]
    return max(seen) if seen else None
