"""Output tokens of every whole ``serve_requests`` call in the window over
the summed wall time of those calls (host clock; each call ends in a
device_get of its results)."""


def read(run):
    return run.tokens_served() / run.window_seconds()
