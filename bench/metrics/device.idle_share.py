"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of device op intervals / window)."""


def read(run):
    t = run.trace
    if t is None or not t.window_s or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
