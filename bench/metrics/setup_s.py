"""Everything before the window: process start, weights made on the device
from the seed, packing, and the warm-up call that compiles (or loads from
the compile cache) every program the window runs (host clock)."""


def read(run):
    return run.setup_s
