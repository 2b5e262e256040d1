"""Kernel: the fused packed matmul's share of its roofline.

Sum over the kernel's device events in the traced window of the least time
each call could take, max(operations / peak, bytes / HBM bandwidth) with
the work of ``work/fused_matmul.py`` counted from the call's own shapes
(read from the event's HLO text: the f32[M, N] result and the u8[K/2, N]
packed codes), over the sum of the events' device time. The peak is the
chip's highest (int8) rate. Decode calls (M = slots) are memory-bound;
long prefills are compute-bound; the run's log says which share of the
least time each bound sets."""

import re
import sys

KERNEL = r"%fused_packed_matmul[.\d]* = "
RESULT = re.compile(r"= f32\[(\d+),(\d+)\]")
CODES = re.compile(r"u8\[(\d+),(\d+)\]")


def read(run):
    if run.peaks is None:          # no chip: no share of its peak
        return None
    ops = run.trace.kernel(KERNEL) if run.trace is not None else []
    if not ops:
        return None
    work = run.finder.module("work", "fused_matmul")
    least = {"compute": 0.0, "memory": 0.0}
    for text, calls, _ in ops:
        m, n = map(int, RESULT.search(text).groups())
        half, n2 = map(int, CODES.search(text).groups())
        assert n2 == n, text[:200]
        secs, bound = run.peaks.least_seconds(*work.cost(m, 2 * half, n))
        least[bound] += calls * secs
    total = sum(least.values())
    print(f"fused_matmul: {sum(c for _, c, _ in ops)} calls, least time "
          f"{total:.6f} s ({100 * least['memory'] / total:.1f}% of it "
          "memory-bound)", file=sys.stderr)
    return 100.0 * total / sum(secs for _, _, secs in ops)
