"""Kernel: paged decode attention's share of its roofline.

The work is counted from the requests the traced calls served
(``work/paged_attention.py``): every layer of every decode step of every
request, over the valid tokens only, so empty slots, masked pages and the
unused part of a page read as waste. Least time = max(operations / peak,
bytes / HBM bandwidth) with the chip's highest (int8) rate; it is
memory-bound by far. Divided by the summed device time of the kernel's
events in the traced window."""

KERNEL = r"%fused_paged_decode_attention[.\d]* = "


def read(run):
    if run.peaks is None:          # no chip: no share of its peak
        return None
    seconds = run.trace.kernel_seconds(KERNEL) if run.trace is not None else 0
    if not seconds:
        return None
    work = run.finder.module("work", "paged_attention")
    ops = nbytes = 0.0
    for c in run.traced_calls():
        for n in c.lengths:
            o, b = work.request_cost(run.sizes, n, run.traffic.new_tokens)
            ops += o
            nbytes += b
    least, _ = run.peaks.least_seconds(ops, nbytes)
    return 100.0 * least / seconds
