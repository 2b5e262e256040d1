"""Model step: model operations of every token the traced calls processed
(prompt and output, ``work/model.py``) over the calls' summed wall time,
as a share of the chip's highest published peak (the int8 rate of
``peaks.json``), so no implementation can read above 100% whatever its
matrix units are fed."""


def read(run):
    if run.peaks is None:          # no chip: no share of its peak
        return None
    model = run.finder.module("work", "model")
    calls = run.traced_calls()
    ops = sum(model.request_ops(run.sizes, n, run.traffic.new_tokens)
              for c in calls for n in c.lengths)
    seconds = sum(c.seconds for c in calls)
    if not ops or not seconds:
        return None
    return 100.0 * ops / seconds / run.peaks.highest_ops
