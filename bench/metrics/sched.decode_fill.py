"""Scheduler: the share of decode slot-steps that make a kept token.

Summed over the traced calls, the slot-steps in which a slot held a
request still inside its token budget (``decode_slot_steps_live`` in
``stats``) over slots times decode steps (``decode_steps``), in %. The
rest are empty slots and the steps a chunk's scan runs past a request's
budget: each still streams its share of the weights and the head, and
yields no token. Moves ``tokens_per_s``."""


def read(run):
    calls = [c.stats for c in run.traced_calls()
             if "decode_slot_steps_live" in c.stats]
    slot_steps = run.batch * sum(s["decode_steps"] for s in calls)
    if not slot_steps:
        return None
    return 100.0 * sum(s["decode_slot_steps_live"] for s in calls) / slot_steps
