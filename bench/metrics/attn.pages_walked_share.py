"""Kernels: the share of the page table that paged decode attention reads.

Summed over the traced calls, the table entries the kernel walks (per slot
and decode step, those below ``ceil(length / page_tokens)``:
``attn_pages_walked`` in ``stats``) over the entries the table holds
(``attn_pages_table``), in %. The rest are entries past a slot's length,
which the kernel skips. Moves ``tokens_per_s``: the fewer entries a step
walks, the shorter the decode step."""


def read(run):
    calls = [c.stats for c in run.traced_calls()
             if "attn_pages_table" in c.stats]
    table = sum(s["attn_pages_table"] for s in calls)
    if not table:
        return None
    return 100.0 * sum(s["attn_pages_walked"] for s in calls) / table
