"""Scheduler: 95th percentile over the traced calls' requests of the time
from the start of the call to the request's first admission (the
``admitted`` time of ``stats["request_times"]`` that the paged scheduler
fills). A request waits for a slot and for the pages of its prompt, so in
a call of more requests than slots it waits for the prefills and decodes
ahead of it."""

import statistics


def read(run):
    waits = [t["admitted"] * 1e3 for c in run.traced_calls()
             for t in c.stats.get("request_times", {}).values()
             if "admitted" in t]
    if len(waits) < 2:
        return waits[0] if waits else None
    return statistics.quantiles(waits, n=100, method="inclusive")[94]
