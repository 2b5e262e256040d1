"""95th percentile over every request of the window of the time from the
start of the call that carried it to that call's return (host clock)."""

import statistics


def read(run):
    lat = [c.seconds * 1e3 for c in run.calls for _ in c.rids]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[94]
