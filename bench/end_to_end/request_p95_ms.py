"""95th percentile of the latencies of all requests of the window."""

import statistics


def read(ctx):
    lat = [1e3 * (r["t1"] - r["t0"]) for r in ctx["records"]]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=20, method="inclusive")[18]
