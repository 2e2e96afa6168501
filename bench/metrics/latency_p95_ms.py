"""95th percentile over every request of the window, each timed as for
``latency_p50_ms``; only for cells whose windows hold hundreds of
requests, so that tens lie beyond it."""

import statistics


def read(rec):
    lat = rec["window"]["latencies_s"]
    if len(lat) < 200:
        return None
    return 1000.0 * statistics.quantiles(lat, n=20, method="inclusive")[-1]
