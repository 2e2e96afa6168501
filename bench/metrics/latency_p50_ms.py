"""Median over every request of the window, each timed from just before
its client sent it to just after its answer was back."""

import statistics


def read(rec):
    lat = rec["window"]["latencies_s"]
    return 1000.0 * statistics.median(lat) if lat else None
