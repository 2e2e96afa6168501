"""Share of the engine's cache-missed queries that ran device-resident
(``EngineStats.device_queries`` against ``device_fallbacks``)."""


def read(rec):
    e = rec["engine"]
    n = e["device_queries"] + e["device_fallbacks"]
    return 100.0 * e["device_queries"] / n if n else None
