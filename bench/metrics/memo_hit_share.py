"""Share of the window's requests answered from the endpoint's result
memo or the engine's result cache (``BatchStats``)."""


def read(rec):
    b = rec["batches"]
    n = sum(x["size"] for x in b)
    if not n:
        return None
    hits = sum(x["memo_hits"] + x["engine_cache_hits"] for x in b)
    return 100.0 * hits / n
