"""Mean wait from a request's admission to its batch's dispatch
(``BatchStats.wait_seconds``), weighted by batch size over the window."""


def read(rec):
    b = rec["batches"]
    n = sum(x["size"] for x in b)
    if not n:
        return None
    return 1000.0 * sum(x["wait_seconds"] * x["size"] for x in b) / n
