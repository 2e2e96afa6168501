"""XLA compilations inside the window per answer completed (JAX's own
monitoring events). Warm-up runs every batch size the window can form,
so anything here is a program that depends on more than the batch size
(a row count, say), compiled while requests waited."""


def read(rec):
    n = rec["window"]["attempted"]
    return rec["compiles"] / n if n else None
