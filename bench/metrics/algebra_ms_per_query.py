"""Self time of the program's ``algebra.evaluate`` spans per answer
attempted in the traced window: the SPARQL algebra around the engine's
batch, which the engine's own spans leave out."""

from bench.lib.spans import ms_per_query


def read(rec):
    return ms_per_query(rec, "algebra.evaluate")
