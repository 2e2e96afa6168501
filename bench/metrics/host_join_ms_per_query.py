"""Self time of the program's ``engine.host_join`` spans per answer
attempted in the traced window: the host join (``match_bgp``) of the
queries that fall back from the device join."""

from bench.lib.spans import ms_per_query


def read(rec):
    return ms_per_query(rec, "engine.host_join")
