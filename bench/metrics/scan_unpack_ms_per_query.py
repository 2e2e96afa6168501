"""Self time of the program's ``engine.scan_unpack`` spans per answer
attempted in the traced window: turning scan masks into candidate ids on
the host."""

from bench.lib.spans import ms_per_query


def read(rec):
    return ms_per_query(rec, "engine.scan_unpack")
