"""Self time of the program's ``engine.scan_fetch`` spans per answer
attempted in the traced window: waiting for the scan kernels and copying
their masks to the host."""

from bench.lib.spans import ms_per_query


def read(rec):
    return ms_per_query(rec, "engine.scan_fetch")
