"""Bytes of scan masks fetched to the host per candidate row the scans
kept, over the window's batches (``BatchStats.scan_fetch_bytes`` and
``scan_rows_kept``): how much of what the host scan path moves is waste.
None for a program without these counters."""


def read(rec):
    b = [x for x in rec["batches"] if "scan_rows_kept" in x]
    rows = sum(x["scan_rows_kept"] for x in b)
    return sum(x["scan_fetch_bytes"] for x in b) / rows if rows else None
