"""Share of the window's reads the scheduler sent to an edge whole (k >= 0)
or split across edges (-2), from ``BatchStats.assignment_counts``."""


def read(rec):
    counts: dict[int, int] = {}
    for b in rec["batches"]:
        for k, n in (b["assignment_counts"] or {}).items():
            counts[int(k)] = counts.get(int(k), 0) + int(n)
    total = sum(counts.values())
    if not total:
        return None
    off_cloud = sum(n for k, n in counts.items() if k >= 0 or k == -2)
    return 100.0 * off_cloud / total
