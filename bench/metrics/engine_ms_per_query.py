"""Engine wall time per query it executed over the window
(``EngineStats.exec_seconds`` and ``queries``, differenced)."""


def read(rec):
    e = rec["engine"]
    if not e["queries"]:
        return None
    return 1000.0 * e["exec_seconds"] / e["queries"]
