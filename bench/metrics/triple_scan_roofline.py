"""``triple_scan``'s share of its roofline: the bytes its operation needs
(``bench/lib/work.py``) at the chip's peak HBM bandwidth
(``bench/lib/peaks.py``), over the device time of its events in the
trace. None where the window ran no such kernel."""

from bench.lib.peaks import peaks


def read(rec):
    k = (rec["trace"] or {}).get("kernels", {}).get("triple_scan")
    if not k or not k["seconds"] or not k["bytes"]:
        return None
    floor_s = k["bytes"] / peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / k["seconds"]
