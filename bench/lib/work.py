"""The bytes each query kernel's operation has to move, from its shapes.

These count what the operation needs, not what an implementation
happens to do: each input read once from HBM, at its own width. A
faster or fused implementation of the same operation reads against the
same floor, so its share of the roofline cannot pass 100% unless the
time leaves out work.

- ``triple_scan`` / ``triple_scan_many``: match Q patterns against a
  shard's T triples: its three int32 columns, read once (Q patterns are
  one pass over the shard).
- ``probe_sorted`` / ``probe_sorted_many``: find the run of each of P
  probe values in K sorted int32 keys: the probes and the keys, read once.
- ``scan_probe``: scan a shard's T triples and probe each row's subject
  or object into K sorted keys: the three columns and the keys, read once.
"""

from __future__ import annotations

INT32 = 4


def triple_scan_bytes(t: int) -> int:
    return 3 * INT32 * t


def probe_sorted_bytes(k: int, p: int) -> int:
    return INT32 * (k + p)


def scan_probe_bytes(t: int, k: int) -> int:
    return 3 * INT32 * t + INT32 * k
