"""Peaks by device kind, and each kernel operation's bytes from its shapes,
worked out by hand."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.lib import peaks, work  # noqa: E402


def test_bytes_by_hand():
    # a 4M-row shard: three int32 columns, 4 B each, read once
    assert work.triple_scan_bytes(4_194_304) == 3 * 4 * 4_194_304 == 50_331_648
    # 1M sorted keys and 65,536 probes, int32, each read once
    assert work.probe_sorted_bytes(1_048_576, 65_536) == 4 * 1_114_112
    # the fused scan and probe: both of the above inputs, once
    assert work.scan_probe_bytes(4_194_304, 1_048_576) == \
        50_331_648 + 4_194_304


def test_peaks_by_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
