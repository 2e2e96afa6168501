"""The trace reduction, pinned on a trace recorded on a TPU v5e: one
traced window of ``cloud10m-anchored`` (one admission batch: an anchored
star and an anchored chain, each a fused ``scan_probe`` and two
``probe_sorted_many`` calls). Recorded with ``bench/run.py --trace 1``."""

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import trace, work  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "cloud10m-anchored.xplane.pb.gz"

# the triple scan's custom call as a TPU v5e trace names it
TRIPLE_SCAN_MANY = (
    '%triple_scan_many.1 = s32[2,2492416]{1,0:T(2,128)} custom-call('
    's32[2,3]{1,0:T(2,128)} %patterns.1, s32[2492416]{0:T(1024)} '
    '%cols_0_.1, s32[2492416]{0:T(1024)} %cols_1_.1, '
    's32[2492416]{0:T(1024)} %cols_2_.1), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{s32[2,3]{1,0}, s32[2492416]{0}, s32[2492416]{0}, s32[2492416]{0}}, '
    'frontend_attributes={kernel_metadata={}}')


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    data = gzip.decompress(RECORDED.read_bytes())
    return trace.reduce_profile(ProfileData.from_serialized_xspace(data))


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(10.163109566, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(9.934061263, abs=1e-9)


def test_kernels(reduced):
    k = reduced["kernels"]
    assert set(k) == {"scan_probe", "probe_sorted"}
    assert k["scan_probe"]["events"] == 2
    assert k["scan_probe"]["seconds"] == pytest.approx(9.876278966, abs=1e-9)
    # star: a 3,520,512-row shard against 518,144 keys; chain: 2,490,368
    # rows against 600,064; each three int32 columns and the keys, once
    assert k["scan_probe"]["bytes"] == 12 * 3_520_512 + 4 * 518_144 \
        + 12 * 2_490_368 + 4 * 600_064 == 76_603_392
    assert k["scan_probe"]["bytes"] == work.scan_probe_bytes(
        3_520_512, 518_144) + work.scan_probe_bytes(2_490_368, 600_064)
    # 1,349,632 and 518,144 keys, each probed by one row of 512 values
    assert k["probe_sorted"]["events"] == 2
    assert k["probe_sorted"]["bytes"] == 4 * (1_349_632 + 512) \
        + 4 * (518_144 + 512) == 7_475_200


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0] == "jit_scan_probe/scan_probe"
    assert ops[2][0] == "jit_probe_sorted_many/probe_sorted_many"
    assert len(ops) == 10 and len(reduced["breakdown"]["idle_gaps"]) == 10
    assert all(label in ("engine_batch", "window")
               for label, _ in reduced["breakdown"]["idle_gaps"])


def test_metrics_from_the_reduction(reduced):
    sys.path.insert(0, str(ROOT))
    from bench import run

    rec = {"trace": reduced, "device": {"kind": "TPU v5 lite"}}
    assert run.reader("scan_probe_roofline")(rec) == pytest.approx(
        100 * 76_603_392 / 819e9 / 9.876278966, rel=1e-9)
    assert run.reader("probe_sorted_roofline")(rec) == pytest.approx(
        0.3053619, rel=1e-6)
    assert run.reader("idle_share")(rec) == pytest.approx(2.2537227,
                                                          rel=1e-6)
    assert run.reader("triple_scan_roofline")(rec) is None


def test_triple_scan_named_and_sized():
    assert trace.kernel_of(TRIPLE_SCAN_MANY) == "triple_scan"
    shapes = trace.operand_shapes(TRIPLE_SCAN_MANY)
    assert shapes == [(2, 3)] + [(2_492_416,)] * 3
    assert trace.kernel_bytes("triple_scan", shapes) == 12 * 2_492_416
    assert trace.kernel_of("%fusion = s32[5]{0} fusion(s32[5]{0} %a)") \
        is None
