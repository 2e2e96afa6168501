"""Every cell, parked ones too, rehearsed off the chip at a tiny scale in
interpret mode: the run reaches the end of its window, every answer agrees
with the plain reference, and the run then exits non-zero with no result
line, because there is no TPU. Without ``--rehearse-scale`` it exits at
once."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SPEC = run.load_spec(parked=True)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reaches_the_end_and_fails_without_a_tpu(cell):
    p = _run("--workload", cell, "--seed", "2147483659", "--seconds", "0.02",
             "--trace", "0", "--rehearse-scale", "1")
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    line = next(ln for ln in p.stderr.splitlines()
                if ln.startswith("rehearsal: "))
    rec = json.loads(line[len("rehearsal: "):])
    assert rec["correct"] is True
    assert rec["attempted"] > 0 and rec["failed"] == 0
    e2e = {m["name"] for m in SPEC["end_to_end"]
           if cell in m.get("workloads", [cell])}
    # a tail needs 200 requests, more than a rehearsal's window holds
    assert set(rec["metrics"]) == {m for m in e2e if "p95" not in m}
    assert list(rec)[-1] == "check"
    assert "no result without a TPU" in p.stderr


def test_no_tpu_no_result():
    p = _run("--workload", run.load_spec()["workloads"][0]["name"],
             "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr
