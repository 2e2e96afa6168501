"""The correctness check fails what it must fail.

- The control, the plain reference at 16-bit ids in the program's place,
  reads as not correct; the reference at the stated 32 bits reads correct.
- The timed path broken underneath, on the CPU at a tiny scale (the
  harness's look for a chip skipped): an answer altered where it is
  produced, and half of each batch's answers left out, each read as not
  correct.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import control, run  # noqa: E402

# 40,000 entities: ids past 2**15 exist, as at the cells' size
CONTROL_SCALE = 20


SPEC = run.load_spec(parked=True)


@pytest.mark.parametrize("cell", ["cloud10m-anchored", "edge3-10m-round",
                                  "cloud10m-complex"])
def test_control_fails_and_reference_passes(cell):
    spec = run.load_cell(cell, SPEC)
    for seed in (5, 2**31 + 11, 77):
        bad = control.control_readings(spec, seed, 16, CONTROL_SCALE)
        assert not bad["correct"]
        assert bad["numbers"]["answers_wrong"]["value"] > 0
        good = control.control_readings(spec, seed, 16, CONTROL_SCALE,
                                         id_dtype=np.int64)
        assert good["correct"], good["numbers"]


def _alter_first(tables):
    t = tables[0]
    if len(t.bindings):
        t.bindings = t.bindings.copy()
        t.bindings[0, 0] += 1
    else:
        t.bindings = np.zeros((1, len(t.var_names)), dtype=np.int64)
    return tables


def _drop_half(tables):
    for t in tables[len(tables) // 2:]:
        t.bindings = t.bindings[:0]
    return tables


FAULTS = {"answer_altered": _alter_first,
          "half_the_batch_left_out": _drop_half}


@pytest.fixture(scope="module")
def jax_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def _with_fault(monkeypatch, fault, mode):
    from repro.sparql.endpoint import SparqlEndpoint

    armed = {"on": False}
    if mode == "round":
        orig = SparqlEndpoint.run_round

        def patched(self, user_texts, **kw):
            rep = orig(self, user_texts, **kw)
            if armed["on"]:
                rep.results = FAULTS[fault](list(rep.results))
            return rep
        monkeypatch.setattr(SparqlEndpoint, "run_round", patched)
    else:
        orig = SparqlEndpoint.query_many

        def patched(self, texts):
            tables = orig(self, texts)
            return FAULTS[fault](list(tables)) if armed["on"] else tables
        monkeypatch.setattr(SparqlEndpoint, "query_many", patched)
    return armed


@pytest.mark.parametrize("cell,mode", [("cloud10m-anchored", "endpoint"),
                                       ("edge3-10m-round", "round"),
                                       ("cloud10m-complex", "endpoint")])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_reads_not_correct(jax_cpu, monkeypatch, cell,
                                             mode, fault):
    from bench.lib import loop

    armed = _with_fault(monkeypatch, fault, mode)
    warm_then_window = loop.run

    def armed_run(queue, requests, clients, deadline_s=None):
        # warm-up runs unbroken; the window, which is what's checked, not
        armed["on"] = deadline_s is not None
        return warm_then_window(queue, requests, clients, deadline_s)
    monkeypatch.setattr(loop, "run", armed_run)
    rec = run_cell(cell)
    assert rec["correct"] is False
    assert rec["check"]["answers_wrong"]["value"] > 0


# the complex template's one constant is a country: scale 1 holds 20, and
# a window's worth of fresh texts needs scale 5's 100
REHEARSAL_SCALE = {"cloud10m-complex": 5}


def run_cell(cell):
    return run.run_cell(run.load_cell(cell, SPEC), seed=2**31 + 3,
                        seconds=0.05, trace=False,
                        scale=REHEARSAL_SCALE.get(cell, 1))
