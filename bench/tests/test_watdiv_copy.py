"""The benchmark's generator copy gives what the program's generator gives:
the same triples, dictionary and template draws."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.lib import watdiv  # noqa: E402


@pytest.mark.parametrize("scale", [1, 5])
@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_same_triples_dictionary_and_queries(scale, seed):
    from repro.rdf.dictionary import Dictionary
    from repro.rdf.generator import generate_watdiv_like, workload_sparql

    want = generate_watdiv_like(scale=scale, seed=seed)
    got = watdiv.generate(scale, seed)
    for a, b in ((got.s, want.store.s), (got.p, want.store.p),
                 (got.o, want.store.o)):
        np.testing.assert_array_equal(a, b)
    d = Dictionary.from_arrays({"entities": got.entities,
                                "predicates": np.asarray(got.predicates)})
    assert d.to_arrays()["entities"].tolist() == \
        want.dictionary.to_arrays()["entities"].tolist()
    assert got.predicates == want.dictionary.to_arrays()["predicates"].tolist()
    for cname, ids in want.class_of.items():
        np.testing.assert_array_equal(got.class_ids[cname], ids)
    for k, names in enumerate([None, ["anchored_star", "anchored_chain"],
                               ["complex"]]):
        assert watdiv.workload_sparql(got, 12, seed + k, names) == \
            workload_sparql(want, 12, seed=seed + k, templates=names)
