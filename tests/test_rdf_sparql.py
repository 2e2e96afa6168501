"""RDF substrate + SPARQL matcher: unit + property tests vs oracle."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.rdf.dictionary import Dictionary
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.graph import TripleStore
from repro.sparql.matcher import match_bgp, match_oracle
from repro.sparql.query import (ParseError, QueryGraph, TriplePattern,
                                parse_sparql)


def small_store():
    d = Dictionary()
    ents = {n: d.add_entity(n) for n in
            ["a", "b", "c", "d", "e"]}
    preds = {n: d.add_predicate(n) for n in ["knows", "likes"]}
    tr = [
        ("a", "knows", "b"), ("b", "knows", "c"), ("a", "knows", "c"),
        ("c", "likes", "d"), ("b", "likes", "d"), ("d", "knows", "a"),
        ("e", "likes", "e"),
    ]
    s = np.array([ents[x[0]] for x in tr])
    p = np.array([preds[x[1]] for x in tr])
    o = np.array([ents[x[2]] for x in tr])
    return TripleStore(s, p, o, d.num_entities, d.num_predicates), d, ents, preds


def test_store_dedup_and_stats():
    st_, d, ents, preds = small_store()
    assert st_.num_triples == 7
    assert st_.pred_count[preds["knows"]] == 4
    assert st_.pred_count[preds["likes"]] == 3
    assert st_.pred_distinct_s[preds["likes"]] == 3
    assert st_.pred_distinct_o[preds["likes"]] == 2


def test_subgraph_preserves_ids():
    st_, d, ents, preds = small_store()
    sub = st_.subgraph(np.array([0, 1]))
    assert sub.num_triples == 2
    assert sub.num_entities == st_.num_entities
    # entity ids are global — decoding still works
    for sid in sub.s:
        d.entity(int(sid))


def test_parse_and_match_chain():
    st_, d, ents, preds = small_store()
    q = parse_sparql(
        "SELECT ?x ?y WHERE { ?x <knows> ?y . ?y <likes> ?z }", d)
    res = match_bgp(st_, q)
    sols, vs = match_oracle(st_, q)
    got = {tuple(row[[res.var_names.index(v) for v in vs]])
           for row in res.bindings}
    assert got == sols
    assert res.num_matches == len(sols) > 0


def test_match_constant_anchor():
    st_, d, ents, preds = small_store()
    q = parse_sparql("SELECT ?y WHERE { <a> <knows> ?y }", d)
    res = match_bgp(st_, q)
    assert sorted(res.column("?y").tolist()) == sorted(
        [ents["b"], ents["c"]])


def test_match_var_predicate():
    st_, d, ents, preds = small_store()
    q = QueryGraph([TriplePattern(ents["a"], "?p", "?y")], ["?p", "?y"])
    res = match_bgp(st_, q)
    sols, vs = match_oracle(st_, q)
    got = {tuple(row[[res.var_names.index(v) for v in vs]])
           for row in res.bindings}
    assert got == sols


def test_match_self_loop_var():
    st_, d, ents, preds = small_store()
    q = QueryGraph([TriplePattern("?x", preds["likes"], "?x")], ["?x"])
    res = match_bgp(st_, q)
    assert res.column("?x").tolist() == [ents["e"]]


def test_match_cycle():
    st_, d, ents, preds = small_store()
    # triangle a->b->c with a->c
    q = QueryGraph([
        TriplePattern("?x", preds["knows"], "?y"),
        TriplePattern("?y", preds["knows"], "?z"),
        TriplePattern("?x", preds["knows"], "?z"),
    ], ["?x", "?y", "?z"])
    res = match_bgp(st_, q)
    sols, vs = match_oracle(st_, q)
    got = {tuple(row[[res.var_names.index(v) for v in vs]])
           for row in res.bindings}
    assert got == sols
    assert (ents["a"], ents["b"], ents["c"]) in got


def test_edge_ids_are_matches():
    st_, d, ents, preds = small_store()
    q = parse_sparql("SELECT ?x ?y ?z WHERE { ?x <knows> ?y . ?y <likes> ?z }", d)
    res = match_bgp(st_, q)
    # each row's edge ids must reproduce the bindings
    for r in range(res.num_matches):
        e0, e1 = res.edge_ids[r]
        assert st_.s[e0] == res.column("?x")[r]
        assert st_.o[e0] == res.column("?y")[r]
        assert st_.s[e1] == res.column("?y")[r]
        assert st_.o[e1] == res.column("?z")[r]


def test_parse_errors():
    st_, d, ents, preds = small_store()
    with pytest.raises(ParseError):
        parse_sparql("SELECT ?x WHERE { ?x <nosuchpred> ?y }", d)
    with pytest.raises(ParseError):
        parse_sparql("ASK { ?x <knows> ?y }", d)


def test_generator_deterministic_and_nonempty():
    g1 = generate_watdiv_like(scale=1.0, seed=7)
    g2 = generate_watdiv_like(scale=1.0, seed=7)
    assert g1.store.num_triples == g2.store.num_triples > 1000
    assert np.array_equal(g1.store.triples(), g2.store.triples())


def test_workload_parses_and_matches():
    g = generate_watdiv_like(scale=0.5, seed=3)
    queries = workload_sparql(g, 10, seed=1)
    assert len(queries) == 10
    nonempty = 0
    for qs in queries:
        q = parse_sparql(qs, g.dictionary)
        assert q.is_weakly_connected()
        res = match_bgp(g.store, q)
        nonempty += res.num_matches > 0
    assert nonempty >= 5  # most template instantiations hit data


# ---------------------------------------------------------------------------
# property tests: random graphs + random small queries vs oracle
# ---------------------------------------------------------------------------

@st.composite
def random_case(draw):
    n_ent = draw(st.integers(3, 8))
    n_pred = draw(st.integers(1, 3))
    n_trip = draw(st.integers(1, 15))
    s = draw(st.lists(st.integers(0, n_ent - 1), min_size=n_trip,
                      max_size=n_trip))
    p = draw(st.lists(st.integers(0, n_pred - 1), min_size=n_trip,
                      max_size=n_trip))
    o = draw(st.lists(st.integers(0, n_ent - 1), min_size=n_trip,
                      max_size=n_trip))
    n_pat = draw(st.integers(1, 3))
    pats = []
    var_pool = ["?a", "?b", "?c", "?d"]
    for _ in range(n_pat):
        def term():
            if draw(st.booleans()):
                return draw(st.sampled_from(var_pool))
            return draw(st.integers(0, n_ent - 1))
        pred = (draw(st.sampled_from(var_pool))
                if draw(st.integers(0, 4)) == 0
                else draw(st.integers(0, n_pred - 1)))
        pats.append(TriplePattern(term(), pred, term()))
    return (np.array(s), np.array(p), np.array(o), n_ent, n_pred, pats)


@given(random_case())
@settings(max_examples=60, deadline=None)
def test_matcher_equals_oracle(case):
    s, p, o, n_ent, n_pred, pats = case
    store = TripleStore(s, p, o, n_ent, n_pred)
    q = QueryGraph(pats, [])
    res = match_bgp(store, q)
    sols, vs = match_oracle(store, q)
    if not vs:  # all-constant query: matcher returns unit/empty table
        assert (res.num_matches > 0) == (len(sols) > 0)
        return
    got = {tuple(row[[res.var_names.index(v) for v in vs]])
           for row in res.bindings}
    assert got == sols
