"""Minimum DFS code canonicalization + pattern index + induced subgraphs."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.induced import (induced_edge_ids, induced_edge_ids_semijoin,
                                induced_subgraph, pattern_to_query)
from repro.core.pattern import (Pattern, PatternIndex, min_dfs_code,
                                pattern_of)
from repro.core.placement import (DynamicPlacement, PatternProfile,
                                  greedy_knapsack)
from repro.rdf.dictionary import Dictionary
from repro.rdf.graph import TripleStore
from repro.sparql.matcher import match_bgp
from repro.sparql.query import QueryGraph, TriplePattern


def permute(edges, n, perm):
    return tuple(sorted((perm[u], perm[v], l) for (u, v, l) in edges))


def all_perms(n):
    import itertools
    return list(itertools.permutations(range(n)))


# -- canonical code properties ----------------------------------------------

CASES = [
    # (edges, n_vertices)
    (((0, 1, 5),), 2),                                   # single edge
    (((0, 0, 3),), 1),                                   # self loop
    (((0, 1, 1), (1, 2, 1)), 3),                         # chain same label
    (((0, 1, 1), (1, 2, 2)), 3),                         # chain diff labels
    (((0, 1, 1), (0, 2, 1), (0, 3, 1)), 4),              # star
    (((0, 1, 1), (1, 2, 1), (2, 0, 1)), 3),              # directed 3-cycle
    (((0, 1, 1), (1, 0, 1)), 2),                         # 2-cycle
    (((0, 1, 1), (0, 1, 2)), 2),                         # parallel edges
    (((0, 1, 1), (1, 2, 2), (2, 3, 3), (3, 0, 4)), 4),   # 4-cycle labeled
    (((0, 1, 1), (1, 2, 1), (0, 2, 1), (2, 3, 2)), 4),   # triangle + tail
]


@pytest.mark.parametrize("edges,n", CASES)
def test_code_permutation_invariant(edges, n):
    base = min_dfs_code(edges, n)
    for perm in all_perms(n):
        assert min_dfs_code(permute(edges, n, perm), n) == base


def test_direction_matters():
    chain = min_dfs_code(((0, 1, 1), (1, 2, 1)), 3)      # a->b->c
    inv = min_dfs_code(((0, 1, 1), (2, 1, 1)), 3)        # a->b<-c
    assert chain != inv


def test_labels_matter():
    c1 = min_dfs_code(((0, 1, 1), (1, 2, 2)), 3)
    c2 = min_dfs_code(((0, 1, 2), (1, 2, 1)), 3)
    assert c1 != c2


def test_nonisomorphic_same_degrees():
    # two graphs, same degree sequence, different structure:
    # 6-cycle vs two 3-cycles are not weakly-connected comparable; use
    # directed: path+backedge variants
    g1 = ((0, 1, 1), (1, 2, 1), (2, 3, 1), (3, 0, 1))    # 4-cycle
    g2 = ((0, 1, 1), (1, 0, 1), (2, 3, 1), (3, 2, 1))    # not connected
    with pytest.raises(ValueError):
        min_dfs_code(g2, 4)
    assert min_dfs_code(g1, 4)


@st.composite
def random_pattern(draw):
    n = draw(st.integers(2, 5))
    n_extra = draw(st.integers(0, 4))
    # build a random connected graph: spanning tree + extra edges
    edges = set()
    for v in range(1, n):
        u = draw(st.integers(0, v - 1))
        if draw(st.booleans()):
            u, v2 = u, v
        else:
            u, v2 = v, u
        edges.add((u, v2, draw(st.integers(0, 2))))
    for _ in range(n_extra):
        u = draw(st.integers(0, n - 1))
        v = draw(st.integers(0, n - 1))
        edges.add((u, v, draw(st.integers(0, 2))))
    return tuple(sorted(edges)), n


@given(random_pattern(), st.randoms())
@settings(max_examples=80, deadline=None)
def test_code_invariance_random(pat, rnd):
    edges, n = pat
    base = min_dfs_code(edges, n)
    perm = list(range(n))
    rnd.shuffle(perm)
    assert min_dfs_code(permute(edges, n, perm), n) == base


@given(random_pattern(), random_pattern())
@settings(max_examples=60, deadline=None)
def test_code_distinguishes(pat_a, pat_b):
    """Equal codes -> actually isomorphic (verified by brute force)."""
    ea, na = pat_a
    eb, nb = pat_b
    ca, cb = min_dfs_code(ea, na), min_dfs_code(eb, nb)
    if (na, ca) == (nb, cb):
        iso = any(permute(ea, na, perm) == tuple(sorted(eb))
                  for perm in all_perms(na))
        assert iso, f"collision: {ea} vs {eb}"


# -- pattern extraction -------------------------------------------------------

def test_pattern_of_merges_constants():
    # <a> k ?y . <a> l ?z -> constant 'a' is one vertex
    q = QueryGraph([TriplePattern(7, 0, "?y"), TriplePattern(7, 1, "?z")], [])
    p = pattern_of(q)
    assert p.n_vertices == 3 and p.n_edges == 2
    # isomorphic query with different constant
    q2 = QueryGraph([TriplePattern(9, 0, "?a"), TriplePattern(9, 1, "?b")], [])
    assert pattern_of(q2).isomorphic_to(p)
    # different structure: two separate subjects would not be connected
    q3 = QueryGraph([TriplePattern("?x", 0, "?y"),
                     TriplePattern("?x", 1, "?z")], [])
    assert pattern_of(q3).isomorphic_to(p)


def test_pattern_index_roundtrip():
    idx = PatternIndex()
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?y", 1, "?z")], [])
    p = pattern_of(q)
    idx.add(p, "ES1")
    # same shape, renamed vars + a constant
    q2 = QueryGraph([TriplePattern(3, 0, "?b"), TriplePattern("?b", 1, "?c")],
                    [])
    assert idx.lookup_query(q2) == ["ES1"]
    # different predicate -> miss
    q3 = QueryGraph([TriplePattern("?x", 1, "?y"),
                     TriplePattern("?y", 0, "?z")], [])
    assert idx.lookup_query(q3) == []


def test_shared_predicate_variable_not_indexable():
    q = QueryGraph([TriplePattern("?x", "?p", "?y"),
                    TriplePattern("?y", "?p", "?z")], [])
    p = pattern_of(q)
    assert not p.indexable
    idx = PatternIndex()
    with pytest.raises(ValueError):
        idx.add(p, "x")
    assert idx.lookup(p) == []


# -- induced subgraphs ---------------------------------------------------------

def star_store():
    d = Dictionary()
    for i in range(10):
        d.add_entity(f"e{i}")
    k = d.add_predicate("k")
    l = d.add_predicate("l")
    # e0 -k-> e1..e3 ; e1 -l-> e4 ; e5 -k-> e6 (no l continuation)
    s = np.array([0, 0, 0, 1, 5])
    p = np.array([k, k, k, l, k])
    o = np.array([1, 2, 3, 4, 6])
    return TripleStore(s, p, o, d.num_entities, d.num_predicates), d, (k, l)


def test_induced_exact_chain():
    store, d, (k, l) = star_store()
    # pattern ?a -k-> ?b -l-> ?c : only e0->e1->e4 participates
    q = QueryGraph([TriplePattern("?a", k, "?b"),
                    TriplePattern("?b", l, "?c")], [])
    p = pattern_of(q)
    eids = induced_edge_ids(store, [p])
    sub = store.subgraph(eids)
    assert sub.num_triples == 2
    # completeness: every match of an isomorphic query over G is in G[P]
    res_g = match_bgp(store, q)
    res_sub = match_bgp(sub, q)
    assert res_g.num_matches == res_sub.num_matches == 1


def test_semijoin_superset_and_acyclic_exact():
    store, d, (k, l) = star_store()
    q = QueryGraph([TriplePattern("?a", k, "?b"),
                    TriplePattern("?b", l, "?c")], [])
    p = pattern_of(q)
    exact = set(induced_edge_ids(store, [p]).tolist())
    semi = set(induced_edge_ids_semijoin(store, [p]).tolist())
    assert exact <= semi
    assert exact == semi  # acyclic pattern -> full reducer is exact


@st.composite
def random_store_and_query(draw):
    n_ent = draw(st.integers(3, 7))
    n_pred = draw(st.integers(1, 3))
    n_trip = draw(st.integers(2, 14))
    s = draw(st.lists(st.integers(0, n_ent - 1), min_size=n_trip,
                      max_size=n_trip))
    p = draw(st.lists(st.integers(0, n_pred - 1), min_size=n_trip,
                      max_size=n_trip))
    o = draw(st.lists(st.integers(0, n_ent - 1), min_size=n_trip,
                      max_size=n_trip))
    # connected random query (2-3 patterns)
    npat = draw(st.integers(1, 3))
    vars_ = ["?a", "?b", "?c", "?d"]
    pats = [TriplePattern("?a", draw(st.integers(0, n_pred - 1)), "?b")]
    used = ["?a", "?b"]
    for i in range(1, npat):
        anchor = draw(st.sampled_from(used))
        nv = vars_[len(used)] if len(used) < len(vars_) else "?a"
        if draw(st.booleans()):
            pats.append(TriplePattern(anchor,
                                      draw(st.integers(0, n_pred - 1)), nv))
        else:
            pats.append(TriplePattern(nv, draw(st.integers(0, n_pred - 1)),
                                      anchor))
        if nv not in used:
            used.append(nv)
    return (np.array(s), np.array(p), np.array(o), n_ent, n_pred,
            QueryGraph(pats, []))


@given(random_store_and_query())
@settings(max_examples=40, deadline=None)
def test_induced_completeness_property(case):
    """Paper's core guarantee: matches of q over G == matches over G[P] when
    q is isomorphic to a stored pattern p (here p = pattern_of(q))."""
    s, p, o, ne, npred, q = case
    store = TripleStore(s, p, o, ne, npred)
    pat = pattern_of(q)
    sub = induced_subgraph(store, [pat], method="exact")
    rg = match_bgp(store, q)
    rs = match_bgp(sub, q)
    def rows(res):
        if not res.var_names:
            return {()} if res.num_matches else set()
        orderv = sorted(res.var_names)
        idx = [res.var_names.index(v) for v in orderv]
        return {tuple(r[idx]) for r in res.bindings}
    assert rows(rg) == rows(rs)
    # semijoin superset never loses matches either
    sub2 = induced_subgraph(store, [pat], method="semijoin")
    rs2 = match_bgp(sub2, q)
    assert rows(rg) == rows(rs2)


# -- placement -----------------------------------------------------------------

def test_greedy_knapsack_prefers_ratio():
    profs = [
        PatternProfile(None, frequency=100, size_bytes=100),   # ratio 1.0
        PatternProfile(None, frequency=10, size_bytes=1),      # ratio 10
        PatternProfile(None, frequency=50, size_bytes=100),    # ratio 0.5
    ]
    chosen = greedy_knapsack(profs, budget_bytes=101)
    assert chosen == [0, 1]


def test_dynamic_placement_evicts_cold():
    q_hot = QueryGraph([TriplePattern("?x", 0, "?y")], [])
    q_cold = QueryGraph([TriplePattern("?x", 1, "?y")], [])
    hot, cold = pattern_of(q_hot), pattern_of(q_cold)
    dp = DynamicPlacement(budget_bytes=100)
    dp.set_size(hot, 80)
    dp.set_size(cold, 80)
    dp.observe(cold, 5)
    added, evicted = dp.rebalance()
    assert [p.key for p in added] == [cold.key]
    for _ in range(10):
        dp.decay_round()
        dp.observe(hot, 10)
    added, evicted = dp.rebalance()
    assert [p.key for p in added] == [hot.key]
    assert [p.key for p in evicted] == [cold.key]
    assert dp.used_bytes() == 80
