"""Batched multi-backend query engine: oracle equivalence, cache
correctness, and the paper's completeness guarantee through the batched
path (G[P] matches == G matches, also after rebalancing)."""

import numpy as np
import pytest

from repro.core.cost import (SystemParams, measured_query_cost,
                             measured_query_cost_batch)
from repro.core.pattern import pattern_of
from repro.edge.system import EdgeCloudSystem
from repro.kernels.triple_scan import triple_scan, triple_scan_many
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.graph import TripleStore
from repro.sparql.engine import (JaxBackend, MatcherBackend, QueryEngine,
                                 available_backends, get_backend, query_key,
                                 scan_key)
from repro.sparql.matcher import match_bgp, match_oracle
from repro.sparql.query import QueryGraph, TriplePattern, parse_sparql

BACKENDS = ["numpy", "jax"]


def sol_rows(res, var_order=None):
    """Solution multiset with columns ordered by variable name."""
    order = var_order or sorted(res.var_names)
    idx = [res.var_names.index(v) for v in order]
    return sorted(map(tuple, res.bindings[:, idx].tolist()))


def random_store(rng, n_ent=12, n_pred=3, n_trip=40):
    return TripleStore(rng.integers(0, n_ent, n_trip),
                       rng.integers(0, n_pred, n_trip),
                       rng.integers(0, n_ent, n_trip), n_ent, n_pred)


# adversarial BGPs: repeated variables (incl. within one pattern), variable
# predicates, cartesian components, and a constant pair guaranteed empty
ADVERSARIAL = [
    [TriplePattern("?x", 0, "?x")],                         # self loop
    [TriplePattern("?x", "?p", "?y")],                      # var predicate
    [TriplePattern("?x", 0, "?y"), TriplePattern("?a", 1, "?b")],  # cartesian
    [TriplePattern("?x", 0, "?y"), TriplePattern("?y", 0, "?x")],  # 2-cycle
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?y", "?p", "?z")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?x", 1, "?y")],  # parallel
    [TriplePattern(0, 0, 1), TriplePattern("?x", 0, 1)],    # ground pattern
    [TriplePattern("?x", "?x", "?x")],                      # s == p == o
]


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_equals_matcher_equals_oracle(backend):
    """Equivalence matrix: match_bgp == match_oracle == batched engine."""
    rng = np.random.default_rng(0)
    eng = QueryEngine(backend=backend)
    for trial in range(6):
        store = random_store(rng, n_trip=int(rng.integers(5, 50)))
        queries = [QueryGraph(pats, []) for pats in ADVERSARIAL]
        batch = eng.execute_batch(store, queries)
        for q, res in zip(queries, batch):
            ref = match_bgp(store, q)
            assert sol_rows(res) == sol_rows(ref)
            sols, vs = match_oracle(store, q)
            if vs:
                got = {tuple(r) for r in res.project(vs).tolist()}
                assert got == sols
            else:
                assert (res.num_matches > 0) == (len(sols) > 0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_on_workload_queries(backend):
    g = generate_watdiv_like(scale=0.5, seed=3)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 12, seed=1)]
    eng = QueryEngine(backend=backend)
    for q, res in zip(qs, eng.execute_batch(g.store, qs)):
        assert sol_rows(res) == sol_rows(match_bgp(g.store, q))


def test_backends_registry():
    assert {"numpy", "jax"} <= set(available_backends())
    assert isinstance(get_backend("jax"), MatcherBackend)
    with pytest.raises(KeyError):
        get_backend("no-such-backend")


def test_scan_and_query_keys():
    # scan identity ignores variable names but not repetition structure
    assert scan_key(TriplePattern("?x", 0, "?x")) == \
        scan_key(TriplePattern("?y", 0, "?y"))
    assert scan_key(TriplePattern("?x", 0, "?y")) != \
        scan_key(TriplePattern("?x", 0, "?x"))
    # alpha-equivalent queries share a cache key; constants differ it
    qa = QueryGraph([TriplePattern("?x", 0, "?y")], [])
    qb = QueryGraph([TriplePattern("?u", 0, "?v")], [])
    qc = QueryGraph([TriplePattern("?x", 1, "?y")], [])
    assert query_key(qa)[0] == query_key(qb)[0]
    assert query_key(qa)[0] != query_key(qc)[0]


def test_alpha_equivalent_queries_share_cache_with_correct_names():
    rng = np.random.default_rng(5)
    store = random_store(rng)
    eng = QueryEngine()
    qa = QueryGraph([TriplePattern("?x", 0, "?y"),
                     TriplePattern("?y", 1, "?z")], [])
    qb = QueryGraph([TriplePattern("?u", 0, "?v"),
                     TriplePattern("?v", 1, "?w")], [])
    ra = eng.execute(store, qa)
    rb = eng.execute(store, qb)
    assert eng.stats.cache_hits == 1         # qb resolved from qa's entry
    assert set(rb.var_names) == {"?u", "?v", "?w"}
    assert sol_rows(ra, ["?x", "?y", "?z"]) == sol_rows(rb, ["?u", "?v", "?w"])


def test_cache_hit_after_repeat_and_invalidation_on_store_change():
    g = generate_watdiv_like(scale=0.5, seed=7)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 8, seed=2)]
    eng = QueryEngine()
    eng.execute_batch(g.store, qs)
    h0, m0 = eng.stats.cache_hits, eng.stats.cache_misses
    again = eng.execute_batch(g.store, qs)
    assert eng.stats.cache_hits - h0 == len(qs)      # all hits on repeat
    assert eng.stats.cache_misses == m0
    # a DIFFERENT store (e.g. post-rebalance deployment) must not serve
    # the old entries: the version token differs
    sub = g.store.subgraph(np.arange(g.store.num_triples // 2))
    assert sub.version != g.store.version
    for q in qs:
        res_sub = eng.execute(sub, q)
        assert sol_rows(res_sub) == sol_rows(match_bgp(sub, q))
    # original store still hits its own (untouched) entries
    h1 = eng.stats.cache_hits
    eng.execute_batch(g.store, qs)
    assert eng.stats.cache_hits - h1 == len(qs)
    for q, res in zip(qs, again):
        assert sol_rows(res) == sol_rows(match_bgp(g.store, q))


def test_cache_lru_eviction_bounds_entries():
    rng = np.random.default_rng(9)
    store = random_store(rng)
    eng = QueryEngine(cache_size=4)
    qs = [QueryGraph([TriplePattern("?x", 0, i)], []) for i in range(10)]
    eng.execute_batch(store, qs)
    assert len(eng._cache) == 4
    assert eng.stats.cache_evictions == 6


def test_scan_dedup_across_batch():
    rng = np.random.default_rng(11)
    store = random_store(rng)
    eng = QueryEngine(cache_size=0)          # isolate scan dedup from cache
    q = QueryGraph([TriplePattern("?x", 0, "?y")], [])
    eng.execute_batch(store, [q] * 16)
    assert eng.stats.scans_requested == 16
    assert eng.stats.scans_executed == 1


def test_cache_put_overwrite_does_not_leak_bytes():
    """Regression: overwriting a result-cache key must release the
    displaced entry's bytes, not inflate _cached_bytes forever."""
    rng = np.random.default_rng(21)
    store = random_store(rng)
    eng = QueryEngine()
    res = eng.execute(store, QueryGraph([TriplePattern("?x", 0, "?y")], []))
    want = eng._cached_bytes
    assert want == eng._result_bytes(res) > 0
    for _ in range(5):                       # repeated overwrites of one key
        eng._cache_put(("k",), res)
    assert eng._cached_bytes == want + eng._result_bytes(res)
    assert eng.stats.cache_evictions == 0    # no spurious evictions


def test_scan_cache_survives_between_batches():
    """Scan LRU: with the result cache disabled, a repeated batch re-joins
    but serves its candidate scans from the cross-round cache."""
    rng = np.random.default_rng(25)
    store = random_store(rng)
    eng = QueryEngine(cache_size=0)          # force re-execution every batch
    qs = [QueryGraph([TriplePattern("?x", p, "?y")], []) for p in range(3)]
    eng.execute_batch(store, qs)
    assert eng.stats.scans_executed == 3
    assert eng.stats.scan_cache_hits == 0
    eng.execute_batch(store, qs)             # scans resolve from the LRU
    assert eng.stats.scans_executed == 3
    assert eng.stats.scan_cache_hits == 3
    # a different store version must not reuse the entries
    sub = store.subgraph(np.arange(store.num_triples // 2))
    eng.execute_batch(sub, qs)
    assert eng.stats.scans_executed == 6
    for q in qs:
        assert sol_rows(eng.execute(sub, q)) == sol_rows(match_bgp(sub, q))


def test_scan_cache_count_bound_with_empty_results():
    """Zero-byte (empty-candidate) entries must still be bounded: the byte
    cap alone would never evict them."""
    rng = np.random.default_rng(29)
    store = random_store(rng, n_ent=12)
    eng = QueryEngine(cache_size=0, scan_cache_size=4)
    # objects >= n_ent never match -> every scan result is empty (0 bytes)
    qs = [QueryGraph([TriplePattern("?x", 0, 1000 + i)], [])
          for i in range(12)]
    eng.execute_batch(store, qs)
    assert all(r.num_matches == 0 for r in eng.execute_batch(store, qs))
    assert len(eng._scan_cache) <= 4
    assert eng.stats.scan_cache_evictions >= 8


def test_scan_cache_byte_bound_eviction():
    rng = np.random.default_rng(27)
    store = random_store(rng, n_trip=200)
    one_scan = store.pred_tids(0).nbytes
    eng = QueryEngine(cache_size=0, scan_cache_bytes=one_scan * 2)
    qs = [QueryGraph([TriplePattern("?x", "?p", i)], []) for i in range(8)]
    eng.execute_batch(store, qs)
    assert eng.stats.scan_cache_evictions > 0
    assert eng._scan_cached_bytes <= eng.scan_cache_bytes
    # entries are (CandidateParts, put-time global-id offset)
    assert sum(parts.nbytes for parts, _ in eng._scan_cache.values()) == \
        eng._scan_cached_bytes
    eng.clear_cache()
    assert eng._scan_cached_bytes == 0 and not eng._scan_cache


def test_triple_scan_many_matches_single():
    rng = np.random.default_rng(13)
    tr = rng.integers(0, 30, (1000, 3)).astype(np.int32)
    import jax.numpy as jnp
    cols = tuple(jnp.asarray(tr[:, c]) for c in range(3))
    pats = np.array([[-1, 1, -1], [4, -1, -1], [-1, -1, -1], [2, 1, 9]],
                    np.int32)
    many = np.asarray(triple_scan_many(cols, jnp.asarray(pats),
                                       bt=1024, interpret=True))
    for i in range(len(pats)):
        one = np.asarray(triple_scan(cols, jnp.asarray(pats[i]),
                                     bt=1024, interpret=True))
        assert np.array_equal(many[i], one)


def test_jax_backend_prescan_equals_numpy_candidates():
    rng = np.random.default_rng(17)
    store = random_store(rng, n_trip=200)
    jb = JaxBackend(bt=1024)
    nb = get_backend("numpy")
    tps = [tp for pats in ADVERSARIAL for tp in pats]
    pre = jb.prescan(store, tps)
    for tp in tps:
        want = np.sort(nb.candidates(store, tp))
        assert np.array_equal(np.sort(pre[scan_key(tp)]), want)
        assert np.array_equal(np.sort(jb.candidates(store, tp)), want)


def test_measured_cost_hooks_match_direct_path():
    g = generate_watdiv_like(scale=0.5, seed=19)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 6, seed=4)]
    eng = QueryEngine()
    c_b, w_b, n_b = measured_query_cost_batch(g.store, qs, eng)
    for i, q in enumerate(qs):
        c, w, n = measured_query_cost(g.store, q)
        assert (c, w, n) == (c_b[i], w_b[i], n_b[i])
        assert measured_query_cost(g.store, q, engine=eng) == (c, w, n)


# ---------------------------------------------------------------------------
# end-to-end: completeness guarantee through the batched system path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=BACKENDS)
def batched_system(request):
    g = generate_watdiv_like(scale=1.0, seed=42)
    params = SystemParams.synthetic(n_users=12, n_edges=3, seed=7)
    sys_ = EdgeCloudSystem(g.store, g.dictionary, params,
                           storage_budgets=200_000, backend=request.param)
    history = [workload_sparql(g, 4, seed=100 + n) for n in range(12)]
    sys_.prepare(history)
    return g, sys_


def make_queries(g, sys_, n, seed):
    texts = workload_sparql(g, n, seed=seed)
    return [(i % sys_.params.N, parse_sparql(t, g.dictionary))
            for i, t in enumerate(texts)]


def _check_completeness(g, sys_, queries):
    """Matches over G[P] == matches over G for pattern-isomorphic queries,
    exercised through the engine (the paper's Def. 5 guarantee)."""
    checked = 0
    for (_, q) in queries:
        p = pattern_of(q)
        want = sol_rows(sys_.engine.execute(sys_.cloud.store, q))
        for es in sys_.edges:
            if es.can_execute(p):
                assert sol_rows(sys_.engine.execute(es.store, q)) == want
                checked += 1
    return checked


def test_batched_round_matches_per_query_round(batched_system):
    g, sys_ = batched_system
    queries = make_queries(g, sys_, n=12, seed=11)
    rep_loop = sys_.run_round(queries, policy="greedy", observe=False)
    rep_batch = sys_.run_round_batched(queries, policy="greedy",
                                       observe=False)
    assert rep_batch.assignment_counts == rep_loop.assignment_counts
    for a, b in zip(rep_loop.outcomes, rep_batch.outcomes):
        assert a.assigned_to == b.assigned_to
        assert a.n_matches == b.n_matches


def test_completeness_through_batched_path_and_rebalance(batched_system):
    g, sys_ = batched_system
    queries = make_queries(g, sys_, n=16, seed=13)
    assert _check_completeness(g, sys_, queries) >= 3
    # drive frequencies through the batched round, then rebalance (new edge
    # stores -> new version tokens -> cache cannot serve stale results)
    for _ in range(3):
        sys_.run_round_batched(queries, policy="greedy", execute=True)
    sys_.rebalance_all()
    assert _check_completeness(g, sys_, queries) >= 3
    rep = sys_.run_round_batched(queries, policy="greedy", execute=True)
    for o in rep.outcomes:
        if o.assigned_to >= 0:
            assert o.assigned_to in o.executable_edges


def test_sparql_serving_runner():
    """runtime.serving executes SPARQL payload batches via the engine."""
    from repro.runtime.serving import (OffloadServingPool, Replica,
                                       make_sparql_runner)
    g = generate_watdiv_like(scale=0.5, seed=23)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 8, seed=6)]
    eng = QueryEngine()
    runner = make_sparql_runner(g.store, eng)
    pool = OffloadServingPool(
        replicas=[Replica(0, classes={0}, cycles_per_s=2e8, link_bps=75e6,
                          runner=runner)],
        cloud_runner=runner)
    requests = [{"class_id": 0, "cycles": 1e6, "result_bits": 1e4,
                 "payload": q} for q in qs]
    served = pool.admit(requests, policy="greedy")
    assert len(served.responses) == len(qs)
    for q, res in zip(qs, served.responses):
        assert sol_rows(res) == sol_rows(match_bgp(g.store, q))
