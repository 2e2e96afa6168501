"""Sharded RDF storage: ShardedTripleStore == TripleStore as solution
multisets over the adversarial BGP matrix on both backends, shard-count edge
cases (S=1, S > num_predicates, empty shards after subgraph), and the
end-to-end batched system path over a sharded cloud store."""

import numpy as np
import pytest

from repro.core.cost import SystemParams
from repro.edge.system import EdgeCloudSystem
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.graph import RDFStore, TripleStore, triples_size_bytes
from repro.rdf.sharding import ShardedTripleStore, shard_of_pred
from repro.sparql.engine import QueryEngine
from repro.sparql.matcher import match_bgp, match_oracle
from repro.sparql.query import QueryGraph, TriplePattern, parse_sparql

from test_engine import ADVERSARIAL, BACKENDS, sol_rows

SHARD_COUNTS = [1, 2, 5, 64]      # 64 > num_predicates of the small stores


def paired_stores(rng, num_shards, n_ent=12, n_pred=3, n_trip=40):
    s = rng.integers(0, n_ent, n_trip)
    p = rng.integers(0, n_pred, n_trip)
    o = rng.integers(0, n_ent, n_trip)
    return (TripleStore(s, p, o, n_ent, n_pred),
            ShardedTripleStore(s, p, o, n_ent, n_pred,
                               num_shards=num_shards))


def test_stores_satisfy_protocol():
    rng = np.random.default_rng(0)
    mono, sharded = paired_stores(rng, 3)
    assert isinstance(mono, RDFStore)
    assert isinstance(sharded, RDFStore)


@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
def test_sharded_store_invariants(num_shards):
    rng = np.random.default_rng(1)
    mono, sh = paired_stores(rng, num_shards, n_trip=80)
    assert sh.num_triples == mono.num_triples
    assert sh.num_shards == num_shards
    assert sorted(map(tuple, sh.triples().tolist())) == \
        sorted(map(tuple, mono.triples().tolist()))
    assert np.array_equal(sh.pred_count, mono.pred_count)
    assert np.array_equal(sh.pred_distinct_s, mono.pred_distinct_s)
    assert np.array_equal(sh.pred_distinct_o, mono.pred_distinct_o)
    assert sh.size_bytes() == mono.size_bytes() == \
        triples_size_bytes(mono.num_triples)
    # composite version: distinct from any shard's and any other store's
    assert sh.version != mono.version
    assert len(set(sh.version)) == len(sh.version)
    for pid in range(mono.num_predicates):
        # global ids resolve to exactly this predicate's triples
        tids = sh.pred_tids(pid)
        k = sh.shard_of_pred(pid)
        assert k == int(shard_of_pred(pid, num_shards))
        assert np.all(sh.p[tids] == pid)
        assert len(tids) == mono.pred_count[pid]
        idx = sh.pred_index(pid)
        assert np.array_equal(sh.s[idx.s_order], idx.s_sorted)
        assert np.array_equal(sh.o[idx.o_order], idx.o_sorted)
        assert np.all(np.diff(idx.s_sorted) >= 0)


def test_sharded_store_dedupes_like_monolithic():
    s = np.array([0, 0, 1, 1, 0])
    p = np.array([0, 0, 1, 1, 0])
    o = np.array([2, 2, 3, 3, 2])    # triple (0,0,2) three times, (1,1,3) x2
    mono = TripleStore(s, p, o, 4, 2)
    sh = ShardedTripleStore(s, p, o, 4, 2, num_shards=3)
    assert sh.num_triples == mono.num_triples == 2


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
def test_sharded_equals_monolithic_adversarial(backend, num_shards):
    """Equivalence matrix through execute_batch on both store kinds."""
    rng = np.random.default_rng(2)
    eng = QueryEngine(backend=backend)
    for trial in range(4):
        mono, sh = paired_stores(rng, num_shards,
                                 n_trip=int(rng.integers(5, 50)))
        queries = [QueryGraph(pats, []) for pats in ADVERSARIAL]
        got = eng.execute_batch(sh, queries)
        want = eng.execute_batch(mono, queries)
        for q, res, ref in zip(queries, got, want):
            assert sol_rows(res) == sol_rows(ref)
            sols, vs = match_oracle(mono, q)
            if vs:
                assert {tuple(r) for r in res.project(vs).tolist()} == sols


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_subgraph_empty_shards(backend):
    """subgraph keeps the store sharded; shards left empty still answer."""
    rng = np.random.default_rng(3)
    mono, sh = paired_stores(rng, 4, n_pred=5, n_trip=120)
    # keep only one predicate's triples -> every other shard is empty
    keep_pid = 2
    sub = sh.subgraph(sh.pred_tids(keep_pid))
    assert isinstance(sub, ShardedTripleStore)
    assert sub.num_shards == 4
    empties = [s for s in sub.shards if s.num_triples == 0]
    assert len(empties) >= 1
    sub_mono = mono.subgraph(mono.pred_tids(keep_pid))
    eng = QueryEngine(backend=backend)
    queries = [QueryGraph(pats, []) for pats in ADVERSARIAL]
    for res, ref in zip(eng.execute_batch(sub, queries),
                        eng.execute_batch(sub_mono, queries)):
        assert sol_rows(res) == sol_rows(ref)
    # fully empty subgraph
    empty = sh.subgraph(np.zeros(0, dtype=np.int64))
    assert empty.num_triples == 0
    for res in eng.execute_batch(empty, queries):
        assert res.num_matches == 0


def test_jax_staging_lru_scales_to_shard_count():
    """A store with more shards than the staging LRU's default slots must
    not evict its own shards mid-scan (re-uploading every round)."""
    from repro.sparql.engine import JaxBackend
    rng = np.random.default_rng(6)
    mono, sh = paired_stores(rng, 6, n_pred=6, n_trip=120)
    jb = JaxBackend(bt=1024, max_staged=2)     # fewer slots than shards
    queries = [QueryGraph(pats, []) for pats in ADVERSARIAL]
    eng = QueryEngine(backend=jb)
    refs = eng.execute_batch(mono, queries)
    for res, ref in zip(eng.execute_batch(sh, queries), refs):
        assert sol_rows(res) == sol_rows(ref)
    non_empty = sum(1 for s in sh.shards if s.num_triples)
    staged_shard_versions = {s.version for s in sh.shards} & \
        set(jb._staged)
    assert len(staged_shard_versions) == non_empty


def test_match_bgp_works_directly_on_sharded_store():
    """The plain matcher path (no engine) also accepts a sharded store."""
    rng = np.random.default_rng(4)
    mono, sh = paired_stores(rng, 3, n_trip=60)
    for pats in ADVERSARIAL:
        q = QueryGraph(pats, [])
        assert sol_rows(match_bgp(sh, q)) == sol_rows(match_bgp(mono, q))


@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_system_round_matches_monolithic(backend):
    """run_round_batched over a sharded cloud store == monolithic system."""
    g = generate_watdiv_like(scale=0.5, seed=31)
    params = SystemParams.synthetic(n_users=8, n_edges=2, seed=5)
    history = [workload_sparql(g, 3, seed=200 + n) for n in range(8)]

    def build(store):
        sys_ = EdgeCloudSystem(store, g.dictionary, params,
                               storage_budgets=150_000, backend=backend)
        sys_.prepare(history)
        return sys_

    sys_mono = build(g.store)
    sys_sh = build(ShardedTripleStore.from_store(g.store, 4))
    assert isinstance(sys_sh.cloud.store, ShardedTripleStore)
    # pattern-induced edge stores inherit the cloud store's kind
    for es in sys_sh.edges:
        assert isinstance(es.store, ShardedTripleStore)
        assert es.used_bytes() <= es.budget
    queries = [(i % 8, parse_sparql(t, g.dictionary))
               for i, t in enumerate(workload_sparql(g, 10, seed=17))]
    rep_mono = sys_mono.run_round_batched(queries, policy="greedy",
                                          observe=False)
    rep_sh = sys_sh.run_round_batched(queries, policy="greedy",
                                      observe=False)
    assert rep_sh.assignment_counts == rep_mono.assignment_counts
    for a, b in zip(rep_mono.outcomes, rep_sh.outcomes):
        assert a.n_matches == b.n_matches
    # per-query solution multisets agree through execute_batch as well
    qs = [q for (_, q) in queries]
    for res, ref in zip(sys_sh.engine.execute_batch(sys_sh.cloud.store, qs),
                        sys_mono.engine.execute_batch(g.store, qs)):
        assert sol_rows(res) == sol_rows(ref)


def test_sharded_rebalance_keeps_completeness():
    """Dynamic placement over a sharded cloud store: G[P] matches == G
    matches after rebalancing (the paper's completeness guarantee)."""
    from repro.core.pattern import pattern_of
    g = generate_watdiv_like(scale=0.5, seed=37)
    params = SystemParams.synthetic(n_users=6, n_edges=2, seed=9)
    sys_ = EdgeCloudSystem(ShardedTripleStore.from_store(g.store, 3),
                           g.dictionary, params, storage_budgets=150_000)
    sys_.prepare([workload_sparql(g, 3, seed=300 + n) for n in range(6)])
    queries = [(i % 6, parse_sparql(t, g.dictionary))
               for i, t in enumerate(workload_sparql(g, 8, seed=19))]
    for _ in range(2):
        sys_.run_round_batched(queries, policy="greedy", execute=True)
    sys_.rebalance_all()
    checked = 0
    for (_, q) in queries:
        p = pattern_of(q)
        want = sol_rows(sys_.engine.execute(sys_.cloud.store, q))
        for es in sys_.edges:
            if es.can_execute(p):
                assert sol_rows(sys_.engine.execute(es.store, q)) == want
                checked += 1
    assert checked >= 1
