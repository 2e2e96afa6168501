"""Shard-parallel join pipeline (PR 3): predicate-variable join bugfixes,
post-mask capacity semantics, plan/scan-counter invariants, shard-local vs
global join equivalence, and overlapped vs sequential round parity —
both backends x monolithic/sharded store x overlapped/sequential rounds."""

import numpy as np
import pytest

from repro.core.cost import SystemParams, result_bits
from repro.edge.server import ExecutionRecord
from repro.edge.system import EdgeCloudSystem
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.graph import TripleStore
from repro.rdf.sharding import ShardedTripleStore
from repro.sparql.engine import QueryEngine
from repro.sparql.matcher import (CandidateParts, JoinStats,
                                  MatchCapacityError, match_bgp,
                                  match_oracle, plan_bgp)
from repro.sparql.query import QueryGraph, TriplePattern, parse_sparql

from test_engine import BACKENDS, sol_rows

# BGPs whose only (or dominant) shared variables are PREDICATE variables —
# the shapes that used to fall through to the cartesian branch
PRED_VAR_ADVERSARIAL = [
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?a", "?p", "?b")],
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?a", "?p", "?b"),
     TriplePattern("?c", "?p", "?d")],
    [TriplePattern("?x", "?p", "?x"), TriplePattern("?a", "?p", "?b")],
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?y", "?q", "?z"),
     TriplePattern("?a", "?p", "?b")],
    [TriplePattern(0, "?p", 1), TriplePattern("?a", "?p", "?b")],
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?a", "?q", "?b")],
    [TriplePattern("?x", "?p", "?y"), TriplePattern("?a", "?p", "?y")],
]


def paired_stores(rng, num_shards=3, n_ent=10, n_pred=4, n_trip=30):
    s = rng.integers(0, n_ent, n_trip)
    p = rng.integers(0, n_pred, n_trip)
    o = rng.integers(0, n_ent, n_trip)
    return (TripleStore(s, p, o, n_ent, n_pred),
            ShardedTripleStore(s, p, o, n_ent, n_pred,
                               num_shards=num_shards))


# ---------------------------------------------------------------------------
# headline bugfix: predicate-variable joins
# ---------------------------------------------------------------------------

def test_pred_var_join_regression_no_capacity_error():
    """A BGP whose only shared variable is a predicate variable must join on
    it, not expand the R*C cartesian product. On the old code this raises
    MatchCapacityError (400*400 pre-mask rows > max_rows) even though the
    true result has only 400 rows."""
    T = 400
    store = TripleStore(np.arange(T), np.arange(T), np.arange(T) + 1,
                        T + 1, T)                  # one triple per predicate
    q = QueryGraph([TriplePattern("?x", "?p", "?y"),
                    TriplePattern("?a", "?p", "?b")], [])
    res = match_bgp(store, q, max_rows=5_000)
    assert res.num_matches == T
    # plan took the predicate-variable join, not the cartesian branch
    js = JoinStats()
    match_bgp(store, q, max_rows=5_000, stats=js)
    assert js.joins_pred_var == 1
    assert js.joins_cartesian == 1                 # only the seed expansion


def test_pred_var_join_equals_oracle():
    rng = np.random.default_rng(0)
    for trial in range(5):
        mono, sh = paired_stores(rng, n_trip=int(rng.integers(10, 40)))
        for pats in PRED_VAR_ADVERSARIAL:
            q = QueryGraph(pats, [])
            sols, vs = match_oracle(mono, q)
            for store in (mono, sh):
                res = match_bgp(store, q)
                got = {tuple(r) for r in res.project(vs).tolist()}
                assert got == sols, (trial, pats)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sharded", [False, True])
def test_pred_var_matrix_through_engine(backend, sharded):
    """Oracle-equivalence matrix for predicate-variable-heavy BGPs through
    execute_batch: both backends x both store kinds."""
    rng = np.random.default_rng(1)
    eng = QueryEngine(backend=backend)
    for trial in range(3):
        mono, sh = paired_stores(rng, n_trip=int(rng.integers(10, 40)))
        store = sh if sharded else mono
        queries = [QueryGraph(pats, []) for pats in PRED_VAR_ADVERSARIAL]
        for q, res in zip(queries, eng.execute_batch(store, queries)):
            sols, vs = match_oracle(mono, q)
            assert {tuple(r) for r in res.project(vs).tolist()} == sols


# ---------------------------------------------------------------------------
# capacity semantics: max_rows bounds SURVIVING rows
# ---------------------------------------------------------------------------

def test_capacity_applies_post_mask_on_vertex_join():
    """Two parallel stars: the ?x-join fans out n*n rows pre-mask but only n
    survive the ?y equality mask; the old pre-mask check raised."""
    n = 150
    s = np.zeros(2 * n, dtype=np.int64)
    p = np.concatenate([np.zeros(n, np.int64), np.ones(n, np.int64)])
    o = np.concatenate([np.arange(n), np.arange(n)])
    store = TripleStore(s, p, o, n + 1, 2)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?x", 1, "?y")], [])
    res = match_bgp(store, q, max_rows=2 * n)      # old: raises at n*n
    assert res.num_matches == n
    sols, vs = match_oracle(store, q)
    assert {tuple(r) for r in res.project(vs).tolist()} == sols


def test_capacity_boundary_exact():
    """max_rows == surviving rows passes; one less raises."""
    n = 64
    s = np.zeros(2 * n, dtype=np.int64)
    p = np.concatenate([np.zeros(n, np.int64), np.ones(n, np.int64)])
    o = np.concatenate([np.arange(n), np.arange(n)])
    store = TripleStore(s, p, o, n + 1, 2)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?x", 1, "?y")], [])
    assert match_bgp(store, q, max_rows=n).num_matches == n
    with pytest.raises(MatchCapacityError):
        match_bgp(store, q, max_rows=n - 1)


def test_capacity_still_raises_on_genuine_blowup():
    rng = np.random.default_rng(2)
    mono, _ = paired_stores(rng, n_trip=40)
    q = QueryGraph([TriplePattern("?x", "?p", "?y"),
                    TriplePattern("?a", "?q", "?b")], [])   # true cartesian
    with pytest.raises(MatchCapacityError):
        match_bgp(mono, q, max_rows=50)


def test_single_row_fanout_is_subchunked():
    """One binding row whose raw fan-out exceeds max_rows must be processed
    in sub-chunks (bounded peak memory) and survive when the equality mask
    keeps few rows."""
    n = 5_000
    # pred 0: one edge 0->5 (binds ?x=0, ?y=5 as the single row);
    # pred 1: star 0 -> {0..n-1}, so the ?x-join fans out n rows pre-mask
    s = np.concatenate([[0], np.zeros(n, np.int64)])
    p = np.concatenate([[0], np.ones(n, np.int64)])
    o = np.concatenate([[5], np.arange(n)])
    store = TripleStore(s, p, o, n + 1, 2)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?x", 1, "?y")], [])
    res = match_bgp(store, q, max_rows=600)        # 600 << n pre-mask rows
    assert res.num_matches == 1
    assert res.column("?y").tolist() == [5]
    # and with no mask to save it, the capacity error still fires
    q2 = QueryGraph([TriplePattern("?x", 0, "?y"),
                     TriplePattern("?x", 1, "?z")], [])
    with pytest.raises(MatchCapacityError):
        match_bgp(store, q2, max_rows=600)


@pytest.mark.parametrize("num_shards", [1, 4])
def test_chunked_join_equals_unchunked(num_shards):
    """Tiny max_rows forces the chunked expansion path; results must be
    identical to the roomy path (same multiset)."""
    rng = np.random.default_rng(3)
    mono, sh = paired_stores(rng, num_shards=num_shards, n_trip=60)
    store = sh if num_shards > 1 else mono
    for pats in PRED_VAR_ADVERSARIAL[:4]:
        q = QueryGraph(pats, [])
        want = sol_rows(match_bgp(store, q))
        if len(want) == 0:
            continue
        assert sol_rows(match_bgp(store, q, max_rows=len(want))) == want


# ---------------------------------------------------------------------------
# shard-local vs global join pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_shard_local_equals_global_join(backend):
    g = generate_watdiv_like(scale=0.5, seed=11)
    sh = ShardedTripleStore.from_store(g.store, 4)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 10, seed=7)]
    eng_shard = QueryEngine(backend=backend, shard_local_joins=True)
    eng_global = QueryEngine(backend=backend, shard_local_joins=False)
    for res, ref in zip(eng_shard.execute_batch(sh, qs),
                        eng_global.execute_batch(sh, qs)):
        assert sol_rows(res) == sol_rows(ref)
    # the shard-local pipeline actually took the presorted path
    assert eng_shard.stats.join.joins_pred_index > 0
    assert eng_global.stats.join.joins_pred_index == 0
    # presorted joins skip their candidate scans entirely
    assert eng_shard.stats.scans_requested < eng_global.stats.scans_requested


def test_plan_marks_pred_index_steps():
    rng = np.random.default_rng(4)
    mono, _ = paired_stores(rng)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?y", 1, "?z")], [])
    plan = plan_bgp(mono, q)
    assert [st.kind for st in plan] == ["seed", "vertex"]
    assert plan[0].needs_scan and not plan[1].needs_scan
    assert plan[1].use_pred_index
    # globally-disabled shard-local path plans every step as a scan
    assert all(st.needs_scan for st in plan_bgp(mono, q, shard_local=False))
    # constants / repeated vars / variable predicates disqualify the
    # presorted path FOR THAT PATTERN (other patterns may still take it)
    for pats in ([TriplePattern("?x", 0, "?y"), TriplePattern("?y", 1, 5)],
                 [TriplePattern("?x", 0, "?y"),
                  TriplePattern("?y", 1, "?y")],
                 [TriplePattern("?x", 0, "?y"),
                  TriplePattern("?y", "?p", "?z")]):
        step = next(st for st in plan_bgp(mono, QueryGraph(pats, []))
                    if st.pattern == 1)
        assert not step.use_pred_index and step.needs_scan


def test_merged_joins_on_sharded_store():
    """Variable-predicate candidates span shards: the engine feeds multi-part
    CandidateParts to the matcher and partial binding tables are merged."""
    rng = np.random.default_rng(5)
    mono, sh = paired_stores(rng, num_shards=4, n_pred=6, n_trip=80)
    eng = QueryEngine(backend="numpy")
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?y", "?p", "?z")], [])
    res = eng.execute(sh, q)
    assert eng.stats.join.merged_joins >= 1
    assert sol_rows(res) == sol_rows(match_bgp(mono, q))


def test_candidate_parts_normalization():
    a = np.array([3, 1], dtype=np.int64)
    parts = CandidateParts([a, np.zeros(0, dtype=np.int64),
                            np.array([7], dtype=np.int64)])
    assert parts.total == len(parts) == 3
    assert parts.nbytes == 3 * 8
    assert sorted(parts.concat().tolist()) == [1, 3, 7]
    assert CandidateParts.of(parts) is parts
    assert CandidateParts.of(a).parts[0] is a


# ---------------------------------------------------------------------------
# stats invariants
# ---------------------------------------------------------------------------

def test_scan_counter_invariants():
    """scans_deduped can never go negative; every executed scan is exactly
    one scan-LRU miss — across repeated batches, cache hits, store switches
    and mid-join lookups."""
    g = generate_watdiv_like(scale=0.5, seed=13)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 8, seed=3)]
    # selective pred-var shapes (self-loop / constant seeds keep the
    # ?p-join small on the ~5k-triple store)
    qs += [QueryGraph(PRED_VAR_ADVERSARIAL[2], []),
           QueryGraph(PRED_VAR_ADVERSARIAL[4], [])]
    stores = [g.store, ShardedTripleStore.from_store(g.store, 3),
              g.store.subgraph(np.arange(g.store.num_triples // 2))]

    def check(eng):
        s = eng.stats
        assert s.scans_deduped >= 0
        assert s.scans_requested >= s.scans_executed
        assert s.scans_executed == s.scan_cache_misses

    for kwargs in ({}, {"cache_size": 0}, {"scan_cache_bytes": 0},
                   {"shard_local_joins": False}):
        eng = QueryEngine(backend="numpy", **kwargs)
        for _ in range(3):
            for store in stores:
                eng.execute_batch(store, qs)
                check(eng)
        for q in qs:                       # single-query path
            eng.execute(stores[0], q)
            check(eng)


def test_per_phase_stats_populated():
    g = generate_watdiv_like(scale=0.5, seed=17)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 6, seed=5)]
    eng = QueryEngine(backend="numpy")
    eng.execute_batch(ShardedTripleStore.from_store(g.store, 4), qs)
    s = eng.stats
    assert s.prescan_seconds > 0 and s.join_seconds > 0
    assert s.exec_seconds >= s.join_seconds
    js = s.join
    assert js.partitions_probed >= (js.joins_pred_index + js.joins_vertex
                                    + js.joins_pred_var + js.joins_cartesian)


# ---------------------------------------------------------------------------
# result_bits single-sourcing
# ---------------------------------------------------------------------------

def test_execution_record_bits_single_sourced():
    rng = np.random.default_rng(19)
    mono, _ = paired_stores(rng, n_trip=50)
    q = QueryGraph([TriplePattern("?x", 0, "?y")], ["?x"])
    res = match_bgp(mono, q)
    rec = ExecutionRecord.of(res, q.projection, 0.01)
    assert rec.result_bits == result_bits(res, q.projection)
    assert rec.result_bits == res.result_bytes(q.projection) * 8
    assert rec.n_matches == res.num_matches


def test_cloud_and_batch_records_agree_on_units():
    from repro.edge.server import CloudServer
    g = generate_watdiv_like(scale=0.5, seed=23)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 4, seed=9)]
    cloud = CloudServer(g.store)
    batch_recs = [rec for _, rec in cloud.execute_batch(qs)]
    for q, brec in zip(qs, batch_recs):
        _, rec = cloud.execute(q)
        assert rec.result_bits == brec.result_bits
        assert rec.n_matches == brec.n_matches


# ---------------------------------------------------------------------------
# overlapped rounds
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["numpy", "jax"])
def overlap_system(request):
    g = generate_watdiv_like(scale=0.5, seed=29)
    params = SystemParams.synthetic(n_users=8, n_edges=3, seed=5)
    systems = {}
    for kind, store in (("mono", g.store),
                        ("sharded", ShardedTripleStore.from_store(g.store,
                                                                  4))):
        sys_ = EdgeCloudSystem(store, g.dictionary, params,
                               storage_budgets=150_000,
                               backend=request.param)
        sys_.prepare([workload_sparql(g, 3, seed=400 + n)
                      for n in range(8)])
        systems[kind] = sys_
    queries = [(i % 8, parse_sparql(t, g.dictionary))
               for i, t in enumerate(workload_sparql(g, 12, seed=31))]
    return g, systems, queries


@pytest.mark.parametrize("kind", ["mono", "sharded"])
def test_overlapped_round_matches_sequential(overlap_system, kind):
    """overlap=True must produce the same RoundReport outcomes as the
    sequential batched round and the per-query round."""
    g, systems, queries = overlap_system
    sys_ = systems[kind]
    rep_seq = sys_.run_round_batched(queries, policy="greedy",
                                     observe=False)
    rep_ov = sys_.run_round_batched(queries, policy="greedy",
                                    observe=False, overlap=True)
    rep_loop = sys_.run_round(queries, policy="greedy", observe=False)
    assert not rep_seq.overlapped and rep_ov.overlapped
    assert rep_ov.assignment_counts == rep_seq.assignment_counts \
        == rep_loop.assignment_counts
    for a, b, c in zip(rep_seq.outcomes, rep_ov.outcomes,
                       rep_loop.outcomes):
        assert a.assigned_to == b.assigned_to == c.assigned_to
        assert a.n_matches == b.n_matches == c.n_matches
        assert a.executable_edges == b.executable_edges
    # per-server wall clock was measured inside each thread
    assert set(rep_ov.server_wall_seconds) == set(rep_ov.assignment_counts)
    assert all(dt >= 0 for dt in rep_ov.server_wall_seconds.values())
    assert rep_ov.execute_wall_seconds > 0


def test_overlapped_round_solutions_complete(overlap_system):
    """Solution multisets through the overlapped round's engine equal the
    direct matcher — the completeness guarantee is execution-strategy
    independent."""
    g, systems, queries = overlap_system
    sys_ = systems["sharded"]
    sys_.run_round_batched(queries, policy="greedy", observe=False,
                           overlap=True)
    for (_, q) in queries[:6]:
        res = sys_.engine.execute(sys_.cloud.store, q)
        assert sol_rows(res) == sol_rows(match_bgp(g.store, q))


_PROCESS_OVERLAP_SCRIPT = r"""
from repro.core.cost import SystemParams
from repro.edge.system import EdgeCloudSystem
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.sharding import ShardedTripleStore
from repro.sparql.query import parse_sparql

g = generate_watdiv_like(scale=0.5, seed=41)
params = SystemParams.synthetic(n_users=6, n_edges=2, seed=3)
sys_ = EdgeCloudSystem(ShardedTripleStore.from_store(g.store, 3),
                       g.dictionary, params, storage_budgets=150_000,
                       backend="numpy")
sys_.prepare([workload_sparql(g, 3, seed=500 + n) for n in range(6)])
queries = [(i % 6, parse_sparql(t, g.dictionary))
           for i, t in enumerate(workload_sparql(g, 10, seed=43))]
try:
    rep_seq = sys_.run_round_batched(queries, policy="greedy",
                                     observe=False)
    rep_pr = sys_.run_round_batched(queries, policy="greedy",
                                    observe=False, overlap="process")
    assert rep_pr.overlapped and rep_pr.overlap_mode == "process"
    assert rep_pr.assignment_counts == rep_seq.assignment_counts
    for a, b in zip(rep_seq.outcomes, rep_pr.outcomes):
        assert a.assigned_to == b.assigned_to
        assert a.n_matches == b.n_matches
    pool1 = sys_._proc_pool
    assert pool1 is not None
    sys_.run_round_batched(queries, policy="greedy", observe=True,
                           overlap="process")
    assert sys_._proc_pool is pool1          # reused while stores stable
    sys_.rebalance_all()                     # may deploy new stores
    rep_pr2 = sys_.run_round_batched(queries, policy="greedy",
                                     observe=False, overlap="process")
    rep_seq2 = sys_.run_round_batched(queries, policy="greedy",
                                      observe=False)
    assert rep_pr2.assignment_counts == rep_seq2.assignment_counts
    for a, b in zip(rep_seq2.outcomes, rep_pr2.outcomes):
        assert a.n_matches == b.n_matches
    # cold-start broadcast: clearing caches must not change results
    sys_.clear_engine_caches()
    rep_pr3 = sys_.run_round_batched(queries, policy="greedy",
                                     observe=False, overlap="process")
    for a, b in zip(rep_pr2.outcomes, rep_pr3.outcomes):
        assert a.n_matches == b.n_matches
finally:
    sys_.close_overlap_pool()
print("PROCESS-OVERLAP-OK")
"""


def test_process_overlap_matches_sequential():
    """overlap='process' (persistent fork pool): same outcomes, pool reused
    across rounds, rebuilt after rebalance. Runs in a fresh subprocess: in
    this pytest process XLA is (eventually) initialized, which correctly
    downgrades process mode to threads — a clean numpy-only process is the
    supported deployment for the fork pool."""
    import os
    import subprocess
    import sys
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _PROCESS_OVERLAP_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "PROCESS-OVERLAP-OK" in proc.stdout


def test_process_overlap_falls_back_to_threads_on_jax():
    g = generate_watdiv_like(scale=0.3, seed=47)
    params = SystemParams.synthetic(n_users=4, n_edges=2, seed=3)
    sys_ = EdgeCloudSystem(g.store, g.dictionary, params,
                           storage_budgets=100_000, backend="jax")
    sys_.prepare([workload_sparql(g, 2, seed=600 + n) for n in range(4)])
    queries = [(i % 4, parse_sparql(t, g.dictionary))
               for i, t in enumerate(workload_sparql(g, 6, seed=49))]
    rep = sys_.run_round_batched(queries, policy="greedy", observe=False,
                                 overlap="process")
    assert rep.overlap_mode == "thread"          # forked XLA is unsafe
    assert sys_._proc_pool is None


def test_overlap_mode_resolution():
    """overlap=True auto-picks per backend (process for GIL-bound numpy,
    thread for jax whose fork is unsafe); explicit strings pass through."""
    from repro.edge.system import resolve_overlap_mode
    assert resolve_overlap_mode(False, "numpy") == ""
    assert resolve_overlap_mode(False, "jax") == ""
    assert resolve_overlap_mode(True, "numpy") == "process"
    assert resolve_overlap_mode(True, "jax") == "thread"
    for explicit in ("thread", "process"):
        assert resolve_overlap_mode(explicit, "numpy") == explicit
        assert resolve_overlap_mode(explicit, "jax") == explicit


def test_device_vs_host_joinstats_parity():
    """The device-resident pipeline reports the SAME join counters as the
    host path for the same plans — joins_device alone says WHERE a presorted
    join ran, never changing what was counted."""
    from dataclasses import asdict

    from repro.sparql.engine import JaxBackend

    g = generate_watdiv_like(scale=0.5, seed=11)
    sh = ShardedTripleStore.from_store(g.store, 4)
    qs = [QueryGraph([TriplePattern("?x", 0, "?y"),
                      TriplePattern("?y", 1, "?z")], []),
          QueryGraph([TriplePattern("?x", 2, "?y"),
                      TriplePattern("?x", 3, "?z")], []),
          QueryGraph([TriplePattern("?a", 1, "?b")], [])]
    eng_dev = QueryEngine(backend=JaxBackend(bt=1024))
    eng_host = QueryEngine(backend=JaxBackend(bt=1024,
                                              device_resident=False))
    for res, ref in zip(eng_dev.execute_batch(sh, qs),
                        eng_host.execute_batch(sh, qs)):
        assert sol_rows(res) == sol_rows(ref)
    dev, host = asdict(eng_dev.stats.join), asdict(eng_host.stats.join)
    assert dev.pop("joins_device") > 0
    assert host.pop("joins_device") == 0
    assert dev == host
    assert eng_dev.stats.device_queries == len(qs)


def test_serving_overlap_matches_sequential():
    from repro.runtime.serving import (OffloadServingPool, Replica,
                                       make_sparql_runner)
    g = generate_watdiv_like(scale=0.5, seed=37)
    qs = [parse_sparql(t, g.dictionary)
          for t in workload_sparql(g, 8, seed=11)]
    eng = QueryEngine()
    runner = make_sparql_runner(g.store, eng)
    pool = OffloadServingPool(
        replicas=[Replica(0, classes={0}, cycles_per_s=2e8, link_bps=75e6,
                          runner=runner),
                  Replica(1, classes={1}, cycles_per_s=2e8, link_bps=75e6,
                          runner=runner)],
        cloud_runner=runner)
    requests = [{"class_id": i % 3, "cycles": 1e6, "result_bits": 1e4,
                 "payload": q} for i, q in enumerate(qs)]
    seq = pool.admit(requests, policy="greedy")
    ov = pool.admit(requests, policy="greedy", overlap=True)
    assert np.array_equal(seq.assignments, ov.assignments)
    for a, b in zip(seq.responses, ov.responses):
        assert sol_rows(a) == sol_rows(b)
