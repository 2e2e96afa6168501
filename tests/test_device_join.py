"""Device-resident join pipeline (PR 7): eligibility, device-vs-host
result parity, the ONE-bulk-transfer-per-batch contract, transparent
fallback, staged-view invalidation after deltas, and capacity parity."""

import numpy as np
import pytest

from repro.rdf.deltas import TripleDelta
from repro.rdf.generator import generate_watdiv_like
from repro.rdf.graph import TripleStore
from repro.rdf.sharding import ShardedTripleStore
from repro.sparql.device_join import device_eligible
from repro.sparql.engine import JaxBackend, QueryEngine
from repro.sparql.matcher import MatchCapacityError, match_bgp, plan_bgp
from repro.sparql.query import QueryGraph, TriplePattern

from test_engine import sol_rows

# bound-predicate star / path / single-pattern shapes — the device class
DEVICE_SHAPES = [
    [TriplePattern("?x", 0, "?y")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?y", 1, "?z")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?x", 1, "?z")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?y", 1, "?z"),
     TriplePattern("?z", 2, "?w")],
    [TriplePattern(3, 0, "?y"), TriplePattern("?y", 1, "?z")],
]

# shapes the device path must decline: variable predicates (wildcard seed
# fans out over shards; var-pred join steps), repeated variables, closing
# joins with both endpoints bound (equality-masked)
HOST_SHAPES = [
    [TriplePattern("?x", "?p", "?y")],
    [TriplePattern("?x", 0, "?x")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?y", "?p", "?z")],
    [TriplePattern("?x", 0, "?y"), TriplePattern("?y", 1, "?z"),
     TriplePattern("?z", 2, "?x")],                      # triangle closes
]


def _stores(scale=0.5, seed=11, shards=4):
    g = generate_watdiv_like(scale=scale, seed=seed)
    return g.store, ShardedTripleStore.from_store(g.store, shards)


def _qs(shapes):
    return [QueryGraph(pats, []) for pats in shapes]


def test_device_eligibility_matrix():
    mono, sh = _stores()
    for pats in DEVICE_SHAPES:
        q = QueryGraph(pats, [])
        assert device_eligible(sh, q, plan_bgp(sh, q)), pats
    for pats in HOST_SHAPES:
        q = QueryGraph(pats, [])
        assert not device_eligible(sh, q, plan_bgp(sh, q)), pats
    # a monolithic store takes wildcard seeds (single flat part) ...
    q = QueryGraph([TriplePattern("?s", "?p", "?o")], [])
    assert device_eligible(mono, q, plan_bgp(mono, q))
    # ... and empty stores decline everything
    empty = TripleStore(np.zeros(0, np.int64), np.zeros(0, np.int64),
                        np.zeros(0, np.int64), 4, 2)
    q = QueryGraph(DEVICE_SHAPES[0], [])
    assert not device_eligible(empty, q, plan_bgp(empty, q))


def _full_rows(res):
    """Multiset of (sorted-var bindings + pattern-order edge ids) rows —
    row order and variable column order are backend implementation
    details, the row CONTENTS are not."""
    idx = [res.var_names.index(v) for v in sorted(res.var_names)]
    rows = np.concatenate([res.bindings[:, idx], res.edge_ids], axis=1)
    return sorted(map(tuple, rows.tolist()))


@pytest.mark.parametrize("sharded", [False, True])
def test_device_results_equal_host(sharded):
    """Bindings AND edge ids through the device pipeline match the numpy
    backend bit-for-bit, for eligible and fallback shapes alike."""
    mono, sh = _stores()
    store = sh if sharded else mono
    qs = _qs(DEVICE_SHAPES + HOST_SHAPES)
    eng_dev = QueryEngine(backend=JaxBackend(bt=1024))
    eng_ref = QueryEngine(backend="numpy")
    for q, res, ref in zip(qs, eng_dev.execute_batch(store, qs),
                           eng_ref.execute_batch(store, qs)):
        assert _full_rows(res) == _full_rows(ref), q.patterns
    n_dev = sum(device_eligible(store, q, plan_bgp(store, q)) for q in qs)
    assert eng_dev.stats.device_queries == n_dev >= len(DEVICE_SHAPES)
    assert eng_dev.stats.device_fallbacks == len(qs) - n_dev > 0
    assert eng_dev.stats.join.joins_device > 0
    assert eng_ref.stats.join.joins_device == 0


def test_single_bulk_transfer_per_batch():
    """THE acceptance criterion: a batch whose every cache-missed query is
    device-eligible costs exactly ONE device->host transfer."""
    _, sh = _stores()
    qs = _qs(DEVICE_SHAPES)
    bk = JaxBackend(bt=1024)
    eng = QueryEngine(backend=bk)
    before = bk.host_transfers
    eng.execute_batch(sh, qs)
    assert bk.host_transfers - before == 1
    # EngineStats mirrors the backend's cumulative totals
    assert eng.stats.host_transfers == bk.host_transfers
    assert eng.stats.host_transfer_bytes == bk.host_transfer_bytes > 0
    assert eng.stats.scalar_syncs == bk.scalar_syncs > 0
    assert eng.stats.device_queries == len(qs)
    assert eng.stats.device_fallbacks == 0

    # a mixed batch adds exactly one more (the host prescan's bulk fetch)
    before = bk.host_transfers
    eng.clear_cache()
    eng.execute_batch(sh, _qs(DEVICE_SHAPES + HOST_SHAPES))
    assert bk.host_transfers - before == 2

    # a warm batch is served from the result cache: zero transfers
    before, hits = bk.host_transfers, eng.stats.cache_hits
    eng.execute_batch(sh, _qs(DEVICE_SHAPES))
    assert bk.host_transfers - before == 0
    assert eng.stats.cache_hits - hits == len(DEVICE_SHAPES)


def test_device_resident_off_falls_back():
    _, sh = _stores()
    qs = _qs(DEVICE_SHAPES)
    eng = QueryEngine(backend=JaxBackend(bt=1024, device_resident=False))
    ref = QueryEngine(backend="numpy")
    for res, want in zip(eng.execute_batch(sh, qs),
                         ref.execute_batch(sh, qs)):
        assert sol_rows(res) == sol_rows(want)
    assert eng.stats.device_queries == 0
    assert eng.stats.join.joins_device == 0


def test_backend_mode_reported():
    assert QueryEngine(backend="numpy").stats.backend_mode == "numpy"
    mode = QueryEngine(backend="jax").stats.backend_mode
    assert mode in ("jax-interpret", "jax-compiled")
    assert QueryEngine(
        backend=JaxBackend(interpret=True)).stats.backend_mode \
        == "jax-interpret"


def test_capacity_error_parity():
    """The device join raises MatchCapacityError at the same max_rows
    threshold as the host (no equality masks -> raw fan-out IS the
    surviving row count)."""
    n = 200
    s = np.concatenate([np.arange(n), np.zeros(n, np.int64)])
    p = np.concatenate([np.zeros(n, np.int64), np.ones(n, np.int64)])
    o = np.concatenate([np.zeros(n, np.int64), np.arange(n)])
    store = ShardedTripleStore(s, p, o, n + 1, 2, num_shards=2)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?y", 1, "?z")], [])
    want = match_bgp(store, q).num_matches
    assert want == n * n
    ok = QueryEngine(backend=JaxBackend(bt=1024), max_rows=want)
    assert ok.execute(store, q).num_matches == want
    assert ok.stats.device_queries == 1
    tight = QueryEngine(backend=JaxBackend(bt=1024), max_rows=want - 1)
    with pytest.raises(MatchCapacityError):
        tight.execute(store, q)


def test_delta_invalidates_staged_views():
    """Staged device pred_index views are keyed by shard version: after an
    in-place delta the next batch re-stages and stays correct."""
    rng = np.random.default_rng(31)
    s, p, o = (rng.integers(0, 20, 80), rng.integers(0, 4, 80),
               rng.integers(0, 20, 80))
    sh = ShardedTripleStore(s, p, o, 20, 4, num_shards=2)
    mono = TripleStore(s, p, o, 20, 4)
    q = QueryGraph([TriplePattern("?x", 0, "?y"),
                    TriplePattern("?y", 1, "?z")], [])
    bk = JaxBackend(bt=1024)
    eng = QueryEngine(backend=bk)
    assert sol_rows(eng.execute(sh, q)) == sol_rows(match_bgp(mono, q))
    staged_before = len(bk._staged_views)
    assert staged_before > 0
    # rewrite part of pred 1 in place (new shard version)
    new_rows = np.stack([np.arange(5), np.ones(5, np.int64),
                         np.arange(5) + 5], axis=1)
    sh.apply_delta(TripleDelta(base_version=sh.version, add=new_rows))
    mono2 = TripleStore(*sh.triples().T, 20, 4)
    assert sol_rows(eng.execute(sh, q)) == sol_rows(match_bgp(mono2, q))
    assert eng.stats.device_queries == 2      # device path both times


def test_staged_view_lru_bounded():
    _, sh = _stores(scale=0.3, seed=7, shards=2)
    bk = JaxBackend(bt=1024)
    bk.max_staged_views = 2
    eng = QueryEngine(backend=bk)
    qs = [QueryGraph([TriplePattern("?x", pid, "?y"),
                      TriplePattern("?y", (pid + 1) % 4, "?z")], [])
          for pid in range(4)]
    ref = QueryEngine(backend="numpy")
    for res, want in zip(eng.execute_batch(sh, qs),
                         ref.execute_batch(sh, qs)):
        assert sol_rows(res) == sol_rows(want)
    assert len(bk._staged_views) <= 2
