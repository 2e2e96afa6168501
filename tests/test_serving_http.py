"""Serving front end: micro-batch admission + SPARQL-protocol HTTP layer.

Covers the ISSUE-6 surface:

- admission coalescing (concurrent submissions -> ONE engine batch, parity
  with ``query_many``), the sequential degenerate mode, queue-full
  backpressure, deadline expiry, eager parse rejection, close semantics;
- HTTP JSON parity with ``SparqlEndpoint.query`` across both backends x
  both store kinds, GET + both POST encodings, ASK, the W3C results shape
  (unbound cells omitted, predicate-space vars typed ``uri``);
- HTTP status mapping: 400 / 404 / 415 / 503 + Retry-After / 504;
- admission racing ``republish`` / ``rebalance_async`` (round and pool
  modes stay correct across placement epochs);
- the three ISSUE-6 regression fixes, each failing on pre-PR code:
  runnerless-replica reassignment (``OffloadServingPool.admit``), plan
  memo keyed on dictionary version (``SparqlEndpoint.parse``), and
  mid-batch store-version moves never caching under a stale version
  (``SparqlEndpoint._run``).
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request
from collections import Counter
from urllib.parse import quote, urlencode

import numpy as np
import pytest

from repro.core.cost import SystemParams
from repro.edge.system import EdgeCloudSystem
from repro.rdf.deltas import TripleDelta
from repro.rdf.dictionary import Dictionary
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.graph import TripleStore
from repro.rdf.sharding import ShardedTripleStore
from repro.runtime.admission import (AdmissionClosed, AdmissionFullError,
                                     AdmissionQueue, DeadlineExceeded)
from repro.runtime.http import SparqlHttpServer, table_to_json
from repro.runtime.serving import (OffloadServingPool, Replica,
                                   make_sparql_runner)
from repro.sparql.endpoint import SparqlEndpoint
from repro.sparql.engine import QueryEngine
from repro.sparql.query import ParseError

BACKENDS = ["numpy", "jax"]
KINDS = ["mono", "sharded"]


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def build_graph():
    d = Dictionary()
    people = ["alice", "bob", "carol", "dave"]
    products = ["p1", "p2", "p3"]
    cities = ["paris", "tokyo"]
    for t in people + products + cities:
        d.add_entity(t)
    for p in ["knows", "likes", "city"]:
        d.add_predicate(p)
    triples = [
        ("alice", "knows", "bob"), ("bob", "knows", "carol"),
        ("alice", "knows", "carol"), ("carol", "knows", "dave"),
        ("alice", "likes", "p1"), ("bob", "likes", "p1"),
        ("carol", "likes", "p2"), ("dave", "likes", "p3"),
        ("alice", "city", "paris"), ("bob", "city", "paris"),
        ("carol", "city", "tokyo"),          # dave: no city
    ]
    s = np.array([d.entity_id(a) for a, _, _ in triples])
    p = np.array([d.predicate_id(b) for _, b, _ in triples])
    o = np.array([d.entity_id(c) for _, _, c in triples])
    return TripleStore(s, p, o, d.num_entities, d.num_predicates), d


QUERIES = [
    'SELECT ?a ?b WHERE { ?a <knows> ?b }',
    'SELECT ?a ?c WHERE { ?a <knows> ?b . OPTIONAL { ?b <city> ?c } }',
    'SELECT ?x WHERE { { ?x <likes> <p1> } UNION { ?x <city> <tokyo> } }',
    'SELECT DISTINCT ?c WHERE { ?a <city> ?c } ORDER BY ?c',
    'SELECT ?p WHERE { <alice> ?p ?x }',
]


def store_of(kind, store):
    return (ShardedTripleStore.from_store(store, 3) if kind == "sharded"
            else store)


def table_multiset(table):
    return Counter(table.rows(decoded=True))


def http_get(url, query, **params):
    qs = urlencode({"query": query, **params})
    with urllib.request.urlopen(f"{url}/sparql?{qs}") as r:
        return r.status, json.loads(r.read())


@pytest.fixture()
def graph():
    return build_graph()


# ---------------------------------------------------------------------------
# admission queue semantics
# ---------------------------------------------------------------------------


def test_admission_coalesces_concurrent_submissions(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    texts = QUERIES + QUERIES[:3]           # duplicates coalesce too
    with AdmissionQueue(ep, window_s=0.25, max_batch=32) as q:
        tickets = [q.submit(t) for t in texts]
        tables = [t.result(timeout=10) for t in tickets]
    ref = SparqlEndpoint(store, d).query_many(texts)
    for got, want in zip(tables, ref):
        assert table_multiset(got) == table_multiset(want)
    # every submission landed in ONE micro-batch
    assert q.stats.batches == 1
    assert q.stats.max_coalesced == len(texts)
    assert len({t.batch_seq for t in tickets}) == 1
    bs = q.stats.recent[-1]
    assert bs.size == len(texts)
    assert bs.unique_texts == len(QUERIES)  # in-batch text dedup visible
    assert bs.window_fill == pytest.approx(len(texts) / 32)


def test_admission_sequential_degenerate_mode(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with AdmissionQueue(ep, window_s=0.0, max_batch=1) as q:
        for t in QUERIES:
            got = q.query(t)
            assert table_multiset(got) == table_multiset(ep.query(t))
    assert q.stats.batches == len(QUERIES)
    assert q.stats.max_coalesced == 1


def test_queue_full_backpressure_and_drain(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    q = AdmissionQueue(ep, window_s=5.0, max_batch=64, max_queue=2,
                       retry_after_s=0.125)
    t1, t2 = q.submit(QUERIES[0]), q.submit(QUERIES[1])
    with pytest.raises(AdmissionFullError) as exc:
        q.submit(QUERIES[2])
    assert exc.value.retry_after_s == 0.125
    assert q.stats.rejected == 1
    # close(drain=True) dispatches the waiting tickets without the window
    q.close(drain=True)
    assert t1.result(timeout=10).num_matches == \
        ep.query(QUERIES[0]).num_matches
    assert t2.result(timeout=10).num_matches == \
        ep.query(QUERIES[1]).num_matches
    with pytest.raises(AdmissionClosed):
        q.submit(QUERIES[0])


def test_deadline_expired_tickets_dropped_before_dispatch(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with AdmissionQueue(ep, window_s=0.3, max_batch=64) as q:
        doomed = q.submit(QUERIES[0], timeout_s=0.01)
        alive = q.submit(QUERIES[1], timeout_s=30.0)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert alive.result(timeout=10).num_matches == \
            ep.query(QUERIES[1]).num_matches
    assert q.stats.expired == 1
    assert q.stats.completed == 1
    assert q.stats.recent[-1].expired == 1


def test_submit_parses_eagerly_without_occupying_queue(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with AdmissionQueue(ep, window_s=1.0) as q:
        with pytest.raises(ParseError):
            q.submit("SELECT garbage")
        assert q.depth == 0
        assert q.stats.submitted == 0


def test_close_without_drain_rejects_pending(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    q = AdmissionQueue(ep, window_s=5.0, max_batch=64)
    t = q.submit(QUERIES[0])
    q.close(drain=False)
    with pytest.raises(AdmissionClosed):
        t.result(timeout=10)


# ---------------------------------------------------------------------------
# HTTP layer: JSON parity, W3C shape, status codes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", KINDS)
def test_http_json_parity_with_endpoint(graph, backend, kind):
    store, d = graph
    st = store_of(kind, store)
    ep = SparqlEndpoint(st, d, backend=backend)
    with SparqlHttpServer(ep, window_s=0.002) as srv:
        for text in QUERIES:
            status, payload = http_get(srv.url, text)
            assert status == 200
            want = ep.query(text)
            assert payload == table_to_json(want)
            assert payload["head"]["vars"] == \
                [v.lstrip("?") for v in want.var_names]
            assert len(payload["results"]["bindings"]) == want.num_matches


def test_http_post_both_encodings_and_ask(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with SparqlHttpServer(ep, window_s=0.002) as srv:
        want = json.loads(json.dumps(table_to_json(ep.query(QUERIES[0]))))
        raw = urllib.request.Request(
            srv.url + "/sparql", data=QUERIES[0].encode(),
            headers={"Content-Type": "application/sparql-query"})
        with urllib.request.urlopen(raw) as r:
            assert r.status == 200 and json.loads(r.read()) == want
        form = urllib.request.Request(
            srv.url + "/sparql",
            data=urlencode({"query": QUERIES[0]}).encode(),
            headers={"Content-Type": "application/x-www-form-urlencoded"})
        with urllib.request.urlopen(form) as r:
            assert r.status == 200 and json.loads(r.read()) == want
        _, yes = http_get(srv.url, 'ASK { ?x <knows> <carol> }')
        assert yes == {"head": {}, "boolean": True}
        _, no = http_get(srv.url, 'ASK { <dave> <city> ?c }')
        assert no == {"head": {}, "boolean": False}


def test_http_w3c_shape_unbound_omitted_and_pred_typing(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with SparqlHttpServer(ep, window_s=0.002) as srv:
        # OPTIONAL: ?c unbound where carol's successor has no city
        _, payload = http_get(srv.url, QUERIES[1])
        bindings = payload["results"]["bindings"]
        missing = [b for b in bindings if "c" not in b]
        assert missing, "unbound OPTIONAL cells must be OMITTED, not empty"
        for b in bindings:
            for var, term in b.items():
                assert set(term) == {"type", "value"}
        # predicate-space variables serialize as IRIs
        _, preds = http_get(srv.url, QUERIES[4])
        kinds = {b["p"]["type"] for b in preds["results"]["bindings"]}
        assert kinds == {"uri"}
        vals = {b["p"]["value"] for b in preds["results"]["bindings"]}
        assert vals == {"knows", "likes", "city"}


def test_http_error_codes(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with SparqlHttpServer(ep, window_s=0.002) as srv:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/sparql")
        assert e.value.code == 400                       # missing query
        with pytest.raises(urllib.error.HTTPError) as e:
            http_get(srv.url, "SELECT garbage")
        assert e.value.code == 400                       # parse error
        assert "error" in json.loads(e.value.read())
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/nope")
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/sparql", data=b"x",
                headers={"Content-Type": "text/plain"}))
        assert e.value.code == 415
        with pytest.raises(urllib.error.HTTPError) as e:
            http_get(srv.url, QUERIES[0], timeout="banana")
        assert e.value.code == 400                       # bad param
        with urllib.request.urlopen(srv.url + "/healthz") as r:
            assert r.status == 200
        stats = json.loads(
            urllib.request.urlopen(srv.url + "/stats").read())
        assert stats["admission"]["rejected"] == 0


def test_http_503_queue_full_with_retry_after(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with SparqlHttpServer(ep, window_s=1.0, max_batch=64,
                          max_queue=1, retry_after_s=0.25) as srv:
        codes = {}

        def first():
            codes["first"] = http_get(srv.url, QUERIES[0])[0]

        t = threading.Thread(target=first)
        t.start()
        # wait until the first request occupies the only queue slot
        deadline = threading.Event()
        for _ in range(100):
            if srv.queue.depth == 1:
                break
            deadline.wait(0.01)
        assert srv.queue.depth == 1
        with pytest.raises(urllib.error.HTTPError) as e:
            http_get(srv.url, QUERIES[1])
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] == "0.250"
        t.join(15)
        assert codes["first"] == 200


def test_http_504_deadline(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with SparqlHttpServer(ep, window_s=0.3, max_batch=64) as srv:
        with pytest.raises(urllib.error.HTTPError) as e:
            http_get(srv.url, QUERIES[0], timeout="0.01")
        assert e.value.code == 504


def test_http_concurrent_clients_one_batch(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    texts = QUERIES * 4
    with SparqlHttpServer(ep, window_s=0.25, max_batch=64) as srv:
        out = [None] * len(texts)

        def client(i, t):
            out[i] = http_get(srv.url, t)

        ths = [threading.Thread(target=client, args=(i, t))
               for i, t in enumerate(texts)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(30)
        for (status, payload), text in zip(out, texts):
            assert status == 200
            assert payload == table_to_json(ep.query(text))
        stats = json.loads(
            urllib.request.urlopen(srv.url + "/stats").read())
    # the window coalesced the burst into very few engine batches
    assert stats["admission"]["batches"] <= 3
    assert stats["admission"]["max_coalesced"] >= len(QUERIES)
    assert stats["endpoint_memo"]["hits"] >= 1   # duplicate texts memo-hit
    # the engine's split of its time and the scan path's counters, as the
    # engine counted them (the numpy backend fetches no scan masks)
    es = ep.stats
    assert stats["engine"]["prescan_seconds"] == round(es.prescan_seconds, 6)
    assert stats["engine"]["join_seconds"] == round(es.join_seconds, 6)
    assert 0 < stats["engine"]["prescan_seconds"]
    assert 0 < stats["engine"]["join_seconds"]
    assert stats["engine"]["scan_fetch_bytes"] == es.scan_fetch_bytes == 0
    assert stats["engine"]["scan_rows_kept"] == es.scan_rows_kept == 0


# ---------------------------------------------------------------------------
# admission x placement churn (round + pool modes)
# ---------------------------------------------------------------------------


def make_system(g, n_edges=2):
    params = SystemParams.synthetic(n_users=6, n_edges=n_edges, seed=3,
                                    cloud_mbps=0.05, f_ghz=2.0)
    sys_ = EdgeCloudSystem(g.store, g.dictionary, params,
                           storage_budgets=10 ** 9)
    sys_.prepare([workload_sparql(g, 3, seed=100 + n) for n in range(6)])
    return sys_


def test_round_mode_collects_results_and_matches_endpoint():
    g = generate_watdiv_like(scale=0.5, seed=11)
    sys_ = make_system(g)
    ep = SparqlEndpoint.from_system(sys_)
    texts = workload_sparql(g, 6, seed=5)
    with AdmissionQueue(ep, window_s=0.2, max_batch=32, mode="round") as q:
        tickets = [q.submit(t, user=i % sys_.params.N)
                   for i, t in enumerate(texts)]
        tables = [t.result(timeout=30) for t in tickets]
    ref = SparqlEndpoint(g.store, g.dictionary).query_many(texts)
    for got, want in zip(tables, ref):
        assert got is not None
        assert table_multiset(got) == table_multiset(want)


def test_pool_mode_matches_endpoint():
    g = generate_watdiv_like(scale=0.5, seed=11)
    eng = QueryEngine()
    runner = make_sparql_runner(g.store, eng)
    pool = OffloadServingPool(
        replicas=[Replica(0, {0}, 2e9, 50e6, runner)],
        cloud_runner=runner)
    ep = SparqlEndpoint(g.store, g.dictionary, engine=eng, pool=pool)
    texts = workload_sparql(g, 6, seed=5)
    # mode_kw forwards scheduling knobs to admit_many: greedy placement
    # keeps wide coalesced batches off the exponential B&B path
    with AdmissionQueue(ep, window_s=0.2, max_batch=32, mode="pool",
                        mode_kw={"policy": "greedy"}) as q:
        tables = [t.result(timeout=30) for t in
                  [q.submit(t) for t in texts]]
    ref = SparqlEndpoint(g.store, g.dictionary).query_many(texts)
    for got, want in zip(tables, ref):
        assert table_multiset(got) == table_multiset(want)


def test_admission_mode_validation(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    with pytest.raises(ValueError):
        AdmissionQueue(ep, mode="round")     # no system attached
    with pytest.raises(ValueError):
        AdmissionQueue(ep, mode="pool")      # no pool attached
    with pytest.raises(ValueError):
        AdmissionQueue(ep, mode="warp")


@pytest.mark.slow
def test_round_mode_admission_racing_rebalance_async():
    """Concurrent clients x rebalance_async: every admitted batch holds the
    placement-epoch barrier, so results stay byte-correct across commits."""
    g = generate_watdiv_like(scale=0.5, seed=11)
    sys_ = make_system(g, n_edges=3)
    ep = SparqlEndpoint.from_system(sys_)
    texts = workload_sparql(g, 8, seed=5)
    ref = {t: table_multiset(r) for t, r in zip(
        texts, SparqlEndpoint(g.store, g.dictionary).query_many(texts))}
    errors = []
    with AdmissionQueue(ep, window_s=0.01, max_batch=64,
                        mode="round") as q:
        stop = threading.Event()

        def client(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    t = texts[rng.integers(len(texts))]
                    got = q.query(t, user=int(rng.integers(6)))
                    assert table_multiset(got) == ref[t], t
            except Exception as exc:      # pragma: no cover - fail path
                errors.append(exc)

        clients = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for c in clients:
            c.start()
        for _ in range(4):                # placement churn mid-traffic
            sys_.rebalance_async().join(30)
        stop.set()
        for c in clients:
            c.join(30)
    assert not errors, errors[:1]
    assert q.stats.completed > 0 and q.stats.failed == 0


@pytest.mark.slow
def test_pool_mode_admission_racing_republish():
    g = generate_watdiv_like(scale=0.5, seed=11)
    eng = QueryEngine()
    runner = make_sparql_runner(g.store, eng)
    pool = OffloadServingPool(
        replicas=[Replica(0, {0}, 2e9, 50e6, runner),
                  Replica(1, {0}, 2e9, 80e6, runner)],
        cloud_runner=runner)
    ep = SparqlEndpoint(g.store, g.dictionary, engine=eng, pool=pool)
    texts = workload_sparql(g, 8, seed=5)
    ref = {t: table_multiset(r) for t, r in zip(
        texts, SparqlEndpoint(g.store, g.dictionary).query_many(texts))}
    errors = []
    with AdmissionQueue(ep, window_s=0.01, max_batch=64, mode="pool") as q:
        stop = threading.Event()

        def client(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    t = texts[rng.integers(len(texts))]
                    assert table_multiset(q.query(t)) == ref[t], t
            except Exception as exc:      # pragma: no cover - fail path
                errors.append(exc)

        clients = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for c in clients:
            c.start()
        for i in range(30):               # class churn mid-traffic
            pool.republish(i % 2, {0} if i % 3 else set())
        stop.set()
        for c in clients:
            c.join(30)
    assert not errors, errors[:1]
    assert pool.epoch == 30 and q.stats.failed == 0


# ---------------------------------------------------------------------------
# ISSUE-6 regression fixes (each fails on pre-PR code)
# ---------------------------------------------------------------------------


def test_runnerless_replica_reassigned_to_cloud():
    """Regression (ISSUE 6 satellite 1): a replica whose ``runner`` is None
    must not report edge assignments while the cloud executed the work."""
    cloud_calls = []

    def cloud_runner(ps):
        cloud_calls.append(len(ps))
        return ["cloud"] * len(ps)

    pool = OffloadServingPool(
        replicas=[Replica(0, {0}, 2e9, 1e8, None)],     # scheduler bait
        cloud_runner=cloud_runner)
    reqs = [{"class_id": 0, "cycles": 1e6, "result_bits": 8e3,
             "payload": i} for i in range(4)]
    # the scheduler itself wants the (fast, feasible) edge
    sim = pool.admit(reqs, policy="edge_first", execute=False)
    assert list(sim.assignments) == [0, 0, 0, 0]
    # ...but at execute time the runnerless replica cannot serve: the
    # executed placement AND the reported assignments must both say cloud
    out = pool.admit(reqs, policy="edge_first", execute=True)
    assert list(out.assignments) == [-1, -1, -1, -1]
    assert out.responses == ["cloud"] * 4
    assert cloud_calls == [4]


@pytest.mark.parametrize("kind", KINDS)
def test_plan_memo_invalidated_by_dictionary_growth(graph, kind):
    """Regression (ISSUE 6 satellite 2): a FILTER constant unknown at first
    compile bakes ``ent_id=None`` into the memoized plan; after live ingest
    adds the term, the SAME text must see it."""
    store, d = graph
    st = store_of(kind, store)
    ep = SparqlEndpoint(st, d)
    text = ('SELECT ?x WHERE { ?x <likes> ?prod . '
            'FILTER (?prod = "pnew") }')
    assert ep.query(text).num_matches == 0   # "pnew" not in the dictionary
    # live ingest: new term + a triple using it (store version moves too,
    # so the RESULT memo self-invalidates — the PLAN memo is what's tested)
    pid = d.add_entity("pnew")
    row = np.array([[d.entity_id("alice"), d.predicate_id("likes"), pid]])
    st.apply_delta(TripleDelta(base_version=st.version, add=row))
    got = ep.query(text)
    assert got.num_matches == 1
    assert got.rows(decoded=True) == [("alice",)]


def test_plan_memo_still_memoizes_within_a_version(graph):
    store, d = graph
    ep = SparqlEndpoint(store, d)
    assert ep.parse(QUERIES[0]) is ep.parse(QUERIES[0])
    v = d.version
    d.add_entity("alice")                    # existing term: NOT a new id
    assert d.version == v                    # so no invalidation
    assert ep.parse(QUERIES[0]) is ep.parse(QUERIES[0])


def test_midbatch_version_move_skips_result_caching(graph, monkeypatch):
    """Regression (ISSUE 6 satellite 3): when the store version moves
    between dispatch and caching, results must NOT be cached under the
    dispatch-time version."""
    import repro.sparql.endpoint as ep_mod
    store, d = graph
    ep = SparqlEndpoint(store, d)
    text = QUERIES[0]
    real = ep_mod.evaluate_many

    def racing(plans, st, engine):
        # a content-no-op delta: same row evicted and re-added — data is
        # unchanged but the version token moves, exactly what a concurrent
        # delta-rebalance commit does mid-batch
        row = st.triples()[:1]
        st.apply_delta(TripleDelta(base_version=st.version,
                                   add=row, evict=row))
        return real(plans, st, engine)

    monkeypatch.setattr(ep_mod, "evaluate_many", racing)
    v_old = store.version
    got = ep.query(text)                     # still answers correctly
    assert got.num_matches == 4
    assert (text, v_old) not in ep._results, \
        "results computed after a version move were cached under the " \
        "dispatch-time version"
    assert not any(k[0] == text for k in ep._results)
    # with the race gone, the same text caches normally again
    monkeypatch.setattr(ep_mod, "evaluate_many", real)
    ep.query(text)
    assert (text, store.version) in ep._results
