"""Host spans and scan counters of the read path (``repro.tracing``).

A small JAX-backend endpoint behind ``AdmissionQueue`` runs a few batches
on the CPU under ``jax.profiler``; the trace must hold every span of
``tracing.SPANS``, each nested where the read path opens it, one
``batch`` per admission batch, and no more spans a batch than the stated
bound. With the profiler off the spans and the ``gc`` hook record nothing.
The prescan's counters count what it fetched and kept.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core.cost import SystemParams
from repro.edge.system import EdgeCloudSystem
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.sharding import ShardedTripleStore
from repro.runtime.admission import AdmissionQueue
from repro.sparql.endpoint import SparqlEndpoint
from repro.sparql.engine import JaxBackend, QueryEngine
from repro.sparql.query import QueryGraph, TriplePattern

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import spans as bench_spans  # noqa: E402

# a bound-predicate chain, which the device join takes; the generator's
# workload adds the complex template, which falls back to the host join
DEVICE_TEXT = "SELECT ?x ?p WHERE { ?x <likes> ?p . ?p <hasGenre> ?g }"

# where the read path opens each span: the names its parent may have
PARENTS = {
    "admission.window": {None},
    "admission.batch": {None},
    "endpoint.run": {"admission.batch"},
    "endpoint.parse": {"endpoint.run", None},      # None: at submit
    "algebra.evaluate": {"endpoint.run", "admission.batch"},
    "engine.execute_batch": {"algebra.evaluate"},
    "engine.scan_launch": {"engine.execute_batch"},
    "engine.scan_fetch": {"engine.execute_batch"},
    "engine.scan_unpack": {"engine.execute_batch"},
    "engine.host_join": {"engine.execute_batch"},
    "device_join.run": {"engine.execute_batch"},
    "device_join.fetch": {"device_join.run"},
    "scheduler.schedule": {"admission.batch"},
}


@pytest.fixture(scope="module")
def graph():
    return generate_watdiv_like(scale=0.5, seed=11)


def _endpoint(g, **kw):
    store = ShardedTripleStore.from_store(g.store, 4)
    return SparqlEndpoint(store, g.dictionary,
                          engine=QueryEngine(backend=JaxBackend(bt=1024)),
                          **kw)


def _round_endpoint(g):
    params = SystemParams.synthetic(n_users=4, n_edges=2, seed=3,
                                    cloud_mbps=0.05, f_ghz=2.0)
    system = EdgeCloudSystem(g.store, g.dictionary, params,
                             storage_budgets=10 ** 9)
    system.prepare([workload_sparql(g, 3, seed=100 + n) for n in range(4)])
    return SparqlEndpoint.from_system(system)


def _serve(ep, batches, **kw):
    """Each list of texts as one admission batch; the tickets, in order."""
    tickets = []
    with AdmissionQueue(ep, window_s=0.05, max_batch=64, **kw) as q:
        for texts in batches:
            ts = [q.submit(t, user=i % 4) for i, t in enumerate(texts)]
            for t in ts:
                t.result(timeout=120)
            tickets += ts
    return tickets


@contextlib.contextmanager
def _profiled():
    """Profile the block; yields a dict that holds ``pd`` afterwards."""
    out = {}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["pd"] = ProfileData.from_file(
            str(sorted(Path(d).glob("**/*.xplane.pb"))[-1]))


def _events(pd, name):
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    return [e for line in host.lines for e in line.events
            if e.name == name]


def _threads(pd):
    """Known spans per host thread, with each span's parent."""
    threads = bench_spans.host_lines(pd)
    parent = {}
    for spans in threads:
        for s in spans:
            for c in s.children:
                parent[id(c)] = s
    return threads, parent


@pytest.fixture(scope="module")
def traced(graph):
    texts = workload_sparql(graph, 6, seed=5)
    # plan memo off: the dispatcher parses again, under endpoint.run
    ep = _endpoint(graph, plan_cache_size=0)
    rep = _round_endpoint(graph)
    batches = [[DEVICE_TEXT] + texts[:2], texts[2:4], texts[4:]]
    _serve(ep, [batches[0]])                # compile outside the trace
    ep.clear_cache()
    with _profiled() as out:
        tickets = _serve(ep, batches)
        _serve(rep, [texts[:3]], mode="round")
        gc.collect()
    return out["pd"], tickets, batches


def test_every_span_recorded_and_nested(traced):
    pd, _, _ = traced
    threads, parent = _threads(pd)
    seen = {s.name for spans in threads for s in spans}
    assert seen == set(tracing.SPANS)
    for spans in threads:
        for s in spans:
            if s.name == "gc":
                continue
            p = parent.get(id(s))
            assert (None if p is None else p.name) in PARENTS[s.name], \
                (s.name, p and p.name)
    # the dispatcher parses under endpoint.run with the plan memo off
    assert any(parent.get(id(s)) is not None
               for spans in threads for s in spans
               if s.name == "endpoint.parse")


def _endpoint_batches(pd):
    """The endpoint queue's admission batches, in order: those with no
    scheduling solve under them."""
    threads, _ = _threads(pd)
    tops = sorted((s for spans in threads for s in spans
                   if s.name == "admission.batch"), key=lambda s: s.start)
    return [s for s in tops
            if not any(c.name == "scheduler.schedule" for c in s.children)]


def test_one_batch_value_per_admission_batch(traced):
    pd, tickets, batches = traced
    evs = sorted(_events(pd, "admission.batch"), key=lambda e: e.start_ns)
    meta = [dict(e.stats) for e in evs]
    # three endpoint batches and one round batch, of two queues
    assert len(evs) == len(batches) + 1
    assert sorted(m["batch"] for m in meta) == [0, 0, 1, 2]
    firsts = np.cumsum([0] + [len(b) for b in batches[:-1]])
    assert [m["batch"] for m in meta[:len(batches)]] == \
        [tickets[i].batch_seq for i in firsts] == [0, 1, 2]
    assert [m["size"] for m in meta[:len(batches)]] == \
        [len(b) for b in batches]


def test_batch_opens_at_most_the_stated_spans(traced):
    pd, _, batches = traced

    def count(s):
        # gc runs wherever it is triggered; parses only where plans miss
        return (s.name not in ("gc", "endpoint.parse")) + sum(
            count(c) for c in s.children)

    tops = _endpoint_batches(pd)
    assert len(tops) == len(batches)
    for s, texts in zip(tops, batches):
        assert count(s) <= 9 + len(texts)


def test_spans_and_gc_hook_record_nothing_with_the_profiler_off(graph):
    ep = _endpoint(graph)
    _serve(ep, [[DEVICE_TEXT]])
    gc.collect()
    with _profiled() as out:
        pass
    names = {e.name for p in out["pd"].planes for line in p.lines
             for e in line.events}
    assert not names & set(tracing.SPANS)
    tracing.install_gc_spans()
    tracing.install_gc_spans()
    assert gc.callbacks.count(tracing._gc_span) == 1


def test_span_is_a_null_context_without_jax(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation", None)
    monkeypatch.delitem(sys.modules, "jax.profiler")
    assert isinstance(tracing.span("engine.host_join"),
                      contextlib.nullcontext)
    tracing._gc_span("start", {"generation": 0})
    assert tracing._gc_open == []
    tracing._gc_span("stop", {"generation": 0})


def test_scan_counters_count_what_the_prescan_fetched_and_kept(graph):
    store = ShardedTripleStore.from_store(graph.store, 4)
    bk = JaxBackend(bt=1024)
    pid = graph.dictionary.predicate_id
    tps = [TriplePattern("?x", pid("likes"), "?y"),
           TriplePattern("?x", pid("follows"), "?y"),
           TriplePattern("?x", pid("follows"), "?x"),
           TriplePattern("?x", "?p", "?y")]
    out = bk.prescan_parts(store, tps)
    kept = sum(len(parts.concat()) for parts in out.values())

    def padded(flat):
        return max(1024, -(-flat.num_triples // 1024) * 1024)

    # one int32 mask row per pattern and shard it touches
    want = 4 * sum(padded(flat) for tp in tps
                   for flat, _ in bk._scan_parts(store, tp))
    assert bk.scan_rows_kept == kept > 0
    assert bk.scan_fetch_bytes == want
    # the one-pattern path counts the same way
    parts = bk.candidate_parts(store, tps[0])
    assert bk.scan_rows_kept == kept + len(parts.concat())
    flat, _ = bk._scan_parts(store, tps[0])[0]
    assert bk.scan_fetch_bytes == want + 4 * padded(flat)
    # the engine mirrors the backend's totals at batch end
    eng = QueryEngine(backend=bk)
    before = bk.scan_fetch_bytes
    eng.execute_batch(store, [QueryGraph([tps[2]], [])])   # host path
    assert bk.scan_fetch_bytes > before
    assert (eng.stats.scan_fetch_bytes, eng.stats.scan_rows_kept) == \
        (bk.scan_fetch_bytes, bk.scan_rows_kept)


def test_batch_stats_carry_the_scan_counters(graph):
    ep = _endpoint(graph)
    texts = workload_sparql(graph, 4, seed=7)
    with AdmissionQueue(ep, window_s=0.05, max_batch=64) as q:
        log = q.start_batch_log()
        for chunk in (texts[:2], texts[2:]):
            for t in [q.submit(x) for x in chunk]:
                t.result(timeout=120)
    es = ep.stats
    assert sum(b.scan_fetch_bytes for b in log) == es.scan_fetch_bytes > 0
    assert sum(b.scan_rows_kept for b in log) == es.scan_rows_kept > 0


def test_bench_reads_the_program_span_names():
    assert bench_spans.SPANS == tracing.SPANS
