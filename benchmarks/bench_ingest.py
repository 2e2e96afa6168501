"""Sustained mixed read/write ingest benchmark (ISSUE 9).

Measures what the live-ingest write path costs concurrent readers — and
gates that it stays bounded. One sharded edge-cloud system serves a
closed-loop reader fleet through the micro-batch admission queue
(``mode="round"``: every read is a scheduled round under the placement
lock, exactly the path writes and rebalance commits contend with) in two
phases:

- ``base``  — readers only: the read-only p99 baseline.
- ``mixed`` — the same fleet, plus a writer issuing ``INSERT DATA`` /
              ``DELETE DATA`` through the SAME admission queue (writes
              serialize against the micro-batch windows they invalidate),
              plus a multi-epoch **pipelined rebalance**
              (``RebalanceManager.run_pipeline``) running mid-phase — the
              continuous-ingest regime where placement maintenance must
              never block reads.

Acceptance gates (process exits nonzero on violation):

- the pipelined rebalance commits ``>= --epochs`` placement epochs while
  the mixed traffic runs;
- mixed-phase read p99 stays within ``--factor`` of
  ``max(base p99, 2 * window)`` — write traffic and rebalances may tax
  reads but never wedge them behind a stop-the-world ingest;
- post-quiesce, every populated edge replica sits at the cloud store's
  exact version and one scheduled round is bit-identical to the cloud
  oracle.

Rows follow the harness contract (``name,us_per_call,derived`` —
``us_per_call`` is MEAN read latency); ``--json`` writes
``BENCH_ingest.json`` for CI upload next to the other bench artifacts.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.core.cost import SystemParams
from repro.core.pattern import pattern_of
from repro.edge.system import EdgeCloudSystem
from repro.kernels import use_compile_cache
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.sharding import ShardedTripleStore
from repro.runtime.admission import AdmissionError, AdmissionQueue
from repro.sparql.endpoint import SparqlEndpoint
from repro.sparql.query import parse_sparql

try:
    from common import emit
except ImportError:                       # invoked as benchmarks/bench_...
    from benchmarks.common import emit

LEAVES = {
    0: ["SELECT ?x ?p WHERE { ?x <likes> ?p }"],
    1: ["SELECT ?p ?gn WHERE { ?p <hasGenre> ?gn }",
        "SELECT ?x ?y WHERE { ?x <follows> ?y }"],
    2: ["SELECT ?x ?c WHERE { ?x <country> ?c }"],
}


def build_system(g, shards: int) -> EdgeCloudSystem:
    store = ShardedTripleStore.from_store(g.store, num_shards=shards)
    K, N = 3, 4
    params = SystemParams(
        F=np.full(K, 1.0e9),
        r_edge=np.full((N, K), 75e6),
        r_cloud=np.full(N, 5e6),
        assoc=np.ones((N, K), dtype=bool),
        r_backhaul=np.full(K, 1e9),
        F_cloud=0.05e9,
    )
    sys_ = EdgeCloudSystem(store, g.dictionary, params,
                           storage_budgets=10_000_000, backend="numpy")
    for k, texts in LEAVES.items():
        sys_.edges[k].deploy(store, [pattern_of(parse_sparql(
            t, g.dictionary)) for t in texts])
    return sys_


def read_phase(queue: AdmissionQueue, texts: list[str], *,
               duration: float, readers: int) -> np.ndarray:
    """Closed-loop reader fleet: each client issues back-to-back reads
    until the deadline; returns the per-request latencies (seconds)."""
    lats: list[list[float]] = [[] for _ in range(readers)]
    deadline = time.perf_counter() + duration

    def client(j: int) -> None:
        i = j
        while time.perf_counter() < deadline:
            t0 = time.perf_counter()
            try:
                queue.query(texts[i % len(texts)], user=i % 4)
            except AdmissionError:
                i += 1
                continue
            lats[j].append(time.perf_counter() - t0)
            i += 1

    threads = [threading.Thread(target=client, args=(j,))
               for j in range(readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return np.array([x for row in lats for x in row])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--duration", type=float, default=1.5,
                    help="seconds of offered load per phase")
    ap.add_argument("--readers", type=int, default=6)
    ap.add_argument("--write-interval-ms", type=float, default=5.0,
                    help="writer think time between updates")
    ap.add_argument("--epochs", type=int, default=2,
                    help="pipelined rebalance epochs during the mixed "
                         "phase (the gate requires all to commit)")
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--factor", type=float, default=30.0,
                    help="mixed p99 must stay within this factor of "
                         "max(base p99, 2*window)")
    ap.add_argument("--json", type=str, default=None,
                    help="write machine-readable results (BENCH_ingest"
                         ".json)")
    args = ap.parse_args()
    use_compile_cache()

    g = generate_watdiv_like(scale=args.scale, seed=0)
    sys_ = build_system(g, args.shards)
    store = sys_.cloud.store
    ep = SparqlEndpoint(system=sys_)
    texts = workload_sparql(g, 8, seed=1)
    window_s = args.window_ms * 1e-3
    queue = AdmissionQueue(ep, window_s=window_s, max_batch=64,
                           max_queue=4096, mode="round",
                           mode_kw={"policy": "greedy"})
    print(f"# ingest bench: {store.num_triples} triples, "
          f"{args.shards} shards, {args.readers} readers, "
          f"{args.duration}s/phase, window={args.window_ms}ms")

    # -- phase 1: read-only baseline -----------------------------------------
    ep.query_many(texts)                  # warm plans + engine LRUs
    base = read_phase(queue, texts, duration=args.duration,
                      readers=args.readers)
    base_p99 = float(np.percentile(base, 99))

    # -- phase 2: mixed read/write with pipelined rebalances ------------------
    writes_done = [0]
    stop_writer = threading.Event()
    writer_err: list[BaseException] = []

    def writer() -> None:
        i = 0
        try:
            while not stop_writer.is_set():
                if i % 3 == 2:
                    text = (f"DELETE DATA {{ <ing_u{i - 1}> <likes> "
                            f"<ing_p{i - 1}> }}")
                else:
                    text = (f"INSERT DATA {{ <ing_u{i}> <likes> "
                            f"<ing_p{i}> . <ing_u{i}> <country> "
                            f"<ing_c{i % 2}> }}")
                queue.query(text)         # writes ride the same admission
                writes_done[0] += 1
                i += 1
                time.sleep(args.write_interval_ms * 1e-3)
        except BaseException as err:
            writer_err.append(err)

    pipe_reports: list = []
    pipe_err: list[BaseException] = []

    def rebalancer() -> None:
        time.sleep(args.duration * 0.25)  # mid-phase, under live traffic
        try:
            pipe_reports.extend(
                sys_.rebalancer.run_pipeline(epochs=args.epochs))
        except BaseException as err:
            pipe_err.append(err)

    wt = threading.Thread(target=writer, name="ingest-writer")
    rt = threading.Thread(target=rebalancer, name="ingest-rebalance")
    wt.start()
    rt.start()
    mixed = read_phase(queue, texts, duration=args.duration,
                       readers=args.readers)
    stop_writer.set()
    wt.join(15.0)
    rt.join(30.0)
    queue.close(drain=True)
    mixed_p99 = float(np.percentile(mixed, 99))

    # -- post-quiesce consistency --------------------------------------------
    for es in sys_.edges:
        if es.store is not None:
            assert es.resident_cloud_version == store.version, (
                f"edge ES{es.server_id} replica at "
                f"{es.resident_cloud_version}, cloud at {store.version}")
    queries = [(i % 4, parse_sparql(t, g.dictionary))
               for i, t in enumerate(texts)]
    rep = sys_.run_round_batched(queries, policy="greedy", execute=True,
                                 collect_results=True)

    def rows_of(res):                     # column-order-independent rows
        idx = [res.var_names.index(v) for v in sorted(res.var_names)]
        return sorted(map(tuple, res.bindings[:, idx].tolist()))

    for (res, (_, q)) in zip(rep.results, queries):
        want = sys_.engine.execute(store, q)
        assert rows_of(res) == rows_of(want), (
            "scheduled round diverged from the cloud oracle post-ingest")

    floor = 2.0 * window_s
    rows = [
        ("read_base", float(base.mean() * 1e6),
         {"p50_ms": round(float(np.percentile(base, 50)) * 1e3, 3),
          "p99_ms": round(base_p99 * 1e3, 3), "n": int(len(base))}),
        ("read_mixed", float(mixed.mean() * 1e6),
         {"p50_ms": round(float(np.percentile(mixed, 50)) * 1e3, 3),
          "p99_ms": round(mixed_p99 * 1e3, 3), "n": int(len(mixed)),
          "writes": writes_done[0],
          "rebalance_epochs": len(pipe_reports)}),
    ]
    for name, us, derived in rows:
        emit(name, us, **derived)

    if args.json:
        payload = {
            "meta": {
                "bench": "bench_ingest",
                "timestamp": time.time(),
                "scale": args.scale, "shards": args.shards,
                "num_triples": int(store.num_triples),
                "readers": args.readers, "duration": args.duration,
                "window_ms": args.window_ms, "factor": args.factor,
                "epochs_requested": args.epochs,
            },
            "rows": [{"name": n, "us_per_call": round(us, 3), **d}
                     for n, us, d in rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    assert not writer_err, writer_err
    assert not pipe_err, pipe_err
    assert writes_done[0] > 0, "writer made no progress"
    assert len(pipe_reports) >= args.epochs, (
        f"pipelined rebalance committed {len(pipe_reports)} epochs, "
        f"wanted >= {args.epochs}")
    bound = args.factor * max(base_p99, floor)
    assert mixed_p99 <= bound, (
        f"mixed read p99 ({mixed_p99 * 1e3:.2f}ms) blew past "
        f"{args.factor}x the read-only baseline "
        f"(p99 {base_p99 * 1e3:.2f}ms, floor {floor * 1e3:.1f}ms): "
        "ingest is blocking reads")
    print(f"# gate ok: mixed p99 {mixed_p99 * 1e3:.2f}ms <= "
          f"{bound * 1e3:.2f}ms across {len(pipe_reports)} pipelined "
          f"rebalance epochs and {writes_done[0]} writes")


if __name__ == "__main__":
    main()
