"""Closed-loop concurrency benchmark for the serving front end (ISSUE 6).

Measures what the micro-batch admission window actually buys under
concurrent load: a paced client fleet offers queries at a target rate
(``--qps`` levels) against two admission configurations of the SAME
endpoint —

- ``seq``   — ``window_s=0, max_batch=1``: the sequential per-request
              baseline (every query is its own engine dispatch);
- ``coal``  — ``window_s=--window-ms, max_batch=--max-batch``: concurrent
              arrivals coalesce into ONE ``query_many`` engine batch.

Each (mode, temperature, qps) cell reports per-request latency percentiles
and achieved throughput. Pacing is closed-loop with a bounded worker
fleet: arrival *i* is scheduled at ``start + i/qps`` round-robin across
``--workers`` clients; a client that falls behind its schedule sends
immediately (so offered load saturates rather than stacking unbounded
threads), and latency is measured from the *scheduled* arrival — queueing
delay counts, as in any serving benchmark.

Temperatures: ``cold`` clears the endpoint+engine caches right before the
run; ``warm`` primes every workload text once. The warm/saturating cell is
the acceptance gate: coalesced admission must beat sequential on p99 —
batching amortizes the per-dispatch overhead that serializes the baseline.

Rows follow the harness contract (``name,us_per_call,derived`` —
``us_per_call`` is MEAN request latency in microseconds); machine-readable
JSON lands in ``BENCH_serving.json`` (``--json``) and CI uploads it next
to ``BENCH_engine.json``.

An optional end-to-end smoke (``--http``) drives one burst through the
real HTTP listener (sockets included) and reports the coalescing stats
observed by ``GET /stats``.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.kernels import use_compile_cache
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.runtime.admission import AdmissionError, AdmissionQueue
from repro.sparql.endpoint import SparqlEndpoint

try:
    from common import emit
except ImportError:                       # invoked as benchmarks/bench_...
    from benchmarks.common import emit


def run_level(ep: SparqlEndpoint, texts: list[str], *, qps: float,
              duration: float, window_s: float, max_batch: int,
              max_queue: int, workers: int, warm: bool) -> dict:
    """Offer ``qps`` for ``duration`` seconds; return latency/throughput."""
    if warm:
        ep.query_many(texts)              # prime result memo + engine LRUs
    else:
        ep.clear_cache()
    n = max(1, int(qps * duration))
    w = min(workers, n)
    lat = np.full(n, np.nan)
    rejected = [0] * w
    expired = [0] * w
    queue = AdmissionQueue(ep, window_s=window_s, max_batch=max_batch,
                           max_queue=max_queue)
    start = time.perf_counter() + 0.05    # common epoch for all clients

    def client(j: int) -> None:
        for i in range(j, n, w):
            due = start + i / qps
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                queue.query(texts[i % len(texts)])
            except AdmissionError as err:
                from repro.runtime.admission import DeadlineExceeded
                if isinstance(err, DeadlineExceeded):
                    expired[j] += 1
                else:
                    rejected[j] += 1
                continue
            # latency from the SCHEDULED arrival: queueing delay counts
            lat[i] = time.perf_counter() - due

    threads = [threading.Thread(target=client, args=(j,)) for j in range(w)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    queue.close(drain=True)
    ok = lat[~np.isnan(lat)]
    st = queue.stats
    return {
        "offered_qps": qps,
        "achieved_qps": float(len(ok) / wall) if wall > 0 else 0.0,
        "completed": int(len(ok)),
        "rejected": int(sum(rejected)), "expired": int(sum(expired)),
        "mean_ms": float(ok.mean() * 1e3) if len(ok) else float("nan"),
        "p50_ms": float(np.percentile(ok, 50) * 1e3) if len(ok) else
        float("nan"),
        "p99_ms": float(np.percentile(ok, 99) * 1e3) if len(ok) else
        float("nan"),
        "batches": st.batches,
        "mean_batch": round(st.mean_batch_size, 2),
        "max_coalesced": st.max_coalesced,
    }


def http_smoke(ep: SparqlEndpoint, texts: list[str], window_s: float,
               max_batch: int, clients: int = 24) -> dict:
    """One concurrent burst through the real HTTP listener.

    Texts are LIMIT-bounded: this cell isolates the serving path (sockets
    + admission + engine), not W3C-JSON encoding of 10k-row tables — the
    in-process cells already charge full result materialization.
    """
    import urllib.request
    from urllib.parse import quote

    from repro.runtime.http import SparqlHttpServer
    texts = [t + " LIMIT 64" for t in texts]
    ep.query_many(texts)                  # warm: overhead, not cold eval
    lat = [0.0] * clients
    with SparqlHttpServer(ep, window_s=window_s,
                          max_batch=max_batch) as srv:
        def client(j: int) -> None:
            url = (srv.url + "/sparql?query="
                   + quote(texts[j % len(texts)]))
            t0 = time.perf_counter()
            with urllib.request.urlopen(url) as r:
                r.read()
            lat[j] = time.perf_counter() - t0

        threads = [threading.Thread(target=client, args=(j,))
                   for j in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats_dict()
    return {
        "clients": clients,
        "mean_ms": float(np.mean(lat) * 1e3),
        "max_ms": float(np.max(lat) * 1e3),
        "batches": stats["admission"]["batches"],
        "max_coalesced": stats["admission"]["max_coalesced"],
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=2.0)
    ap.add_argument("--unique", type=int, default=12,
                    help="distinct query texts in the workload")
    ap.add_argument("--qps", type=str, default="500,4000,40000",
                    help="comma-separated offered-qps levels; the top "
                         "level should exceed the sequential dispatch "
                         "ceiling (~25k qps warm) so the baseline "
                         "actually saturates")
    ap.add_argument("--duration", type=float, default=1.5,
                    help="seconds of offered load per level")
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-queue", type=int, default=8192)
    ap.add_argument("--workers", type=int, default=96,
                    help="client fleet size (in-flight bound)")
    ap.add_argument("--http", action="store_true",
                    help="also run the end-to-end HTTP burst smoke")
    ap.add_argument("--json", type=str, default=None,
                    help="write machine-readable results (BENCH_serving"
                         ".json)")
    args = ap.parse_args()
    use_compile_cache()

    g = generate_watdiv_like(scale=args.scale, seed=0)
    texts = workload_sparql(g, args.unique, seed=1)
    levels = [float(x) for x in args.qps.split(",") if x]
    print(f"# serving bench: {g.store.num_triples} triples, "
          f"{len(texts)} distinct texts, levels={levels}, "
          f"window={args.window_ms}ms, max_batch={args.max_batch}")

    modes = {"seq": (0.0, 1), "coal": (args.window_ms * 1e-3,
                                       args.max_batch)}
    rows: list[tuple[str, float, dict]] = []
    cells: dict[tuple, dict] = {}

    def run_cell(mode: str, temp: str, qps: float) -> dict:
        win, mb = modes[mode]
        # fresh endpoint per cell: no cross-cell memo leakage
        ep = SparqlEndpoint(g.store, g.dictionary)
        r = run_level(ep, texts, qps=qps,
                      duration=args.duration, window_s=win,
                      max_batch=mb, max_queue=args.max_queue,
                      workers=args.workers, warm=temp == "warm")
        name = f"serve_{mode}_{temp}_q{int(qps)}"
        derived = {
            "p50_ms": f"{r['p50_ms']:.3f}",
            "p99_ms": f"{r['p99_ms']:.3f}",
            "achieved_qps": f"{r['achieved_qps']:.0f}",
            "completed": r["completed"],
            "rejected": r["rejected"],
            "batches": r["batches"],
            "mean_batch": r["mean_batch"],
            "max_coalesced": r["max_coalesced"],
        }
        emit(name, r["mean_ms"] * 1e3, **derived)
        rows.append((name, r["mean_ms"] * 1e3, {**derived, **r}))
        cells[(mode, temp, qps)] = r
        return r

    for temp in ("cold", "warm"):
        for mode in modes:
            for qps in levels:
                run_cell(mode, temp, qps)

    # -- gate level selection (de-flaked) ---------------------------------
    # The p99 gate is only meaningful when the offered rate exceeds the
    # sequential dispatch ceiling — below it there is no backlog for the
    # window to coalesce and seq-vs-coal p99 is pure noise. If the top
    # configured level failed to saturate the sequential baseline (it
    # achieved >= 80% of offered), auto-raise to 4x the measured
    # sequential throughput and re-run the two warm cells there.
    gate_qps = max(levels)
    gate_ok = True
    seq = cells[("seq", "warm", gate_qps)]
    if seq["achieved_qps"] >= 0.8 * gate_qps:
        boosted = float(round(4.0 * seq["achieved_qps"]))
        print(f"# gate: {int(gate_qps)} qps did not saturate the "
              f"sequential baseline (achieved "
              f"{seq['achieved_qps']:.0f} qps) — auto-raising the gate "
              f"level to {int(boosted)} qps")
        for mode in modes:
            run_cell(mode, "warm", boosted)
        gate_qps = boosted
        seq = cells[("seq", "warm", gate_qps)]
        if seq["achieved_qps"] >= 0.8 * gate_qps:
            gate_ok = False
            print("# WARNING: sequential dispatch still keeps up at "
                  f"{int(gate_qps)} offered qps (achieved "
                  f"{seq['achieved_qps']:.0f}); this machine/workload has "
                  "no dispatch backlog to amortize — SKIPPING the "
                  "coalesced-p99 gate")

    if args.http:
        ep = SparqlEndpoint(g.store, g.dictionary)
        r = http_smoke(ep, texts, args.window_ms * 1e-3, args.max_batch)
        emit("serve_http_burst", r["mean_ms"] * 1e3,
             clients=r["clients"], batches=r["batches"],
             max_coalesced=r["max_coalesced"],
             max_ms=f"{r['max_ms']:.3f}")
        rows.append(("serve_http_burst", r["mean_ms"] * 1e3, r))

    if args.json:
        payload = {
            "meta": {
                "bench": "bench_serving",
                "timestamp": time.time(),
                "scale": args.scale,
                "num_triples": int(g.store.num_triples),
                "unique_texts": len(texts),
                "qps_levels": levels,
                "duration_s": args.duration,
                "window_ms": args.window_ms,
                "max_batch": args.max_batch,
                "workers": args.workers,
                "http_smoke": bool(args.http),
            },
            "rows": [{"name": n, "us_per_call": round(us, 3),
                      "derived": d} for n, us, d in rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    # acceptance gate (ISSUE 6): at a genuinely saturating offered rate,
    # warm, coalesced micro-batch admission must beat sequential on p99
    if gate_ok:
        seq99 = cells[("seq", "warm", gate_qps)]["p99_ms"]
        coal99 = cells[("coal", "warm", gate_qps)]["p99_ms"]
        print(f"# warm @ {int(gate_qps)} qps: seq p99={seq99:.3f}ms "
              f"coal p99={coal99:.3f}ms")
        assert coal99 < seq99, (
            f"coalesced admission (p99 {coal99:.3f}ms) should beat "
            f"sequential per-request (p99 {seq99:.3f}ms) at "
            f"{gate_qps:.0f} offered qps warm")


if __name__ == "__main__":
    main()
