"""Bring-up smoke test: the SPARQL device path on one TPU chip at 10M triples.

Drives the query path a user calls, once, at the size of WatDiv's standard
10M-triple data set:

  generate  a WatDiv-like graph (``scale=1000``: ~10M triples, ~2M
            entities), sharded four ways;
  stage     an edge-cloud system (cloud + 3 edges, ``backend="jax"``)
            places pattern-induced subgraphs on its edges from per-user
            history, and the cloud's shards go to the device as columns;
  reference the same queries through a ``backend="numpy"`` endpoint;
  first     one batch through ``AdmissionQueue`` -> ``SparqlEndpoint`` ->
            ``QueryEngine`` -> ``JaxBackend`` / ``DeviceBatch`` -> Pallas
            kernels, compiling as it goes;
  warm      the same batch again after the result caches are dropped —
            nothing should compile;
  round     one ``run_round_batched`` cloud-edge round (B&B scheduling),
            which stages and queries the edges' stores too.

The queries are WatDiv-style templates: constant-anchored stars and paths,
which run device-resident, and the ``complex`` template, whose second
constant makes it fall back to the host join by design. Every answer is
compared, as a multiset of binding rows, with the numpy reference.

Runs in this one process, which owns the chip: no subprocess, no fork pool.
The last line of standard output is one JSON object naming the device. The
script exits non-zero, without that line, when the platform is not a TPU,
when a kernel ran in interpret mode, when no query ran on the device, when
any answer differs from the reference, or when any phase raised.

    python chip_smoke.py                      # on the chip, full size
    JAX_PLATFORMS=cpu python chip_smoke.py --scale 1
        # rehearsal: the phases run in interpret mode, then the script
        # fails because there is no TPU
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FULL_SCALE = 1000.0
#: largest --scale run off-TPU: a rehearsal is a control-flow check, and
#: the full size in interpret mode would take hours and tens of GiB
MAX_REHEARSAL_SCALE = 5.0
TEMPLATES = ("anchored_star", "anchored_chain", "complex")
PER_TEMPLATE = 8                  # 24 queries per batch
N_USERS = 12
EDGE_BUDGET_BYTES = 256 << 20
BATCH_TIMEOUT_S = 900.0           # wait for one batch's answers


class SmokeFailure(Exception):
    pass


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits, process-wide."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def sorted_rows(table) -> tuple[list[str], np.ndarray]:
    """(sorted variable names, rows in those columns, sorted) — the
    binding multiset of one answer, independent of row and column order."""
    names = sorted(table.var_names)
    b = np.asarray(table.bindings, dtype=np.int64)
    b = b[:, [table.var_names.index(v) for v in names]]
    if len(b):
        b = b[np.lexsort(b.T[::-1])]
    return names, b


def check_answers(phase: str, texts, got, want) -> int:
    """Raise unless every answer equals its reference; returns rows."""
    rows = 0
    for i, (text, g, w) in enumerate(zip(texts, got, want, strict=True)):
        gn, gb = sorted_rows(g)
        wn, wb = sorted_rows(w)
        if gn != wn or not np.array_equal(gb, wb):
            raise SmokeFailure(
                f"{phase}: answer {i} differs from the numpy reference "
                f"({len(gb)} rows {gn} vs {len(wb)} rows {wn}): {text}")
        rows += len(gb)
    return rows


def serve_batch(endpoint, texts: list[str], timeout_s: float) -> list:
    """One admission window: submit every text, then wait for each ticket
    (a rejected ticket raises its error here)."""
    from repro.runtime.admission import AdmissionQueue

    with AdmissionQueue(endpoint, window_s=0.5,
                        max_batch=len(texts)) as queue:
        tickets = [queue.submit(t) for t in texts]
        tables = [t.result(timeout=timeout_s) for t in tickets]
        batches = queue.stats.batches
    print(f"  admission batches={batches} queries={len(texts)}")
    return tables


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=FULL_SCALE,
                    help="WatDiv-like graph scale (1000 ~= 10M triples)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.kernels import use_compile_cache

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and args.scale > MAX_REHEARSAL_SCALE:
        raise SmokeFailure(
            f"no TPU: JAX found platform {platform!r}; this smoke test "
            f"runs the device path on a TPU chip (off-TPU, --scale <= "
            f"{MAX_REHEARSAL_SCALE:g} rehearses the phases in interpret "
            f"mode)")
    cache_dir = use_compile_cache()
    counter = CompileCounter()
    print(f"device platform={platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}")

    from repro.core.cost import SystemParams
    from repro.edge.system import EdgeCloudSystem
    from repro.rdf.generator import generate_watdiv_like, workload_sparql
    from repro.rdf.sharding import ShardedTripleStore
    from repro.sparql.endpoint import SparqlEndpoint

    times: dict[str, float] = {}

    def phase(name: str, fn):
        c0, h0 = counter.compiles, counter.cache_hits
        t0 = time.perf_counter()
        out = fn()
        times[name] = time.perf_counter() - t0
        print(f"phase {name}: wall_s={times[name]:.3f} "
              f"compiles={counter.compiles - c0} "
              f"persistent_cache_hits={counter.cache_hits - h0}",
              flush=True)
        return out

    # -- generate ----------------------------------------------------------
    g = phase("generate",
              lambda: generate_watdiv_like(scale=args.scale, seed=args.seed))
    print(f"  triples={g.store.num_triples} "
          f"entities={g.store.num_entities} "
          f"predicates={g.store.num_predicates}")
    texts: list[str] = []
    for k, name in enumerate(TEMPLATES):
        texts += workload_sparql(g, PER_TEMPLATE, seed=args.seed + k,
                                 templates=[name])
    # placement history: the device-eligible templates only — ``complex``
    # stays cloud-only (its pattern is never resident at an edge)
    history = [workload_sparql(g, 4, seed=1000 + n, templates=TEMPLATES[:2])
               for n in range(N_USERS)]

    # -- stage ---------------------------------------------------------------
    def stage():
        store = ShardedTripleStore.from_store(g.store, 4)
        params = SystemParams.synthetic(n_users=N_USERS, n_edges=3,
                                        seed=args.seed)
        system = EdgeCloudSystem(store, g.dictionary, params,
                                 storage_budgets=EDGE_BUDGET_BYTES,
                                 backend="jax")
        system.prepare(history)
        nbytes = system.engine.backend.stage(store)
        return store, system, nbytes

    store, system, staged = phase("stage", stage)
    engine = system.engine
    print(f"  shards={[sh.num_triples for sh in store.shards]} "
          f"backend_mode={engine.stats.backend_mode} "
          f"staged_bytes={staged} edge_triples="
          f"{[es.store.num_triples if es.store else 0 for es in system.edges]}")
    if engine.backend.interpret:
        print("  kernels run in interpret mode (no TPU)")

    ref = SparqlEndpoint(store, g.dictionary, backend="numpy")
    want = phase("reference", lambda: ref.query_many(texts))
    print(f"  reference rows={sum(len(t) for t in want)}")

    ep = SparqlEndpoint.from_system(system)

    def counters():
        s = engine.stats
        return (s.device_queries, s.device_fallbacks, s.host_transfers,
                s.scalar_syncs)

    def report(name: str, before) -> None:
        d = [a - b for a, b in zip(counters(), before)]
        print(f"  {name}: device_queries={d[0]} device_fallbacks={d[1]} "
              f"host_transfers={d[2]} scalar_syncs={d[3]}")

    # -- first (compiling) and warm batch ------------------------------------
    for name in ("first", "warm"):
        ep.clear_cache()              # both batches execute every query
        before = counters()
        got = phase(name, lambda: serve_batch(ep, texts, BATCH_TIMEOUT_S))
        rows = check_answers(name, texts, got, want)
        report(name, before)
        print(f"  {name}: {len(texts)} answers equal the reference "
              f"({rows} rows)")

    # -- one cloud-edge round ------------------------------------------------
    ep.clear_cache()
    before = counters()
    rep = phase("round", lambda: ep.run_round(
        [(i % N_USERS, t) for i, t in enumerate(texts)], policy="bnb",
        collect_results=True))
    rows = check_answers("round", texts, rep.results, want)
    report("round", before)
    print(f"  round: assignment_counts={dict(rep.assignment_counts)} "
          f"partial_queries={rep.partial_queries}; {len(texts)} answers "
          f"equal the reference ({rows} rows)")

    s = engine.stats
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"totals: backend_mode={s.backend_mode} "
          f"device_queries={s.device_queries} "
          f"device_fallbacks={s.device_fallbacks} "
          f"host_transfers={s.host_transfers} "
          f"scalar_syncs={s.scalar_syncs} peak_bytes_in_use={peak}")
    print("wall_s " + " ".join(f"{k}={v:.3f}" for k, v in times.items()))

    if platform != "tpu":
        raise SmokeFailure(f"no TPU: the phases ran on platform "
                           f"{platform!r}, not on a TPU chip")
    if engine.backend.interpret or s.backend_mode != "jax-compiled":
        raise SmokeFailure(f"kernels ran as {s.backend_mode}, not compiled")
    if s.device_queries == 0:
        raise SmokeFailure("no query ran on the device")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
