"""Per-backend batch throughput of the BGP query engine, with a shard axis.

Contract (benchmarks/common.py): ``name,us_per_call,derived`` CSV rows —
``us_per_call`` is microseconds per *query* (per *scan* for ``scan_*`` rows).
Modes:

- ``engine_loop``         per-query ``match_bgp`` calls (the pre-engine path)
- ``engine_numpy_batch``  engine batch, NumPy backend, cold cache
- ``engine_numpy_warm``   same batch again: LRU result-cache hits
- ``engine_jax_batch``    engine batch, ``triple_scan`` Pallas backend
                          (interpret mode off-TPU — compiled on TPU; the CPU
                          number is an upper bound, reported for completeness)
- ``..._s{S}``            the same against a ``ShardedTripleStore`` with S
                          predicate-hash shards
- ``scan_{backend}_*``    candidate-scan microbench: one ``prescan`` of the
                          workload's deduplicated bound-predicate patterns.
                          This isolates shard pruning: on the monolithic
                          store the JAX backend streams all T triples per
                          scan, on the sharded store only the owning shard's
                          ~T/S — the sharded scan should win on
                          bound-predicate workloads (the common case).

``--join`` adds the join-pipeline axis (PR 3):

- ``join_shard_s{S}`` /   cold engine batches on the sharded store with the
  ``join_global_s{S}``    shard-local presorted join pipeline vs the global
                          scan+argsort baseline (``shard_local_joins=False``)
                          — per-phase timings (prescan/join seconds) and
                          ``JoinStats`` counters land in ``derived``.
- ``round_seq_s{S}``      one multi-edge ``EdgeCloudSystem`` scheduling round
  ``round_thread_s{S}``   executed sequentially, with per-server batches
  ``round_process_s{S}``  through a thread pool (``overlap=True`` — wins
                          where the hot paths release the GIL), and through
                          the persistent fork pool (``overlap="process"`` —
                          true parallelism for the GIL-bound numpy path);
                          the number reported is the execute phase's wall
                          clock, best of interleaved repeats.

``--algebra`` adds the SPARQL algebra axis (PR 5): three operator-heavy
workloads (FILTER-heavy, OPTIONAL-heavy / left-joins, UNION fan-out) run
through :class:`repro.sparql.endpoint.SparqlEndpoint` on the largest
sharded store, cold (caches cleared) and warm (repeated texts hit the
endpoint's version-keyed full-result memo; distinct-but-alpha-equivalent
sub-BGPs hit the engine's result LRU) —
``algebra_{filter,optional,union}_{cold,warm}_s{S}`` rows with per-operator
counters (``bgp_leaves`` / ``filters_applied`` / ``optional_joins`` /
``union_branches``) in ``derived``. Warm must beat cold: that is the
cache-reuse contract of compiling algebra onto the batched BGP engine.

``--rebalance`` adds the placement data-plane axis (PR 4): two identically
drifted systems rebalance with full re-ship vs delta shipping
(``rebalance_full_s{S}`` / ``rebalance_delta_s{S}`` — wall clock per
rebalance, modeled wire bytes in ``derived``; delta must move strictly
fewer bytes at the 100k+ scale), and a rebalance+round pair runs
sequentially vs overlapped (``round_rebalance_sync_s{S}`` /
``round_rebalance_overlap_s{S}`` — the async compute phase overlaps the
round; commit waits at the epoch barrier).

``--kernels`` adds the device-kernel axis (PR 7):

- ``kernel_triple_scan_many``   batched candidate scan: Q deduplicated
                                patterns x T triples in one launch; derived
                                reports bytes streamed per scan and the
                                achieved GB/s (compare against the roofline's
                                memory-bound peak)
- ``kernel_probe_sorted_many``  the sorted-probe join kernel over the hottest
                                predicate's sorted index
- ``engine_jax_{device,host}_s{S}``  cold engine batches with the
                                device-resident join pipeline vs the forced
                                host path (``device_resident=False``) —
                                ``host_transfers`` / ``transfer_bytes`` /
                                ``scalar_syncs`` in ``derived`` record the
                                one-bulk-transfer-per-batch contract

``--partial`` adds the collaborative partial-evaluation axis (PR 8): a
bandwidth-constrained placement where two edges each hold ONE leaf of a
two-leaf join runs one scheduling round with the three-way scheduler
(``round_partial_eval``) vs the legacy binary cloud-only scheduler
(``round_cloudonly_eval``, ``enable_partial=False``) — Eq. 5
modeled/realized response times, cloud-server wall, and
``partial_bytes_shipped`` vs the full induced-subgraph re-ship bytes
land in ``derived``; partial must win response time AND ship fewer
bytes than full re-ship.

The workload repeats a pool of template queries (users re-issue hot
queries), so scan dedup and the result cache both engage — the acceptance
targets are ``engine_numpy_batch`` beating ``engine_loop`` on a >=64-query
batch over a >=100k-triple store, and sharded ``scan_jax`` beating the
monolithic scan at the same scale.

Timings are also written as machine-readable JSON (``--json``, default
``BENCH_engine.json``) so the perf trajectory is tracked across PRs; CI
uploads it as an artifact.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.kernels import use_compile_cache
from repro.rdf.generator import generate_watdiv_like, workload_sparql
from repro.rdf.sharding import ShardedTripleStore
from repro.sparql.engine import QueryEngine, get_backend, scan_key
from repro.sparql.matcher import match_bgp
from repro.sparql.query import parse_query, parse_sparql


def bench(fn, n_calls: int, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best / n_calls




def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=15.0,
                    help="graph scale (15 ~= 100k+ triples)")
    ap.add_argument("--batch", type=int, default=96,
                    help="queries per batch (>=64 for the acceptance run)")
    ap.add_argument("--unique", type=int, default=16,
                    help="distinct query texts in the pool")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--shards", type=str, default="4,8",
                    help="comma-separated shard counts for the sharded-store "
                         "axis ('' disables)")
    ap.add_argument("--json", type=str, default="BENCH_engine.json",
                    help="write timings as machine-readable JSON "
                         "('' disables)")
    ap.add_argument("--skip-jax", action="store_true",
                    help="skip the interpret-mode JAX backend (slow off-TPU)")
    ap.add_argument("--join", action="store_true",
                    help="join-pipeline axis: shard-local vs global joins "
                         "+ overlapped vs sequential multi-edge rounds")
    ap.add_argument("--algebra", action="store_true",
                    help="SPARQL algebra axis: FILTER-heavy / "
                         "OPTIONAL-heavy / UNION fan-out workloads through "
                         "SparqlEndpoint, cold vs warm")
    ap.add_argument("--rebalance", action="store_true",
                    help="placement data-plane axis: full re-ship vs delta "
                         "rebalance bytes/wall-clock + sync vs overlapped "
                         "rebalance-round pairs")
    ap.add_argument("--kernels", action="store_true",
                    help="device-kernel axis (PR 7): triple_scan_many / "
                         "probe_sorted_many throughput + the device-resident "
                         "vs host join pipeline with transfer accounting")
    ap.add_argument("--partial", action="store_true",
                    help="collaborative partial-evaluation axis (PR 8): a "
                         "bandwidth-constrained multi-edge placement where "
                         "no single edge holds every leaf — partial "
                         "(edge-set -> cloud assembler) vs the cloud-only "
                         "legacy round on Eq. 5 response time and shipped "
                         "bytes")
    ap.add_argument("--round-edges", type=int, default=4,
                    help="edge servers in the --join/--rebalance rounds")
    args = ap.parse_args()
    use_compile_cache()
    if args.batch < 1 or args.unique < 1 or args.scale <= 0:
        ap.error("--batch/--unique must be >= 1 and --scale > 0")
    shard_counts = [int(x) for x in args.shards.split(",") if x.strip()]
    if any(s < 1 for s in shard_counts):
        ap.error("--shards entries must be >= 1")

    g = generate_watdiv_like(scale=args.scale, seed=0)
    texts = workload_sparql(g, args.unique, seed=123)
    pool = [parse_sparql(t, g.dictionary) for t in texts]
    queries = [pool[i % len(pool)] for i in range(args.batch)]
    stores = [("", g.store)]
    stores += [(f"_s{S}", ShardedTripleStore.from_store(g.store, S))
               for S in shard_counts]
    # deduplicated candidate scans of the pool — all templates use bound
    # predicates, so this is the partition-pruned common case
    scan_tps = list({scan_key(tp): tp
                     for q in pool for tp in q.patterns}.values())
    print(f"# store: {g.store.num_triples} triples, "
          f"{g.store.num_entities} entities; batch {len(queries)} "
          f"({len(pool)} unique, {len(scan_tps)} distinct scans); "
          f"shards {shard_counts or '-'}")

    rows: list[tuple[str, float, str]] = []

    t_loop = bench(lambda: [match_bgp(g.store, q) for q in queries],
                   len(queries), args.repeats)
    rows.append(("engine_loop", t_loop * 1e6, "backend=none"))

    t_scan: dict[tuple[str, str], float] = {}   # (backend, suffix) -> s/scan

    def bench_backend(backend: str, suffix: str, store, repeats: int) -> float:
        eng = QueryEngine(backend=backend)

        def cold():
            eng.clear_cache()
            eng.execute_batch(store, queries)
        t_cold = bench(cold, len(queries), repeats)
        s = eng.stats
        rows.append((f"engine_{backend}_batch{suffix}", t_cold * 1e6,
                     f"backend={backend}|scans_deduped={s.scans_deduped}"
                     f"|speedup_vs_loop={t_loop / t_cold:.2f}x"))
        # scan microbench: prescan the deduplicated bound-predicate pool
        # directly (bypasses the engine's scan LRU)
        be = eng.backend
        be.prescan(store, scan_tps)              # stage arrays / compile
        t_s = bench(lambda: be.prescan(store, scan_tps), len(scan_tps),
                    repeats)
        t_scan[(backend, suffix)] = t_s
        rows.append((f"scan_{backend}{suffix}", t_s * 1e6,
                     f"backend={backend}|scans={len(scan_tps)}"))
        return t_cold

    t_cold = bench_backend("numpy", "", g.store, args.repeats)

    eng = QueryEngine(backend="numpy")
    eng.execute_batch(g.store, queries)          # prime
    t_warm = bench(lambda: eng.execute_batch(g.store, queries),
                   len(queries), args.repeats)
    rows.append(("engine_numpy_warm", t_warm * 1e6,
                 f"backend=numpy|cache=hit"
                 f"|speedup_vs_loop={t_loop / t_warm:.2f}x"))

    for suffix, store in stores[1:]:
        bench_backend("numpy", suffix, store, args.repeats)

    # ---- join-pipeline axis (--join): shard-local vs global joins ---------
    # Runs BEFORE the jax section: interpret-mode Pallas leaves XLA worker
    # threads and staged device buffers behind that perturb the wall-clock
    # A/B below. Reps are interleaved shard/global for the same reason.
    t_join: dict[str, float] = {}
    t_round: dict[str, float] = {}
    if args.join and shard_counts:
        S = max(shard_counts)
        store_s = dict(stores)[f"_s{S}"]
        join_engines = {mode: QueryEngine(backend="numpy",
                                          shard_local_joins=flag)
                        for mode, flag in (("shard", True),
                                           ("global", False))}
        t_join = {mode: float("inf") for mode in join_engines}
        join_reps = max(3, args.repeats)
        for _ in range(join_reps):                   # interleaved best-of
            for mode, eng in join_engines.items():
                eng.clear_cache()
                t0 = time.perf_counter()
                eng.execute_batch(store_s, queries)
                t_join[mode] = min(t_join[mode],
                                   (time.perf_counter() - t0)
                                   / len(queries))
        for mode, eng in join_engines.items():
            # stats accumulate over all repeats (clear_cache keeps them);
            # report per-repeat values (exact for counters, mean for the
            # phase seconds) so they pair with the per-repeat best-of time
            js = eng.stats.join
            rows.append((
                f"join_{mode}_s{S}", t_join[mode] * 1e6,
                f"backend=numpy|shard_local={eng.shard_local_joins}"
                f"|pred_index_joins={js.joins_pred_index // join_reps}"
                f"|vertex_joins={js.joins_vertex // join_reps}"
                f"|pred_var_joins={js.joins_pred_var // join_reps}"
                f"|merged_joins={js.merged_joins // join_reps}"
                f"|prescan_s={eng.stats.prescan_seconds / join_reps:.4f}"
                f"|join_s={eng.stats.join_seconds / join_reps:.4f}"))
        rows[-2] = (rows[-2][0], rows[-2][1], rows[-2][2] +
                    f"|speedup_vs_global="
                    f"{t_join['global'] / t_join['shard']:.2f}x")

        # ---- overlapped vs sequential multi-edge round --------------------
        from repro.core.cost import SystemParams
        from repro.edge.system import EdgeCloudSystem
        K = max(2, args.round_edges)
        params = SystemParams.synthetic(n_users=max(8, 2 * K), n_edges=K,
                                        seed=5)
        sys_ = EdgeCloudSystem(store_s, g.dictionary, params,
                               storage_budgets=10**9, backend="numpy")
        sys_.prepare([texts for _ in range(params.N)])
        round_queries = [(i % params.N, q) for i, q in enumerate(queries)]
        servers = len(sys_.run_round_batched(            # warm indexes
            round_queries, policy="greedy",
            observe=False).assignment_counts)

        # explicit mode strings: overlap=True now auto-picks process for
        # numpy engines, so the thread row must ask for threads by name
        modes = (("seq", False), ("thread", "thread"),
                 ("process", "process"))
        t_round = {name: float("inf") for name, _ in modes}
        mode_seen = {name: "seq" for name, _ in modes}
        for _ in range(max(3, args.repeats)):            # interleaved
            for name, ov in modes:
                sys_.clear_engine_caches()
                rep = sys_.run_round_batched(round_queries, policy="greedy",
                                             observe=False, overlap=ov)
                t_round[name] = min(t_round[name], rep.execute_wall_seconds)
                mode_seen[name] = rep.overlap_mode or "seq"
        sys_.close_overlap_pool()
        for name, _ in modes:
            extra = ("" if name == "seq" else
                     f"|speedup_vs_seq={t_round['seq'] / t_round[name]:.2f}x")
            rows.append((f"round_{name}_s{S}", t_round[name] * 1e6,
                         f"backend=numpy|edges={K}|servers={servers}"
                         f"|batch={len(round_queries)}"
                         f"|mode={mode_seen[name]}{extra}"))

    # ---- SPARQL algebra axis (--algebra) ----------------------------------
    t_alg: dict[tuple[str, str], float] = {}
    if args.algebra:
        from repro.sparql.endpoint import SparqlEndpoint
        S = max(shard_counts) if shard_counts else None
        alg_suffix = f"_s{S}" if S else ""
        store_a = dict(stores)[alg_suffix] if S else g.store
        n_c = min(8, len(g.class_of["Country"]))
        workloads = {
            "filter": [
                f'SELECT ?x ?c WHERE {{ ?x <country> ?c . ?x <likes> ?p . '
                f'FILTER (?c != "Country{k}" && REGEX(?c, "Country[0-9]$")) '
                f'}}' for k in range(n_c)],
            "optional": [
                f'SELECT ?x ?g ?rt WHERE {{ ?x <likes> ?p . '
                f'OPTIONAL {{ ?p <hasGenre> ?g }} . '
                f'OPTIONAL {{ ?p <retailedBy> ?rt }} . '
                f'?x <country> ?c . FILTER (?c = "Country{k}") }}'
                for k in range(n_c)],
            "union": [
                f'SELECT ?x ?y WHERE {{ '
                f'{{ ?x <follows> ?y }} UNION {{ ?x <likes> ?y }} '
                f'UNION {{ ?x <makesPurchase> ?y }} . '
                f'?x <country> ?c . FILTER (?c = "Country{k}") }}'
                for k in range(n_c)],
        }
        for name, pool_t in workloads.items():
            batch_t = [pool_t[i % len(pool_t)] for i in range(args.batch)]
            ep = SparqlEndpoint(store_a, g.dictionary, backend="numpy")

            def cold():
                ep.clear_cache()
                ep.query_many(batch_t)
            t_c = bench(cold, len(batch_t), args.repeats)
            ep.query_many(batch_t)               # prime
            t_w = bench(lambda: ep.query_many(batch_t), len(batch_t),
                        args.repeats)
            t_alg[(name, "cold")] = t_c
            t_alg[(name, "warm")] = t_w
            s = ep.stats
            ops = (f"bgp_leaves={s.bgp_leaves}"
                   f"|filters={s.filters_applied}"
                   f"|optional_joins={s.optional_joins}"
                   f"|union_branches={s.union_branches}")
            rows.append((f"algebra_{name}_cold{alg_suffix}", t_c * 1e6,
                         f"backend=numpy|workload={name}|{ops}"))
            rows.append((f"algebra_{name}_warm{alg_suffix}", t_w * 1e6,
                         f"backend=numpy|workload={name}|cache=hit"
                         f"|speedup_vs_cold={t_c / t_w:.2f}x"))

    # ---- placement data-plane axis (--rebalance) --------------------------
    reb_stats: dict[str, dict] = {}
    if args.rebalance and shard_counts:
        from repro.core.cost import SystemParams
        from repro.edge.system import EdgeCloudSystem
        S = max(shard_counts)
        store_s = dict(stores)[f"_s{S}"]
        K = max(2, args.round_edges)
        # budget admits the whole prepared residency with room for the
        # drift's additions: the regime delta shipping targets is
        # incremental growth/partial overlap (a swap of one of few HUGE
        # patterns is near-total churn, where plan_rebalance's wire-cost
        # fallback re-ships in full — bounded at parity by construction)
        budget = store_s.size_bytes()

        def drifted_system():
            """Deterministic system with *incremental* workload drift:
            prepared on the template pool, then a few new templates turn
            hot on top of it — the regime dynamic placement targets (most
            residency unchanged, a handful of adds/evicts per epoch)."""
            params = SystemParams.synthetic(n_users=max(8, 2 * K),
                                            n_edges=K, seed=11)
            sys_ = EdgeCloudSystem(store_s, g.dictionary, params,
                                   storage_budgets=budget, backend="numpy")
            sys_.prepare([texts for _ in range(params.N)])
            drift_texts = texts + workload_sparql(
                g, max(4, args.unique // 2), seed=777)
            dq = [(i % params.N, parse_sparql(t, g.dictionary))
                  for i, t in enumerate(drift_texts)]
            for _ in range(3):
                sys_.run_round_batched(dq, policy="greedy", execute=False)
            return sys_, dq

        for mode, use_deltas in (("full", False), ("delta", True)):
            sys_r, dq = drifted_system()
            t0 = time.perf_counter()
            sys_r.rebalance_all(use_deltas=use_deltas)
            dt = time.perf_counter() - t0
            rep = sys_r.last_rebalance
            reb_stats[mode] = {
                "wall": dt, "bytes": rep.shipped_bytes,
                "full_bytes": rep.full_bytes, "changed": rep.changed,
                "matcher_calls": rep.matcher_calls,
                "induced_hits": rep.induced_hits,
                "changes": sum(a + e for a, e in rep.changes.values())}
            rows.append((
                f"rebalance_{mode}_s{S}", dt * 1e6,
                f"backend=numpy|edges={K}|use_deltas={use_deltas}"
                f"|bytes_shipped={rep.shipped_bytes}"
                f"|pattern_changes={reb_stats[mode]['changes']}"
                f"|matcher_calls={rep.matcher_calls}"
                f"|induced_hits={rep.induced_hits}"
                f"|commit_s={rep.commit_seconds:.4f}"))
        if reb_stats["delta"]["bytes"]:
            rows[-1] = (rows[-1][0], rows[-1][1], rows[-1][2] +
                        f"|bytes_vs_full={reb_stats['full']['bytes'] / reb_stats['delta']['bytes']:.1f}x")

        # sync (rebalance then round) vs overlapped (compute || round)
        for mode in ("sync", "overlap"):
            sys_r, dq = drifted_system()
            t0 = time.perf_counter()
            if mode == "sync":
                sys_r.rebalance_all()
                sys_r.run_round_batched(dq, policy="greedy", observe=False)
            else:
                handle = sys_r.rebalance_async()
                sys_r.run_round_batched(dq, policy="greedy", observe=False)
                handle.join(120)
            dt = time.perf_counter() - t0
            reb_stats[f"round_{mode}"] = {"wall": dt}
            extra = ("" if mode == "sync" else
                     f"|speedup_vs_sync="
                     f"{reb_stats['round_sync']['wall'] / dt:.2f}x")
            rows.append((f"round_rebalance_{mode}_s{S}", dt * 1e6,
                         f"backend=numpy|edges={K}|batch={len(dq)}{extra}"))

    # ---- collaborative partial evaluation axis (--partial, PR 8) ----------
    part_stats: dict[str, dict] = {}
    reship = 0
    if args.partial:
        import numpy as np

        from repro.core.cost import SystemParams
        from repro.core.induced import reship_bytes
        from repro.core.pattern import pattern_of
        from repro.edge.system import EdgeCloudSystem
        from repro.sparql.algebra import compile_query

        # Bandwidth-constrained placement: two edges each hold ONE leaf of
        # a two-leaf join, the user->cloud uplink is slow (5 Mbps) and the
        # cloud compute pool is congested (finite F_cloud), while the
        # edge->assembler backhaul is a fast datacenter link — the regime
        # partial evaluation targets. Neither edge can run the whole query,
        # so the legacy binary scheduler's only option is cloud.
        Kp, Np = 2, 4
        pparams = SystemParams(
            F=np.full(Kp, 1.0e9),
            r_edge=np.full((Np, Kp), 75e6),
            r_cloud=np.full(Np, 5e6),
            assoc=np.ones((Np, Kp), dtype=bool),
            r_backhaul=np.full(Kp, 1e9),
            F_cloud=0.05e9,
        )
        d = g.dictionary
        pat_a = pattern_of(parse_sparql(
            "SELECT ?x ?p WHERE { ?x <likes> ?p }", d))
        pat_b = pattern_of(parse_sparql(
            "SELECT ?p ?gn WHERE { ?p <hasGenre> ?gn }", d))
        plan_p = compile_query(parse_query(
            "SELECT ?x ?gn WHERE { { ?x <likes> ?p } "
            "{ ?p <hasGenre> ?gn } }", d), d)
        # one query per round: the shipped-bytes gate compares ONE partial
        # evaluation's binding tables against ONE full induced-subgraph
        # re-ship — q identical partial queries would q-count the tables
        # while full residency ships the subgraph once
        pqueries = [(0, plan_p)]
        reship = reship_bytes(g.store, [pat_a, pat_b])
        for mode, enable in (("partial", True), ("cloudonly", False)):
            sys_p = EdgeCloudSystem(g.store, d, pparams,
                                    storage_budgets=10**9,
                                    enable_partial=enable, backend="numpy")
            sys_p.edges[0].deploy(g.store, [pat_a])
            sys_p.edges[1].deploy(g.store, [pat_b])
            rep = sys_p.run_round_batched(pqueries, policy="bnb",
                                          observe=False)
            n = len(pqueries)
            part_stats[mode] = {
                "modeled": rep.total_modeled_latency / n,
                "realized": rep.total_realized_latency / n,
                "cloud_wall": rep.server_wall_seconds.get(-1, 0.0),
                "partial_queries": rep.partial_queries,
                "bytes": rep.partial_bytes_shipped,
                "fallbacks": rep.partial_fallbacks,
            }
            st = part_stats[mode]
            extra = ""
            if mode == "cloudonly" and part_stats["partial"]["modeled"]:
                extra = (f"|modeled_speedup_of_partial="
                         f"{st['modeled'] / part_stats['partial']['modeled']:.2f}x")
            rows.append((
                f"round_{mode}_eval", rep.execute_wall_seconds / n * 1e6,
                f"backend=numpy|edges={Kp}|batch={n}"
                f"|partial_queries={st['partial_queries']}"
                f"|partial_bytes_shipped={st['bytes']}"
                f"|reship_bytes={reship}"
                f"|modeled_ms={st['modeled'] * 1e3:.3f}"
                f"|realized_ms={st['realized'] * 1e3:.3f}"
                f"|cloud_wall_s={st['cloud_wall']:.4f}"
                f"|fallbacks={st['fallbacks']}{extra}"))

    if not args.skip_jax:
        import jax
        mode = ("compiled" if jax.default_backend() == "tpu"
                else "interpret")
        jax_repeats = max(1, args.repeats - 2)
        for suffix, store in stores:
            bench_backend("jax", suffix, store, jax_repeats)
            rows[-2] = (rows[-2][0], rows[-2][1],
                        rows[-2][2] + f"|pallas={mode}")

    # ---- device-kernel axis (--kernels, PR 7) -----------------------------
    if args.kernels:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from collections import Counter

        from repro.kernels import default_interpret
        from repro.kernels.join_probe import probe_sorted_many
        from repro.kernels.triple_scan import triple_scan_many
        from repro.sparql.engine import JaxBackend

        interp = default_interpret()
        pmode = "interpret" if interp else "compiled"
        kern_repeats = max(1, args.repeats - 2)

        # batched candidate scan over the workload's deduplicated patterns:
        # each of the Q patterns streams all T triple rows (12 B each, as
        # three int32 columns)
        cols = tuple(jnp.asarray(c, jnp.int32)
                     for c in (g.store.s, g.store.p, g.store.o))
        scan_bytes = sum(int(c.nbytes) for c in cols)
        pat_mat = jnp.asarray(np.stack(
            [[tp.s if isinstance(tp.s, int) else -1,
              tp.p if isinstance(tp.p, int) else -1,
              tp.o if isinstance(tp.o, int) else -1] for tp in scan_tps]),
            jnp.int32)

        def scan_call():
            jax.block_until_ready(
                triple_scan_many(cols, pat_mat, interpret=interp))

        scan_call()                              # stage + compile
        t_sc = bench(scan_call, len(scan_tps), kern_repeats)
        rows.append((
            "kernel_triple_scan_many", t_sc * 1e6,
            f"backend=jax|pallas={pmode}|patterns={len(scan_tps)}"
            f"|triples={g.store.num_triples}"
            f"|bytes_per_scan={scan_bytes}"
            f"|achieved_gbps={scan_bytes / t_sc / 1e9:.3f}"))

        # sorted-probe join kernel over the hottest predicate's index:
        # each probe row streams all K keys (4 B each)
        pid = Counter(tp.p for tp in scan_tps
                      if isinstance(tp.p, int)).most_common(1)[0][0]
        keys = jnp.asarray(g.store.pred_index(pid).s_sorted, jnp.int32)
        rng_p = np.random.default_rng(0)
        probes = jnp.asarray(
            rng_p.integers(0, g.store.num_entities, (8, 1024)), jnp.int32)

        def probe_call():
            jax.block_until_ready(
                probe_sorted_many(keys, probes, interpret=interp))

        probe_call()
        t_pr = bench(probe_call, int(probes.shape[0]), kern_repeats)
        rows.append((
            "kernel_probe_sorted_many", t_pr * 1e6,
            f"backend=jax|pallas={pmode}|keys={int(keys.shape[0])}"
            f"|probes_per_row={int(probes.shape[1])}"
            f"|bytes_per_row={int(keys.nbytes)}"
            f"|achieved_gbps={max(keys.nbytes, 1) / t_pr / 1e9:.3f}"))

        # end-to-end: device-resident join pipeline vs forced host path on
        # the largest sharded store, with the transfer accounting that
        # backs the one-bulk-transfer-per-batch contract
        ks = f"_s{max(shard_counts)}" if shard_counts else ""
        store_k = dict(stores)[ks]
        for dr_name, dr in (("device", True), ("host", False)):
            bk = JaxBackend(device_resident=dr)
            eng_k = QueryEngine(backend=bk)

            def cold_k():
                eng_k.clear_cache()
                eng_k.execute_batch(store_k, queries)

            t_k = bench(cold_k, len(queries), kern_repeats)
            s = eng_k.stats
            rows.append((
                f"engine_jax_{dr_name}{ks}", t_k * 1e6,
                f"backend=jax|pallas={pmode}|device_resident={dr}"
                f"|device_queries={s.device_queries}"
                f"|device_fallbacks={s.device_fallbacks}"
                f"|device_joins={s.join.joins_device}"
                f"|host_transfers={s.host_transfers}"
                f"|transfer_bytes={s.host_transfer_bytes}"
                f"|scalar_syncs={s.scalar_syncs}"))

    for name, us, derived in rows:
        qps = 1e6 / us
        print(f"{name},{us:.1f},{derived}|qps={qps:.0f}")

    if args.json:
        payload = {
            "meta": {
                "bench": "bench_engine",
                "timestamp": time.time(),
                "scale": args.scale,
                "num_triples": int(g.store.num_triples),
                "num_entities": int(g.store.num_entities),
                "batch": len(queries),
                "unique": len(pool),
                "distinct_scans": len(scan_tps),
                "shards": shard_counts,
                "repeats": args.repeats,
                "jax": not args.skip_jax,
                "join_axis": bool(args.join),
                "kernel_axis": bool(args.kernels),
                "algebra_axis": bool(args.algebra),
                "rebalance_axis": bool(args.rebalance),
                "partial_axis": bool(args.partial),
                "round_edges": (args.round_edges
                                if args.join or args.rebalance else None),
            },
            "rows": [{"name": n, "us_per_call": round(us, 3),
                      "qps": round(1e6 / us, 1), "derived": d}
                     for n, us, d in rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    assert t_cold < t_loop, "batched engine should beat the per-query loop"
    if (not args.skip_jax and shard_counts
            and g.store.num_triples >= 100_000):
        mono = t_scan[("jax", "")]
        best_s = min(t_scan[("jax", f"_s{S}")] for S in shard_counts)
        assert best_s < mono, (
            f"sharded bound-predicate scan ({best_s * 1e6:.0f}us) should "
            f"beat the monolithic scan ({mono * 1e6:.0f}us)")
    if args.join and shard_counts and g.store.num_triples >= 100_000:
        assert t_join["shard"] < t_join["global"], (
            f"shard-local join ({t_join['shard'] * 1e6:.0f}us/q) should "
            f"beat the global join ({t_join['global'] * 1e6:.0f}us/q)")
        # thread overlap is advisory (GIL-releasing fraction is platform-
        # dependent); the fork pool must genuinely overlap
        assert t_round["process"] < t_round["seq"], (
            f"process-overlapped round ({t_round['process']:.3f}s) should "
            f"beat the sequential round ({t_round['seq']:.3f}s)")
    if args.algebra:
        for name in ("filter", "optional", "union"):
            assert t_alg[(name, "warm")] < t_alg[(name, "cold")], (
                f"warm algebra batch ({name}) should beat cold — leaf BGPs "
                f"must resolve from the result cache")
    if args.partial:
        ps, cs = part_stats["partial"], part_stats["cloudonly"]
        assert ps["partial_queries"] > 0, (
            "the bandwidth-constrained placement should route queries "
            "through the partial (edge-set -> assembler) path")
        assert cs["partial_queries"] == 0, (
            "enable_partial=False must keep the legacy binary assignment")
        assert ps["modeled"] < cs["modeled"], (
            f"partial round modeled response ({ps['modeled'] * 1e3:.3f}ms) "
            f"should beat cloud-only ({cs['modeled'] * 1e3:.3f}ms) on the "
            f"bandwidth-constrained placement")
        # the realized metric now derives server cycles from measured
        # per-phase engine wall (prescan + join seconds), not final row
        # counts alone — it registers the cloud's intermediate join work,
        # so the partial win is GATED on both metrics
        assert ps["realized"] < cs["realized"], (
            f"partial round realized response ({ps['realized'] * 1e3:.3f}"
            f"ms) should beat cloud-only ({cs['realized'] * 1e3:.3f}ms) "
            f"once cloud cycles derive from measured engine wall")
        assert 0 < ps["bytes"] < reship, (
            f"partial binding tables ({ps['bytes']}B) should ship fewer "
            f"bytes than re-shipping the full induced subgraph ({reship}B)")
    if args.rebalance and shard_counts:
        assert reb_stats["delta"]["changed"], (
            "drift workload produced no placement changes — the "
            "full-vs-delta comparison is vacuous")
        if g.store.num_triples >= 100_000:
            assert (reb_stats["delta"]["bytes"]
                    < reb_stats["full"]["bytes"]), (
                f"delta rebalance ({reb_stats['delta']['bytes']}B) should "
                f"ship strictly fewer bytes than full re-ship "
                f"({reb_stats['full']['bytes']}B)")


if __name__ == "__main__":
    main()
