"""Workload-harness benchmark: verified traffic through the full stack.

Samples a ≥4-shape template mix (star / path / flower / snowflake) from
the live store with :class:`repro.workload.PatternSampler` — every
template carries its exact sample-time cardinality — then replays seeded
Zipf-skewed schedules through the admission queue in three A/B arms:

- ``admission``  — sequential per-request dispatch vs coalesced
  micro-batch admission on a read-only skewed mix (what the window buys
  under template popularity skew: memo/cache hit trajectories included);
- ``scheduler``  — ``mode="round"`` with the greedy placement policy vs
  full branch-and-bound on a system-attached endpoint (per-window
  full-edge / cloud / partial assignment counts and modeled objectives);
- ``writes``     — a churn-style read/write mix (burst arrivals) with
  per-ticket commits vs window-level write coalescing on a LIVE system:
  each commit pays placement propagation, so the arm also reports the
  rebalance churn (placement-epoch movement) the coalescing amortizes.

Acceptance gates (asserted, non-zero exit on failure):

- every served answer in every arm matches its template's recorded
  cardinality (the churn write style never invalidates them — writes ride
  a sampler-excluded predicate with fresh entities);
- no arm produces a single admission/scheduler/engine error;
- round-mode arms account every read in their assignment counts.

Rows follow the harness contract (``name,us_per_call,derived``;
``us_per_call`` is mean request latency); ``--json`` writes
``BENCH_workload.json`` (``{"meta": ..., "rows": [...]}``) for the CI
artifact trail next to ``BENCH_engine.json`` / ``BENCH_serving.json``.
"""

from __future__ import annotations

import argparse
import json

from repro.kernels import use_compile_cache
from repro.rdf.generator import generate_watdiv_like
from repro.runtime.admission import AdmissionQueue
from repro.sparql.endpoint import SparqlEndpoint
from repro.workload import (PatternSampler, ShapeConfig, TrafficConfig,
                            build_schedule, replay)
from repro.workload.sampler import SHAPES

try:
    from common import build_system, emit
except ImportError:                       # invoked as benchmarks/bench_...
    from benchmarks.common import build_system, emit

CHURN_PREDICATE = "country"               # reserved for the write mix


def sample_templates(store, dictionary, *, n_per: int, seed: int):
    smp = PatternSampler(store, dictionary, seed=seed,
                         exclude_predicates=[CHURN_PREDICATE])
    cfgs = [ShapeConfig(s, size=3, const_frac=0.3,
                        decorations=(None, "filter", "limit"))
            for s in SHAPES]
    templates = smp.sample_mix(cfgs, n_per)
    got = {q.shape for q in templates}
    assert got == set(SHAPES), f"missing shapes: {set(SHAPES) - got}"
    return templates


def row_from_report(name: str, rep, **extra) -> dict:
    lats = [l for r in list(rep.per_shape.values()) + [rep.writes]
            for l in r.latencies]
    mean_s = sum(lats) / len(lats) if lats else 0.0
    shape_p99 = {f"p99_ms_{s}": round(r.percentiles()["p99"] * 1e3, 3)
                 for s, r in sorted(rep.per_shape.items())}
    row = {"name": name, "us_per_call": round(mean_s * 1e6, 1),
           "completed": rep.completed, "errors": rep.errors,
           "verified": rep.verified, "mismatched": rep.mismatched,
           **shape_p99, **extra}
    emit(name, row["us_per_call"],
         **{k: v for k, v in row.items()
            if k not in ("name", "us_per_call")})
    return row


def gate(name: str, rep, schedule) -> None:
    assert rep.errors == 0, f"{name}: {rep.errors} errors"
    assert rep.completed == len(schedule.events), \
        f"{name}: {rep.completed}/{len(schedule.events)} completed"
    assert rep.verification_ok, \
        f"{name}: cardinality mismatches {rep.mismatches}"
    assert rep.verified == schedule.n_queries, \
        f"{name}: verified {rep.verified} != {schedule.n_queries} reads"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--per-shape", type=int, default=3,
                    help="templates sampled per shape")
    ap.add_argument("--qps", type=float, default=300.0)
    ap.add_argument("--duration", type=float, default=0.6,
                    help="schedule length in seconds (per arm)")
    ap.add_argument("--window-ms", type=float, default=4.0)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--write-fraction", type=float, default=0.25)
    ap.add_argument("--json", type=str, default=None,
                    help="write machine-readable results "
                         "(BENCH_workload.json)")
    args = ap.parse_args()
    use_compile_cache()

    rows: list[dict] = []
    g = generate_watdiv_like(scale=args.scale, seed=args.seed)
    templates = sample_templates(g.store, g.dictionary,
                                 n_per=args.per_shape, seed=args.seed)
    print(f"# {len(templates)} templates over {len(SHAPES)} shapes, "
          f"{g.store.num_triples} triples")

    # -- arm 1: sequential vs coalesced admission under skew ---------------
    read_cfg = TrafficConfig(duration_s=args.duration, qps=args.qps,
                             zipf_s=1.2, cold_fraction=0.15,
                             seed=args.seed + 1)
    sched = build_schedule(templates, read_cfg)
    for mode, window_s, max_batch in (
            ("seq", 0.0, 1),
            ("coal", args.window_ms / 1e3, args.max_batch)):
        ep = SparqlEndpoint(g.store, g.dictionary)
        with AdmissionQueue(ep, window_s=window_s,
                            max_batch=max_batch) as q:
            rep = replay(q, sched)
        gate(f"admission_{mode}", rep, sched)
        traj = rep.cache_trajectory
        rows.append(row_from_report(
            f"workload_admission_{mode}", rep,
            batches=len(traj),
            memo_hits=sum(b["memo_hits"] for b in traj),
            engine_cache_hits=sum(b["engine_cache_hits"] for b in traj)))

    # -- arm 2: greedy vs branch-and-bound round scheduling ----------------
    bench = build_system(scale=args.scale, seed=args.seed,
                         n_users=8, n_edges=3)
    # re-deploy edge residency from the SAMPLED templates (every user saw
    # the whole template pool), so the scheduling A/B has edge-eligible
    # patterns to place rather than an unrelated history
    n_users = bench.system.params.assoc.shape[0]
    bench.system.prepare([[q.text for q in templates]
                          for _ in range(n_users)])
    r_sched = build_schedule(templates, TrafficConfig(
        duration_s=args.duration, qps=min(args.qps, 150.0),
        zipf_s=1.2, seed=args.seed + 2))
    for policy in ("greedy", "bnb"):
        bench.system.engine.clear_cache()
        ep = SparqlEndpoint(system=bench.system)
        with AdmissionQueue(ep, window_s=args.window_ms / 1e3,
                            max_batch=8, mode="round",
                            mode_kw={"policy": policy}) as q:
            rep = replay(q, r_sched)
        gate(f"scheduler_{policy}", rep, r_sched)
        counts = {int(k): v for k, v in rep.assignment_counts.items()}
        assert sum(counts.values()) == r_sched.n_queries, \
            f"scheduler_{policy}: unaccounted reads {counts}"
        rows.append(row_from_report(
            f"workload_scheduler_{policy}", rep,
            cloud=counts.get(-1, 0), partial=counts.get(-2, 0),
            edge=sum(v for k, v in counts.items() if k >= 0)))

    # -- arm 3: churn write mix, per-ticket vs coalesced commits -----------
    w_cfg = TrafficConfig(duration_s=args.duration, qps=args.qps,
                          arrival="burst", zipf_s=1.2,
                          write_fraction=args.write_fraction,
                          write_style="churn", seed=args.seed + 3)
    w_sched = build_schedule(templates, w_cfg,
                             churn_predicate=CHURN_PREDICATE)
    assert w_sched.has_writes and w_sched.verifiable
    for mode, coalesce in (("seq", False), ("coal", True)):
        ep = SparqlEndpoint(system=bench.system)
        epoch0 = bench.system.placement_epoch
        with AdmissionQueue(ep, window_s=args.window_ms / 1e3,
                            max_batch=args.max_batch,
                            coalesce_writes=coalesce) as q:
            rep = replay(q, w_sched)
        gate(f"writes_{mode}", rep, w_sched)
        adm = rep.admission
        assert adm["updates_served"] == w_sched.n_updates
        rows.append(row_from_report(
            f"workload_writes_{mode}", rep,
            updates=adm["updates_served"],
            write_commits=adm["write_commits"],
            writes_coalesced=adm["writes_coalesced"],
            epochs=bench.system.placement_epoch - epoch0))

    if args.json:
        payload = {
            "meta": {
                "bench": "workload",
                "scale": args.scale, "seed": args.seed,
                "qps": args.qps, "duration_s": args.duration,
                "shapes": list(SHAPES),
                "templates": len(templates),
                "per_shape": args.per_shape,
                "window_ms": args.window_ms,
                "max_batch": args.max_batch,
                "write_fraction": args.write_fraction,
                "triples": int(g.store.num_triples),
            },
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")
    print("# workload gates passed: all answers matched recorded "
          "cardinalities; zero scheduler/admission errors")


if __name__ == "__main__":
    main()
