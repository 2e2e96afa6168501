#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration file (``bench/configs/``) says what to deploy, its traffic
file (``bench/traffic/<traffic>.json``) what to send, and each of its
metrics is read by ``bench/metrics/<metric>.py``. A run:

1. generates the WatDiv-like graph from ``--seed`` (``bench/lib/watdiv``)
   and draws the run's requests from the traffic file;
2. builds the deployment (``bench/deployments/<kind>.py``);
3. warms up: sends warm-up's own requests (``warmup`` in the traffic
   file) with one client, then two, up to the cell's clients, so that
   every batch size the window can form has run and its programs are
   compiled. The program keeps its admission defaults and its memos;
4. measures: closed-loop clients send the requests after warm-up's, none
   of which warm-up sent, for ``--seconds``, and the window closes when
   the last answer sent in it is back. With ``--trace 1`` the window is
   traced, and the per-layer metrics are reported instead of the
   end-to-end ones;
5. checks every answer of the window against the plain reference
   (``bench/lib/reference``), after the program's state is freed.

The last line of standard output is one JSON object. Off a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits
non-zero; ``--rehearse-scale`` runs every phase at a small scale off the
chip and then exits non-zero, for rehearsal, of a cell of
``BENCHMARK.json`` or of ``bench/parked.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: the largest --rehearse-scale: a rehearsal checks control flow
MAX_REHEARSAL_SCALE = 5.0
#: warm-up requests per number of clients in a rehearsal, whose tiny graph
#: holds too few distinct constants for the traffic file's warm-up
REHEARSAL_WARMUP = 2

EXIT_NO_CHIP = 2
EXIT_REHEARSED = 3
EXIT_FAILED = 1


class BenchError(Exception):
    pass


def load_spec(parked: bool = False) -> dict:
    """``BENCHMARK.json``; with ``parked``, also the cells and metrics of
    ``bench/parked.json``, which wait for a change to the program before
    they can be measured, and which the tests rehearse meanwhile."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if parked:
        more = json.loads((HERE / "parked.json").read_text())
        for key, entries in more.items():
            spec[key] = spec[key] + entries
    return spec


def load_cell(name: str, spec: dict | None = None) -> dict:
    """The cell ``name`` of ``spec`` (``load_spec()`` by default) with its
    configuration, traffic and metrics."""
    spec = spec or load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def in_cell(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if in_cell(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if in_cell(m) and m["moves"] in names]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def deployment_module(kind: str):
    import importlib

    return importlib.import_module(f"bench.deployments.{kind}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stats_snapshot(engine) -> dict:
    s = engine.stats
    return {k: getattr(s, k) for k in (
        "queries", "batches", "cache_hits", "exec_seconds",
        "device_queries", "device_fallbacks", "host_transfers",
        "scalar_syncs")}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             scale: float | None = None) -> dict:
    """Build, warm up, measure and check one cell; returns the record the
    metric readers take, with the check's numbers under ``check``."""
    import jax

    from bench.lib import loop, traffic, watdiv
    from bench.lib.compiles import CompileCounter

    counter = CompileCounter()
    config, mix = cell["config"], cell["traffic"]
    if scale is not None:
        mix = dict(mix, warmup=min(mix["warmup"], REHEARSAL_WARMUP))
    times: dict[str, float] = {}
    t0 = time.perf_counter()
    graph = watdiv.generate(scale or config["data"]["scale"], seed)
    times["generate"] = time.perf_counter() - t0
    requests = traffic.Requests(mix, graph, seed)
    kind = config["deployment"]["kind"]
    dep = deployment_module(kind).build(config, graph, seed, times)

    clients = mix["clients"]
    c0, h0, s0 = counter.compiles, counter.cache_hits, counter.seconds
    t0 = time.perf_counter()
    for k in range(1, clients + 1):
        with contextlib.closing(dep.queue()) as q:
            warm = loop.run(q, requests.warmup(k), k)
        bad = [o for o in warm.outcomes if o.error]
        if bad:
            raise BenchError(f"warm-up request {bad[0].index} failed: "
                             f"{bad[0].error}")
    times["warmup"] = time.perf_counter() - t0
    log(f"setup: triples={graph.num_triples} entities={len(graph.entities)} "
        f"warmup_compiles={counter.compiles - c0} warmup_cache_hits="
        f"{counter.cache_hits - h0} warmup_compile_s="
        f"{counter.seconds - s0:.3f} "
        + " ".join(f"{k}_s={v:.3f}" for k, v in times.items()))
    del warm
    gc.collect()

    spans = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        spans = jax.profiler.TraceAnnotation
    queue = dep.queue(spans=spans)
    batches = queue.start_batch_log()
    before = stats_snapshot(dep.engine)
    window_requests = requests.window()
    c0 = counter.compiles
    setup_s = time.perf_counter() - T_START
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans, not every call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with (spans("window") if trace else contextlib.nullcontext()):
            res = loop.run(queue, window_requests, clients, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    queue.close()
    compiles = counter.compiles - c0
    after = stats_snapshot(dep.engine)
    if res.exhausted:
        raise BenchError(f"the window ran out of requests after "
                         f"{len(res.outcomes)}, before its end: raise "
                         f"'requests' in the traffic file")

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    reduced = None
    if trace:
        from bench.lib import trace as trace_lib

        reduced = trace_lib.reduce_dir(TRACE_DIR)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    del dep, queue
    gc.collect()

    t0 = time.perf_counter()
    check = check_answers(graph, requests, res)
    log(f"check: reference_s={time.perf_counter() - t0:.3f} "
        f"answers={len(res.outcomes)}")
    window = {
        "seconds": res.seconds,
        "attempted": len(res.outcomes),
        "correct_answers": check["correct_answers"],
        "latencies_s": [o.done - o.sent for o in res.outcomes],
    }
    engine = {k: after[k] - before[k] for k in after}
    return {"setup_s": setup_s, "window": window,
            "batches": [vars(b) for b in batches], "engine": engine,
            "compiles": compiles, "trace": reduced,
            "device": device, "check": check["numbers"],
            "correct": check["correct"]}


def check_answers(graph, requests, res) -> dict:
    """Compare every answer of the window with the plain reference, as a
    multiset of binding rows over the same variables."""
    from bench.lib.reference import Reference, sort_rows

    ref = Reference(graph.s, graph.p, graph.o)
    wrong = missing = 0
    first_bad = None
    for o in res.outcomes:
        req = requests[o.index]
        if o.error is not None or o.table is None:
            missing += 1
            first_bad = first_bad or f"request {o.index}: {o.error}"
            continue
        names, want = ref.match(list(req.patterns))
        t = o.table
        if sorted(t.var_names) != names:
            wrong += 1
            first_bad = first_bad or (f"request {o.index}: variables "
                                      f"{sorted(t.var_names)} != {names}")
            continue
        got = np.asarray(t.bindings, dtype=np.int64)
        got = sort_rows(got[:, [list(t.var_names).index(v) for v in names]])
        if got.shape != want.shape or not np.array_equal(got, want):
            wrong += 1
            first_bad = first_bad or (f"request {o.index}: {len(got)} rows, "
                                      f"reference {len(want)}: {req.text}")
    if first_bad:
        log(f"check: first difference: {first_bad}")
    return {"correct": wrong == 0 and missing == 0 and len(res.outcomes) > 0,
            "correct_answers": len(res.outcomes) - wrong - missing,
            "numbers": {"answers_wrong": {"value": wrong, "limit": 0},
                        "answers_missing": {"value": missing, "limit": 0}}}


def result_line(cell: dict, rec: dict, trace: bool) -> dict:
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": rec["correct"], "attempted": rec["window"]["attempted"],
           "failed": rec["window"]["attempted"]
           - rec["window"]["correct_answers"],
           "metrics": metrics, "device": rec["device"]}
    if trace and rec["trace"] is not None:
        out["breakdown"] = rec["trace"]["breakdown"]
    out["check"] = rec["check"]
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-scale", type=float, default=None,
                    help="off the chip only: run every phase at this data "
                         "scale, then exit non-zero")
    args = ap.parse_args(argv)

    # a rehearsal may also run a parked cell
    cell = load_cell(args.workload,
                     load_spec(parked=args.rehearse_scale is not None))
    if args.rehearse_scale is None:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime logs to a fixed directory under /tmp unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from repro.kernels import use_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    chips = cell["cell"]["chips"]
    if args.rehearse_scale is not None:
        if platform == "tpu":
            raise BenchError("--rehearse-scale is for runs off the chip")
        if args.rehearse_scale > MAX_REHEARSAL_SCALE:
            raise BenchError(f"--rehearse-scale above "
                             f"{MAX_REHEARSAL_SCALE:g}")
    elif platform != "tpu" or len(devices) < chips:
        log(f"run.py: this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform {platform!r}")
        return EXIT_NO_CHIP
    else:
        use_compile_cache()
    log(f"device platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)} compile_cache={CACHE_DIR}")

    rec = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   scale=args.rehearse_scale)
    line = result_line(cell, rec, bool(args.trace))
    log(f"window: seconds={rec['window']['seconds']:.3f} "
        f"answers={rec['window']['attempted']} compiles={rec['compiles']} "
        f"batches={len(rec['batches'])} engine={rec['engine']}")
    for name, c in rec["check"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    if args.rehearse_scale is not None:
        log("rehearsal: " + json.dumps(line))
        log(f"run.py: rehearsal on platform {platform!r} at scale "
            f"{args.rehearse_scale:g}; no result without a TPU")
        return EXIT_REHEARSED
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"run.py: FAILED: {e}", file=sys.stderr)
        sys.exit(EXIT_FAILED)
