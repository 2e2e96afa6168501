"""Benchmark entry point: one function per paper table + roofline report.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # full grid
  PYTHONPATH=src python -m benchmarks.run --only storage,matcher
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.kernels import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (e.g. storage,scale)")
    ap.add_argument("--full", action="store_true",
                    help="larger grids (slower)")
    args, _ = ap.parse_known_args()
    use_compile_cache()

    from . import paper_tables, roofline
    selected = [s for s in args.only.split(",") if s]
    benches = [(fn.__name__.replace("bench_", ""), fn)
               for fn in paper_tables.ALL]
    benches.append(("roofline", lambda quick: roofline.main(quick=quick)))

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        if selected and name not in selected:
            continue
        t0 = time.time()
        try:
            fn(quick=not args.full)
            print(f"bench_{name}_wall,{(time.time() - t0) * 1e6:.0f},ok=1")
        except Exception as e:  # noqa: BLE001 — report, keep going
            failures += 1
            traceback.print_exc()
            print(f"bench_{name}_wall,{(time.time() - t0) * 1e6:.0f},"
                  f"ok=0|error={type(e).__name__}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
