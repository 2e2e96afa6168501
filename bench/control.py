#!/usr/bin/env python3
"""The control of a cell's correctness check: the check must fail it.

    python3 bench/control.py --workload <cell> --requests <n> --seeds <n>...

The control is the plain reference put in the program's place, with
dictionary ids compared at 16 bits instead of the 32 the configuration
states (``bench/lib/reference.py``). For each seed it draws the cell's data
and requests as a run does, answers the first ``--requests`` of the
window's requests with the control, and puts those answers through the
run's own check. It prints, per seed,
the numbers the check compares; the check has failed the control when any
of them is over its limit. ``--requests`` should be as many as a
run's window answers. The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

from bench import run  # noqa: E402
from bench.lib import loop, traffic  # noqa: E402
from bench.lib.reference import Reference, RowLimit  # noqa: E402


#: the control stops a join past this many rows (the exact answers of the
#: cells' queries hold at most some thousands)
CONTROL_MAX_ROWS = 200_000


@dataclass
class Table:
    var_names: list[str]
    bindings: np.ndarray


def control_readings(cell: dict, seed: int, n: int,
                     scale: float | None = None, id_dtype=np.int16) -> dict:
    dataset = cell["dataset"]
    graph = dataset.generate(scale or cell["config"]["data"]["scale"], seed)
    requests = traffic.Requests(cell["traffic"], dataset, graph, seed)
    window = requests.window()
    control = Reference(graph.s, graph.p, graph.o, id_dtype=id_dtype,
                        max_rows=CONTROL_MAX_ROWS)
    res = loop.LoopResult(0.0, 0.0)
    for r in (window[j] for j in range(n)):
        try:
            names, rows = control.match(list(r.patterns))
        except RowLimit:
            # narrowed ids collide until a join blows up: an answer far
            # larger than any the cells' queries have, so a wrong one
            names = sorted({t for tp in r.patterns for t in tp
                            if isinstance(t, str)})
            rows = np.full((1, len(names)), -1, dtype=np.int64)
        res.outcomes.append(loop.Outcome(r.index, 0.0, 0.0,
                                         table=Table(names, rows)))
    return run.check_answers(graph, requests, res)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True,
                    help="how many of the cell's requests to answer: as "
                         "many as a run's window does")
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, run.load_spec(parked=True))
    failed_all = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = control_readings(cell, seed, args.requests, args.scale)
        failed_all &= not out["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": out["correct"],
                          "check": out["numbers"],
                          "seconds": round(time.perf_counter() - t0, 3)}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
