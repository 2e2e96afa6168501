"""The one traffic generator: a mix file under ``bench/traffic/`` and a
data set (``bench/lib/datasets.py``) in, the run's list of requests out.

A mix file (JSON) holds:

- ``loop``: ``"closed"`` (each client sends its next request when its
  previous answer is back);
- ``clients``: how many clients send at once;
- ``warmup``: how many requests warm-up sends at each number of clients
  from one to ``clients``, so that every batch size the window can form
  has run; they are the first drawn, and the window never sees them;
- ``requests``: how many requests are drawn for a run, at most, warm-up's
  included; the window takes those after warm-up's, in order, as many as
  it has time for;
- ``templates``: template names of the data set, taken in turn;
- ``constants``: ``"fresh"``, each constant slot filled anew from a random
  triple of its predicate (the generator's rule), with no query text drawn
  twice in a run, so no result cache can answer a request;
- ``users``: how many users send, request ``j`` as user ``j % users``.

Every seed gets the same sequence of templates and users; only the
constants differ.
"""

from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType, SimpleNamespace

import numpy as np

#: draws of one template in a row that may repeat texts already drawn
#: before the template's distinct supply counts as spent
MAX_REDRAWS = 1000
#: a mix's ``requests`` is at most the count up to which a new text still
#: takes this many draws, as the mean over the ``SUPPLY_SPAN`` requests up
#: to that count (``supply``)
MAX_NEW_TEXT_DRAWS = 10
SUPPLY_SPAN = 500


class Exhausted(IndexError):
    """The run's requests ran out; the message says which supply did."""


@dataclass(frozen=True)
class Request:
    index: int
    user: int
    patterns: tuple
    text: str


def check(mix: dict, dataset: ModuleType) -> None:
    """Raise ``ValueError`` where the mix cannot run on the data set; the
    harness calls it when it loads a cell, before any set-up."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    if mix["constants"] != "fresh":
        raise ValueError(f"unknown constants rule {mix['constants']!r}")
    if mix["warmup"] * mix["clients"] >= mix["requests"]:
        raise ValueError("warm-up would take every request")
    unknown = [t for t in mix["templates"] if t not in dataset.TEMPLATES]
    if unknown:
        raise ValueError(f"templates {unknown} are not in "
                         f"{dataset.__name__}.TEMPLATES")


class Requests:
    """The run's requests, drawn in order as they are first asked for, up
    to the mix's ``requests``: the same seed gives the same sequence. The
    mix is one ``check`` has passed."""

    def __init__(self, mix: dict, dataset: ModuleType, graph,
                 seed: int) -> None:
        self._mix, self._dataset, self._graph = mix, dataset, graph
        self._rng = np.random.default_rng([seed, 1])
        self._seen: set[str] = set()
        self._drawn: list[Request] = []

    def __len__(self) -> int:
        return self._mix["requests"]

    def __getitem__(self, j: int) -> Request:
        if j < 0:
            raise IndexError(j)
        if j >= len(self):
            raise Exhausted(f"the traffic file's 'requests' ({len(self)}) "
                            f"ran out")
        while len(self._drawn) <= j:
            self._drawn.append(self._next(len(self._drawn)))
        return self._drawn[j]

    def warmup(self, clients: int) -> list[Request]:
        """Warm-up's requests when it runs ``clients`` clients: the
        ``warmup`` requests after those of fewer clients."""
        n = self._mix["warmup"]
        return [self[j] for j in range(n * (clients - 1), n * clients)]

    def window(self) -> "Tail":
        """The window's requests: every one after warm-up's."""
        return Tail(self, self._mix["warmup"] * self._mix["clients"])

    def _next(self, j: int) -> Request:
        templates = self._mix["templates"]
        name = templates[j % len(templates)]
        for _ in range(MAX_REDRAWS):
            q = self._dataset.instantiate(self._graph, name, self._rng)
            if q is not None and q[1] not in self._seen:
                break
        else:
            raise Exhausted(f"the distinct supply of template {name!r} "
                            f"ran out at request {j}: no fresh text after "
                            f"{MAX_REDRAWS} draws")
        self._seen.add(q[1])
        return Request(j, j % int(self._mix.get("users", 1)), tuple(q[0]),
                       q[1])


class Tail:
    """The requests of ``requests`` from ``start`` on, drawn as asked for."""

    def __init__(self, requests: Requests, start: int) -> None:
        self._requests, self._start = requests, start

    def __len__(self) -> int:
        return len(self._requests) - self._start

    def __getitem__(self, j: int) -> Request:
        if j < 0:
            raise IndexError(j)
        return self._requests[self._start + j]


def draw_costs(mix: dict, dataset: ModuleType, graph, seed: int,
               n: int) -> list[int]:
    """How many draws each of the mix's first ``n`` requests takes under
    the run's rule, with ``n`` in place of the mix's ``requests``. The list
    ends early where a template's distinct supply runs out."""
    draws = 0

    def instantiate(*args):
        nonlocal draws
        draws += 1
        return dataset.instantiate(*args)

    counted = SimpleNamespace(TEMPLATES=dataset.TEMPLATES,
                              instantiate=instantiate)
    requests = Requests(dict(mix, requests=n), counted, graph, seed)
    costs = []
    try:
        for j in range(n):
            before = draws
            requests[j]
            costs.append(draws - before)
    except Exhausted:
        pass
    return costs


def supply(costs: list[int]) -> int:
    """The largest count of requests at which a new text still takes at
    most ``MAX_NEW_TEXT_DRAWS`` draws, as the mean over the
    ``SUPPLY_SPAN`` requests up to that count; ``costs`` as
    ``draw_costs`` gives them."""
    c = np.cumsum([0, *costs])
    means = (c[SUPPLY_SPAN:] - c[:-SUPPLY_SPAN]) / SUPPLY_SPAN
    ok = np.flatnonzero(means <= MAX_NEW_TEXT_DRAWS)
    return int(ok[-1]) + SUPPLY_SPAN if len(ok) else 0


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json

    from bench import run

    ap = argparse.ArgumentParser(
        description="Count a cell's distinct request supply at a scale: "
                    "per seed, how many texts its templates yield before "
                    "a draw fails, and the count up to which a new text "
                    f"takes at most {MAX_NEW_TEXT_DRAWS} draws.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, run.load_spec(parked=True))
    dataset = cell["dataset"]
    scale = args.scale or cell["config"]["data"]["scale"]
    for seed in args.seeds:
        graph = dataset.generate(scale, seed)
        costs = draw_costs(cell["traffic"], dataset, graph, seed, 10**7)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "scale": scale, "distinct": len(costs),
                          "draws": sum(costs), "supply": supply(costs),
                          "requests": cell["traffic"]["requests"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
