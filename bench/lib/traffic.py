"""The one traffic generator: a mix file under ``bench/traffic/`` in, the
run's list of requests out.

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
- ``templates``: template names, taken in turn;
- ``constants``: ``"fresh"``, each constant slot filled anew from a random
  triple of its predicate (the generator's rule), with no query text drawn
  twice in a run, so no result cache can answer a request;
- ``users``: how many users send, request ``j`` as user ``j % users``.

Every seed gets the same sequence of templates and users; only the
constants differ.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import watdiv

MAX_REDRAWS = 1000


@dataclass(frozen=True)
class Request:
    index: int
    user: int
    patterns: tuple
    text: str


class Requests:
    """The run's requests, drawn in order as they are first asked for, up
    to the mix's ``requests``: the same seed gives the same sequence."""

    def __init__(self, mix: dict, graph: watdiv.Graph, seed: int) -> None:
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        if mix["constants"] != "fresh":
            raise ValueError(f"unknown constants rule {mix['constants']!r}")
        if mix["warmup"] * mix["clients"] >= mix["requests"]:
            raise ValueError("warm-up would take every request")
        self._mix, self._graph = mix, graph
        self._rng = np.random.default_rng([seed, 1])
        self._seen: set[str] = set()
        self._drawn: list[Request] = []

    def __len__(self) -> int:
        return self._mix["requests"]

    def __getitem__(self, j: int) -> Request:
        if not 0 <= j < len(self):
            raise IndexError(j)
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
            q = watdiv.instantiate(self._graph, name, self._rng)
            if q is not None and q[1] not in self._seen:
                break
        else:
            # the graph holds no more distinct queries of the template
            raise IndexError(f"no fresh {name} query after "
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
        if not 0 <= j < len(self):
            raise IndexError(j)
        return self._requests[self._start + j]
