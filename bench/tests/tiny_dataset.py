"""A second data set, for the tests only: people who know people and live
in cities. Two predicates and one template. A configuration names it by
``data.module`` like any other, and the harness runs it with no edit."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

PREDICATES = ["knows", "livesIn"]

# "C0" is a constant slot, filled from the subject of a random "knows" row
TEMPLATES = {"friends_city": [("C0", "knows", "?y"),
                              ("?y", "livesIn", "?c")]}


@dataclass
class Graph:
    s: np.ndarray
    p: np.ndarray
    o: np.ndarray
    entities: np.ndarray
    predicates: list[str]

    @property
    def num_triples(self) -> int:
        return len(self.s)


def generate(scale: float, seed: int) -> Graph:
    """200 people a unit of scale, each knowing 1-4 others, and 10 cities,
    each person living in one."""
    rng = np.random.default_rng(seed)
    people, cities = max(2, int(200 * scale)), max(2, int(10 * scale))
    entities = np.array([f"Person{i}" for i in range(people)]
                        + [f"City{i}" for i in range(cities)], dtype=object)
    degree = rng.integers(1, 5, size=people)
    knows = np.stack([np.repeat(np.arange(people), degree),
                      np.zeros(degree.sum(), dtype=np.int64),
                      rng.integers(0, people, size=degree.sum())], axis=1)
    lives = np.stack([np.arange(people), np.ones(people, dtype=np.int64),
                      people + rng.integers(0, cities, size=people)], axis=1)
    t = np.unique(np.concatenate([knows, lives]).astype(np.int64), axis=0)
    return Graph(t[:, 0], t[:, 1], t[:, 2], entities, list(PREDICATES))


def instantiate(g: Graph, name: str, rng: np.random.Generator):
    edges = TEMPLATES[name]
    rows = np.flatnonzero(g.p == PREDICATES.index("knows"))
    c0 = int(g.s[rows[int(rng.integers(len(rows)))]])

    def term(t: str):
        return c0 if t == "C0" else t

    def text(t) -> str:
        return t if isinstance(t, str) else f"<{g.entities[t]}>"

    patterns = [(term(s), PREDICATES.index(p), term(o)) for s, p, o in edges]
    body = " . ".join(f"{text(term(s))} <{p}> {text(term(o))}"
                      for s, p, o in edges)
    return patterns, f"SELECT ?c ?y WHERE {{ {body} }}"


def workload_sparql(g: Graph, n_queries: int, seed: int,
                    templates: list[str] | None = None) -> list[str]:
    rng = np.random.default_rng(seed)
    return [instantiate(g, "friends_city", rng)[1] for _ in range(n_queries)]
