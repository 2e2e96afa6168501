"""The WatDiv-like data generator and its template instantiation, as the
benchmark's own copy.

Same schema, class sizes, draw order and template rule as the program's
``repro.rdf.generator``, so a seed gives the same triples, the same
dictionary and the same queries (``bench/tests/test_watdiv_copy.py``
checks this). Kept apart so that a change to the program cannot move the
data the benchmark measures on. Two things differ in how, not in what:

- terms are built as arrays in bulk, not by one dictionary call each;
- duplicate triples are dropped through one packed int64 key per triple,
  whose sort order is the lexicographic (s, p, o) order that
  ``np.unique(..., axis=0)`` gives.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# (class_from, predicate, class_to, out_degree_low, out_degree_high, coverage)
SCHEMA = [
    ("User",     "follows",     "User",     1, 8,  0.6),
    ("User",     "likes",       "Product",  1, 10, 0.8),
    ("User",     "makesPurchase", "Purchase", 1, 4, 0.5),
    ("Purchase", "purchaseFor", "Product",  1, 1,  1.0),
    ("Purchase", "purchaseDate", "Date",    1, 1,  1.0),
    ("Product",  "hasGenre",    "Genre",    1, 3,  0.9),
    ("Product",  "producedBy",  "Producer", 1, 1,  0.7),
    ("Product",  "hasReview",   "Review",   0, 12, 0.7),
    ("Review",   "reviewer",    "User",     1, 1,  1.0),
    ("Review",   "rating",      "Rating",   1, 1,  1.0),
    ("Product",  "retailedBy",  "Retailer", 1, 4,  0.8),
    ("Retailer", "country",     "Country",  1, 1,  1.0),
    ("User",     "country",     "Country",  1, 1,  0.9),
    ("Producer", "country",     "Country",  1, 1,  0.9),
    ("Genre",    "subgenreOf",  "Genre",    0, 2,  0.4),
]

CLASS_SIZE = {
    "User": 500, "Product": 400, "Purchase": 300, "Review": 600,
    "Producer": 40, "Retailer": 30, "Genre": 25, "Date": 80,
    "Rating": 5, "Country": 20,
}

# vertices "?x" are variables, "C*" are constant slots filled from a
# random triple of the slot's predicate (WatDiv's instantiation rule)
TEMPLATES: dict[str, list[tuple[str, str, str]]] = {
    "star2": [("?x", "likes", "?p1"), ("?x", "follows", "?u1")],
    "star3": [("?x", "likes", "?p1"), ("?x", "follows", "?u1"),
              ("?x", "country", "?c")],
    "chain2": [("?x", "likes", "?y"), ("?y", "hasGenre", "?g")],
    "chain3": [("?x", "makesPurchase", "?pu"), ("?pu", "purchaseFor", "?pr"),
               ("?pr", "producedBy", "?prod")],
    "snowflake": [("?x", "likes", "?p"), ("?p", "hasReview", "?r"),
                  ("?r", "reviewer", "?u"), ("?p", "retailedBy", "?rt")],
    "complex": [("?x", "likes", "?p"), ("?x", "country", "C0"),
                ("?p", "hasGenre", "?g"), ("?p", "retailedBy", "?rt"),
                ("?rt", "country", "C0")],
    "anchored_star": [("?x", "likes", "C0"), ("?x", "follows", "?u"),
                      ("?x", "country", "?c")],
    "anchored_chain": [("C0", "hasReview", "?r"), ("?r", "reviewer", "?u"),
                       ("?u", "country", "?c")],
}


@dataclass
class Graph:
    """Deduplicated triples in (s, p, o) order plus the term arrays:
    entity ``i`` is ``entities[i]``, predicate ``j`` is ``predicates[j]``."""

    s: np.ndarray
    p: np.ndarray
    o: np.ndarray
    entities: np.ndarray
    predicates: list[str]
    class_ids: dict[str, np.ndarray]

    @property
    def num_triples(self) -> int:
        return len(self.s)

    def predicate_id(self, name: str) -> int:
        return self.predicates.index(name)

    def pred_rows(self, pid: int) -> np.ndarray:
        """Row ids of predicate ``pid`` in ascending (s, o) order — the
        order ``TripleStore.pred_tids`` lists them in."""
        lo, hi = self._pred_bounds[pid], self._pred_bounds[pid + 1]
        return self._by_pred[lo:hi]

    def __post_init__(self) -> None:
        self._by_pred = np.argsort(self.p, kind="stable")
        self._pred_bounds = np.searchsorted(
            self.p[self._by_pred], np.arange(len(self.predicates) + 1))


def generate(scale: float, seed: int) -> Graph:
    """The WatDiv-like graph at ``scale`` (1000 ~= 10M triples)."""
    rng = np.random.default_rng(seed)
    names, class_ids, start = [], {}, 0
    for cname, base in CLASS_SIZE.items():
        n = max(2, int(base * scale))
        names.append(np.char.add(cname, np.arange(n).astype(str)))
        class_ids[cname] = np.arange(start, start + n, dtype=np.int64)
        start += n
    entities = np.concatenate(names).astype(object)
    predicates: list[str] = []
    s_all, p_all, o_all = [], [], []
    for cfrom, pred, cto, lo, hi, cov in SCHEMA:
        if pred not in predicates:
            predicates.append(pred)
        pid = predicates.index(pred)
        src, dst = class_ids[cfrom], class_ids[cto]
        srcs = src[rng.random(len(src)) < cov]
        weights = 1.0 / np.arange(1, len(dst) + 1) ** 0.8
        weights /= weights.sum()
        degs = rng.integers(lo, hi + 1, size=len(srcs))
        total = int(degs.sum())
        if total == 0:
            continue
        o_all.append(rng.choice(dst, size=total, p=weights, replace=True))
        s_all.append(np.repeat(srcs, degs))
        p_all.append(np.full(total, pid, dtype=np.int64))
    s, p, o = (np.concatenate(a).astype(np.int64)
               for a in (s_all, p_all, o_all))
    s, p, o = dedup(s, p, o, len(entities), len(predicates))
    return Graph(s, p, o, entities, predicates, class_ids)


def dedup(s, p, o, n_entities: int, n_predicates: int):
    """Distinct (s, p, o) rows in lexicographic order."""
    pb = max(1, int(n_predicates - 1).bit_length())
    eb = max(1, int(n_entities - 1).bit_length())
    if 2 * eb + pb > 63:
        raise ValueError("ids too wide to pack into one int64 key")
    key = np.unique((s << (pb + eb)) | (p << eb) | o)
    emask = (1 << eb) - 1
    return key >> (pb + eb), (key >> eb) & ((1 << pb) - 1), key & emask


def instantiate(g: Graph, name: str, rng: np.random.Generator):
    """One query of template ``name``: ``(patterns, text)`` or ``None``.

    ``patterns`` holds (s, p, o) with ints for constants (entity or
    predicate ids) and ``"?v"`` strings for variables; ``text`` is the
    SPARQL SELECT over every variable.
    """
    edges = TEMPLATES[name]
    const: dict[str, int] = {}
    for sv, pred, ov in edges:
        for slot, is_subj in ((sv, True), (ov, False)):
            if slot.startswith("C") and slot not in const:
                rows = g.pred_rows(g.predicate_id(pred))
                if len(rows) == 0:
                    return None
                r = int(rows[int(rng.integers(len(rows)))])
                const[slot] = int(g.s[r] if is_subj else g.o[r])

    def term(t: str):
        return t if t.startswith("?") else const[t]

    patterns = [(term(sv), g.predicate_id(pred), term(ov))
                for sv, pred, ov in edges]

    def text_of(t) -> str:
        return t if isinstance(t, str) else f"<{g.entities[t]}>"

    variables = sorted({t for e in edges for t in (e[0], e[2])
                        if t.startswith("?")})
    body = " . ".join(f"{text_of(term(sv))} <{pred}> {text_of(term(ov))}"
                      for sv, pred, ov in edges)
    return patterns, f"SELECT {' '.join(variables)} WHERE {{ {body} }}"


def workload_sparql(g: Graph, n_queries: int, seed: int,
                    templates: list[str] | None = None) -> list[str]:
    """The program's ``workload_sparql`` draw: ``n_queries`` texts, each
    of a template picked at random from ``templates``."""
    rng = np.random.default_rng(seed)
    names = templates or list(TEMPLATES)
    out: list[str] = []
    attempts = 0
    while len(out) < n_queries and attempts < n_queries * 20:
        attempts += 1
        q = instantiate(g, names[int(rng.integers(len(names)))], rng)
        if q is not None:
            out.append(q[1])
    return out
