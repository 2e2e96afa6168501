"""The plain reference: basic graph patterns evaluated over the generated
triples, with numpy and nothing of the program.

Semantics are SPARQL's for a SELECT of every variable over a set of
triples: each solution mapping once. Every pattern has a constant
predicate (all of the benchmark's templates do). Evaluation takes the
pattern with the fewest candidate rows first, then repeatedly joins a
pattern that shares a variable with what is bound, through a per-predicate
index sorted by subject or by object.

``id_dtype`` is the width the ids are compared in. The configuration
states 32-bit ids; the benchmark's control evaluates at ``np.int16``, the
nearest width below, where distinct ids collide and joins can blow up:
``max_rows`` bounds every join's output, past it :class:`RowLimit`.
"""

from __future__ import annotations

import numpy as np


class RowLimit(Exception):
    pass


class Reference:
    def __init__(self, s: np.ndarray, p: np.ndarray, o: np.ndarray,
                 id_dtype=np.int64, max_rows: int = 50_000_000) -> None:
        self.dtype = np.dtype(id_dtype)
        self.max_rows = int(max_rows)
        s = s.astype(self.dtype)
        o = o.astype(self.dtype)
        self._by: dict[tuple[int, int], tuple[np.ndarray, np.ndarray]] = {}
        order = np.argsort(p, kind="stable")
        bounds = np.searchsorted(p[order], np.arange(int(p.max()) + 2))
        self._rows = {}
        for pid in range(len(bounds) - 1):
            rows = order[bounds[pid]:bounds[pid + 1]]
            self._rows[pid] = (s[rows], o[rows])

    def _sorted(self, pid: int, col: int):
        """(values of ``col`` sorted, the other column in that order)."""
        key = (pid, col)
        if key not in self._by:
            s, o = self._rows.get(pid, (np.zeros(0, self.dtype),) * 2)
            a, b = (s, o) if col == 0 else (o, s)
            order = np.argsort(a, kind="stable")
            self._by[key] = (a[order], b[order])
        return self._by[key]

    def _const(self, t) -> np.ndarray:
        return np.asarray([t]).astype(self.dtype)

    def count(self, tp) -> int:
        s, pid, o = tp
        if not isinstance(s, str):
            a, _ = self._sorted(pid, 0)
            c = self._const(s)[0]
            return int(np.searchsorted(a, c, "right")
                       - np.searchsorted(a, c, "left"))
        if not isinstance(o, str):
            a, _ = self._sorted(pid, 2)
            c = self._const(o)[0]
            return int(np.searchsorted(a, c, "right")
                       - np.searchsorted(a, c, "left"))
        return len(self._sorted(pid, 0)[0])

    def match(self, patterns: list[tuple]) -> tuple[list[str], np.ndarray]:
        """(sorted variable names, solution rows in those columns, rows in
        lexicographic order) of a basic graph pattern."""
        for tp in patterns:
            if isinstance(tp[1], str):
                raise ValueError("the reference needs constant predicates")
        todo = sorted(range(len(patterns)),
                      key=lambda i: self.count(patterns[i]))
        cols: dict[str, np.ndarray] = {}
        n = 1                                  # one empty solution
        while todo:
            pick = next((i for i in todo
                         if any(isinstance(t, str) and t in cols
                                for t in (patterns[i][0], patterns[i][2]))),
                        todo[0])
            todo.remove(pick)
            cols, n = self._join(cols, n, patterns[pick])
        names = sorted(cols)
        rows = (np.stack([cols[v] for v in names], axis=1).astype(np.int64)
                if names else np.zeros((n, 0), np.int64))
        return names, sort_rows(rows)

    def _join(self, cols, n, tp):
        s, pid, o = tp

        def bound(t):
            if isinstance(t, str):
                return cols.get(t)
            return np.full(n, self._const(t)[0], dtype=self.dtype)

        bs, bo = bound(s), bound(o)
        if bs is not None or bo is None:
            col, key, other = 0, bs, bo
        else:
            col, key, other = 2, bo, None
        a, b = self._sorted(pid, col)
        if key is None:                        # nothing bound: every row
            if n * len(a) > self.max_rows:
                raise RowLimit(f"a join would pass {self.max_rows} rows")
            row_idx = np.repeat(np.arange(n), len(a))
            pos = np.tile(np.arange(len(a)), n)
        else:
            lo = np.searchsorted(a, key, "left")
            hi = np.searchsorted(a, key, "right")
            counts = hi - lo
            if int(counts.sum()) > self.max_rows:
                raise RowLimit(f"a join would pass {self.max_rows} rows")
            row_idx = np.repeat(np.arange(n), counts)
            starts = np.repeat(lo, counts)
            pos = starts + (np.arange(len(row_idx))
                            - np.repeat(np.cumsum(counts) - counts, counts))
        new = {v: c[row_idx] for v, c in cols.items()}
        if key is None:
            new_vals = {0: a[pos], 2: b[pos]}
        else:
            new_vals = {col: a[pos], 2 - col: b[pos]}
        keep = np.ones(len(row_idx), dtype=bool)
        if other is not None:                  # both ends bound
            keep &= new_vals[2] == other[row_idx]
        for t, c in ((s, 0), (o, 2)):
            if isinstance(t, str) and t not in cols:
                if t in new:                   # ?v <p> ?v
                    keep &= new[t] == new_vals[c]
                else:
                    new[t] = new_vals[c]
        return {v: c[keep] for v, c in new.items()}, int(keep.sum())


def sort_rows(rows: np.ndarray) -> np.ndarray:
    if len(rows) and rows.shape[1]:
        rows = rows[np.lexsort(rows.T[::-1])]
    return rows
