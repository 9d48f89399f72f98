"""The plain reference of an index that changes while it is searched.

Numpy alone; imports nothing of the program.  A corpus is its base rows,
the rows added since (ids in arrival order after the base's: a row's id is
its slot and is never reused) and the set of ids deleted; `LiveReference`
applies a writer's operations in order and keeps, for every prefix of
them, the exact top-k of the checked queries:

    state m = base rows + the first m operations.

An add appends its rows.  A delete is by content, as SPTAG's
`DeleteIndex(vectors)`: every live STREAMED row within `DELETE_EPS`
(float64 squared L2) of one of the given vectors is tombstoned.  Base
rows are never deleted here (the configuration's assumption: the load
generator does not see base vectors), so a query's base top-k is taken
once, by harness/reference.py, and every state merges it with the
streamed rows it holds.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import reference

ADD, DELETE = 0, 1
DELETE_EPS = 1e-6


def sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float64 squared L2 of every row of `a` to every row of `b`, in
    the expanded form: its rounding is ~1e-10 at these norms, far under
    `DELETE_EPS` and under a float32 ulp of a distance (~4e-4), and it
    is one product where the differences are len(a) x len(b) x dim
    temporaries."""
    a, b = a.astype(np.float64), b.astype(np.float64)
    return np.maximum((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                      - 2.0 * (a @ b.T), 0.0)


class LiveReference:
    """`ops`: the writer's operations in order, each (kind, (r, dim)
    float32 rows).  `queries`: the (Q, dim) queries to keep answers for.
    After construction `ids[m]`, `scores[m]` are the exact top-k of every
    query in state m (float64 scores, nearest first), m = 0..len(ops),
    and `live_upto[m]` / `deleted_at` say which streamed rows state m
    holds."""

    def __init__(self, data: np.ndarray, queries: np.ndarray, k: int,
                 ops: list):
        self.data, self.queries, self.k = data, queries, k
        self.base = len(data)
        adds = [rows for kind, rows in ops if kind == ADD]
        self.streamed = (np.concatenate(adds) if adds
                         else np.zeros((0, data.shape[1]), np.float32))
        # streamed row j joins at operation `born[j]` (it is in state m
        # for m > born[j]) and leaves at `died[j]` (gone for m > died[j])
        self.born = np.zeros(len(self.streamed), np.int64)
        self.died = np.full(len(self.streamed), len(ops) + 1, np.int64)
        self.rows_at = np.zeros(len(ops) + 1, np.int64)   # rows in state m
        self.tombstoned = np.zeros(len(ops), np.int64)    # by operation
        base_ids, base_scores = reference.exact_topk(data, queries, k)
        # every query's float64 distance to every streamed row: some
        # ten thousand rows a window
        near = sq_dists(queries, self.streamed)
        self.ids = np.empty((len(ops) + 1, len(queries), k), np.int64)
        self.scores = np.empty((len(ops) + 1, len(queries), k), np.float64)
        self.ids[0], self.scores[0] = base_ids, base_scores
        self.rows_at[0] = self.base
        n = 0
        for m, (kind, rows) in enumerate(ops):
            if kind == ADD:
                self.born[n:n + len(rows)] = m
                n += len(rows)
            else:
                live = np.flatnonzero(self.died[:n] > m)   # of state m
                if len(live):
                    d = sq_dists(rows, self.streamed[live])
                    hit = live[(d <= DELETE_EPS).any(axis=0)]
                    self.died[hit] = m
                    self.tombstoned[m] = len(hit)
            self.rows_at[m + 1] = self.base + n
            alive = np.flatnonzero((self.born[:n] <= m)
                                   & (self.died[:n] > m))
            cand_ids = np.concatenate(
                [base_ids, np.broadcast_to(alive + self.base,
                                           (len(queries), len(alive)))], 1)
            cand = np.concatenate([base_scores, near[:, alive]], 1)
            order = np.argsort(cand, axis=1, kind="stable")[:, :k]
            self.ids[m + 1] = np.take_along_axis(cand_ids, order, 1)
            self.scores[m + 1] = np.take_along_axis(cand, order, 1)

    def rows(self, ids: np.ndarray) -> np.ndarray:
        """The vectors of `ids` (any shape), base or streamed; an id out
        of range reads as the nearest valid one (the caller counts it)."""
        ids = np.asarray(ids)
        total = self.base + len(self.streamed)
        safe = np.clip(ids, 0, max(total - 1, 0))
        out = self.data[np.minimum(safe, self.base - 1)]
        late = safe >= self.base
        if late.any():
            out = out.copy()
            out[late] = self.streamed[safe[late] - self.base]
        return out

    def exact_scores(self, q_idx: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """float64 squared L2 of each returned id to its query; q_idx
        (m,) rows of `queries`, ids (m, k)."""
        x = self.rows(ids).astype(np.float64)
        q = self.queries[q_idx].astype(np.float64)[:, None, :]
        return ((x - q) ** 2).sum(-1)

    def live(self, m: int, ids: np.ndarray) -> np.ndarray:
        """Whether each of `ids` is a live row of state `m`."""
        ids = np.asarray(ids)
        ok = (ids >= 0) & (ids < self.base)
        j = np.clip(ids - self.base, 0, max(len(self.streamed) - 1, 0))
        if len(self.streamed):
            ok |= ((ids >= self.base) & (ids < self.rows_at[m])
                   & (self.born[j] < m) & (self.died[j] >= m))
        return ok
