"""What the checks share: the answers a window produced, reduced to the
distinct ones, and the comparisons against the plain reference.  The
comparison that decides `correct` is here and in benchmark/checks/, never
in the program.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import reference


def number(name: str, value, limit, better: str) -> dict:
    """One compared number beside its limit.  `better` = "lower": ok when
    value <= limit; "higher": ok when value >= limit."""
    ok = bool(value <= limit) if better == "lower" else bool(value >= limit)
    return {"name": name, "value": float(value), "limit": float(limit),
            "better": better, "ok": ok}


def answers_as_window(ids, dists) -> dict:
    """Bare answers (one per query, in query order) in the shape of a
    window's record — how a control is put in the program's place."""
    n = len(ids)
    return {"query": np.arange(n), "status": np.zeros(n, np.int64),
            "success_status": np.int64(0),
            "ids": np.asarray(ids, np.int64),
            "dists": np.asarray(dists, np.float32)}


def distinct_answers(record: dict, sample: np.ndarray):
    """Every Success answer of the window to a query of `sample`, reduced
    to the distinct (query, ids, distances) triples -> (query index (m,),
    ids (m, k), float32 distances (m, k), answers compared)."""
    ok = record["status"] == record["success_status"]
    mine = ok & np.isin(record["query"], sample)
    q = record["query"][mine]
    ids = record["ids"][mine]
    bits = record["dists"][mine].astype(np.float32).view(np.uint32)
    key = np.concatenate([q[:, None], ids, bits.astype(np.int64)], axis=1)
    uniq = np.unique(key, axis=0)
    k = ids.shape[1]
    return (uniq[:, 0], uniq[:, 1:1 + k],
            uniq[:, 1 + k:].astype(np.uint32).view(np.float32),
            int(mine.sum()))


def invalid_lists(ids: np.ndarray, rows: int) -> int:
    """Lists with an id out of range or an id twice."""
    bad = ((ids < 0) | (ids >= rows)).any(axis=1)
    srt = np.sort(ids, axis=1)
    return int((bad | (srt[:, 1:] == srt[:, :-1]).any(axis=1)).sum())


def dist_err_ulps(data, queries, q_idx, ids, dists) -> np.ndarray:
    """|returned distance - exact float64 distance of the id it came
    with|, in float32 ulps of |q|^2+|x|^2 — the magnitude the expanded
    form rounds at.  (m, k)."""
    safe = np.clip(ids, 0, len(data) - 1)
    exact = reference.exact_scores(data, queries[q_idx], safe)
    scale = reference.ulp_scale(data, queries[q_idx])[:, None]
    return np.abs(dists.astype(np.float64) - exact) / (reference.F32_EPS
                                                       * scale)
