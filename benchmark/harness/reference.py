"""The plain reference: exact top-k by brute force in numpy.

Imports nothing of the program and takes nothing the program made.  Copied
from chip_smoke.py (PR 22: `exact_topk`, `exact_scores`, `recall_at_k`) so
that a later change to that script cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)


def exact_topk(data: np.ndarray, queries: np.ndarray, k: int,
               block: int = 131_072, slack: int = 22):
    """Exact L2 top-k -> ((Q, k) ids, (Q, k) float64 squared distances,
    nearest first).

    Each corpus block is ranked with one float32 GEMM, its best k+slack
    rows are kept, and the survivors are re-scored in float64 — so float32
    rounding can only reorder rows inside the slack, never decide the
    answer."""
    q32 = queries.astype(np.float32)
    keep = k + slack
    cand = []
    for lo in range(0, data.shape[0], block):
        x32 = data[lo:lo + block].astype(np.float32)
        rank = (x32 * x32).sum(1)[None, :] - 2.0 * (q32 @ x32.T)
        kk = min(keep, rank.shape[1])
        part = np.argpartition(rank, kk - 1, axis=1)[:, :kk]
        cand.append(part + lo)
    cand = np.concatenate(cand, axis=1)
    scores = exact_scores(data, queries, cand)
    order = np.argsort(scores, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(cand, order, axis=1),
            np.take_along_axis(scores, order, axis=1))


def exact_scores(data, queries, ids) -> np.ndarray:
    """float64 squared L2 distance of each (query, id) pair; ids (Q, m)."""
    x = data[ids].astype(np.float64)                         # (Q, m, d)
    q = queries.astype(np.float64)[:, None, :]
    return ((x - q) ** 2).sum(-1)


def ulp_scale(data, queries) -> np.ndarray:
    """(Q,) the magnitude float32 rounds at when it computes
    |q|^2 + |x|^2 - 2 q.x: one float32 ulp of a distance is
    F32_EPS * this.  The corpus term is the largest row norm of the first
    4096 rows, as in chip_smoke.compare_exact."""
    return ((queries.astype(np.float64) ** 2).sum(1)
            + float((data[:4096].astype(np.float64) ** 2).sum(1).max()))


def recall_at_k(got_ids, ref_ids, k: int) -> float:
    return float(np.mean([len(set(g[:k]) & set(r[:k])) / k
                          for g, r in zip(np.asarray(got_ids).tolist(),
                                          np.asarray(ref_ids).tolist())]))


def lower_precision_answers(data, queries, k: int, mantissa_bits: int = 7):
    """The control at a size a test can hold: the same exact scan with
    every input rounded to `mantissa_bits` explicit bits of mantissa
    (23 = float32 as it is, 15 = about what three bfloat16 passes keep of
    a product, 7 = bfloat16), the distances left as that arithmetic gives
    them.  Returns (ids, float32 distances) in the program's place."""
    drop = 23 - mantissa_bits

    def chop(x):
        x = np.ascontiguousarray(x, np.float32)
        if drop == 0:
            return x
        bits = x.view(np.uint32).astype(np.uint64)
        rounded = ((bits + (1 << (drop - 1))) >> drop) << drop
        return rounded.astype(np.uint32).view(np.float32)
    d, q = chop(data), chop(queries)
    scores = ((q * q).sum(1)[:, None] + (d * d).sum(1)[None, :]
              - 2.0 * (q @ d.T)).astype(np.float32)
    ids = np.argpartition(scores, k - 1, axis=1)[:, :k]
    near = np.take_along_axis(scores, ids, axis=1)
    order = np.argsort(near, axis=1, kind="stable")
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(near, order, axis=1))


def device_answers(data, queries, k: int, precision: str,
                   block: int = 131_072):
    """The control on the chip's own arithmetic: the same plain scan in
    jax.numpy on the default device, its matrix product at `precision`
    ("highest" = float32, the sound reading; "high" = three bfloat16
    passes; "default" = one).  Imports nothing of the program.  Returns
    (ids, float32 distances) in the program's place."""
    import jax
    import jax.numpy as jnp

    q = jnp.asarray(queries, jnp.float32)
    qn = (q * q).sum(1)
    ids, dists = [], []
    for lo in range(0, len(data), block):
        x = jnp.asarray(data[lo:lo + block], jnp.float32)
        scores = (qn[:, None] + (x * x).sum(1)[None, :]
                  - 2.0 * jnp.dot(q, x.T, precision=precision))
        neg, idx = jax.lax.top_k(-scores, min(k, x.shape[0]))
        ids.append(np.asarray(idx) + lo)
        dists.append(-np.asarray(neg))
    ids, dists = np.concatenate(ids, 1), np.concatenate(dists, 1)
    order = np.argsort(dists, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ids, order, 1),
            np.take_along_axis(dists, order, 1).astype(np.float32))
