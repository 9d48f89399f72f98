"""The plain reference for SPTAG's integer cosine: exact top-k by brute
force in numpy, in row blocks.

Imports nothing of the program and takes nothing the program made.  The
convention (DistanceUtils.h:452, Utils::Normalize CommonUtils.h:93-108):
every row — corpus and query alike — is rescaled to length 127 in float64
and C-cast back to int8, i.e. TRUNCATED; the distance of a query and a row
is the integer 127^2 - dot of the two truncated vectors.  A float cosine of
the rows as given ranks quantisation near-ties differently and is not what
an int8 index promises.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

BASE = 127
BASE_SQ = BASE * BASE           # 16129


def cores() -> int:
    """Cores this process may run on: what the pools of workers below are
    sized by (os.cpu_count() counts the machine's)."""
    return max(1, len(os.sched_getaffinity(0)))


def spans(n: int, work) -> list:
    """`work(lo, hi)` over `n` rows split into one contiguous span a
    core, side by side -> the results in row order.  A worker keeps its
    scratch for the whole span and writes it in place: block-sized arrays
    made and released per block ran the chip's 40 GiB host out of memory
    (its sandbox gives freed mappings back late)."""
    span = -(-n // min(cores(), max(1, n)))
    with concurrent.futures.ThreadPoolExecutor(-(-n // span)) as pool:
        return list(pool.map(lambda lo: work(lo, min(lo + span, n)),
                             range(0, n, span)))


def normalize_int8(x: np.ndarray, block: int = 8_192) -> np.ndarray:
    """Utils::Normalize for int8 rows: length 127, truncated; a zero row
    becomes the constant row 127/sqrt(D).  Rows are independent."""
    x = np.asarray(x)
    out = np.empty(x.shape, np.int8)
    if not x.size:
        return out
    x2, out2 = x.reshape(-1, x.shape[-1]), out.reshape(-1, x.shape[-1])
    constant = BASE / np.sqrt(x.shape[-1])

    def rows(lo: int, hi: int) -> None:
        f = np.empty((min(block, hi - lo), x2.shape[1]), np.float64)
        sq = np.empty_like(f)
        for b in range(lo, hi, block):
            fb, sb = f[:min(block, hi - b)], sq[:min(block, hi - b)]
            np.copyto(fb, x2[b:b + len(fb)])
            np.multiply(fb, fb, out=sb)
            norm = np.sqrt(sb.sum(-1, keepdims=True))
            np.divide(fb, np.maximum(norm, 1e-30), out=fb)
            np.multiply(fb, BASE, out=fb)
            fb[norm[:, 0] < 1e-6] = constant
            np.trunc(fb, out=fb)
            np.copyto(out2[b:b + len(fb)], fb, casting="unsafe")

    spans(len(x2), rows)
    return out


def exact_topk_int8_cosine(data: np.ndarray, queries: np.ndarray, k: int,
                           block: int = 8_192):
    """Exact top-k over ALL rows -> ((Q, k) ids, (Q, k) int64 scores,
    nearest first; among equal scores in no promised order).

    One float32 GEMM a block is exact: rows no longer than 127 keep every
    partial sum of integer products within 127^2 (Cauchy-Schwarz), far
    inside float32's 2^24.  Every core takes a span of rows block by
    block and keeps its best k; the survivors are ranked once."""
    q = normalize_int8(queries).astype(np.float32)
    x = normalize_int8(data)

    def best_of(lo: int, hi: int):
        xf = np.empty((min(block, hi - lo), x.shape[1]), np.float32)
        dots = np.empty((len(q), len(xf)), np.float32)
        ids, scores = [], []
        for b in range(lo, hi, block):
            n = min(block, hi - b)
            np.copyto(xf[:n], x[b:b + n])
            np.matmul(q, xf[:n].T, out=dots[:, :n])         # exact integers
            kk = min(k, n)
            part = np.argpartition(dots[:, :n], n - kk, axis=1)[:, n - kk:]
            ids.append(part + b)
            scores.append(BASE_SQ - np.take_along_axis(
                dots[:, :n], part, axis=1).astype(np.int64))
        return np.concatenate(ids, axis=1), np.concatenate(scores, axis=1)

    parts = spans(len(x), best_of)
    ids = np.concatenate([p[0] for p in parts], axis=1)
    scores = np.concatenate([p[1] for p in parts], axis=1)
    order = np.argsort(scores, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(scores, order, axis=1))


def exact_scores(data, queries, ids) -> np.ndarray:
    """int64 127^2 - dot of each (query, id) pair, both normalised as
    above; ids (Q, m)."""
    x = normalize_int8(data[ids].reshape(-1, data.shape[1])).reshape(
        ids.shape + (data.shape[1],)).astype(np.int64)
    q = normalize_int8(queries).astype(np.int64)[:, None, :]
    return BASE_SQ - (x * q).sum(-1)


def device_answers(data, queries, k: int, mode: str,
                   block: int = 1_048_576):
    """The control on the chip's own arithmetic: the same plain scan in
    jax.numpy on the default device over the normalised rows, its
    contraction in `mode` — "int32" (int8 operands, int32 accumulation:
    the sound reading), "bf16" (bfloat16 operands and a bfloat16 result:
    the nearest precision below), "bf16_jnp" (what `jnp.dot` of bfloat16
    operands returns, widened at once: XLA may keep the float32
    accumulator there, `xla_allow_excess_precision`, and int8 values are
    exact in bfloat16, so this one can read exact; "bf16" asks for the
    rounding by name) or "f32_default" (float32 operands at the default
    matmul precision: one bfloat16 pass on the chip, exact for the same
    reason).  Imports nothing of the program.  Returns (ids, float32
    distances) in the program's place."""
    import jax
    import jax.numpy as jnp

    def dots(q, x):
        if mode == "int32":
            return jnp.dot(q, x.T, preferred_element_type=jnp.int32)
        if mode == "bf16":
            return jax.lax.reduce_precision(
                jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32),
                exponent_bits=8, mantissa_bits=7)
        if mode == "bf16_jnp":
            return jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T)
        if mode == "f32_default":
            return jnp.dot(q.astype(jnp.float32), x.astype(jnp.float32).T,
                           precision="default")
        raise ValueError(mode)

    @jax.jit
    def best_of(q, x):
        d = jnp.float32(BASE_SQ) - dots(q, x).astype(jnp.float32)
        neg, idx = jax.lax.top_k(-d, min(k, x.shape[0]))
        return -neg, idx

    q = jnp.asarray(normalize_int8(queries))
    ids, dists = [], []
    for lo in range(0, len(data), block):
        d, idx = best_of(q, jnp.asarray(normalize_int8(data[lo:lo + block])))
        ids.append(np.asarray(idx) + lo)
        dists.append(np.asarray(d))
    ids, dists = np.concatenate(ids, 1), np.concatenate(dists, 1)
    order = np.argsort(dists, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ids, order, 1),
            np.take_along_axis(dists, order, 1).astype(np.float32))
