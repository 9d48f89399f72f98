"""Batched k-means for the BKT builder — the TPU reshape of the reference's
per-node Lloyd loop (/root/reference/AnnService/inc/Core/Common/
BKTree.h:324-503).

The reference clusters ONE tree node at a time, with OpenMP threads splitting
the node's samples (KmeansAssign, BKTree.h:325-439).  A TPU would starve on
that shape: deep tree levels have tens of thousands of tiny nodes.  Here the
builder processes a whole tree level at once — every node at the level is one
row of a (B, P, D) padded batch, and all of them run k-means **simultaneously**
as batched MXU matmuls under one jit.  Semantics preserved from the reference:

* count-balancing lambda: assignment cost is ``dist + lambda*count[k]`` with
  ``lambda = base^2 / (100 * node_size)`` (BKTree.h:329,346).
* multiple random restarts picking the lowest-cost initialization
  (KmeansClustering, BKTree.h:448-460).
* Lloyd iterations on a bounded sample of the node (m_iSamples=1000,
  BKTree.h:446,454), final assignment over the full node (:491).
* cluster centers re-normalized for cosine (:421-423).
* the final assignment tracks, per cluster, the member **closest** to the
  centroid (updateCenters=false path, :364-367) — that sample becomes the
  child node's centerid in the tree.
* empty clusters are re-seeded from the largest cluster's farthest member
  (:391-416; here: the globally farthest-from-center sample, a simplification
  with the same balancing intent).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


MAX_DIST = np.float32(3.4e38)   # plain scalar: module import must NOT init a backend


def _pairwise(data: jax.Array, centers: jax.Array, metric: int,
              base: int) -> jax.Array:
    """(B, P, D) x (B, K, D) -> (B, P, K) distances, float32.

    metric 0 = squared L2, 1 = cosine (base^2 - dot; centers are kept
    base-normalized by the update step so no center-norm term is needed).
    """
    dot = jnp.einsum("bpd,bkd->bpk", data, centers,
                     preferred_element_type=jnp.float32)
    if metric == 1:
        return float(base) * float(base) - dot
    dn = jnp.sum(data * data, axis=-1)[..., None]
    cn = jnp.sum(centers * centers, axis=-1)[:, None, :]
    return jnp.maximum(dn + cn - 2.0 * dot, 0.0)


def _assign(data, valid, centers, counts, lam, metric, base):
    """One assignment: returns (labels (B,P), dist-to-own (B,P), cost (B,))."""
    d = _pairwise(data, centers, metric, base)          # (B, P, K)
    penalized = d + lam[:, None, None] * counts[:, None, :].astype(jnp.float32)
    labels = jnp.argmin(penalized, axis=-1).astype(jnp.int32)
    own = jnp.take_along_axis(d, labels[..., None], axis=-1)[..., 0]
    own = jnp.where(valid, own, 0.0)
    cost = jnp.sum(jnp.where(valid, jnp.take_along_axis(
        penalized, labels[..., None], axis=-1)[..., 0], 0.0), axis=-1)
    return labels, own, cost


def _update_centers(data, valid, labels, own, centers, K, metric, base):
    """Mean update + cosine renorm + empty-cluster reseed."""
    onehot = (jax.nn.one_hot(labels, K, dtype=jnp.float32)
              * valid[..., None].astype(jnp.float32))      # (B, P, K)
    counts = jnp.sum(onehot, axis=1)                       # (B, K)
    sums = jnp.einsum("bpk,bpd->bkd", onehot, data,
                      preferred_element_type=jnp.float32)
    means = sums / jnp.maximum(counts, 1.0)[..., None]
    if metric == 1:
        norm = jnp.sqrt(jnp.sum(means * means, axis=-1, keepdims=True))
        means = means / jnp.maximum(norm, 1e-30) * float(base)
    # empty cluster -> farthest valid sample from its current center
    far = jnp.argmax(jnp.where(valid, own, -1.0), axis=-1)        # (B,)
    far_vec = jnp.take_along_axis(
        data, far[:, None, None], axis=1)[:, 0, :]                # (B, D)
    empty = (counts <= 0.0)[..., None]                            # (B, K, 1)
    centers = jnp.where(empty, far_vec[:, None, :], means)
    return centers, counts.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("K", "iters", "restarts", "metric", "base"))
def kmeans_fit(data: jax.Array, valid: jax.Array, key: jax.Array,
               K: int, iters: int, restarts: int, metric: int,
               base: int):
    """Fit K centers per batch row.

    data (B, P, D) float32 (padded sample of each tree node), valid (B, P)
    bool.  Returns (centers (B, K, D) float32, counts (B, K) int32).
    """
    B, P, _ = data.shape
    nvalid = jnp.sum(valid, axis=-1)                       # (B,)
    lam = (float(base) * float(base)
           / (100.0 * jnp.maximum(nvalid.astype(jnp.float32), 1.0)))

    # --- restarts: random K valid samples as centers, keep lowest cost ---
    def init_cost(key_r):
        u = jax.random.uniform(key_r, (B, P))
        u = jnp.where(valid, u, -1.0)
        _, pos = jax.lax.top_k(u, K)                       # (B, K) positions
        centers = jnp.take_along_axis(data, pos[..., None], axis=1)
        zero = jnp.zeros((B, K), jnp.int32)
        _, _, cost = _assign(data, valid, centers, zero,
                             jnp.zeros_like(lam), metric, base)
        return centers, cost

    keys = jax.random.split(key, restarts)
    all_centers, all_costs = jax.vmap(init_cost)(keys)     # (R,B,K,D),(R,B)
    best = jnp.argmin(all_costs, axis=0)                   # (B,)
    centers = jnp.take_along_axis(
        all_centers, best[None, :, None, None], axis=0)[0]

    # --- Lloyd iterations with count-balancing ---
    def body(_, carry):
        centers, counts = carry
        labels, own, _ = _assign(data, valid, centers, counts, lam,
                                 metric, base)
        centers, counts = _update_centers(
            data, valid, labels, own, centers, K, metric, base)
        return centers, counts

    counts0 = jnp.zeros((B, K), jnp.int32)
    centers, counts = jax.lax.fori_loop(0, iters, body, (centers, counts0))
    return centers, counts


@functools.partial(jax.jit, static_argnames=("K", "metric", "base"))
def kmeans_final_assign(data: jax.Array, valid: jax.Array,
                        centers: jax.Array, K: int, metric: int, base: int):
    """Full-node assignment with lambda=0 (reference final KmeansAssign,
    BKTree.h:489-492) plus per-cluster medoid: the member closest to its
    center (the child node's centerid, BKTree.h:197-203 via clusterIdx).

    Returns (labels (B, P) int32, counts (B, K) int32,
             medoid_pos (B, K) int32 — position in P, -1 for empty).
    """
    d = _pairwise(data, centers, metric, base)             # (B, P, K)
    d = jnp.where(valid[..., None], d, MAX_DIST)
    labels = jnp.argmin(d, axis=-1).astype(jnp.int32)
    own = jnp.take_along_axis(d, labels[..., None], axis=-1)[..., 0]

    onehot = jax.nn.one_hot(labels, K, dtype=jnp.float32) \
        * valid[..., None].astype(jnp.float32)
    counts = jnp.sum(onehot, axis=1).astype(jnp.int32)     # (B, K)

    member_d = jnp.where(
        (labels[..., None] == jnp.arange(K)[None, None, :]) &
        valid[..., None],
        own[..., None], MAX_DIST)                          # (B, P, K)
    medoid_pos = jnp.argmin(member_d, axis=1).astype(jnp.int32)
    medoid_pos = jnp.where(counts > 0, medoid_pos, -1)
    labels = jnp.where(valid, labels, -1)
    return labels, counts, medoid_pos
