"""Device kernels for k-NN graph construction.

TPU reshape of the reference's graph-build hot loops
(/root/reference/AnnService/inc/Core/Common/NeighborhoodGraph.h:43-341 and
RelativeNeighborhoodGraph.h:18-71):

* ``leaf_allpairs_topk`` — the reference walks every TPTree leaf and, for each
  ordered pair inside it, calls the scalar SIMD distance and a per-node
  insertion sort (NeighborhoodGraph.h:80-105 via Utils::AddNeighbor,
  CommonUtils.h:153-180).  Here a whole *batch of leaves* is one (B, P, P)
  distance tensor on the MXU followed by one `lax.top_k` — the all-pairs join
  of thousands of leaves becomes a handful of matmuls.

* ``rng_select`` — the RNG pruning rule (RelativeNeighborhoodGraph.h:18-35):
  scanning candidates in ascending distance order, a candidate is kept only if
  no already-kept neighbor is closer to it than the candidate is to the node.
  Runs SLOT-major: a `lax.fori_loop` over the <= m kept slots (not the C
  candidates) — each step takes every row's first unblocked candidate and
  vector-marks everything it occludes, so the pair distances consulted are
  exactly the kept x all ones the reference evaluates lazily
  (B*m*C*D matmul FLOPs and min(m, C) sequential steps instead of
  B*C*C*D and C).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


MAX_DIST = np.float32(3.4e38)   # plain scalar: module import must NOT init a backend


def _batch_pairwise(a: jax.Array, b: jax.Array, metric: int,
                    base: int) -> jax.Array:
    """(B, P, D) x (B, C, D) -> (B, P, C) distances, float32 inputs.

    metric 0 = squared L2, 1 = cosine ``base^2 - dot`` (rows pre-normalized
    to length `base` at ingest, so no norm correction is needed).
    """
    dot = jnp.einsum("bpd,bcd->bpc", a, b,
                     preferred_element_type=jnp.float32)
    if metric == 1:
        return float(base) * float(base) - dot
    an = jnp.sum(a * a, axis=-1)[..., None]
    bn = jnp.sum(b * b, axis=-1)[:, None, :]
    return jnp.maximum(an + bn - 2.0 * dot, 0.0)


@functools.partial(jax.jit, static_argnames=("num_candidates", "metric",
                                             "base"))
def leaf_allpairs_topk(vecs: jax.Array, valid: jax.Array,
                       num_candidates: int, metric: int, base: int):
    """All-pairs nearest neighbors inside each leaf of a batch.

    vecs (B, P, D) float32 — padded leaf members; valid (B, P) bool.
    Returns (pos (B, P, num_candidates) int32 positions within the leaf,
    -1 for empty slots; dists (B, P, num_candidates) float32, MAX padded).
    """
    d = _batch_pairwise(vecs, vecs, metric, base)          # (B, P, P)
    P = vecs.shape[1]
    eye = jnp.eye(P, dtype=bool)[None]
    d = jnp.where(eye | ~valid[:, None, :] | ~valid[:, :, None], MAX_DIST, d)
    k = min(num_candidates, P)
    neg, pos = jax.lax.top_k(-d, k)
    dists = -neg
    pos = jnp.where(dists >= MAX_DIST, -1, pos).astype(jnp.int32)
    if k < num_candidates:
        pad = num_candidates - k
        B = vecs.shape[0]
        pos = jnp.concatenate(
            [pos, jnp.full((B, P, pad), -1, jnp.int32)], axis=-1)
        dists = jnp.concatenate(
            [dists, jnp.full((B, P, pad), MAX_DIST, jnp.float32)], axis=-1)
    return pos, dists


@jax.jit
def merge_candidates(cand_ids: jax.Array, cand_d: jax.Array,
                     new_ids: jax.Array, new_d: jax.Array):
    """Merge two (N, C) candidate lists into the best C unique neighbors.

    The reference merges one neighbor at a time with an insertion sort under
    a per-row lock (Utils::AddNeighbor, CommonUtils.h:153-180); here a whole
    tree's worth of new candidates merges in one device program: concat,
    sort-by-id to mark duplicates, then top_k by distance.

    Returns (ids (N, C) int32 -1 padded, dists (N, C) float32 MAX padded),
    sorted ascending by distance.
    """
    C = cand_ids.shape[1]
    ids = jnp.concatenate([cand_ids, new_ids], axis=1)          # (N, 2C)
    d = jnp.concatenate([cand_d, new_d], axis=1)

    # order duplicates of an id adjacently, best distance first, so the
    # shifted compare keeps exactly one copy: a stable sort by id applied
    # after a sort by distance preserves distance order among equal ids
    d_order = jnp.argsort(d, axis=1, stable=True)
    ids_d = jnp.take_along_axis(ids, d_order, axis=1)
    d_d = jnp.take_along_axis(d, d_order, axis=1)
    id_order = jnp.argsort(
        jnp.where(ids_d < 0, jnp.int32(2**31 - 1), ids_d), axis=1,
        stable=True)
    ids_s = jnp.take_along_axis(ids_d, id_order, axis=1)
    d_s = jnp.take_along_axis(d_d, id_order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((ids.shape[0], 1), bool),
         ids_s[:, 1:] == ids_s[:, :-1]], axis=1)
    d_s = jnp.where(dup | (ids_s < 0), MAX_DIST, d_s)
    neg, pos = jax.lax.top_k(-d_s, C)
    out_d = -neg
    out_ids = jnp.take_along_axis(ids_s, pos, axis=1)
    out_ids = jnp.where(out_d >= MAX_DIST, -1, out_ids)
    return out_ids.astype(jnp.int32), out_d


@functools.partial(jax.jit, static_argnames=("metric", "base"))
def node_candidate_dists(node_vecs: jax.Array, cand_vecs: jax.Array,
                         metric: int, base: int) -> jax.Array:
    """(U, D) node vectors x (U, C, D) per-node candidates -> (U, C)
    distances — one batched contraction feeding `rng_select`."""
    return _batch_pairwise(node_vecs[:, None, :], cand_vecs, metric,
                           base)[:, 0, :]


@functools.partial(jax.jit, static_argnames=("m", "metric", "base"))
def rng_select(node_vecs: jax.Array, cand_vecs: jax.Array,
               cand_dists: jax.Array, cand_valid: jax.Array,
               m: int, metric: int, base: int):
    """Apply the RNG pruning rule to pre-sorted candidate lists.

    node_vecs (B, D) float32; cand_vecs (B, C, D) float32 — candidates of
    each node sorted ascending by distance-to-node; cand_dists (B, C);
    cand_valid (B, C) bool.  Returns (keep_pos (B, m) int32 positions into C
    in kept-then-filled order, -1 padded).

    Parity: RelativeNeighborhoodGraph::RebuildNeighbors
    (RelativeNeighborhoodGraph.h:18-35) — candidate j is kept iff no
    already-kept g has dist(g, j) <= dist(node, j), until m are kept.

    TPU departure: slots the RNG rule leaves empty are FILLED with the
    nearest occluded candidates (the reference leaves them -1 and recovers
    reachability by re-descending its trees mid-walk, BKTIndex.cpp:153-155;
    the batched engine seeds once up front, so row degree must carry the
    connectivity — sparse RNG-only rows strand the walk in a small
    component).
    """
    del node_vecs  # distances to node come pre-computed in cand_dists
    B, C, D = cand_vecs.shape

    # Slot-major reformulation of the sequential scan: instead of walking
    # all C candidates (C loop steps, an upfront (B, C, C) pair tensor),
    # iterate over the <= m KEPT slots — each step takes every row's FIRST
    # not-yet-occluded candidate, then vector-marks everything that new
    # neighbor occludes (pair(g, j) <= d_j) across the whole row at once.
    # This is exactly the candidate-order greedy (the next kept candidate
    # is always the first unoccluded one), i.e. the reference's lazy
    # per-pair evaluation (RelativeNeighborhoodGraph.h:18-35) batched:
    # min(m, C) sequential steps and B*m*C*D matmul FLOPs instead of C
    # steps and B*C*C*D.
    cf = cand_vecs.astype(jnp.float32)
    if metric != 1:
        cnorm = jnp.sum(cf * cf, axis=-1)                      # (B, C)
    pos = jnp.arange(C, dtype=jnp.int32)[None, :]              # (1, C)

    def slot(_, carry):
        keep_mask, blocked = carry
        # first candidate neither kept nor occluded nor invalid
        avail = ~blocked
        j = jnp.argmax(avail, axis=1)                          # (B,)
        exists = jnp.take_along_axis(avail, j[:, None], axis=1)[:, 0]
        keep_mask = keep_mask | (exists[:, None] & (pos == j[:, None]))
        # distances from the chosen neighbor to every candidate of its row
        gvec = jnp.take_along_axis(cf, j[:, None, None], axis=1)  # (B,1,D)
        dot = jnp.einsum("bd,bcd->bc", gvec[:, 0], cf,
                         preferred_element_type=jnp.float32)
        if metric == 1:
            gd = float(base) * float(base) - dot
        else:
            gn = jnp.take_along_axis(cnorm, j[:, None], axis=1)
            gd = jnp.maximum(gn + cnorm - 2.0 * dot, 0.0)
        occ = exists[:, None] & (gd <= cand_dists)
        return keep_mask, blocked | occ | keep_mask

    keep_mask = jnp.zeros((B, C), bool)
    blocked = ~cand_valid
    keep_mask, _ = jax.lax.fori_loop(0, min(m, C), slot,
                                     (keep_mask, blocked))

    # order: RNG-kept candidates first (ascending), then fill with the
    # nearest non-kept valid candidates; invalid slots last
    n_kept = jnp.sum(keep_mask, axis=1, dtype=jnp.int32)[:, None]  # (B, 1)
    rank_kept = jnp.cumsum(keep_mask.astype(jnp.int32), axis=1) - 1
    fill_mask = cand_valid & ~keep_mask
    rank_fill = jnp.cumsum(fill_mask.astype(jnp.int32), axis=1) - 1
    k = min(m, C)
    src = jnp.where(keep_mask, rank_kept,
                    jnp.where(fill_mask, n_kept + rank_fill, k))
    src = jnp.minimum(src, k)                                     # clamp dump
    out = jnp.full((B, k), -1, jnp.int32)
    out = jax.vmap(
        lambda o, s: o.at[s].set(jnp.arange(C, dtype=jnp.int32),
                                 mode="drop"))(out, src)
    if k < m:
        out = jnp.concatenate(
            [out, jnp.full((B, m - k), -1, jnp.int32)], axis=1)
    return out
