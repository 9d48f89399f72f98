"""Mesh-wide segment engine — continuous batching over a sharded index.

`parallel/sharded.py` runs the beam walk over every shard of a mesh as
ONE program, but only as a monolithic dispatch: every query in a batch
pays for the slowest query's iterations on the slowest shard, and the
serve tier cannot stream per-query results.  This module is the mesh
face of the continuous-batching machinery (algo/scheduler.py + the
segment kernels of algo/engine.py): it exposes the SAME engine surface
the `BeamSlotScheduler` drives (`walk_plan` / `seed_state` /
`run_segment` / `finalize` / `chunk_size`), but every kernel is a
`shard_map` program over the shard axis —

* **seed**: each shard scores its OWN pivot set against the (replicated)
  query batch and initializes a per-shard walk state;
* **segment**: each shard advances its walk by at most S iterations of
  the shared `_walk_machine` body (no collectives — shards converge
  independently; a query stays resident until EVERY shard's row is done);
* **finalize**: each shard reranks/tombstone-filters its local pool,
  remaps to global ids, and the ICI all-gather + `lax.top_k` merge
  returns the replicated global top-k — the same merge contract as
  `ShardedBKTIndex.search`.

State layout: the loop-carried arrays are QUERY-major with the shard
axis second — ``cand_ids (Q, n_shards, L)``, ``no_better (Q,
n_shards)``, … — so the scheduler's slot bookkeeping (insert / blank /
compact / retire are axis-0 fancy indexing) works unchanged; one slot
row IS one query's residency across the whole mesh.  That is what makes
the slot pools span the mesh: one bucketed refill queue feeds a
mesh-wide segment step, and occupancy/slot-wait/retire accounting covers
every shard at once (the admission controller reads those same gauges).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from sptag_tpu.algo.engine import (
    _VISITED_BUDGET,
    _finalize,
    _init_walk_state,
    _seed_from_pivots,
    _walk_machine,
    beam_pool_size,
    beam_width_for,
)
from sptag_tpu.ops import topk_bins
from sptag_tpu.utils import recompile_guard

SHARD_AXIS = "shard"

#: the scheduler round-trips these through the device each segment
_STATE_KEYS = ("cand_ids", "cand_d", "expanded", "visited", "no_better",
               "ptr", "it")


def _shardax(arr):
    """Re-insert the shard axis (size 1) at position 1 of a per-shard
    body output, so out_specs ``P(None, SHARD_AXIS, ...)`` tile the
    per-shard results into the query-major global layout."""
    return jnp.expand_dims(arr, 1)


def _state_specs():
    """(in/out) PartitionSpecs of the 7 loop-carried state arrays +
    spares in the query-major layout: axis 1 is the shard axis."""
    r3 = P(None, SHARD_AXIS, None)
    r2 = P(None, SHARD_AXIS)
    return (r3, r3, r3, r3, r2, r2, r2)


@functools.partial(jax.jit, static_argnames=("L", "metric", "mesh",
                                             "seed_keep"))
def _mesh_seed_kernel(pivot_ids, pivot_vecs, pivot_mask, queries, L: int,
                      metric: int, mesh: Mesh, seed_keep: int = 0):
    """Per-shard pivot seeding of the replicated query batch: each shard
    runs the single-chip `_seed_from_pivots` against its own pivot set
    and returns the initialized walk state with the shard axis at
    position 1 (plus the per-shard spare-pivot queues)."""

    def local(pids, pvecs, pmask, q):
        cand_ids, cand_d, visited, spare_ids, spare_d = _seed_from_pivots(
            pids[0], pvecs[0], pmask[0], q, L, metric,
            seed_keep=seed_keep)
        state = _init_walk_state(cand_ids, cand_d, visited)
        return tuple(_shardax(a) for a in state) + (
            _shardax(spare_ids), _shardax(spare_d))

    r3 = P(None, SHARD_AXIS, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None, None),
                  P(SHARD_AXIS, None), P(None, None)),
        out_specs=_state_specs() + (r3, r3),
        check_vma=False,
    )(pivot_ids, pivot_vecs, pivot_mask, queries)


@functools.partial(
    jax.jit,
    static_argnames=("k_local", "L", "B", "S", "metric", "base",
                     "nbp_limit", "inject", "mesh", "merge_bins",
                     "score_scale"))
def _mesh_segment_kernel(data, sqnorm, graph, queries, t_limit, cand_ids,
                         cand_d, expanded, visited, no_better, ptr, it,
                         spare_ids, spare_d, k_local: int, L: int, B: int,
                         S: int, metric: int, base: int, nbp_limit: int,
                         inject: int, mesh: Mesh, merge_bins: int = 0,
                         score_scale: float = 0.0, data_score=None):
    """Mesh-wide segment step: every shard advances its rows by at most
    S iterations of the SAME `_walk_machine` body the single-chip
    segment kernel runs, over its own slice of the corpus/graph.  No
    collectives — shards walk and converge independently, which keeps a
    segment exactly as cheap as the single-chip one per shard.  Returns
    the updated state plus the per-(query, shard) alive flags; the
    caller ORs over the shard axis (a query retires only when every
    shard's row reached the absorbing done state)."""

    def local(data_s, sqnorm_s, graph_s, q, tl, ci, cd, ex, vi, nb, pt,
              itr, si, sd, *score_s):
        state = (ci[:, 0], cd[:, 0], ex[:, 0], vi[:, 0], nb[:, 0],
                 pt[:, 0], itr[:, 0])
        body, row_alive = _walk_machine(
            data_s, sqnorm_s, graph_s, q, tl, k_local, L, B, metric,
            base, nbp_limit, spare_ids=si[:, 0], spare_d=sd[:, 0],
            inject=inject, merge_bins=merge_bins,
            data_score=score_s[0] if score_s else None,
            score_scale=score_scale)

        def cond(carry):
            seg, st = carry
            return (seg < S) & jnp.any(row_alive(st))

        def sbody(carry):
            seg, st = carry
            return seg + 1, body(st)

        _, state = jax.lax.while_loop(cond, sbody, (jnp.int32(0), state))
        return tuple(_shardax(a) for a in state) + (
            _shardax(row_alive(state)),)

    r3 = P(None, SHARD_AXIS, None)
    # the optional int8 scoring shadow (CascadeSearch) rides as an extra
    # row-sharded operand, exactly like the monolithic sharded kernel
    args = (data, sqnorm, graph, queries, t_limit, cand_ids, cand_d,
            expanded, visited, no_better, ptr, it, spare_ids, spare_d)
    in_specs = (P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS, None),
                P(None, None), P(None)) + _state_specs() + (r3, r3)
    if data_score is not None:
        args = args + (data_score,)
        in_specs = in_specs + (P(SHARD_AXIS, None),)
    return shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=_state_specs() + (P(None, SHARD_AXIS),),
        check_vma=False,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("k_local", "k_final", "metric", "base", "mesh",
                     "binned_bins", "rerank"))
def _mesh_finalize_kernel(data, sqnorm, deleted, queries, cand_ids,
                          cand_d, k_local: int, k_final: int, metric: int,
                          base: int, mesh: Mesh, binned_bins: int = 0,
                          rerank: bool = False):
    """Retire epilogue: per-shard rerank/tombstone-filter/top-k_local
    (identical to the single-chip finalize), shard-local ids remapped to
    global, then the ICI all-gather + `lax.top_k` global merge — the
    same merge the monolithic `_sharded_beam_kernel` performs.
    `binned_bins` routes the per-shard local select through the bin
    reduction (BinnedTopK): the all-gather still moves only k_local
    entries per shard, so the reduction shrinks the local sort without
    touching ICI bytes (MeshKLocal owns that axis)."""
    from sptag_tpu.parallel.sharded import _gather_merge

    def local(data_s, sqnorm_s, del_s, q, ci, cd):
        n_local = data_s.shape[0]
        shard = jax.lax.axis_index(SHARD_AXIS)
        d, ids = _finalize(data_s, sqnorm_s, del_s, q, ci[:, 0], cd[:, 0],
                           k_local, metric, base, rerank=rerank,
                           binned_bins=binned_bins)
        gids = jnp.where(ids >= 0, ids + shard * n_local, -1)
        return _gather_merge(d, gids, k_final)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(None, None), P(None, SHARD_AXIS, None),
                  P(None, SHARD_AXIS, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(data, sqnorm, deleted, queries, cand_ids, cand_d)


class MeshGraphEngine:
    """`BeamSlotScheduler`-drivable engine over a `ShardedBKTIndex`.

    Wraps the sharded index's already-placed device arrays (no second
    corpus copy); one engine instance is one immutable mesh placement —
    a snapshot swap builds a NEW engine over the new placement and
    retires the old scheduler (parallel/sharded.py ServingAdapter).

    Only pivot seeding is supported (the scheduler path serves BKT/KDT
    shards through their fallback pivot sets); per-query kd seed lists
    would need a per-shard descent per refill bucket — those callers use
    the monolithic mesh search instead.
    """

    def __init__(self, sharded):
        self._sharded = sharded
        # BinnedTopK rides the shard params (the same engine-baked knob
        # the single-chip engine resolves); one shared rule per site —
        # topk_bins.walk_merge_bins / resolve_bins — so the scheduler
        # path stays id-identical to the monolithic mesh search
        self.binned_mode = topk_bins.normalize_mode(
            getattr(getattr(sharded, "params", None), "binned_topk",
                    "off"))
        self.recall_target = topk_bins.validate_recall_target(
            getattr(getattr(sharded, "params", None),
                    "approx_recall_target", 0.99))
        self.mesh: Mesh = sharded.mesh
        self.n = int(sharded.n)
        self.n_local = int(sharded.n_local)
        self.n_shards = int(self.mesh.devices.size)
        self.metric = sharded.metric
        self.base = sharded.base
        self.data = sharded.data
        # tiered cascade (CascadeSearch): the int8 scoring shadow + its
        # STATIC dequantization scale come from the sharded placement —
        # the same values the monolithic _sharded_beam_kernel compiles
        # with, so the two paths stay id-identical
        self.data_score = getattr(sharded, "data_score", None)
        self.score_scale = float(getattr(sharded, "score_scale", 0.0))
        self.sqnorm = sharded.sqnorm
        self.graph = sharded.graph
        self.deleted = sharded.deleted
        self.pivot_ids = sharded.pivot_ids
        self.pivot_vecs = sharded.pivot_vecs
        self.pivot_mask = sharded.pivot_mask

    # ---- scheduler surface (GraphSearchEngine contract) -------------------

    def walk_plan(self, k: int, max_check: int, beam_width: int = 16,
                  pool_size: Optional[int] = None, nbp_limit: int = 3
                  ) -> Tuple[int, int, int, int, int]:
        """Same formula as `ShardedBKTIndex._search_raw`: the per-shard
        plan is computed at the SHARD size (every shard runs the full
        budget — the fan-out semantics of the socket aggregator), and
        k_eff is the GLOBAL merge width the futures resolve at."""
        k_local = self._merge_k_local(k)
        L = beam_pool_size(k_local, max_check, self.n_local, pool_size)
        B = beam_width_for(beam_width, max_check, L)
        T = max(1, -(-max_check // B))
        limit = max(nbp_limit, (max_check // 64) // B, 1)
        k_final = min(k, self.n, k_local * self.n_shards)
        return k_final, L, B, T, limit

    def _merge_k_local(self, k: int) -> int:
        # delegate to THE one MeshKLocal clamp (ShardedBKTIndex) so the
        # scheduler path returns the same ids as the monolithic mesh
        # search at the same knobs — two copies would silently diverge
        return self._sharded._merge_k_local(k)

    def _k_local(self, k_eff: int) -> int:
        return self._merge_k_local(k_eff)

    def chunk_size(self) -> int:
        """Visited-bitset budget per SHARD (each device holds one (Q,
        W_local) bitset), same ladder as the single-chip engine."""
        return max(1, min(_VISITED_BUDGET // max(self.n_local // 8, 1),
                          1024))

    def merge_bins_for(self, L: int, B: int) -> int:
        """Shared walk-merge bin rule (see GraphSearchEngine)."""
        return topk_bins.walk_merge_bins(
            self.binned_mode, L, L + B * int(self.graph.shape[1]))

    def seed_keep_for(self, L: int) -> int:
        """Shared binned-seeding rule at the PER-SHARD pivot width."""
        return topk_bins.seed_spare_keep(
            self.binned_mode, L,
            max(int(self.pivot_ids.shape[1]), L))

    def finalize_bins_for(self, k_local: int, L: int) -> int:
        return topk_bins.resolve_bins(self.binned_mode, k_local, L,
                                      self.recall_target)

    def seed_state(self, queries: jax.Array, L: int,
                   seeds: Optional[jax.Array] = None) -> dict:
        if seeds is not None:
            raise NotImplementedError(
                "mesh scheduler path seeds from per-shard pivots only")
        out = _mesh_seed_kernel(self.pivot_ids, self.pivot_vecs,
                                self.pivot_mask, queries, L,
                                int(self.metric), self.mesh,
                                seed_keep=self.seed_keep_for(L))
        (cand_ids, cand_d, expanded, visited, no_better, ptr, it,
         spare_ids, spare_d) = out
        return {"queries": queries, "cand_ids": cand_ids, "cand_d": cand_d,
                "expanded": expanded, "visited": visited,
                "no_better": no_better, "ptr": ptr, "it": it,
                "spare_ids": spare_ids, "spare_d": spare_d}

    def run_segment(self, state: dict, t_limit: jax.Array, k_eff: int,
                    L: int, B: int, nbp_limit: int, S: int,
                    inject: int = 0) -> Tuple[dict, jax.Array]:
        out = _mesh_segment_kernel(
            self.data, self.sqnorm, self.graph, state["queries"], t_limit,
            state["cand_ids"], state["cand_d"], state["expanded"],
            state["visited"], state["no_better"], state["ptr"],
            state["it"], state["spare_ids"], state["spare_d"],
            self._k_local(k_eff), L, B, S, int(self.metric), self.base,
            nbp_limit, inject, self.mesh,
            merge_bins=self.merge_bins_for(L, B),
            score_scale=self.score_scale, data_score=self.data_score)
        new = dict(state)
        (new["cand_ids"], new["cand_d"], new["expanded"], new["visited"],
         new["no_better"], new["ptr"], new["it"], alive) = out
        # a query is resident until EVERY shard's row reached the
        # absorbing done state — the mesh-wide liveness reduction
        return new, jnp.any(alive, axis=1)

    def finalize(self, state: dict, k_eff: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
        d, ids = _mesh_finalize_kernel(
            self.data, self.sqnorm, self.deleted, state["queries"],
            state["cand_ids"], state["cand_d"], self._k_local(k_eff),
            k_eff, int(self.metric), self.base, self.mesh,
            binned_bins=self.finalize_bins_for(
                self._k_local(k_eff),
                int(state["cand_ids"].shape[-1])),
            # same rerank predicate as _walk's epilogue: an int8 shadow
            # demands the exact fp re-rank before the ICI merge
            rerank=(self.data_score is not None
                    and self.data_score.dtype != self.data.dtype))
        return (recompile_guard.device_get(d),
                recompile_guard.device_get(ids))
