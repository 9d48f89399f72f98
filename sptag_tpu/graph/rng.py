"""RelativeNeighborhoodGraph — the k-NN graph with RNG pruning.

Parity targets (all under /root/reference/AnnService/inc/Core/Common/):

* NeighborhoodGraph::BuildGraph (NeighborhoodGraph.h:43-110): `TPTNumber`(32)
  random-projection trees partition the corpus into <=`TPTLeafSize`(2000)
  leaves; every leaf is all-pairs joined and each node keeps its best
  ``NeighborhoodSize * GraphNeighborhoodScale`` candidates; refine passes then
  shrink rows to `NeighborhoodSize` under the RNG rule.
* NeighborhoodGraph::RefineGraph (:113-143): each pass re-searches every node
  (budget `MaxCheckForRefineGraph`) and rebuilds its row via
  RelativeNeighborhoodGraph::RebuildNeighbors (RelativeNeighborhoodGraph.h:
  18-35).
* GraphAccuracyEstimation (RelativeNeighborhoodGraph.h:73-112): sampled
  exact-vs-stored row overlap.

TPU reshape: leaf all-pairs and candidate merging are single device programs
per tree (ops/graph.py); the refine pass batches thousands of node-queries
through the beam-search engine at once and double-buffers the graph (the
reference refines rows in place one node at a time under per-row locks —
sequential semantics a TPU batch cannot and need not reproduce).
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from sptag_tpu.io import format as fmt
from sptag_tpu.graph.tptree import tpt_partition
from sptag_tpu.ops import graph as graph_ops
from sptag_tpu.utils import shape_bucket, trace

log = logging.getLogger(__name__)

MAX_DIST = np.float32(3.4e38)

# device budget for one (B, P, P) all-pairs tensor (floats)
_ALLPAIRS_BUDGET = 1 << 26
# node rows per rng_select / refine chunk
_PRUNE_CHUNK = 4096
# min seconds between candidate-stage checkpoint rewrites (build_candidates)
_CKPT_MIN_INTERVAL_S = 60.0

# SearchFn(queries (Q, D), k) -> (dists (Q, k), ids (Q, k))
SearchFn = Callable[[np.ndarray, int], Tuple[np.ndarray, np.ndarray]]


def _pad_rows(arr: np.ndarray, rows: int, fill) -> np.ndarray:
    """Pad arr's first axis up to `rows` with `fill`."""
    if arr.shape[0] >= rows:
        return arr
    pad = np.full((rows - arr.shape[0],) + arr.shape[1:], fill, arr.dtype)
    return np.concatenate([arr, pad])


class RelativeNeighborhoodGraph:
    def __init__(self, neighborhood_size: int = 32, tpt_number: int = 32,
                 tpt_leaf_size: int = 2000, neighborhood_scale: int = 2,
                 cef_scale: int = 2, refine_iterations: int = 2,
                 cef: int = 1000, tpt_top_dims: int = 5,
                 tpt_samples: int = 1000,
                 refine_accuracy_guard: bool = True,
                 refine_accuracy_floor: float = 0.35):
        self.neighborhood_size = neighborhood_size
        self.tpt_number = tpt_number
        self.tpt_leaf_size = tpt_leaf_size
        self.neighborhood_scale = neighborhood_scale
        self.cef_scale = cef_scale
        self.refine_iterations = refine_iterations
        self.cef = cef
        self.tpt_top_dims = tpt_top_dims
        self.tpt_samples = tpt_samples
        self.refine_accuracy_guard = refine_accuracy_guard
        # absolute rollback floor (RefineAccuracyFloor): see the rollback
        # condition in refine() — tunable per dataset, since a corpus
        # whose legitimate post-refine precision@m sits below the default
        # would otherwise have good passes rolled back
        self.refine_accuracy_floor = refine_accuracy_floor
        # (N, row_width) int32 neighbor ids, -1 padded.  Width is
        # neighborhood_size after the final refine; candidate-width before.
        self.graph = np.zeros((0, neighborhood_size), np.int32)

    # ------------------------------------------------------------------ build

    def build(self, data: np.ndarray, metric: int, base: int,
              search_fn_factory: Optional[Callable[..., SearchFn]] = None,
              seed: int = 31, checkpoint=None,
              guard_final: bool = True) -> None:
        """Full build: TPT candidates, then refine passes.

        `search_fn_factory(graph, final=bool)` returns a SearchFn over
        the *current* graph (the index wires the beam engine in; `final`
        marks the pass that defines the saved edges, for the
        FinalRefineSearchMode guardrail); when None, refine falls back to
        candidate-only pruning (no re-search).

        `checkpoint` (utils/build_ckpt.BuildCheckpoint): resumable-build
        stage store — each refine pass saves its output graph, and a
        resumed build skips every pass a prior run completed (the
        candidate stage checkpoints per TPT tree inside build_candidates).
        """
        m = self.neighborhood_size
        # RefineIterations counts SEARCH passes, like the reference's
        # m_iRefineIter (RefineGraph runs iter-1 wide passes + 1 final,
        # NeighborhoodGraph.h:113-130; its first pass walks the raw TPT
        # candidate rows).  Here the candidate lists are RNG-pruned once
        # at wide width to make them walkable, then every refine pass
        # re-searches — non-final passes at CEF*CEFScale budget and wide
        # width, the final pass at CEF and the target width.  Round-3
        # direction-B A/B traced our graph-quality gap (0.959 vs their
        # 0.995 on equal knobs) to running one search pass FEWER than the
        # reference at equal RefineIterations plus the unused CEFScale.
        passes = self.refine_iterations if search_fn_factory is not None \
            else 0
        width_wide = min(max(m * self.neighborhood_scale, 1),
                         max(data.shape[0] - 1, 1))
        start = 0
        if checkpoint is not None and passes > 0:
            for it in reversed(range(passes - 1)):     # last pass not saved
                saved = checkpoint.get_arrays(f"graph_pass{it}")
                if saved is not None:
                    self.graph = saved["graph"]
                    start = it + 1
                    log.info("build resume: refine pass %d/%d from "
                             "checkpoint", it + 1, passes)
                    break
        if start == 0:
            with trace.span("build.tpt_candidates"):
                cand_ids, cand_d = self.build_candidates(
                    data, metric, base, seed, checkpoint=checkpoint)
            with trace.span("build.rng_prune"):
                # prune-only width: wide when refine passes will narrow
                # it, final width when none will (RefineIterations=0 is
                # the candidates-only escape hatch)
                self.graph = self.prune_candidates(
                    data, cand_ids, cand_d,
                    width_wide if passes > 0 else m, metric, base)
            log.info("RNG initial prune width=%d",
                     width_wide if passes > 0 else m)
        # Accuracy guard (round 5, measured at 10M: a refine pass whose
        # search budget is starved — nprobe=1 over the shard's partition —
        # REPLACES good TPT candidate edges with near-random results,
        # taking recall@2048 from 0.589 to 0.469).  The
        # estimator's sample is seeded, so pre/post is a PAIRED
        # comparison on the same 100 nodes.  A pass that both drops the
        # paired estimate and lands below a catastrophic absolute floor
        # (see the rollback condition below) is rolled back and the
        # remaining passes skipped — they would redo the same damage.
        # skip the guard's (samples, N) truth sweep entirely when rollback
        # is structurally impossible: with guard_final=False (engine-
        # switch final pass) and a single pass, no pass could ever roll
        # back
        guard = self.refine_accuracy_guard and passes > 0 and \
            (guard_final or passes > 1)
        acc_truth = pre_acc = None
        if guard and start < passes:
            # truth once per build (the (100, N) sweep dominates the
            # estimate); width=m for EVERY guard estimate so pre/post is
            # a paired comparison of the same quantity — the raw metric's
            # value depends on stored row width, and rows are m wide
            # after the final pass but m*scale before it
            acc_truth = self.accuracy_truth(data, metric, base, width=m)
            pre_acc = self.accuracy_estimation(data, metric, base,
                                               width=m, truth=acc_truth)
        for it in range(start, passes):
            last = it == passes - 1
            width = m if last else width_wide
            # alias, not copy: refine_once is double-buffered (builds
            # new_graph and reassigns) so the pre-pass array is never
            # mutated; the rollback branch copies when it truncates
            before = self.graph if guard else None
            with trace.span("build.refine_pass"):
                # the factory learns which pass this is: the FINAL pass
                # defines the saved edges, and the index may route it
                # through a different engine (FinalRefineSearchMode
                # guardrail — see algo/bkt._refine_search_factory)
                fn = search_fn_factory(self.graph, final=last)
                self.refine_once(data, fn, width, metric, base,
                                 cef=(self.cef if last
                                      else self.cef * self.cef_scale))
            # sampled graph-accuracy log per pass — reference RefineGraph
            # prints GraphAccuracyEstimation after every iteration
            # (NeighborhoodGraph.h:123,134).  With the guard on it is
            # also the rollback signal; without, the estimate costs a
            # (100, N) distance pass, so skip it when nobody listens
            if guard or log.isEnabledFor(logging.INFO):
                acc = self.accuracy_estimation(data, metric, base,
                                               width=(m if guard else None),
                                               truth=acc_truth)
                log.info("RNG refine pass %d/%d width=%d acc=%.4f",
                         it + 1, passes, width, acc)
                # Rollback needs BOTH a drop and a catastrophic absolute
                # floor: RNG refine over a richer pool legitimately
                # LOWERS precision@m (it prunes occluded near neighbors
                # for diverse far edges — measured 0.90 -> 0.69 on a
                # healthy 4k default build), so a relative threshold
                # alone would roll back good passes.  The 10M failure
                # mode this guards (budget-starved searches replacing TPT
                # edges with noise) lands far below any legitimate refine
                # outcome observed (0.22-0.24 vs >= 0.5 on every healthy
                # build).  An engine-switch final pass
                # (FinalRefineSearchMode != RefineSearchMode) is measured
                # but never rolled back: it optimizes walk NAVIGABILITY,
                # which precision@m does not measure (the caller signals
                # this via guard_final=False).
                if guard and acc < pre_acc - 0.02 and \
                        acc < self.refine_accuracy_floor and \
                        (guard_final or not last):
                    log.warning(
                        "RNG refine pass %d/%d DEGRADED sampled graph "
                        "accuracy %.4f -> %.4f (starved search budget? "
                        "MaxCheckForRefineGraph raises it) — pass rolled "
                        "back, remaining passes skipped; lower "
                        "RefineAccuracyFloor (now %.2f) or set "
                        "RefineAccuracyGuard=0 to keep degrading passes",
                        it + 1, passes, pre_acc, acc,
                        self.refine_accuracy_floor)
                    # the restored graph may still be at candidate width
                    # (the final pass normally narrows to m); rows are in
                    # RNG-keep order (ascending distance among kept), so
                    # truncation keeps the top-m RNG picks
                    self.graph = (before[:, :m].copy()
                                  if before.shape[1] > m else before)
                    break
                pre_acc = acc
            if checkpoint is not None and not last:
                # the final pass is not checkpointed: the full build's own
                # save (or the bench cache) captures the finished graph
                checkpoint.put_arrays(f"graph_pass{it}", graph=self.graph)
        self.repair_connectivity()

    def repair_connectivity(self) -> None:
        """Give every zero-in-degree node a reverse edge from its own
        nearest stored neighbor.

        The reference tolerates unreachable graph nodes because its walk can
        re-descend the space-partition trees to ANY leaf sample mid-search
        (SearchTrees refill, BKTIndex.cpp:153-155) — the tree, not the
        graph, guarantees reachability.  The batched device walk seeds from
        a bounded pivot set, so the graph itself must be navigable: an
        orphan row is findable by no budget at all.  Overwriting the last
        (farthest) slot of the neighbor's row costs the least-useful edge.
        """
        g = self.graph
        n = g.shape[0]
        if n == 0:
            return
        indeg = np.bincount(np.clip(g[g >= 0].ravel(), 0, n - 1),
                            minlength=n)
        fixed = 0
        # displacing a row's tail removes one of ITS in-edges — only evict
        # tails with other in-edges or the repair just moves the orphan
        # around; the in-degree ledger makes each fix permanent
        for _ in range(16):                    # cascade bound (paranoia)
            orphans = np.flatnonzero(indeg[:n] == 0)
            progress = False
            for v in orphans:
                nbrs = g[v][g[v] >= 0]
                placed = False
                for t in nbrs:                 # free slot costs nothing
                    row = g[t]
                    empty = np.flatnonzero(row < 0)
                    if len(empty):
                        row[empty[0]] = v
                        placed = True
                        break
                if not placed:
                    for t in nbrs:
                        row = g[t]
                        tail = int(row[-1])
                        if tail >= 0 and tail != v and indeg[tail] > 1:
                            row[-1] = v
                            indeg[tail] -= 1
                            placed = True
                            break
                if placed:
                    indeg[v] += 1
                    fixed += 1
                    progress = True
            if not progress or not len(orphans):
                break
        if fixed:
            log.info("connectivity repair: %d orphan nodes linked", fixed)

    def build_candidates(self, data: np.ndarray, metric: int, base: int,
                         seed: int, checkpoint=None
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """TPT forest -> (N, C) best-candidate lists, ascending distance.

        Parity: the TPT scatter phase of BuildGraph (NeighborhoodGraph.h:
        61-110); one `leaf_allpairs_topk` + `merge_candidates` device program
        pair per tree replaces the per-pair AddNeighbor insertion sorts.

        Each tree draws from its own `[seed, t]`-keyed generator so a
        checkpointed resume (`checkpoint` stage "candidates") reproduces
        the exact partition stream the interrupted run would have used.
        """
        n = data.shape[0]
        C = min(max(self.neighborhood_size * self.neighborhood_scale, 1),
                max(n - 1, 1))
        cand_ids = np.full((n, C), -1, np.int32)
        cand_d = np.full((n, C), MAX_DIST, np.float32)
        start_t = 0
        if checkpoint is not None:
            saved = checkpoint.get_arrays("candidates")
            if (saved is not None
                    and saved["cand_ids"].shape == cand_ids.shape):
                cand_ids = saved["cand_ids"]
                cand_d = saved["cand_d"]
                start_t = int(saved["trees_done"])
                log.info("build resume: %d/%d TPT trees from checkpoint",
                         start_t, self.tpt_number)

        last_save = time.monotonic()
        for t in range(start_t, self.tpt_number):
            rng = np.random.default_rng([seed, t])
            leaves = tpt_partition(data, self.tpt_leaf_size,
                                   self.tpt_top_dims, self.tpt_samples, rng)
            new_ids, new_d = self._tree_candidates(
                data, leaves, C, metric, base)
            merged_ids, merged_d = graph_ops.merge_candidates(
                jnp.asarray(cand_ids), jnp.asarray(cand_d),
                jnp.asarray(new_ids), jnp.asarray(new_d))
            cand_ids = np.asarray(merged_ids)
            cand_d = np.asarray(merged_d)
            log.info("TPT tree %d/%d merged", t + 1, self.tpt_number)
            if checkpoint is not None:
                # throttled: the (N, C) arrays can be ~100 MB — rewriting
                # them after EVERY tree would put O(trees x N x C) of
                # synchronous IO on the build path for little extra resume
                # granularity.  Always write the final tree's merge.
                now = time.monotonic()
                if (t + 1 == self.tpt_number
                        or now - last_save >= _CKPT_MIN_INTERVAL_S):
                    checkpoint.put_arrays("candidates", cand_ids=cand_ids,
                                          cand_d=cand_d,
                                          trees_done=np.int64(t + 1))
                    last_save = now
        return cand_ids, cand_d

    def _tree_candidates(self, data, leaves, C, metric, base):
        """All-pairs join of one tree's leaves -> (N, C) candidates."""
        n = data.shape[0]
        new_ids = np.full((n, C), -1, np.int32)
        new_d = np.full((n, C), MAX_DIST, np.float32)
        max_leaf = max(len(leaf) for leaf in leaves)
        # bucket the leaf pad: max_leaf varies per tree, and every distinct
        # (B, P) shape recompiles the all-pairs kernel (seconds each)
        P = shape_bucket(max(max_leaf, 128), lo=128)
        batch = max(1, _ALLPAIRS_BUDGET // (P * P))
        for off in range(0, len(leaves), batch):
            chunk = leaves[off:off + batch]
            # bucket for compile reuse but never past the budget-derived
            # chunk cap (bucketing past it would overshoot _ALLPAIRS_BUDGET)
            B = min(shape_bucket(len(chunk), lo=1), batch)
            ids_pad = np.full((B, P), -1, np.int64)
            vecs = np.zeros((B, P, data.shape[1]), np.float32)
            valid = np.zeros((B, P), bool)
            for b, leaf in enumerate(chunk):
                ids_pad[b, :len(leaf)] = leaf
                vecs[b, :len(leaf)] = data[leaf].astype(np.float32)
                valid[b, :len(leaf)] = True
            pos, d = graph_ops.leaf_allpairs_topk(
                jnp.asarray(vecs), jnp.asarray(valid), C, metric, base)
            pos = np.asarray(pos)              # (B, P, C) within-leaf
            d = np.asarray(d)
            gids = np.where(pos >= 0,
                            np.take_along_axis(
                                np.broadcast_to(ids_pad[:, :, None],
                                                pos.shape),
                                np.maximum(pos, 0), axis=1), -1)
            rows = ids_pad[valid]
            new_ids[rows] = gids[valid]
            new_d[rows] = d[valid]
        return new_ids, new_d

    # ----------------------------------------------------------------- refine

    def prune_candidates(self, data: np.ndarray, cand_ids: np.ndarray,
                         cand_d: np.ndarray, width: int, metric: int,
                         base: int) -> np.ndarray:
        """RNG-prune sorted candidate lists into rows of `width` neighbors."""
        n, C = cand_ids.shape
        out = np.full((n, width), -1, np.int32)
        for off in range(0, n, _PRUNE_CHUNK):
            stop = min(off + _PRUNE_CHUNK, n)
            cnt = stop - off
            # pad the tail chunk to the fixed size — a remainder shape
            # would compile a second rng_select kernel
            pad = _PRUNE_CHUNK if n > _PRUNE_CHUNK else cnt
            ids = _pad_rows(cand_ids[off:stop], pad, -1)
            d = _pad_rows(cand_d[off:stop], pad, MAX_DIST)
            vecs = data[np.maximum(ids, 0)].astype(np.float32)
            keep = np.asarray(graph_ops.rng_select(
                jnp.asarray(_pad_rows(
                    data[off:stop].astype(np.float32), pad, 0.0)),
                jnp.asarray(vecs), jnp.asarray(d),
                jnp.asarray(ids >= 0), width, metric, base))[:cnt]
            ids = ids[:cnt]
            sel = np.where(keep >= 0,
                           np.take_along_axis(ids, np.maximum(keep, 0),
                                              axis=1), -1)
            out[off:stop] = sel
        return out

    def refine_once(self, data: np.ndarray, search_fn: SearchFn, width: int,
                    metric: int, base: int,
                    cef: Optional[int] = None) -> None:
        """One refine pass: re-search every node, RNG-prune the results.

        Parity: RefineGraph (NeighborhoodGraph.h:113-143) — each node's new
        row comes from a fresh `cef`-budget search (default self.cef; the
        build's non-final passes pass cef*cef_scale, matching the
        reference's wide iterations), self excluded.  Batched and
        double-buffered: all searches in the pass read the pass-start graph.
        """
        n = data.shape[0]
        cef = self.cef if cef is None else cef
        k = min(cef + 1, n)
        new_graph = np.full((n, width), -1, np.int32)
        for off in range(0, n, _PRUNE_CHUNK):
            stop = min(off + _PRUNE_CHUNK, n)
            cnt = stop - off
            pad = _PRUNE_CHUNK if n > _PRUNE_CHUNK else cnt
            # pad the tail chunk so the search + rng_select kernels keep one
            # shape across the whole pass (padding rows repeat row `off`;
            # their results are discarded)
            queries = _pad_rows(data[off:stop], pad, 0)
            if cnt < pad:
                queries[cnt:] = data[off]
            d, ids = search_fn(queries, k)
            # drop self-hits, keep ascending order
            node_ids = np.arange(off, off + pad)[:, None]
            is_self = ids == node_ids
            d = np.where(is_self, MAX_DIST, d)
            order = np.argsort(d, axis=1, kind="stable")
            d = np.take_along_axis(d, order, axis=1)
            ids = np.take_along_axis(ids, order, axis=1)
            ids = np.where(d >= MAX_DIST, -1, ids)
            C = min(ids.shape[1], cef)
            ids = ids[:, :C]
            d = d[:, :C]
            vecs = data[np.maximum(ids, 0)].astype(np.float32)
            keep = np.asarray(graph_ops.rng_select(
                jnp.asarray(queries.astype(np.float32)),
                jnp.asarray(vecs), jnp.asarray(d),
                jnp.asarray(ids >= 0), width, metric, base))[:cnt]
            ids = ids[:cnt]
            new_graph[off:stop] = np.where(
                keep >= 0,
                np.take_along_axis(ids, np.maximum(keep, 0), axis=1), -1)
        self.graph = new_graph

    # ------------------------------------------------------- quality estimate

    def accuracy_truth(self, data: np.ndarray, metric: int, base: int,
                       samples: int = 100, seed: int = 0,
                       width: Optional[int] = None):
        """(pick, truth) for `accuracy_estimation` — the exact-NN half of
        the estimate, independent of the stored graph.  Computed once per
        build and reused across refine passes (the (samples, N) distance
        sweep is the expensive part; only the stored-row lookup changes
        between passes)."""
        from sptag_tpu.ops import distance as dist_ops

        n = data.shape[0]
        rng = np.random.default_rng(seed)
        pick = rng.choice(n, min(samples, n), replace=False)
        q = jnp.asarray(data[pick])
        d = np.array(dist_ops.pairwise_distance(
            q, jnp.asarray(data), metric))
        d[np.arange(len(pick)), pick] = MAX_DIST
        m = min(width or self.graph.shape[1], max(n - 1, 1))
        # argpartition: O(N) per row vs argsort's O(N log N) — this runs
        # on the build hot path once per refine pass when INFO logging or
        # the accuracy guard is enabled
        part = np.argpartition(d, m - 1, axis=1)[:, :m]
        rows = np.take_along_axis(d, part, axis=1)
        order = np.argsort(rows, axis=1)
        return pick, np.take_along_axis(part, order, axis=1)

    def accuracy_estimation(self, data: np.ndarray, metric: int, base: int,
                            samples: int = 100,
                            seed: int = 0,
                            width: Optional[int] = None,
                            truth=None) -> float:
        """Sampled fraction of stored neighbors that are true nearest
        neighbors (parity: GraphAccuracyEstimation,
        RelativeNeighborhoodGraph.h:73-112).

        `width` restricts the scoring to each node's first `width` stored
        neighbors — the accuracy guard compares pre/post refine at
        matched width because the metric's value depends on row width
        (precision@64 and precision@32 are different quantities).
        `truth` short-circuits the exact-NN sweep with a cached
        `accuracy_truth` result."""
        n = data.shape[0]
        if n == 0 or self.graph.shape[0] == 0:
            return 0.0
        if truth is None:
            truth = self.accuracy_truth(data, metric, base, samples, seed,
                                        width=width)
        pick, true_ids = truth
        hits = 0
        total = 0
        for row, node in enumerate(pick):
            stored_row = self.graph[node] if width is None \
                else self.graph[node][:width]
            stored = set(int(x) for x in stored_row if x >= 0)
            if not stored:
                continue
            hits += len(stored & set(true_ids[row][:len(stored)].tolist()))
            total += len(stored)
        return hits / max(total, 1)

    # ------------------------------------------------------------ persistence

    def save(self, path_or_stream) -> None:
        fmt.write_graph(path_or_stream, self.graph)

    @classmethod
    def load(cls, path_or_stream, **kwargs) -> "RelativeNeighborhoodGraph":
        g = cls(**kwargs)
        g.graph = fmt.read_graph(path_or_stream)
        g.neighborhood_size = g.graph.shape[1]
        return g
