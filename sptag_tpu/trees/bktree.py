"""BKTree — balanced k-means tree forest (TPU-native build).

Parity target: COMMON::BKTree (/root/reference/AnnService/inc/Core/Common/
BKTree.h:107-513).  Same node layout (``BKTNode{centerid, childStart,
childEnd}`` :26-33), same on-disk format (SaveTrees :219-229), same tree
semantics:

* root node's centerid is the sample count (:168); children of a node occupy
  the contiguous node range [childStart, childEnd) (:175,:206).
* a node with <= leaf_size samples expands into per-sample leaf children
  (:176-181).
* otherwise the node k-means-clusters its samples; each non-empty cluster
  becomes a child whose centerid is the cluster member closest to the
  centroid, and that member is excluded from deeper recursion (:196-204 with
  KmeansClustering's final-assign medoid, :364-367,:489-501).
* a degenerate all-one-cluster node (duplicate points) flips childStart
  negative, keeps the first sample as centerid, stores the remaining
  duplicates as children, and records them in the sample-center map
  (:184-195) — the search side chases this chain so duplicates stay
  reachable (BKTIndex.cpp:120-138).
* each tree is terminated by a sentinel node with centerid=-1 (:208).

TPU reshape: the reference clusters one node at a time with OpenMP threads
(BKTree.h:144-211); here each tree level is processed as ONE batched device
k-means over all nodes of the level (padded (B, P, D) batches bucketed by
size — see ops/kmeans.py), with only the cheap bookkeeping (child ranges,
permutations) on host.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from sptag_tpu.io import format as fmt
from sptag_tpu.ops import kmeans as km

# device batch budget: rows per (B, P) padded batch (times D floats)
_MAX_BATCH_ROWS = 1 << 21


from sptag_tpu.utils import shape_bucket as _shape_bucket

# Every distinct (B, P) pair compiles a fresh XLA kernel pair — measured
# 77% of a 20k-corpus tree build was 37 recompiles (compiles, seconds
# each, dominate a cold build).  The coarse
# utils.shape_bucket ladder cuts the shape zoo at the cost of ≤4x padding
# compute, which is cheap on the MXU.


class BKTree:
    """A built forest: flat node arrays + sample-center map."""

    def __init__(self, tree_number: int = 1, kmeans_k: int = 32,
                 leaf_size: int = 8, samples: int = 1000,
                 metric: int = 0, base: int = 1,
                 lloyd_iterations: int = 16, restarts: int = 3):
        self.tree_number = tree_number
        self.kmeans_k = kmeans_k
        self.leaf_size = leaf_size
        self.samples = samples
        self.metric = metric
        self.base = base
        self.lloyd_iterations = lloyd_iterations
        self.restarts = restarts

        self.tree_starts = np.zeros(0, np.int32)
        self.nodes = np.zeros(0, fmt.BKT_NODE_DTYPE)
        self.sample_center_map: Dict[int, int] = {}

    # ------------------------------------------------------------------ build

    def build(self, data: np.ndarray, seed: int = 42,
              sample_ids: Optional[np.ndarray] = None) -> None:
        """Build the forest over `data` rows (or the given subset ids).

        Level-synchronous: every pending node of the current level is
        clustered in one (bucketed) batched device k-means call.
        """
        rng = np.random.default_rng(seed)
        n = data.shape[0] if sample_ids is None else len(sample_ids)
        ids_all = (np.arange(n, dtype=np.int64) if sample_ids is None
                   else np.asarray(sample_ids, np.int64))

        centerid: List[int] = []
        child_start: List[int] = []
        child_end: List[int] = []
        tree_starts: List[int] = []
        self.sample_center_map = {}

        def new_node(cid: int) -> int:
            centerid.append(cid)
            child_start.append(-1)
            child_end.append(-1)
            return len(centerid) - 1

        key = jax.random.PRNGKey(seed)

        for t in range(self.tree_number):
            perm = rng.permutation(ids_all)
            tree_starts.append(len(centerid))
            root = new_node(n)
            # level items: (node_idx, sample-id array, has_center_sample —
            # False for the root, whose centerid is the count sentinel)
            level: List[Tuple[int, np.ndarray, bool]] = [(root, perm, False)]
            while level:
                level = self._expand_level(
                    data, level, centerid, child_start, child_end,
                    new_node, rng, key)
                key, _ = jax.random.split(key)
            new_node(-1)     # per-tree sentinel (reference BKTree.h:208)

        self.tree_starts = np.asarray(tree_starts, np.int32)
        self.nodes = np.zeros(len(centerid), fmt.BKT_NODE_DTYPE)
        self.nodes["centerid"] = centerid
        self.nodes["childStart"] = child_start
        self.nodes["childEnd"] = child_end

    def _expand_level(self, data, level, centerid, child_start, child_end,
                      new_node, rng, key):
        """Expand all items of one level; returns the next level's items."""
        K = self.kmeans_k
        next_level: List[Tuple[int, np.ndarray, bool]] = []

        leaf_items = [(ni, ids) for ni, ids, _ in level
                      if len(ids) <= self.leaf_size]
        km_items = [(ni, ids, hc) for ni, ids, hc in level
                    if len(ids) > self.leaf_size]

        for ni, ids in leaf_items:
            child_start[ni] = len(centerid)
            for s in ids:
                new_node(int(s))
            child_end[ni] = len(centerid)

        # ---- bucket k-means items by padded size, run batched device kmeans
        results: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
        buckets: Dict[int, List[int]] = {}
        for idx, (ni, ids, hc) in enumerate(km_items):
            buckets.setdefault(_shape_bucket(len(ids)), []).append(idx)

        for p_full, idxs in sorted(buckets.items()):
            p_sub = _shape_bucket(min(p_full, self.samples))
            max_b = max(1, _MAX_BATCH_ROWS // p_full)
            # when the bucket spans multiple chunks, pad the TAIL chunk's
            # batch dim up to max_b too: it then reuses the full chunks'
            # already-compiled (max_b, P) shape instead of minting its own
            # — one compiled kernel pair per level instead of two (a
            # compile costs seconds; the padding is one extra partial
            # batch of MXU compute)
            force_b = max_b if len(idxs) > max_b else None
            for off in range(0, len(idxs), max_b):
                chunk = idxs[off:off + max_b]
                self._run_kmeans_chunk(
                    data, km_items, chunk, p_full, p_sub, max_b, rng, key,
                    results, force_b=force_b)

        # ---- materialize children from labels
        for idx, (ni, ids, has_center) in enumerate(km_items):
            labels, counts, medoids = results[idx]
            nonzero = np.flatnonzero(counts)
            child_start[ni] = len(centerid)
            if len(nonzero) <= 1:
                # degenerate duplicate cluster (reference BKTree.h:184-195).
                # The node's own centerid sample was excluded from `ids` by
                # the parent's clustering; the reference re-includes it
                # (`end = min(item.last + 1, ...)` reaches the parent's
                # medoid slot) so no sample is lost from the tree.  Only
                # nodes created by a parent's clustering carry such a
                # sample (`has_center`) — the root's centerid is the count
                # sentinel and must never be re-included.
                old_center = int(centerid[ni])
                if has_center and old_center not in ids:
                    ids = np.concatenate([ids, [old_center]])
                ids_sorted = np.sort(ids)
                center = int(ids_sorted[0])
                centerid[ni] = center
                child_start[ni] = -child_start[ni]
                for dup in ids_sorted[1:]:
                    new_node(int(dup))
                    self.sample_center_map[int(dup)] = center
                self.sample_center_map[-1 - center] = ni
            else:
                order = np.argsort(labels, kind="stable")
                sorted_ids = ids[order]
                offsets = np.concatenate([[0], np.cumsum(counts)])
                for k in nonzero:
                    members = sorted_ids[offsets[k]:offsets[k + 1]]
                    med = medoids[k]
                    cni = new_node(int(med))
                    # sample ids are unique within a node: removing the
                    # medoid drops exactly one member (reference excludes the
                    # cluster's center from deeper recursion, BKTree.h:201)
                    rest = members[members != med]
                    if len(rest) > 0:
                        next_level.append((cni, rest, True))
            child_end[ni] = len(centerid)
        return next_level

    def _run_kmeans_chunk(self, data, km_items, chunk, p_full, p_sub,
                          max_b, rng, key, results, force_b=None):
        """Run one padded (B, P) batch through device kmeans; fill results
        as (labels over the item's ids, counts (K,), medoid sample ids)."""
        # a node smaller than K can't seed K distinct centers; clamp (the
        # reference's per-node loop never hits this because it k-means only
        # nodes with > leaf_size samples and K <= default leaf budgets)
        K = min(self.kmeans_k, p_sub)
        # bucket the batch dim too — same recompile argument as the row
        # dim — but never past the device row budget the caller chunked by;
        # `force_b` pins the tail chunk to the full chunks' shape (see
        # _next_level) so a bucket compiles exactly one kernel pair
        B = (force_b if force_b is not None
             else min(_shape_bucket(len(chunk), lo=1), max_b))
        D = data.shape[1]
        sub = np.zeros((B, p_sub, D), np.float32)
        sub_valid = np.zeros((B, p_sub), bool)
        full = np.zeros((B, p_full, D), np.float32)
        full_valid = np.zeros((B, p_full), bool)
        for row, idx in enumerate(chunk):
            ids = km_items[idx][1]
            cnt = len(ids)
            take = min(cnt, self.samples)
            pick = (ids if cnt <= self.samples
                    else rng.choice(ids, self.samples, replace=False))
            sub[row, :take] = data[pick].astype(np.float32)
            sub_valid[row, :take] = True
            full[row, :cnt] = data[ids].astype(np.float32)
            full_valid[row, :cnt] = True

        centers, _ = km.kmeans_fit(
            sub, sub_valid, key, K, self.lloyd_iterations,
            self.restarts, self.metric, self.base)
        labels, counts, medoid_pos = km.kmeans_final_assign(
            full, full_valid, centers, K, self.metric, self.base)
        labels = np.asarray(labels)
        counts = np.asarray(counts)
        medoid_pos = np.asarray(medoid_pos)
        for row, idx in enumerate(chunk):
            ids = km_items[idx][1]
            cnt = len(ids)
            med_ids = np.where(medoid_pos[row] >= 0,
                               ids[np.clip(medoid_pos[row], 0, cnt - 1)], -1)
            results[idx] = (labels[row, :cnt], counts[row], med_ids)

    # ---------------------------------------------------------------- queries

    @property
    def num_nodes(self) -> int:
        return len(self.nodes)

    def _subtree_sizes(self) -> np.ndarray:
        """Nodes under each node, itself included (every sample is some
        node's centerid, so this is the rows a node stands for)."""
        cs = self.nodes["childStart"]
        ce = self.nodes["childEnd"]
        size = np.ones(len(cs), np.int64)
        # children are appended after their parents (algo/dense.py's cut
        # leans on the same): a reverse scan sees them first
        for ni in np.flatnonzero(cs >= 0)[::-1]:
            size[ni] += size[cs[ni]:ce[ni]].sum()
        return size

    def collect_pivots(self, max_pivots: int) -> np.ndarray:
        """Node centerids (actual sample ids) from the top of the trees
        down, the node that stands for the most rows expanded first — the
        dense pivot set that replaces the reference's dynamic tree-descent
        seeding (InitSearchTrees/SearchTrees, BKTree.h:279-320) with one
        (Q, n_pivots) matmul at query time.

        Largest first keeps the set as dense where the rows are as
        anywhere else: every pivot ends up standing for about
        n / max_pivots rows.  Level by level, cut where the budget ends
        (this function until PR 32), the last level's pivots all went to
        the first few subtrees: of 195 Gaussian clusters of 512 rows,
        6-44 by seed held no pivot at all, the walk cannot cross to a
        cluster no edge leads to, and every query drawn from one read
        recall 0.0 (100k x 128, 4,166 pivots, MaxCheck 2048: recall@10
        0.79-0.999 by seed; PERF.md section 2, PR 32)."""
        out: List[int] = []
        seen = set()
        cs = self.nodes["childStart"]
        ce = self.nodes["childEnd"]
        cid = self.nodes["centerid"]
        size = self._subtree_sizes()
        # ties: the lower node index, which is the older order's
        heap = [(-int(size[t]), int(t)) for t in self.tree_starts]
        heapq.heapify(heap)
        while heap and len(out) < max_pivots:
            _, ni = heapq.heappop(heap)
            if cs[ni] < 0:
                # leaf or degenerate-duplicate node: nothing to descend
                continue
            for c in range(cs[ni], ce[ni]):
                sid = int(cid[c])
                if sid >= 0 and sid not in seen:
                    seen.add(sid)
                    out.append(sid)
                if cs[c] >= 0:
                    heapq.heappush(heap, (-int(size[c]), c))
        return np.asarray(out[:max_pivots], np.int32)

    # ------------------------------------------------------------ persistence

    def save(self, path_or_stream) -> None:
        """Reference-binary format (BKTree::SaveTrees, BKTree.h:219-229)."""
        fmt.write_tree_forest(path_or_stream, self.tree_starts, self.nodes)

    @classmethod
    def load(cls, path_or_stream, **kwargs) -> "BKTree":
        tree = cls(**kwargs)
        tree.tree_starts, tree.nodes = fmt.read_tree_forest(
            path_or_stream, fmt.BKT_NODE_DTYPE)
        tree.tree_number = len(tree.tree_starts)
        # restore sentinel if an old file lacks it (reference LoadTrees
        # BKTree.h:253) and rebuild the duplicate map from negated childStart
        if len(tree.nodes) and tree.nodes["centerid"][-1] != -1:
            sentinel = np.zeros(1, fmt.BKT_NODE_DTYPE)
            sentinel["centerid"] = -1
            sentinel["childStart"] = -1
            sentinel["childEnd"] = -1
            tree.nodes = np.concatenate([tree.nodes, sentinel])
        tree._rebuild_sample_center_map()
        return tree

    def _rebuild_sample_center_map(self) -> None:
        self.sample_center_map = {}
        cid = self.nodes["centerid"]
        cs = self.nodes["childStart"]
        ce = self.nodes["childEnd"]
        # degenerate nodes store a negated childStart; cs == -1 is ambiguous
        # (the leaf default) unless childEnd shows materialized children
        for ni in np.flatnonzero((cs < -1) | ((cs == -1) & (ce > 0))):
            center = int(cid[ni])
            if center < 0:
                continue
            self.sample_center_map[-1 - center] = int(ni)
            for c in range(-cs[ni], ce[ni]):
                self.sample_center_map[int(cid[c])] = center
