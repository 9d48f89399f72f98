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

import concurrent.futures
import heapq
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from sptag_tpu.io import format as fmt
from sptag_tpu.ops import kmeans as km

# device batch budget: rows per (B, P) padded batch (times D floats)
_MAX_BATCH_ROWS = 1 << 21

# rows a worker of `_gather_f32` copies a step
_GATHER_ROWS = 1 << 16


from sptag_tpu.utils import host_cores, shape_bucket as _shape_bucket

# Every distinct (B, P) pair compiles a fresh XLA kernel pair — measured
# 77% of a 20k-corpus tree build was 37 recompiles (compiles, seconds
# each, dominate a cold build).  The coarse
# utils.shape_bucket ladder cuts the shape zoo at the cost of ≤4x padding
# compute, which is cheap on the MXU.


class _NodeTable:
    """The node arrays while a forest is built.  Every sample is exactly
    one node, and a tree adds a root and a sentinel, so the arrays are
    made once at their final size; children of one node lie side by
    side."""

    def __init__(self, nodes: int):
        self.centerid = np.empty(nodes, np.int64)
        self.child_start = np.full(nodes, -1, np.int64)
        self.child_end = np.full(nodes, -1, np.int64)
        self.n = 0

    def add(self, centerids) -> int:
        """Append one node a center id; returns the first one's index."""
        first = self.n
        self.n += len(centerids)
        self.centerid[first:self.n] = centerids
        return first


def _gather_f32(data: np.ndarray, jobs) -> None:
    """out[i] = data[ids[i]] as float32 for every (ids, out) of `jobs`,
    with no temporary where the rows are float32 already, in spans of
    `_GATHER_ROWS` over the cores the process may run on: a level of a
    10M x 96 tree gathers 3.84 GB of scattered rows, and `take` leaves
    the interpreter lock."""
    spans = [(ids[lo:lo + _GATHER_ROWS],
              out[lo:min(lo + _GATHER_ROWS, len(ids))])
             for ids, out in jobs for lo in range(0, len(ids), _GATHER_ROWS)]

    def gather(span) -> None:
        ids, out = span
        if data.dtype == np.float32:
            np.take(data, ids, axis=0, out=out, mode="clip")
        else:
            out[:] = data[ids]

    workers = min(len(spans), host_cores())
    if workers <= 1 or sum(len(ids) for ids, _ in spans) < _GATHER_ROWS:
        for span in spans:
            gather(span)
    else:
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            list(pool.map(gather, spans))


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

        table = _NodeTable(self.tree_number * (n + 2))
        tree_starts: List[int] = []
        self.sample_center_map = {}

        key = jax.random.PRNGKey(seed)

        for t in range(self.tree_number):
            perm = rng.permutation(ids_all)
            tree_starts.append(table.n)
            root = table.add([n])
            # level items: (node_idx, sample-id array, has_center_sample —
            # False for the root, whose centerid is the count sentinel)
            level: List[Tuple[int, np.ndarray, bool]] = [(root, perm, False)]
            while level:
                level = self._expand_level(data, level, table, rng, key)
                key, _ = jax.random.split(key)
            table.add([-1])  # per-tree sentinel (reference BKTree.h:208)

        self.tree_starts = np.asarray(tree_starts, np.int32)
        self.nodes = np.zeros(table.n, fmt.BKT_NODE_DTYPE)
        self.nodes["centerid"] = table.centerid[:table.n]
        self.nodes["childStart"] = table.child_start[:table.n]
        self.nodes["childEnd"] = table.child_end[:table.n]

    def _expand_level(self, data, level, table, rng, key):
        """Expand all items of one level; returns the next level's items."""
        next_level: List[Tuple[int, np.ndarray, bool]] = []

        leaf_items = [(ni, ids) for ni, ids, _ in level
                      if len(ids) <= self.leaf_size]
        km_items = [(ni, ids, hc) for ni, ids, hc in level
                    if len(ids) > self.leaf_size]

        if leaf_items:
            # one node a sample, every item's children side by side (a
            # 10M-row tree has a million such items: no Python step each)
            lens = np.fromiter((len(ids) for _, ids in leaf_items), np.int64,
                               len(leaf_items))
            owners = np.fromiter((ni for ni, _ in leaf_items), np.int64,
                                 len(leaf_items))
            first = table.add(np.concatenate([ids for _, ids in leaf_items]))
            stops = first + np.cumsum(lens)
            table.child_start[owners] = stops - lens
            table.child_end[owners] = stops

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
            table.child_start[ni] = table.n
            if len(nonzero) <= 1:
                # degenerate duplicate cluster (reference BKTree.h:184-195).
                # The node's own centerid sample was excluded from `ids` by
                # the parent's clustering; the reference re-includes it
                # (`end = min(item.last + 1, ...)` reaches the parent's
                # medoid slot) so no sample is lost from the tree.  Only
                # nodes created by a parent's clustering carry such a
                # sample (`has_center`) — the root's centerid is the count
                # sentinel and must never be re-included.
                old_center = int(table.centerid[ni])
                if has_center and old_center not in ids:
                    ids = np.concatenate([ids, [old_center]])
                ids_sorted = np.sort(ids)
                center = int(ids_sorted[0])
                table.centerid[ni] = center
                table.child_start[ni] = -table.child_start[ni]
                table.add(ids_sorted[1:])
                for dup in ids_sorted[1:]:
                    self.sample_center_map[int(dup)] = center
                self.sample_center_map[-1 - center] = ni
            else:
                order = np.argsort(labels, kind="stable")
                sorted_ids = ids[order]
                offsets = np.concatenate([[0], np.cumsum(counts)])
                first = table.add(medoids[nonzero])
                for cni, k in enumerate(nonzero, first):
                    members = sorted_ids[offsets[k]:offsets[k + 1]]
                    # sample ids are unique within a node: removing the
                    # medoid drops exactly one member (reference excludes the
                    # cluster's center from deeper recursion, BKTree.h:201)
                    rest = members[members != medoids[k]]
                    if len(rest) > 0:
                        next_level.append((cni, rest, True))
            table.child_end[ni] = table.n
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
        jobs = []
        for row, idx in enumerate(chunk):
            ids = km_items[idx][1]
            cnt = len(ids)
            take = min(cnt, self.samples)
            pick = (ids if cnt <= self.samples
                    else rng.choice(ids, self.samples, replace=False))
            jobs.append((pick, sub[row, :take]))
            sub_valid[row, :take] = True
        _gather_f32(data, jobs)
        centers, _ = km.kmeans_fit(
            sub, sub_valid, key, K, self.lloyd_iterations,
            self.restarts, self.metric, self.base)

        if p_full > _MAX_BATCH_ROWS:
            # a node larger than the device batch (B is 1 here): the final
            # assignment is row by row, so it runs a batch of rows at a time
            (idx,) = chunk
            results[idx] = self._assign_in_spans(data, km_items[idx][1],
                                                 centers, K)
            return
        full = np.zeros((B, p_full, D), np.float32)
        full_valid = np.zeros((B, p_full), bool)
        for row, idx in enumerate(chunk):
            full_valid[row, :len(km_items[idx][1])] = True
        _gather_f32(data, [(km_items[idx][1], full[row])
                           for row, idx in enumerate(chunk)])
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

    def _assign_in_spans(self, data, ids, centers, K):
        """`kmeans_final_assign` of ONE node over spans of `_MAX_BATCH_ROWS`
        of its rows: labels side by side, counts added, and per cluster the
        medoid of the span whose medoid lies nearest its center.  The root
        of a 10M x 96 tree is otherwise one (1, 2^24, 96) float32 batch:
        6.4 GB on the host, as much on the device, and the (rows, K)
        temporaries beside it."""
        D = data.shape[1]
        centers_h = np.asarray(centers)[0].astype(np.float64)
        labels = np.empty(len(ids), np.int32)
        counts = np.zeros(K, np.int64)
        best = np.full(K, np.inf)
        med_ids = np.full(K, -1, np.int64)
        span = np.zeros((1, _MAX_BATCH_ROWS, D), np.float32)
        valid = np.zeros((1, _MAX_BATCH_ROWS), bool)
        for lo in range(0, len(ids), _MAX_BATCH_ROWS):
            part = ids[lo:lo + _MAX_BATCH_ROWS]
            _gather_f32(data, [(part, span[0])])
            valid[0] = np.arange(_MAX_BATCH_ROWS) < len(part)
            lab, cnt, pos = km.kmeans_final_assign(
                span, valid, centers, K, self.metric, self.base)
            labels[lo:lo + len(part)] = np.asarray(lab)[0, :len(part)]
            counts += np.asarray(cnt)[0]
            pos = np.asarray(pos)[0]
            has = np.flatnonzero(pos >= 0)
            cand = part[pos[has]]
            x = data[cand].astype(np.float64)
            if self.metric == 1:
                d = float(self.base) ** 2 - (x * centers_h[has]).sum(1)
            else:
                d = ((x - centers_h[has]) ** 2).sum(1)
            nearer = d < best[has]
            best[has[nearer]] = d[nearer]
            med_ids[has[nearer]] = cand[nearer]
        return labels, counts, med_ids

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
