"""Dense tree-partition search — the MXU-native fast path for BKT.

The reference's only search strategy is the budgeted best-first graph walk
(§3.2, BKTIndex.cpp:105-157), whose serial, gather-per-step shape is hostile
to a systolic-array machine even after batching (algo/engine.py).  This
module adds the TPU-first alternative the hardware actually wants, built
from the SAME balanced-k-means tree:

* a **cut** through the BKT forest's first tree (every subtree at the cut
  holds ≈ DenseClusterSize samples — near-uniform BECAUSE the reference's
  k-means is count-balanced, BKTree.h:329,346) defines a partition of the
  corpus;
* corpus rows are re-laid out cluster-contiguously as one (C, P, D) block
  (P = padded cluster size), so "fetch a cluster" is a single contiguous
  block read instead of P scattered row gathers;
* a query batch scores all cut-node centers with ONE (Q, C) matmul (these
  centers are the tree's real medoid samples — the same pivots the walk
  seeds from), picks the top `nprobe = ceil(MaxCheck / P)` clusters, block-
  gathers them, and scores all Q x nprobe x P candidates as one batched
  contraction + `lax.top_k`.

`MaxCheck` keeps its reference meaning — the number of candidates scored per
query — so the recall/latency knob transfers unchanged.  Tombstones are
masked in the final top-k exactly like the other TPU paths.
"""

from __future__ import annotations

import concurrent.futures
import functools
import heapq
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sptag_tpu.core.types import DeviceTopK, DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops import pallas_kernels
from sptag_tpu.ops import topk_bins
from sptag_tpu.utils import (devmem, host_cores, metrics, query_bucket,
                             round_up, trace)

MAX_DIST = np.float32(3.4e38)   # plain scalar: module import must NOT init a backend

# score-buffer budget per kernel call (bytes): Q * nprobe * P * D * 4
_GATHER_BUDGET = 1 << 30


def gather_budget() -> int:
    """Bytes the gathered candidates of ONE kernel call may take:
    `_GATHER_BUDGET`, or a quarter of the device's memory where the
    device says how much it has (a TPU's `bytes_limit`: 4 GB on a v5e) —
    what is free beside the resident blocks is the device's to give, and
    a batch that fits runs as ONE program (`_dense_search_kernel`) where
    a fixed 1 GB split the 128 callers of a 10M-row index at MaxCheck
    32,768 into two chunks of 85 (PERF.md section 6, PR 48)."""
    from sptag_tpu.algo.engine import _device_memory

    limit, _ = _device_memory()
    return max(_GATHER_BUDGET, limit // 4) if limit else _GATHER_BUDGET


# rows a worker of `build_layout` packs a step (bytes): its temporaries
# are this large, times the workers
_PACK_BYTES = 1 << 24


def _child_ranges(cs: np.ndarray, ce: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """[first child, one past the last) of every node, (0, 0) for a leaf.
    leaf: cs == -1 and ce <= 0; a degenerate duplicate node stores a
    negated childStart (bktree.py loader disambiguation)."""
    degenerate = (cs < -1) | ((cs == -1) & (ce > 0))
    has = (cs >= 0) | degenerate
    return (np.where(cs >= 0, cs, np.where(degenerate, -cs, 0)),
            np.where(has, ce, 0))


def _expand_ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """arange(lo[0], hi[0]), arange(lo[1], hi[1]), ... as one array."""
    lens = hi - lo
    total = int(lens.sum())
    if not total:
        return np.zeros(0, np.int64)
    first = np.cumsum(lens) - lens
    return np.repeat(lo - first, lens) + np.arange(total, dtype=np.int64)


def _segment_sums(values: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Sums of consecutive runs of `values`, `lens[i]` long (0 allowed)."""
    total = np.concatenate([[0], np.cumsum(values)])
    stop = np.cumsum(lens)
    return total[stop] - total[stop - lens]


def partition_from_tree(tree, n: int, target_size: int,
                        place_loose: bool = True
                        ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Cut the first BKT tree into subtrees of <= target_size samples.

    Returns (cut-node center sample ids (C,), list of C member id arrays —
    every sample id in [0, n) appears in exactly one cluster; each cluster's
    center sample is a member of that cluster).

    The center samples of the nodes ABOVE the cut are in no subtree at the
    cut.  They are the medoids of whole regions — the rows most queries of
    their region have among their nearest (a Gaussian cluster's hubs) —
    so where they land decides a recall ceiling no budget lifts.  With
    `place_loose` each joins the smallest cluster, wherever that lies
    (sizes stay balanced; all a caller without the rows can ask for): on
    500k-2M x 96 rows 2 % of all true neighbours were then out of reach
    at any MaxCheck (recall@10 0.977-0.981 from MaxCheck 32,768 to
    131,072; PERF.md section 6, PR 48).  A caller that holds the rows
    passes False, gets them in no cluster and places them by distance
    (`place_rows`: 0.998 on the same rows).

    Level by level over the node arrays (a 10M-row tree has 10M nodes: one
    Python step a node was minutes)."""
    nodes = tree.nodes
    start = int(tree.tree_starts[0])
    end = int(tree.tree_starts[1]) if len(tree.tree_starts) > 1 \
        else len(nodes)
    lo, hi = _child_ranges(nodes["childStart"][start:end].astype(np.int64),
                           nodes["childEnd"][start:end].astype(np.int64))
    # node indexes below are relative to `start`
    leaf = hi <= lo
    lo, hi = lo - start, hi - start
    lo[leaf] = 0
    hi[leaf] = 0
    sample = nodes["centerid"][start:end].astype(np.int64)
    # the ROOT's centerid is the build-time sample count, not a sample
    # (reference BKTree.h:168); after online adds grow n past it, that
    # sentinel would masquerade as a real id without this check
    sample[0] = -1
    sample[(sample < 0) | (sample >= n)] = -1

    # the tree by level, each level in the order its parents list it
    levels = [np.zeros(1, np.int64)]
    while len(levels[-1]):
        f = levels[-1]
        levels.append(_expand_ranges(lo[f], hi[f]))
    levels.pop()
    # bottom-up subtree sample counts
    counts = (sample >= 0).astype(np.int64)
    for f, kids in zip(levels[-2::-1], levels[:0:-1]):
        counts[f] += _segment_sums(counts[kids], hi[f] - lo[f])

    # top-down: a node is a cluster root once its subtree fits
    rank = np.full(end - start, -1, np.int64)     # node -> its cluster
    splits = []                                   # per level: nodes split
    roots = 0
    f = levels[0]
    while len(f):
        c = counts[f]
        is_root = (c > 0) & ((c <= target_size) | (hi[f] <= lo[f]))
        rank[f[is_root]] = roots + np.arange(int(is_root.sum()))
        roots += int(is_root.sum())
        split = f[(c > 0) & ~is_root]
        splits.append(split)
        f = _expand_ranges(lo[split], hi[split])
    root_nodes = np.flatnonzero(rank >= 0)
    root_nodes = root_nodes[np.argsort(rank[root_nodes])]

    # every sample under a root, by root
    got_s, got_r = [], []
    f, r = root_nodes, rank[root_nodes]
    while len(f):
        keep = sample[f] >= 0
        got_s.append(sample[f][keep])
        got_r.append(r[keep])
        r = np.repeat(r, hi[f] - lo[f])
        f = _expand_ranges(lo[f], hi[f])
    if place_loose and roots:
        # each joins the cluster that is smallest when its turn comes
        # (ties: the first) — keeps sizes balanced
        loose = np.concatenate([sample[s][sample[s] >= 0] for s in splits])
        sizes = np.bincount(np.concatenate(got_r), minlength=roots)
        heap = list(zip(sizes.tolist(), range(roots)))
        heapq.heapify(heap)
        owners = []
        for _ in range(len(loose)):
            size, ci = heap[0]
            heapq.heapreplace(heap, (size + 1, ci))
            owners.append(ci)
        got_s.append(loose)
        got_r.append(np.asarray(owners, np.int64))
    members = np.concatenate(got_s) if got_s else np.zeros(0, np.int64)
    owner = np.concatenate(got_r) if got_r else np.zeros(0, np.int64)
    members = members[np.argsort(owner, kind="stable")]
    sizes = np.bincount(owner, minlength=roots)
    first = np.cumsum(sizes) - sizes
    # a root with no sample of its own (the tree's root alone) is stood
    # for by its first member
    centers = np.where(sample[root_nodes] >= 0, sample[root_nodes],
                       members[np.minimum(first, max(len(members) - 1, 0))])
    cut, packed_centers = _pack_plan(sizes, centers, target_size)
    stops = np.concatenate([first, [len(members)]])[cut]
    return packed_centers, [members[a:b]
                            for a, b in zip(stops[:-1], stops[1:])]


def _pack_plan(sizes, centers, target_size: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Greedily merge adjacent small clusters into near-full blocks.

    A tree cut yields MANY subtrees far below target_size (k=32 fan-out:
    one level is ~N/32, the next ~N/1024), and the searcher pads every
    cluster to the max size: measured on a 200k corpus, 8371 raw clusters
    averaged 24 rows padded to 256 — 90% of every probe's score budget was
    padding, which both wastes HBM and guts recall at a given MaxCheck.
    Merging BFS-adjacent clusters (tree siblings == spatially close by
    construction) makes blocks ~full, so a probe scores ~target_size REAL
    candidates.  The merged block keeps the center of its largest
    constituent.

    Returns (the index of each block's first cluster, and len(sizes) at
    the end: B + 1 cuts; the B blocks' centers)."""
    cuts: List[int] = []
    packed_id: List[int] = []
    cur_center, cur_best, cur_n = -1, -1, 0
    for ci, (sz, center) in enumerate(zip(np.asarray(sizes).tolist(),
                                          np.asarray(centers).tolist())):
        if cur_n and cur_n + sz > target_size:
            packed_id.append(cur_center)
            cur_center, cur_best, cur_n = -1, -1, 0
        if not cur_n:
            cuts.append(ci)
        if sz > cur_best:
            cur_best, cur_center = sz, center
        cur_n += sz
    if cur_n:
        packed_id.append(cur_center)
    cuts.append(len(sizes))
    return np.asarray(cuts, np.int64), np.asarray(packed_id, np.int64)


def _pack_clusters(clusters: List[np.ndarray], centers: List[int],
                   target_size: int
                   ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """`_pack_plan` over clusters held as a list of id arrays."""
    cut, packed_centers = _pack_plan([len(c) for c in clusters], centers,
                                     target_size)
    return packed_centers, [np.concatenate(clusters[a:b])
                            for a, b in zip(cut[:-1], cut[1:])]


def place_rows(data: np.ndarray, centers: np.ndarray,
               clusters: List[np.ndarray], rows: np.ndarray, capacity: int,
               metric: DistCalcMethod, chunk: int = 1024
               ) -> List[np.ndarray]:
    """Append each of `rows` (ids in no cluster yet: rows added since the
    tree was built, center samples of the nodes above the cut) to the
    cluster whose center sample is nearest among those that still have
    room; `capacity` (or the largest cluster, if larger) is what a
    cluster may hold, so placing rows never grows the padded block size
    every block pays for.  Only where every cluster is full does a row
    join its nearest one regardless.

    Host numpy (the mesh packer calls this without touching the device),
    `chunk` rows against all centers at a time: 34k rows x 45k centers at
    10M rows is one (1024, 96) x (96, 45k) product a step, not a 6 GB
    score matrix."""
    sizes = np.fromiter((len(c) for c in clusters), np.int64, len(clusters))
    room = max(int(capacity), int(sizes.max())) - sizes
    c = data[centers].astype(np.float32)
    c_sq = (c ** 2).sum(1)
    near = min(8, len(clusters))
    owner = np.empty(len(rows), np.int64)
    for lo in range(0, len(rows), chunk):
        q = data[rows[lo:lo + chunk]].astype(np.float32)
        score = -(q @ c.T)                 # max dot = min distance
        if metric != DistCalcMethod.Cosine:
            score = c_sq[None, :] + 2.0 * score
        cand = np.argpartition(score, near - 1, axis=1)[:, :near]
        cand = np.take_along_axis(
            cand, np.argsort(np.take_along_axis(score, cand, axis=1),
                             axis=1, kind="stable"), axis=1)
        for i, row_cand in enumerate(cand.tolist()):
            pick = next((b for b in row_cand if room[b] > 0), -1)
            if pick < 0:
                order = np.argsort(score[i], kind="stable")
                free = order[room[order] > 0]
                pick = int(free[0]) if len(free) else row_cand[0]
            room[pick] -= 1
            owner[lo + i] = pick
    order = np.argsort(owner, kind="stable")
    stops = np.searchsorted(owner[order], np.arange(len(clusters) + 1))
    placed = np.asarray(rows, np.int64)[order]
    return [np.concatenate([c, placed[a:b]]) if b > a else c
            for c, a, b in zip(clusters, stops[:-1], stops[1:])]


def partition_from_kdtree(tree, n: int, target_size: int
                          ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Cut the first kd-tree into subtrees of <= target_size samples.

    The kd-tree analog of `partition_from_tree`: kd nodes
    (`trees/kdtree.py`) store left/right child node indices with negative
    ``-id-1`` encodings for single-sample leaves, and children are always
    appended after their parent, so a reverse scan yields subtree sizes
    and a BFS emits the cut.  A kd cell is an axis-aligned box — spatially
    coherent, so block means rank blocks well (same principle as the
    reference's own kd-cells-bound search, KDTree.h:178-215).  Returns
    (center sample ids (C,), list of C member arrays covering [0, n)
    exactly once).
    """
    nodes = tree.nodes
    left = nodes["left"].astype(np.int64)
    right = nodes["right"].astype(np.int64)
    start = int(tree.tree_starts[0])
    end = int(tree.tree_starts[1]) if len(tree.tree_starts) > 1 \
        else len(nodes)

    def kids(ni: int):
        return (int(left[ni]), int(right[ni]))

    # bottom-up subtree sample counts (children appended after parents)
    counts = np.zeros(end - start, np.int64)
    for ni in range(end - 1, start - 1, -1):
        c = 0
        for ch in kids(ni):
            c += 1 if ch < 0 else int(counts[ch - start])
        counts[ni - start] = c

    def collect(ni: int) -> List[int]:
        out: List[int] = []
        stack = [ni]
        while stack:
            cur = stack.pop()
            for ch in kids(cur):
                if ch < 0:
                    sid = -ch - 1
                    if 0 <= sid < n:
                        out.append(sid)
                else:
                    stack.append(ch)
        return out

    clusters: List[np.ndarray] = []
    centers: List[int] = []
    loose: List[int] = []
    frontier = [start]
    while frontier:
        nxt: List[int] = []
        for ni in frontier:
            if counts[ni - start] == 0:
                continue
            if counts[ni - start] <= target_size:
                members = collect(ni)
                if members:
                    # degenerate duplicate leaves (one-row corpus) collapse
                    members = sorted(set(members))
                    clusters.append(np.asarray(members, np.int64))
                    centers.append(members[0])
            else:
                for ch in kids(ni):
                    if ch < 0:
                        sid = -ch - 1
                        if 0 <= sid < n:
                            loose.append(sid)
                    else:
                        nxt.append(ch)
        frontier = nxt
    if loose and not clusters:
        clusters.append(np.asarray(sorted(set(loose)), np.int64))
        centers.append(clusters[0][0])
        loose = []
    for s in loose:
        smallest = min(range(len(clusters)), key=lambda i: len(clusters[i]))
        clusters[smallest] = np.append(clusters[smallest], s)
    return _pack_clusters(clusters, centers, target_size)


def _finalize_topk(nd, ids, dead, dedup: bool, k: int, binned_bins: int = 0):
    """Shared epilogue of the dense kernels: tombstone/sentinel masking,
    optional replica de-duplication, masked top-k, -1 id sentinel.
    `dead` holds the candidates' dead bits, (Q, W) like `nd` and `ids`:
    true for a padding slot (id < 0) and for a tombstoned row.
    `binned_bins` > 0 replaces the full (Q, nprobe*P)-wide `lax.top_k`
    with the bin-reduction select (ops/topk_bins.py) — the peak-FLOP/s
    recipe's answer to the scan's sort bottleneck; callers size bins via
    the recall-target math so returned-set recall meets the configured
    ApproxRecallTarget."""
    with jax.named_scope("dense.mask"):
        nd = jnp.where(dead, MAX_DIST, nd)
        if dedup:
            # closure-assigned replicas: the same row can appear in
            # several probed blocks with identical distances — keep one
            # occurrence
            from sptag_tpu.algo.engine import _sorted_dup_mask

            nd = jnp.where(_sorted_dup_mask(jnp.where(ids >= 0, ids, -1)) &
                           (ids >= 0), MAX_DIST, nd)
    with jax.named_scope("dense.topk"):
        k_eff = min(k, nd.shape[1])
        if binned_bins:
            out_d, pos = topk_bins.binned_topk(nd, k_eff, binned_bins)
        else:
            neg, pos = jax.lax.top_k(-nd, k_eff)
            out_d = -neg
        out_ids = jnp.take_along_axis(ids, pos, axis=1)
        out_ids = jnp.where(out_d < MAX_DIST, out_ids, -1)
        return out_d, out_ids.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "metric", "base",
                                    "use_pallas", "interpret", "dedup",
                                    "binned_bins"))
def _dense_search_kernel(data_perm, member_ids, member_sq, centroids,
                        cent_sq, dead_slot, queries, k: int, nprobe: int,
                        metric: int, base: int, use_pallas: bool = False,
                        interpret: bool = False, dedup: bool = False,
                        binned_bins: int = 0):
    """One program: (Q,C) center scores -> top-nprobe block gather ->
    (Q, nprobe*P) candidate scores -> masked top-k.

    `dead_slot` is the (C, P) tombstone table of the layout's slots
    (`DenseTreeSearcher.set_deleted`): a candidate's dead bit arrives with its block, as its
    id and its norm do, never by its row id (that was one element fetch
    for each of the Q x nprobe x P candidates).

    With `use_pallas`, the block gather + scoring runs as the Pallas DMA
    kernel (ops/pallas_kernels.py) — the XLA gather materializes the
    (Q, nprobe, P, D) candidate tensor in HBM; the kernel streams blocks
    through VMEM instead."""
    Q = queries.shape[0]
    C, P, D = data_perm.shape
    # centroids are float32 block MEANS even for integer corpora — score
    # them with float queries (int8/int16 values are exact in f32; the
    # integer dot branch would truncate the means to int32 and mis-rank
    # blocks against the float cent_sq term)
    # the scope names (dense.centroids / gather / probe, and dense.mask /
    # dense.topk in _finalize_topk) are what a profiler trace calls the
    # stages; the grouped twin uses the same ones: kernel PRs keep them
    with jax.named_scope("dense.centroids"):
        d0 = dist_ops.pairwise_distance(
            queries.astype(jnp.float32), centroids, DistCalcMethod(metric),
            x_sqnorm=cent_sq)
        _, topc = jax.lax.top_k(-d0, nprobe)                 # (Q, nprobe)
    with jax.named_scope("dense.gather"):
        ids = member_ids[topc].reshape(Q, nprobe * P)
        sq = member_sq[topc].reshape(Q, nprobe * P)
    with jax.named_scope("dense.probe"):
        if use_pallas:
            from sptag_tpu.ops import pallas_kernels

            # int8 blocks contract int8 queries with exact int32
            # accumulation in-kernel; float blocks take float queries
            q_in = queries if data_perm.dtype == jnp.dtype(jnp.int8) \
                else queries.astype(jnp.float32)
            dot = pallas_kernels.probe_block_dots(
                data_perm, q_in, topc.astype(jnp.int32),
                interpret=interpret
            ).reshape(Q, nprobe * P).astype(jnp.float32)
            if int(metric) == int(DistCalcMethod.Cosine):
                nd = float(base) * float(base) - dot
            else:
                qf = queries.astype(jnp.float32)
                qn = jnp.sum(qf * qf, axis=-1)[:, None]
                nd = jnp.maximum(qn + sq - 2.0 * dot, 0.0)
        else:
            vecs = data_perm[topc].reshape(Q, nprobe * P, D)
            nd = dist_ops.batched_gathered_distance(
                queries, vecs, DistCalcMethod(metric), base, sq)
    # under dense.mask, not dense.gather: the scope holds everything a
    # tombstone costs (benchmark kernel.dense_mask_ms_per_batch)
    with jax.named_scope("dense.mask"):
        dead = dead_slot[topc].reshape(Q, nprobe * P)
    return DeviceTopK(*_finalize_topk(nd, ids, dead, dedup, k,
                                      binned_bins=binned_bins))


def _segmented_min(vals, first):
    """Segmented inclusive min-scan along axis 1: `first` marks run starts;
    each run's LAST element ends up holding the run minimum."""
    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, jnp.minimum(av, bv)), af | bf
    mn, _ = jax.lax.associative_scan(op, (vals, first), axis=1)
    return mn


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "U", "G", "metric",
                                    "base", "use_pallas", "interpret",
                                    "dedup", "binned_bins"))
def _dense_search_grouped_kernel(data_perm, member_ids, member_sq, centroids,
                                 cent_sq, dead_slot, queries, nq_valid,
                                 k: int, nprobe: int, U: int, G: int,
                                 metric: int, base: int,
                                 use_pallas: bool = False,
                                 interpret: bool = False,
                                 dedup: bool = False,
                                 binned_bins: int = 0):
    """Query-grouped probing: sort the batch by nearest centroid, split into
    groups of G neighbors, probe each group's UNION of blocks (top-U by best
    center distance), and score group x block as real (G, D) x (D, P)
    contractions.

    vs the per-query kernel: (Q/G)*U grid steps instead of Q*nprobe (fewer
    per-step fixed costs, G-fold DMA reuse on shared blocks, G MXU rows busy
    per pass), and every query is scored against U >= nprobe blocks, so at
    U = 2*nprobe each query sees ~2x MaxCheck candidates for a fraction of
    the per-query kernel's time.  Queries are un-sorted before returning —
    the output contract is identical to `_dense_search_kernel`.

    Callers must enforce G <= U: the union ranking admits at most G distinct
    rank-0 entries per group, so G <= U GUARANTEES every query's top-1 block
    survives the top-U cut (within-rank overflow would otherwise score a
    query against none of its own probed blocks).  `nq_valid` (traced
    scalar) marks queries [nq_valid:] as padding: they sort to the back and
    never claim union slots."""
    Q = queries.shape[0]
    C, P, D = data_perm.shape
    NG = Q // G
    qf = queries.astype(jnp.float32)
    with jax.named_scope("dense.centroids"):
        d0 = dist_ops.pairwise_distance(
            qf, centroids, DistCalcMethod(metric),
            x_sqnorm=cent_sq)                                    # (Q, C)
        nd0, topc = jax.lax.top_k(-d0, nprobe)                   # (Q, nprobe)
        valid = jnp.arange(Q, dtype=jnp.int32) < nq_valid        # (Q,)

        # sort queries by their best block id so groups share probed
        # blocks; padding sorts to the back (key C) so it doesn't split
        # real groups.  The inverse permutation comes from a SCATTER of
        # the forward one — the same trick as engine._sorted_dedup; the
        # old back-to-back argsort+argsort paid a second full sort for
        # what one O(Q) scatter computes
        order = jnp.argsort(jnp.where(valid, topc[:, 0], C))
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        qs = queries[order]
        qsf = qf[order]
        topc_s = topc[order].reshape(NG, G * nprobe)
        # union-ranking score: probe RANK first, center distance as tie-break.
        # Ranking by raw distance lets a tight query's far probes crowd out a
        # loose query's top-1 block — every query's rank-r block must outrank
        # ALL rank-r+1 blocks or per-query recall collapses for batch outliers.
        # The tie-break is the distance's position within the query's own probe
        # SPREAD (shift- and scale-invariant, in [0, 0.999]): raw distances can
        # be uniformly huge (int cosine ~ base^2 - dot) or uniformly tiny, and
        # any absolute squash would collapse to a constant and leave block-id
        # ordering as the de-facto tie-break
        dc = -nd0                                 # ascending per query (top_k)
        rel = dc - dc[:, :1]
        tie = rel / (rel[:, -1:] + 1e-20) * 0.999
        comp = (jnp.arange(nprobe, dtype=jnp.float32)[None, :]
                + tie)                                           # (Q, nprobe)
        # padding queries' probes never evict a real query's blocks
        comp = jnp.where(valid[:, None], comp, MAX_DIST)
        topd_s = comp[order].reshape(NG, G * nprobe)

        # distinct union blocks per group, ranked by best (min) score:
        # sort by block id, segmented-min over runs, keep each run's last
        o2 = jnp.argsort(topc_s, axis=1)
        bid = jnp.take_along_axis(topc_s, o2, axis=1)
        bd = jnp.take_along_axis(topd_s, o2, axis=1)
        first = jnp.concatenate(
            [jnp.ones((NG, 1), bool), bid[:, 1:] != bid[:, :-1]], axis=1)
        mn = _segmented_min(bd, first)
        last = jnp.concatenate(
            [bid[:, 1:] != bid[:, :-1], jnp.ones((NG, 1), bool)], axis=1)
        rank_d = jnp.where(last, mn, MAX_DIST)
        negu, upos = jax.lax.top_k(-rank_d, U)                   # (NG, U)
        union = jnp.where(-negu < MAX_DIST,
                          jnp.take_along_axis(bid, upos, axis=1), -1)
        union_safe = jnp.maximum(union, 0).astype(jnp.int32)

    with jax.named_scope("dense.gather"):
        ids_u = member_ids[union_safe]                           # (NG, U, P)
        sq_u = member_sq[union_safe]                             # (NG, U, P)
    with jax.named_scope("dense.probe"):
        if use_pallas:
            q_in = qs if data_perm.dtype == jnp.dtype(jnp.int8) else qsf
            dot = pallas_kernels.group_block_dots(
                data_perm, q_in, union_safe,
                interpret=interpret).astype(jnp.float32)     # (NG, U, G, P)
            dot = dot.transpose(0, 2, 1, 3)                  # (NG, G, U, P)
        else:
            vecs = data_perm[union_safe]                     # (NG, U, P, D)
            if dist_ops.exact_int_dot(queries.dtype):
                # exact integer dot (reference int convention, DistanceUtils.h:
                # 452): int32 accumulation, then float for the metric algebra.
                # int16 falls through to the float32 branch — int32 overflows
                # on raw int16 data (ops/distance.py pairwise_dot)
                dot = jnp.einsum(
                    "gqd,gupd->gqup", qs.reshape(NG, G, D).astype(jnp.int32),
                    vecs.astype(jnp.int32),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
            else:
                dot = jnp.einsum(
                    "gqd,gupd->gqup", qsf.reshape(NG, G, D),
                    vecs.astype(jnp.float32),
                    precision=dist_ops.float_precision(),
                    preferred_element_type=jnp.float32)
        if int(metric) == int(DistCalcMethod.Cosine):
            nd = float(base) * float(base) - dot
        else:
            qn = jnp.sum(qsf * qsf, axis=-1).reshape(NG, G, 1, 1)
            nd = jnp.maximum(qn + sq_u[:, None, :, :] - 2.0 * dot, 0.0)

    ids = jnp.broadcast_to(ids_u[:, None, :, :],
                           (NG, G, U, P)).reshape(Q, U * P)
    nd = nd.reshape(Q, U * P)
    with jax.named_scope("dense.mask"):
        # the union's dead bits by block, a padding union entry all dead,
        # one copy for each of the group's queries
        dead_u = dead_slot[union_safe] | (union < 0)[:, :, None]  # (NG, U, P)
        dead = jnp.broadcast_to(dead_u[:, None, :, :],
                                (NG, G, U, P)).reshape(Q, U * P)
    out_d, out_ids = _finalize_topk(nd, ids, dead, dedup, k,
                                    binned_bins=binned_bins)
    # un-sort back to the caller's query order
    return DeviceTopK(out_d[inv], out_ids[inv])


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "U", "G", "metric",
                                    "base", "use_pallas", "interpret",
                                    "dedup", "binned_bins"))
def _dense_search_grouped_chunked(data_perm, member_ids, member_sq,
                                  centroids, cent_sq, dead_slot, queries3,
                                  valid3, k: int, nprobe: int, U: int,
                                  G: int, metric: int, base: int,
                                  use_pallas: bool = False,
                                  interpret: bool = False,
                                  dedup: bool = False,
                                  binned_bins: int = 0):
    def body(args):
        q, nv = args
        return _dense_search_grouped_kernel(
            data_perm, member_ids, member_sq, centroids, cent_sq, dead_slot,
            q, nv, k, nprobe, U, G, metric, base, use_pallas, interpret,
            dedup, binned_bins)
    return jax.lax.map(body, (queries3, valid3))


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "metric", "base",
                                    "use_pallas", "interpret", "dedup",
                                    "binned_bins"))
def _dense_search_chunked(data_perm, member_ids, member_sq, centroids,
                          cent_sq, dead_slot, queries3, k: int, nprobe: int,
                          metric: int, base: int, use_pallas: bool = False,
                          interpret: bool = False, dedup: bool = False,
                          binned_bins: int = 0):
    """(M, chunk, D) query chunks -> ((M, chunk, k), (M, chunk, k)).

    `lax.map` over the chunk axis keeps the WHOLE multi-chunk search one
    device program: one host->device upload, one dispatch, one
    device->host read.  Every synced host round trip has a fixed cost, so
    per-chunk Python loops serialize into RTT * chunks while this stays
    at ~2 RTTs total.  Memory: chunks run sequentially, so the
    per-chunk score buffer is reused rather than multiplied."""
    def body(q):
        return _dense_search_kernel(
            data_perm, member_ids, member_sq, centroids, cent_sq, dead_slot,
            q, k, nprobe, metric, base, use_pallas, interpret, dedup,
            binned_bins)
    return jax.lax.map(body, queries3)


@functools.lru_cache(maxsize=8)
def _replica_scores(metric: int, extra: int):
    """jitted (chunk, D) x (C, D) closure-assignment scorer: distances to
    every block mean, own block masked out, nearest `extra` returned."""
    @jax.jit
    def score(q, means, msq, own):
        if metric == int(DistCalcMethod.Cosine):
            d = -(q @ means.T)
        else:
            # full L2: the per-row |q|^2 term matters because the intake
            # cap compares distances ACROSS rows, not just within one row
            d = ((q * q).sum(1)[:, None] + msq[None, :]
                 - 2.0 * (q @ means.T))
        d = d.at[jnp.arange(q.shape[0]), own].set(jnp.inf)
        neg, top = jax.lax.top_k(-d, extra)
        return top, -neg
    return score


def replicate_clusters(data: np.ndarray, clusters: List[np.ndarray],
                       replicas: int, metric: DistCalcMethod,
                       chunk: int = 8192) -> List[np.ndarray]:
    """Closure assignment: append every row to its `replicas - 1` nearest
    OTHER blocks (by block-mean distance).

    Boundary rows — whose true neighbors straddle a partition edge — are
    the dense mode's main recall loss; duplicating them into the adjacent
    blocks recovers those neighbors at the cost of ~replicas x block
    memory (the SPANN closure-assignment idea applied to the tree
    partition).  Results stay duplicate-free: the search kernel masks
    repeated ids before its final top-k."""
    if replicas <= 1:
        return clusters

    means = np.stack([data[c].astype(np.float32).mean(axis=0)
                      for c in clusters])
    # -1 = row not covered by any primary cluster (possible when callers
    # pass a raw partition_from_tree cut); such rows are skipped — replica
    # placement only duplicates rows the partition already holds
    own = np.full(data.shape[0], -1, np.int64)
    for ci, c in enumerate(clusters):
        own[c] = ci
    extra = min(replicas - 1, len(clusters) - 1)
    # per-chunk accumulation (a Python tuple per (row, replica) would
    # dominate multi-million-row builds); capped below so a popular block
    # can't balloon the padded block size P (P = max block size, so one
    # hot block would multiply EVERY block's memory).  The (chunk, C)
    # scoring runs on DEVICE: at 10M rows x 20k blocks it is ~40 TFLOP —
    # hours of host BLAS, seconds of MXU — with only the (chunk, extra)
    # winners read back per round trip.
    score = _replica_scores(int(metric), extra)
    means_d = jnp.asarray(means)
    msq_d = jnp.asarray((means ** 2).sum(1, dtype=np.float32))
    chunk_rows, chunk_blocks, chunk_dists = [], [], []
    for off in range(0, data.shape[0], chunk):
        rows = np.arange(off, min(off + chunk, data.shape[0]))
        rows = rows[own[rows] >= 0]
        if not len(rows):
            continue
        q = data[rows].astype(np.float32)
        pad = chunk - len(rows)            # one compiled shape per run
        if pad:
            q = np.concatenate([q, np.zeros((pad, q.shape[1]), q.dtype)])
        own_pad = np.concatenate([own[rows],
                                  np.zeros(pad, np.int64)]) if pad \
            else own[rows]
        top, dtop = score(jnp.asarray(q), means_d, msq_d,
                          jnp.asarray(own_pad.astype(np.int32)))
        top = np.asarray(top)[:len(rows)]
        dtop = np.asarray(dtop)[:len(rows)]
        chunk_rows.append(np.repeat(rows, extra))
        chunk_blocks.append(top.ravel())
        chunk_dists.append(dtop.ravel())
    if not chunk_rows:
        return clusters
    all_rows = np.concatenate(chunk_rows)
    all_blocks = np.concatenate(chunk_blocks)
    all_dists = np.concatenate(chunk_dists)
    order = np.argsort(all_blocks, kind="stable")
    all_rows, all_blocks, all_dists = (
        all_rows[order], all_blocks[order], all_dists[order])
    starts = np.searchsorted(all_blocks, np.arange(len(clusters) + 1))
    out = []
    for ci, c in enumerate(clusters):
        lo, hi = starts[ci], starts[ci + 1]
        cap = len(c) * (replicas - 1)      # proportional replica intake
        rows_b, dists_b = all_rows[lo:hi], all_dists[lo:hi]
        if len(rows_b) > cap:              # keep the closest boundary rows
            keep = np.argpartition(dists_b, cap - 1)[:cap] if cap else []
            rows_b = rows_b[keep]
        out.append(np.concatenate([c, rows_b.astype(np.int64)])
                   if len(rows_b) else c)
    return out


class DenseTreeSearcher:
    """Immutable device snapshot of the cluster-contiguous layout.

    Probe ranking uses per-block MEAN centroids computed here from
    `clusters`; the `centers` medoid-sample ids are NOT used for ranking —
    they only serve callers that need a representative sample per block
    (BKTIndex._build_dense_searcher assigns tree-uncovered rows to their
    nearest center).  With `replicas > 1` the blocks already contain
    closure-assigned duplicate rows; the kernel de-duplicates ids before
    the final top-k."""

    @staticmethod
    def build_layout(data: np.ndarray, clusters: List[np.ndarray],
                     metric: DistCalcMethod, replicas: int = 1) -> dict:
        """HOST-side cluster-contiguous layout: packed blocks, member ids,
        squared norms, block-mean centroids — all numpy.  Shared by
        __init__ (which device_puts the result) and the mesh packer
        (parallel/sharded._place_dense), which pads layouts across shards
        and must not round-trip every shard's corpus through the default
        device just to read the arrays back."""
        clusters = replicate_clusters(data, clusters, max(1, replicas),
                                      DistCalcMethod(metric))
        C = len(clusters)
        sizes = np.fromiter((len(c) for c in clusters), np.int64, C)
        # int8 VMEM tiles are (32, 128): pad P so the Pallas probe kernel's
        # block shape is legal for integer corpora too
        p_align = 32 if np.dtype(data.dtype) == np.int8 else 8
        P = round_up(int(sizes.max()), p_align)
        D = data.shape[1]
        mids = np.full((C, P), -1, np.int32)
        mids.reshape(-1)[_expand_ranges(np.arange(C) * P,
                                        np.arange(C) * P + sizes)] = \
            np.concatenate(clusters)
        perm = np.empty((C, P, D), data.dtype)
        sq = np.empty((C, P), np.float32)
        means = np.empty((C, D), np.float32)
        integer = np.issubdtype(perm.dtype, np.integer)

        def pack(lo: int) -> None:
            """Blocks [lo, lo + span): their rows, square sums and means,
            from ONE gather into the layout itself.  A 10M x 96 float32
            corpus is 3.84 GB: a whole-corpus temporary a step (the rows
            again for the norms, their squares, a second gather for the
            means) held five copies on a 40 GiB host."""
            ids = mids[lo:lo + span].reshape(-1)
            blk = perm[lo:lo + span].reshape(-1, D)
            np.take(data, np.maximum(ids, 0), axis=0, out=blk, mode="clip")
            # padding rows get zeros and sqnorm 0 == a real-looking
            # vector; the id mask excludes them anyway
            blk[ids < 0] = 0
            # numpy mirror of ops/distance.row_sqnorms (f32 accumulation;
            # int8/uint8 exact via int64 host sums)
            if integer:
                s = (blk.astype(np.int64) ** 2).sum(1).astype(np.float32)
            else:
                s = (blk.astype(np.float32) ** 2).sum(1, dtype=np.float32)
            sq[lo:lo + span] = s.reshape(-1, P)
            # probe ranking uses the block MEAN (an IVF-style centroid):
            # packed blocks hold several tree subtrees, and a single medoid
            # sample of one constituent ranks the block far worse than its
            # mean does
            means[lo:lo + span] = (
                blk.reshape(-1, P, D).astype(np.float32).sum(1)
                / sizes[lo:lo + span, None].astype(np.float32))

        span = max(1, _PACK_BYTES // (P * D * perm.itemsize))
        workers = min(-(-C // span), host_cores())
        if workers <= 1:
            for lo in range(0, C, span):
                pack(lo)
        else:
            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                list(pool.map(pack, range(0, C, span)))
        cent_sq = (means ** 2).sum(1, dtype=np.float32)
        return dict(perm=perm, ids=mids, sq=sq, cent=means,
                    cent_sq=cent_sq, cluster_size=P, num_clusters=C)

    @staticmethod
    def pad_layout(lay: dict, C: int, Pb: int, dim: int,
                   out: Optional[dict] = None) -> dict:
        """Pad one `build_layout` result to an agreed (C, Pb) geometry
        (shared by the single-host mesh packer and the multi-controller
        build so the padding semantics cannot diverge): -1 ids, zero
        vectors/norms, and a centroid-validity mask over the real blocks.

        `out` may supply pre-allocated (C, Pb, ...) arrays (e.g. VIEWS
        into a stacked per-shard buffer) to fill in place — the mesh
        packer uses this so all shards' padded layouts never exist twice
        in host memory.  Provided arrays must be zero-initialized except
        dense_ids (filled with -1 here)."""
        c, p = lay["perm"].shape[:2]
        if out is None:
            out = dict(
                dense_perm=np.zeros((C, Pb, dim), lay["perm"].dtype),
                dense_ids=np.empty((C, Pb), np.int32),
                dense_sq=np.zeros((C, Pb), np.float32),
                dense_cent=np.zeros((C, dim), np.float32),
                dense_cent_sq=np.zeros((C,), np.float32),
                dense_cent_valid=np.zeros((C,), bool),
            )
        out["dense_ids"][:] = -1
        out["dense_perm"][:c, :p] = lay["perm"]
        out["dense_ids"][:c, :p] = lay["ids"]
        out["dense_sq"][:c, :p] = lay["sq"]
        out["dense_cent"][:c] = lay["cent"]
        out["dense_cent_sq"][:c] = lay["cent_sq"]
        out["dense_cent_valid"][:c] = True
        return out

    def __init__(self, data: np.ndarray, centers: np.ndarray,
                 clusters: List[np.ndarray],
                 deleted: Optional[np.ndarray],
                 metric: DistCalcMethod, base: int,
                 replicas: int = 1,
                 cascade_cfg: Optional[dict] = None):
        self.metric = DistCalcMethod(metric)
        self.base = base
        self.n = data.shape[0]
        self.replicas = max(1, replicas)
        # tiered cascade (CascadeSearch, ops/cascade.py ISSUE 14): the
        # block layout holds the int8 quantization (quarter the f32
        # bytes; the probe prefilter is the coarse tier), queries score
        # in the quantized space (q / scale), and the final candidates
        # re-rank against exact fp rows — device-resident or host-RAM
        # per CorpusTier.  Integer corpora ignore the config (already
        # quantized); cascade_cfg keys: tier, rerank_budget.
        self.cascade_cfg = None
        self.fp_d = None
        self.fp_host: Optional[np.ndarray] = None
        self.scale = 0.0
        src = data
        if cascade_cfg is not None \
                and np.issubdtype(np.asarray(data).dtype, np.floating):
            from sptag_tpu.ops import cascade as cascade_ops

            tier = cascade_ops.normalize_tier(
                cascade_cfg.get("tier", "device"))
            if tier == "host_all":
                tier = "host"       # dense has no sketch tier to keep
            int8_np, scale = cascade_ops.quantize_int8(
                np.asarray(data, np.float32))
            self.scale = float(scale)
            self.cascade_cfg = {
                "tier": tier,
                "rerank_budget": int(cascade_cfg.get("rerank_budget", 0)
                                     or 0),
            }
            src = int8_np
            if tier == "device":
                self.fp_d = jnp.asarray(np.asarray(data, np.float32))
            else:
                self.fp_host = np.ascontiguousarray(
                    np.asarray(data, np.float32))
        lay = self.build_layout(src, clusters, self.metric, self.replicas)
        self.cluster_size = lay["cluster_size"]
        self.num_clusters = lay["num_clusters"]
        self.data_perm = jnp.asarray(lay["perm"])
        self.member_ids = jnp.asarray(lay["ids"])
        self.member_sq = jnp.asarray(lay["sq"])
        self.centroids = jnp.asarray(lay["cent"])
        self.cent_sq = jnp.asarray(lay["cent_sq"])
        # the ids stay on the host too: `set_deleted`'s pass over the slots
        self._slot_ids = lay["ids"]
        # what was placed: the padded geometry every probe pays for
        slots = self.num_clusters * self.cluster_size
        self._pad_slots = int((self._slot_ids < 0).sum())
        metrics.set_gauge("dense.blocks", self.num_clusters)
        metrics.set_gauge("dense.block_rows", self.cluster_size)
        metrics.set_gauge("dense.pad_share", self._pad_slots / slots)
        self.set_deleted(deleted)
        self.last_effective_group = 0     # set by search(); diagnostic only
        self.last_use_pallas = False      # likewise: the last search's route
        self._demotions = set()
        self.register_devmem()

    def register_devmem(self) -> None:
        """(Re-)register the block layout's resident bytes under a
        dtype-split component (the int8-resident shards of the tiered-
        HBM plan account separately from f32 blocks); called at build
        and on DeviceBytesLedger re-enable."""
        lay_bytes = (self.data_perm.nbytes + self.member_ids.nbytes
                     + self.member_sq.nbytes + self.centroids.nbytes
                     + self.cent_sq.nbytes + self.dead_slot.nbytes)
        if self.data_perm.dtype == jnp.dtype(jnp.int8):
            devmem.track("int8_blocks", self, lay_bytes)
        else:
            devmem.track("dense_blocks", self, lay_bytes)
        if self.fp_d is not None:
            # cascade fp re-rank tier, device-resident (CorpusTier=device)
            devmem.track("corpus", self, self.fp_d.nbytes)
        if self.fp_host is not None:
            # host-RAM fp tier: on /debug/memory, excluded from the HBM
            # total (the capacity contract devmem's host flag exists for)
            devmem.track("host_corpus", self, self.fp_host.nbytes,
                         host=True)

    def set_deleted(self, deleted: Optional[np.ndarray]) -> None:
        """Swap only the tombstones (delete-only mutation path): the
        (C, P) per-slot dead table the kernels fetch by block, true where
        a slot is padding (id < 0) or its row is tombstoned, in every
        block that holds a replica of the row.  ONE pass over the C x P
        slots on the host, from the FULL row mask (a mask with fewer bits
        than the last brings its rows back), where the layout is placed
        and once a swap, so that no search looks a tombstone up by row id;
        the row mask itself never goes to the device."""
        dead = self._slot_ids < 0
        if deleted is not None:
            dead |= np.asarray(deleted[:self.n])[
                np.maximum(self._slot_ids, 0)]
        self.dead_slot = jnp.asarray(dead)
        metrics.inc("dense.tombstone_rebuilds")
        # live tombstones x the blocks that hold them (DenseReplicas)
        metrics.set_gauge("dense.dead_slots",
                          int(dead.sum()) - self._pad_slots)

    def _group_floor(self) -> int:
        """Smallest legal query-group size: the Pallas (G, D) query block's
        sublane minimum for this dtype ((8,128) f32, (32,128) int8)."""
        return 32 if self.data_perm.dtype == jnp.dtype(jnp.int8) else 8

    def _rerank_budget(self, k: int) -> int:
        """Static fp-tier budget (TierBudgetInt8 semantics of
        cascade.resolve_budgets: 0 = auto, power-of-two quantized,
        >= k, <= corpus)."""
        from sptag_tpu.ops import cascade as cascade_ops

        b2 = self.cascade_cfg.get("rerank_budget", 0)
        _, b2 = cascade_ops.resolve_budgets(max(self.n, 1), b2, k,
                                            max(self.n, 1))
        return max(b2, min(k, self.n))

    def search(self, queries: np.ndarray, k: int, max_check: int = 2048,
               group: int = 0, union_factor: int = 2,
               binned: str = "off",
               recall_target: float = topk_bins.DEFAULT_RECALL_TARGET
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Public search; with a cascade config the int8 block scan
        produces a `TierBudgetInt8`-wide shortlist that the exact fp
        tier re-ranks (device gather or host fetch per CorpusTier) —
        returned distances are exact fp either way."""
        if self.cascade_cfg is None:
            return self._scan_topk(queries, k, max_check, group,
                                   union_factor, binned, recall_target)
        from sptag_tpu.ops import cascade as cascade_ops

        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        b2 = self._rerank_budget(k)
        # the int8 blocks hold x/scale: scoring q/scale against them
        # keeps every per-query ordering identical to dequantized
        # scoring without touching the block kernels
        q_scaled = queries.astype(np.float32) / np.float32(self.scale)
        _, ids = self._scan_topk(q_scaled, b2, max_check, group,
                                 union_factor, binned, recall_target)
        k_eff = min(k, ids.shape[1])
        q_dev = jnp.asarray(queries.astype(np.float32))
        if self.fp_host is not None:
            # the shared ACCOUNTED gather (out-of-range ids drop to -1
            # and count into cascade.host_fetch_dropped — never a silent
            # clamp onto row 0's data)
            rows, ids, _ = cascade_ops.gather_host_rows(self.fp_host, ids)
            d, out = cascade_ops._fp_rerank_kernel(
                q_dev, jnp.asarray(rows), jnp.asarray(ids), k_eff,
                int(self.metric), self.base)
        else:
            d, out = cascade_ops._fp_rerank_resident_kernel(
                self.fp_d, q_dev, jnp.asarray(ids), k_eff,
                int(self.metric), self.base)
        out_d = np.full((nq, k), np.float32(MAX_DIST), np.float32)
        out_i = np.full((nq, k), -1, np.int32)
        out_d[:, :k_eff] = np.asarray(d)[:, :k_eff]
        out_i[:, :k_eff] = np.asarray(out)[:, :k_eff]
        return out_d, out_i

    def _scan_topk(self, queries: np.ndarray, k: int, max_check: int = 2048,
                   group: int = 0, union_factor: int = 2,
                   binned: str = "off",
                   recall_target: float = topk_bins.DEFAULT_RECALL_TARGET
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """`group` > 1 enables query-grouped probing (DenseQueryGroup):
        the batch is sorted by nearest centroid, split into groups of
        `group` queries, and each group probes the top
        ``union_factor * nprobe`` blocks of its probe UNION — fewer, fatter
        MXU contractions and more candidates per query than the per-query
        kernel.  `group` must be a power of two (padding buckets are).

        `binned` (BinnedTopK: off/on/auto) routes the final candidate
        select through the bin reduction (ops/topk_bins.py) at the bin
        count the `recall_target` math demands over the
        (nprobe*P)-or-(U*P)-wide score row."""
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq, D = queries.shape
        P = self.cluster_size
        nprobe = int(np.clip(-(-max_check // P), 1, self.num_clusters))
        G = int(group) if group and group > 1 else 0
        if G and (G & (G - 1)):
            raise ValueError(f"DenseQueryGroup must be a power of two: {G}")
        if G:
            # adaptive cap: groups only share probes when several batch
            # queries land on each partition block.  A sparse batch
            # (queries/block < ~G/4) makes unions wide and the top-U cut
            # starves individual queries, so shrink the group to ~4 blocks'
            # worth of queries (power of two to keep padding buckets tiling)
            per_block = max(1, nq // max(self.num_clusters, 1))
            cap = 1 << max(1, (4 * per_block).bit_length() - 1)
            G = min(G, max(cap, 2))
        U = (min(max(int(union_factor), 1) * nprobe, self.num_clusters)
             if G else 0)
        if G:
            # a group admits at most G distinct rank-0 union entries, so
            # G <= U guarantees every query's top-1 block survives the
            # top-U cut (see _dense_search_grouped_kernel)
            G = min(G, 1 << (U.bit_length() - 1))
            # dtype tile floor: the Pallas (G, D) query block needs the
            # sublane minimum ((8,128) f32 / (32,128) int8); below it, use
            # the UNGROUPED kernel rather than compile an illegal block.
            # Applied on every platform so CPU and TPU return the same
            # results
            if G < self._group_floor():
                G = 0
            # only G*nprobe distinct blocks can exist in a group's union —
            # a wider top-k over the (NG, G*nprobe) rank buffer would be
            # out of bounds
            U = min(U, G * nprobe) if G else U
        # grouping degenerates to a full scan when the union would cover
        # every block anyway — the per-query kernel is cheaper there
        if G and U >= self.num_clusters and nprobe >= self.num_clusters:
            G = 0
        # observability: callers asked for grouping but the adaptive cap /
        # tile floor / U clamp demoted it — record the effective value and
        # log each distinct demotion once (silent demotion has already
        # misled bench configs)
        self.last_effective_group = G
        if group and int(group) > 1 and G != int(group):
            # keyed on (requested, effective) only — including nq would
            # grow the set without bound in a long-lived server receiving
            # many distinct batch sizes
            key = (int(group), G)
            if key not in self._demotions:
                self._demotions.add(key)
                import logging

                logging.getLogger(__name__).info(
                    "dense grouped probing: requested group=%s -> "
                    "effective %s (nq=%d, clusters=%d, nprobe=%d, U=%s)",
                    group, G or "off", nq, self.num_clusters, nprobe,
                    U or "-")
        k_eff = min(k, (U if G else nprobe) * P, self.n)
        # bin-reduction final select (BinnedTopK): bins sized by the
        # recall-target formula over the scored row width; 0 = exact.
        # Resolved per (G, U, nprobe) shape — a static kernel parameter
        # like k_eff, so it mints no extra compiles beyond the mode flip
        bins = topk_bins.resolve_bins(binned, k_eff,
                                      (U if G else nprobe) * P,
                                      recall_target)

        bytes_q = ((U * P * D * 4 + G - 1) // G if G
                   else nprobe * P * D * 4)
        chunk = max(1, min(gather_budget() // bytes_q, 1024))
        if G:
            chunk = max(G, (chunk // G) * G)    # groups must tile the chunk
        # the int8 kernel needs int8 queries too (dot_general forbids mixed
        # dtypes); float queries against an int8 corpus take the XLA path
        use_pallas = pallas_kernels.supported(self.data_perm) and (
            self.data_perm.dtype != np.dtype(np.int8)
            or queries.dtype == np.dtype(np.int8))
        # diagnostic, like last_effective_group: which route the LAST
        # search took.  The route is decided here, before the call; a
        # Pallas kernel that then fails raises — nothing retries through
        # XLA behind the caller's back
        self.last_use_pallas = use_pallas
        return self._search_impl(queries, nq, k, k_eff, nprobe, chunk, D,
                                 use_pallas, G, U, bins)

    def _publish_scored(self, blocks: int) -> None:
        """What one query of the search now running scores: the whole
        centroid table and the rows of the `blocks` blocks it probes.
        A roofline is computed from these, not from MaxCheck taken on
        trust (benchmark kernel.dense_scan_roofline).  Gauges of the most
        recent search: totals would mix in the build's own searches."""
        metrics.set_gauge("dense.centroids_per_query", self.num_clusters)
        metrics.set_gauge("dense.rows_per_query", blocks * self.cluster_size)

    def _search_impl(self, queries, nq, k, k_eff, nprobe, chunk, D,
                     use_pallas, G=0, U=0, bins=0):
        out_d = np.full((nq, k), np.float32(MAX_DIST), np.float32)
        out_i = np.full((nq, k), -1, np.int32)
        interp = pallas_kernels.interpret()
        dedup = self.replicas > 1
        if nq <= chunk:
            q_pad = query_bucket(nq, chunk)
            g_eff = min(G, q_pad) if G else 0     # buckets are powers of 2
            if g_eff < self._group_floor():
                g_eff = 0                         # tile floor (see search)
            if g_eff != G:
                self.last_effective_group = g_eff
            self._publish_scored(U if g_eff > 1 else nprobe)
            q = queries
            if q_pad != nq:
                q = np.concatenate(
                    [q, np.zeros((q_pad - nq, D), q.dtype)])
            if g_eff > 1:
                d, ids = _dense_search_grouped_kernel(
                    self.data_perm, self.member_ids, self.member_sq,
                    self.centroids, self.cent_sq, self.dead_slot,
                    jnp.asarray(q), jnp.int32(nq), k_eff, nprobe, U, g_eff,
                    int(self.metric), self.base, use_pallas=use_pallas,
                    interpret=interp, dedup=dedup, binned_bins=bins)
            else:
                d, ids = _dense_search_kernel(
                    self.data_perm, self.member_ids, self.member_sq,
                    self.centroids, self.cent_sq, self.dead_slot,
                    jnp.asarray(q), k_eff, nprobe, int(self.metric),
                    self.base, use_pallas=use_pallas, interpret=interp,
                    dedup=dedup, binned_bins=bins)
            with trace.span("index.readback"):
                # the host blocks here until the program has run
                d, ids = np.asarray(d), np.asarray(ids)
            out_d[:, :d.shape[1]] = d[:nq]
            out_i[:, :ids.shape[1]] = ids[:nq]
            return out_d, out_i
        # multi-chunk: ONE device program (lax.map over chunks) — a Python
        # chunk loop would pay a synced host round trip per chunk; this
        # costs ~2 round trips total for any batch size
        m = -(-nq // chunk)
        self._publish_scored(U if G > 1 else nprobe)
        q = queries
        if m * chunk != nq:
            q = np.concatenate(
                [q, np.zeros((m * chunk - nq, D), q.dtype)])
        if G > 1:
            # per-chunk valid counts mask the tail chunk's zero padding out
            # of the union ranking
            valid3 = np.clip(nq - chunk * np.arange(m), 0, chunk)
            d, ids = _dense_search_grouped_chunked(
                self.data_perm, self.member_ids, self.member_sq,
                self.centroids, self.cent_sq, self.dead_slot,
                jnp.asarray(q.reshape(m, chunk, D)),
                jnp.asarray(valid3, np.int32),
                k_eff, nprobe, U, min(G, chunk), int(self.metric),
                self.base, use_pallas=use_pallas,
                interpret=interp, dedup=dedup, binned_bins=bins)
        else:
            d, ids = _dense_search_chunked(
                self.data_perm, self.member_ids, self.member_sq,
                self.centroids, self.cent_sq, self.dead_slot,
                jnp.asarray(q.reshape(m, chunk, D)),
                k_eff, nprobe, int(self.metric), self.base,
                use_pallas=use_pallas, interpret=interp, dedup=dedup,
                binned_bins=bins)
        with trace.span("index.readback"):
            d, ids = np.asarray(d), np.asarray(ids)
        d = d.reshape(m * chunk, -1)
        ids = ids.reshape(m * chunk, -1)
        out_d[:, :d.shape[1]] = d[:nq]
        out_i[:, :ids.shape[1]] = ids[:nq]
        return out_d, out_i
