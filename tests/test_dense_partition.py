"""The dense partition at a size where one Python step a node is minutes
(PR 48): the level-by-level cut against the node-by-node one it replaced,
the placement by distance of the rows the cut leaves out, the block-wise
layout against the plain one, and the tree's span-wise final assignment
against the whole-node one.
"""

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.algo import dense
from sptag_tpu.algo.dense import (DenseTreeSearcher, partition_from_tree,
                                  place_rows)
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.trees import bktree
from sptag_tpu.trees.bktree import BKTree


def _corpus(n, d, seed, clusters=16):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((clusters, d)).astype(np.float32) * 4
    return (centers[rng.integers(0, clusters, n)]
            + rng.standard_normal((n, d)).astype(np.float32))


def _node_by_node(tree, n, target_size):
    """`partition_from_tree` as it was until PR 48, before packing: (the
    raw clusters' center samples, their member sets, the loose samples in
    the order they were met)."""
    nodes = tree.nodes
    cid = nodes["centerid"].astype(np.int64)
    cs = nodes["childStart"].astype(np.int64)
    ce = nodes["childEnd"].astype(np.int64)
    start, end = int(tree.tree_starts[0]), len(nodes)

    def children(ni):
        if cs[ni] >= 0:
            return range(int(cs[ni]), int(ce[ni]))
        if cs[ni] < -1 or (cs[ni] == -1 and ce[ni] > 0):
            return range(int(-cs[ni]), int(ce[ni]))
        return range(0)

    def sample_of(ni):
        if ni == start:
            return -1
        c = int(cid[ni])
        return c if 0 <= c < n else -1

    counts = np.zeros(end - start, np.int64)
    for ni in range(end - 1, start - 1, -1):
        counts[ni - start] = (sample_of(ni) >= 0) + sum(
            counts[ch - start] for ch in children(ni))
    roots, loose, frontier = [], [], [start]
    while frontier:
        nxt = []
        for ni in frontier:
            if counts[ni - start] == 0:
                continue
            kids = children(ni)
            if counts[ni - start] <= target_size or len(kids) == 0:
                roots.append(ni)
            else:
                nxt.extend(kids)
                if sample_of(ni) >= 0:
                    loose.append(sample_of(ni))
        frontier = nxt
    clusters, centers = [], []
    for r in roots:
        members, stack = [], [r]
        while stack:
            ni = stack.pop()
            if sample_of(ni) >= 0:
                members.append(sample_of(ni))
            stack.extend(children(ni))
        clusters.append(set(members))
        centers.append(sample_of(r) if sample_of(r) >= 0 else members[0])
    return centers, clusters, loose


SHAPES = [(800, 12, 5, 8, 8, 64), (2000, 16, 9, 8, 8, 64),
          (6000, 24, 1, 4, 4, 32), (12000, 32, 3, 32, 128, 256)]


@pytest.fixture(scope="module", params=SHAPES,
                ids=[f"{s[0]}x{s[1]}" for s in SHAPES])
def cut(request):
    n, d, seed, kk, leaf, target = request.param
    data = _corpus(n, d, seed)
    tree = BKTree(tree_number=1, kmeans_k=kk, leaf_size=leaf, samples=100)
    tree.build(data)
    return data, tree, target


def test_the_cut_is_the_node_by_node_cut(cut, monkeypatch):
    """Unpacked, with the loose samples left out: the same clusters in
    the same order under the same centers."""
    data, tree, target = cut
    centers, clusters, loose = _node_by_node(tree, len(data), target)
    monkeypatch.setattr(
        dense, "_pack_plan", lambda sizes, c, t: (
            np.arange(len(sizes) + 1), np.asarray(c)))
    got_centers, got = partition_from_tree(tree, len(data), target,
                                           place_loose=False)
    assert [set(c.tolist()) for c in got] == clusters
    assert got_centers.tolist() == centers
    left_out = set(range(len(data))) - set(np.concatenate(got).tolist())
    assert left_out == set(loose) and len(loose) > 0


def test_loose_samples_join_the_smallest_cluster_in_turn(cut):
    """What a caller without the rows gets: every id once, each loose
    sample in the cluster that was smallest when its turn came (the
    first among equals), blocks packed up to the target."""
    data, tree, target = cut
    n = len(data)
    centers, clusters, loose = _node_by_node(tree, n, target)
    for s in loose:
        smallest = min(range(len(clusters)), key=lambda i: len(clusters[i]))
        clusters[smallest].add(s)
    want_centers, want = dense._pack_clusters(
        [np.asarray(sorted(c)) for c in clusters], centers, target)
    got_centers, got = partition_from_tree(tree, n, target)
    assert sorted(np.concatenate(got).tolist()) == list(range(n))
    assert [set(c.tolist()) for c in got] == [set(c.tolist()) for c in want]
    assert got_centers.tolist() == want_centers.tolist()
    assert all(int(c) in set(m.tolist()) for c, m in zip(got_centers, got))


def test_rows_left_out_are_placed_by_distance_where_there_is_room():
    rng = np.random.default_rng(4)
    centers_xy = np.array([[0, 0], [10, 0], [0, 10]], np.float32)
    data = np.concatenate([c + 0.1 * rng.standard_normal((4, 2))
                           for c in centers_xy]
                          + [np.array([[9.5, 0.2], [9.6, 0.1], [9.7, 0.3],
                                       [0.2, 9.9]])]).astype(np.float32)
    clusters = [np.arange(0, 4), np.arange(4, 8), np.arange(8, 12)]
    centers = np.array([0, 4, 8])
    rows = np.array([12, 13, 14, 15])
    # room for two more a cluster: the third row near (10, 0) goes to the
    # next nearest center, (0, 0)
    got = place_rows(data, centers, clusters, rows, 6, DistCalcMethod.L2)
    assert [c.tolist() for c in got] == [[0, 1, 2, 3, 14],
                                         [4, 5, 6, 7, 12, 13],
                                         [8, 9, 10, 11, 15]]
    # nowhere any room: the nearest center regardless
    got = place_rows(data, centers, clusters, rows, 4, DistCalcMethod.L2)
    assert [len(c) for c in got] == [4, 7, 5]
    # cosine ranks by the largest dot product
    got = place_rows(data, centers, clusters, rows[3:], 8,
                     DistCalcMethod.Cosine)
    assert got[2].tolist() == [8, 9, 10, 11, 15]


def test_no_budget_leaves_a_hub_out_of_reach():
    """The center samples above the cut are their regions' medoids: the
    rows most queries have among their nearest.  Placed by distance, a
    budget of every block finds every neighbour; and a fifth of the
    blocks finds nearly all (joined to the smallest block wherever it
    lay, 2 % were out of reach at any budget: PERF.md section 6, PR 48)."""
    data = _corpus(20000, 32, 11, clusters=40)
    queries = data[np.random.default_rng(5).integers(0, len(data), 64)] \
        + 0.5 * np.random.default_rng(6).standard_normal(
            (64, 32)).astype(np.float32)
    index = sp.create_instance("BKT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BuildGraph", "0"), ("BKTKmeansK", "32"),
                        ("BKTLeafSize", "128"), ("DenseClusterSize", "256"),
                        ("MaxCheck", "4096")]:
        index.set_parameter(name, value)
    index.build(data)
    exact = np.argsort(((queries[:, None] - data[None]) ** 2).sum(-1),
                       axis=1)[:, :10]

    def recall(max_check):
        index.set_parameter("MaxCheck", str(max_check))
        _, ids = index.search_batch(queries, 10)
        return np.mean([len(set(a) & set(b)) / 10
                        for a, b in zip(ids.tolist(), exact.tolist())])

    assert recall(4096) >= 0.97
    assert recall(1 << 20) == 1.0
    # every block at most DenseClusterSize rows: placing grew none
    assert index._dense.cluster_size == 256


@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_the_block_wise_layout_is_the_plain_one(dtype, monkeypatch):
    rng = np.random.default_rng(7)
    data = (rng.standard_normal((3000, 24)) * 20).astype(dtype)
    sizes = rng.integers(5, 64, 80)
    order = rng.permutation(3000)[:sizes.sum()]
    clusters = np.split(order, np.cumsum(sizes)[:-1])
    # several spans over several workers, the last one short
    monkeypatch.setattr(dense, "_PACK_BYTES", 7 * 64 * 24 * data.itemsize)
    lay = DenseTreeSearcher.build_layout(data, clusters,
                                         DistCalcMethod.L2)
    C, P = len(clusters), lay["cluster_size"]
    assert P == 64          # the largest cluster (<= 63 rows) to the tile
    assert lay["perm"].shape == (C, P, 24) and lay["perm"].dtype == dtype
    for i, members in enumerate(clusters):
        m = len(members)
        assert (lay["ids"][i, :m] == members).all()
        assert (lay["ids"][i, m:] == -1).all()
        assert (lay["perm"][i, :m] == data[members]).all()
        assert (lay["perm"][i, m:] == 0).all()
        rows = data[members].astype(np.float64)
        np.testing.assert_allclose(lay["sq"][i, :m], (rows ** 2).sum(1),
                                   rtol=1e-6)
        assert (lay["sq"][i, m:] == 0).all()
        np.testing.assert_allclose(lay["cent"][i], rows.mean(0),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lay["cent_sq"],
                               (lay["cent"].astype(np.float64) ** 2).sum(1),
                               rtol=1e-5)


def test_a_node_larger_than_a_device_batch_is_assigned_in_spans(monkeypatch):
    """One node's final assignment as spans of rows (labels side by side,
    counts added, the nearest of the spans' medoids) against the whole
    node as one batch."""
    import jax

    from sptag_tpu.ops import kmeans as km

    data = _corpus(5000, 16, 21)
    ids = np.random.default_rng(8).permutation(5000)[:4500].astype(np.int64)
    K = 8
    sub = data[ids[:1024]][None]
    centers, _ = km.kmeans_fit(sub, np.ones((1, 1024), bool),
                               jax.random.PRNGKey(0), K, 8, 2, 0, 1)
    full = np.zeros((1, 8192, 16), np.float32)
    full[0, :4500] = data[ids]
    labels, counts, pos = km.kmeans_final_assign(
        full, np.arange(8192)[None] < 4500, centers, K, 0, 1)
    tree = BKTree(tree_number=1, kmeans_k=K, leaf_size=32, samples=200)
    monkeypatch.setattr(bktree, "_MAX_BATCH_ROWS", 1024)     # five spans
    got_labels, got_counts, got_medoids = tree._assign_in_spans(
        data, ids, centers, K)
    assert (got_labels == np.asarray(labels)[0, :4500]).all()
    assert (got_counts == np.asarray(counts)[0]).all()
    assert got_counts.sum() == 4500 and (got_counts > 0).all()
    assert (got_medoids == ids[np.asarray(pos)[0]]).all()
    # and a tree built that way holds every row once
    tree.build(data)
    cid = tree.nodes["centerid"]
    assert sorted(cid[1:-1].tolist()) == list(range(5000))
