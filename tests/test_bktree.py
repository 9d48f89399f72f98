"""BKTree build invariants + persistence round-trip.

Mirrors what the reference guarantees structurally (BKTree::BuildTrees,
/root/reference/AnnService/inc/Core/Common/BKTree.h:144-211): every sample
appears exactly once per tree as a node centerid, child ranges partition the
node array, and the on-disk format round-trips.
"""

import io

import numpy as np
import pytest

from sptag_tpu.trees.bktree import BKTree


def _collect_tree_centerids(tree, t):
    start = tree.tree_starts[t]
    end = (tree.tree_starts[t + 1] if t + 1 < len(tree.tree_starts)
           else len(tree.nodes))
    cids = []
    for ni in range(start, end):
        cid = int(tree.nodes["centerid"][ni])
        cids.append(cid)
    return cids


def test_every_sample_is_a_center_exactly_once():
    rng = np.random.default_rng(0)
    n, d = 500, 16
    data = rng.standard_normal((n, d)).astype(np.float32)
    tree = BKTree(tree_number=2, kmeans_k=8, leaf_size=4, samples=200,
                  lloyd_iterations=8, restarts=2)
    tree.build(data, seed=1)

    assert len(tree.tree_starts) == 2
    for t in range(2):
        cids = _collect_tree_centerids(tree, t)
        # root holds the sample count; sentinel holds -1
        assert cids[0] == n
        assert cids[-1] == -1
        samples = sorted(c for c in cids[1:-1] if 0 <= c < n)
        assert samples == list(range(n)), "each sample once per tree"


def test_child_ranges_wellformed():
    rng = np.random.default_rng(3)
    data = rng.standard_normal((300, 8)).astype(np.float32)
    tree = BKTree(tree_number=1, kmeans_k=4, leaf_size=4, samples=100,
                  lloyd_iterations=6, restarts=1)
    tree.build(data, seed=2)
    cs = tree.nodes["childStart"]
    ce = tree.nodes["childEnd"]
    nn = len(tree.nodes)
    internal = np.flatnonzero(cs > 0)
    assert len(internal) > 0
    for ni in internal:
        assert 0 < cs[ni] <= ce[ni] <= nn


def test_duplicate_samples_degenerate_cluster():
    # 40 identical vectors force the all-one-cluster path
    data = np.ones((40, 8), np.float32)
    tree = BKTree(tree_number=1, kmeans_k=4, leaf_size=4, samples=100,
                  lloyd_iterations=4, restarts=1)
    tree.build(data, seed=0)
    # duplicates map to a single retained center
    assert len(tree.sample_center_map) >= 40  # 39 dups + center back-pointer
    centers = {v for k, v in tree.sample_center_map.items() if k >= 0}
    assert len(centers) == 1


def test_save_load_roundtrip():
    rng = np.random.default_rng(5)
    data = rng.standard_normal((200, 12)).astype(np.float32)
    tree = BKTree(tree_number=2, kmeans_k=4, leaf_size=4, samples=64,
                  lloyd_iterations=4, restarts=1)
    tree.build(data, seed=7)
    buf = io.BytesIO()
    tree.save(buf)
    buf.seek(0)
    loaded = BKTree.load(buf)
    np.testing.assert_array_equal(loaded.tree_starts, tree.tree_starts)
    np.testing.assert_array_equal(loaded.nodes, tree.nodes)
    assert loaded.sample_center_map == tree.sample_center_map


def test_collect_pivots():
    rng = np.random.default_rng(9)
    n = 400
    data = rng.standard_normal((n, 8)).astype(np.float32)
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=4, samples=200,
                  lloyd_iterations=6, restarts=1)
    tree.build(data, seed=3)
    piv = tree.collect_pivots(64)
    assert 0 < len(piv) <= 64
    assert np.all((piv >= 0) & (piv < n))
    assert len(np.unique(piv)) == len(piv)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pivots_are_as_dense_where_the_rows_are_as_anywhere(seed):
    """The largest subtree is expanded first: a budget of n / 24 pivots
    leaves no cluster without one and none with many times its share
    (cut level by level at the budget, the last level's pivots all went
    to the first few subtrees: ISSUE 32)."""
    rng = np.random.default_rng(seed)
    clusters, per, dim = 24, 250, 16
    centers = rng.standard_normal((clusters, dim)).astype(np.float32) * 4
    label = np.repeat(np.arange(clusters), per)
    data = centers[label] + rng.standard_normal(
        (clusters * per, dim)).astype(np.float32)
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=1000)
    tree.build(data, seed=seed)
    budget = len(data) // 24
    piv = tree.collect_pivots(budget)
    assert len(piv) == budget == len(np.unique(piv))
    held = np.bincount(label[piv], minlength=clusters)
    assert held.min() >= 1, held
    assert held.max() <= 4 * budget / clusters, held
    # a node's rows: itself and everything under it (the root: every
    # node but the sentinel the reference format ends on)
    size = tree._subtree_sizes()
    assert [int(size[t]) for t in tree.tree_starts] \
        == [len(tree.nodes) - 1]
    # the whole budget's worth and more: every centre, once
    every = tree.collect_pivots(10 * len(data))
    assert len(every) == len(np.unique(every)) <= len(data)
    assert set(piv) <= set(every)
