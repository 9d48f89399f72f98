"""Graph construction tests: TPT partition, candidate generation, RNG prune.

Models the reference's graph-quality checks (GraphAccuracyEstimation,
RelativeNeighborhoodGraph.h:73-112) plus brute-force assertions the reference
lacks (SURVEY.md §4 implication)."""

import jax.numpy as jnp
import numpy as np
import pytest

from sptag_tpu.graph.rng import RelativeNeighborhoodGraph
from sptag_tpu.graph.tptree import tpt_partition
from sptag_tpu.ops import graph as graph_ops


def _corpus(n=600, d=16, seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((8, d)).astype(np.float32) * 5
    data = (centers[rng.integers(0, 8, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    return data


def test_tpt_partition_covers_all_ids_once():
    data = _corpus()
    rng = np.random.default_rng(0)
    leaves = tpt_partition(data, leaf_size=64, top_dims=5, samples=100,
                           rng=rng)
    all_ids = np.concatenate(leaves)
    assert len(all_ids) == len(data)
    assert len(np.unique(all_ids)) == len(data)
    assert max(len(leaf) for leaf in leaves) <= 64
    # median splits keep leaves near-uniform
    sizes = [len(leaf) for leaf in leaves]
    assert max(sizes) - min(sizes) <= 1


def test_merge_candidates_dedupes_and_sorts():
    cand_ids = jnp.asarray(np.array([[3, 5, -1]], np.int32))
    cand_d = jnp.asarray(np.array([[1.0, 2.0, 3.4e38]], np.float32))
    new_ids = jnp.asarray(np.array([[5, 7, 2]], np.int32))
    new_d = jnp.asarray(np.array([[2.0, 0.5, 1.5]], np.float32))
    ids, d = graph_ops.merge_candidates(cand_ids, cand_d, new_ids, new_d)
    ids, d = np.asarray(ids), np.asarray(d)
    assert ids[0].tolist() == [7, 3, 2]
    assert np.allclose(d[0], [0.5, 1.0, 1.5])


def test_rng_select_prunes_occluded():
    # node at origin; candidates: a at d=1, b right next to a (occluded by a),
    # c far on the other side (kept).  b comes back as FILL after the RNG
    # set, so the kept-first order is [a, c, b].
    node = np.zeros((1, 2), np.float32)
    a = np.array([1.0, 0.0])
    b = np.array([1.1, 0.0])       # dist(a,b)=0.01 <= dist(node,b)=1.21
    c = np.array([-2.0, 0.0])
    cand = np.stack([a, b, c])[None].astype(np.float32)
    d = np.array([[1.0, 1.21, 4.0]], np.float32)
    valid = np.ones((1, 3), bool)
    keep = np.asarray(graph_ops.rng_select(
        jnp.asarray(node), jnp.asarray(cand), jnp.asarray(d),
        jnp.asarray(valid), 3, 0, 1))
    assert keep[0].tolist() == [0, 2, 1]
    # with m=2 the fill never displaces an RNG-kept candidate
    keep2 = np.asarray(graph_ops.rng_select(
        jnp.asarray(node), jnp.asarray(cand), jnp.asarray(d),
        jnp.asarray(valid), 2, 0, 1))
    assert keep2[0].tolist() == [0, 2]


def test_rng_select_matches_scalar_reference():
    """The slot-major kernel must match a straightforward scalar
    implementation of the RNG rule (RelativeNeighborhoodGraph.h:18-35 plus
    this framework's fill-occluded-slots departure) on random inputs,
    including invalid candidates and rows that exhaust before m keeps."""
    rng = np.random.default_rng(11)
    B, C, D, m = 17, 90, 8, 12
    nodes = rng.standard_normal((B, D)).astype(np.float32)
    cand = rng.standard_normal((B, C, D)).astype(np.float32)
    d = ((cand - nodes[:, None, :]) ** 2).sum(-1).astype(np.float32)
    order = np.argsort(d, axis=1)
    cand = np.take_along_axis(cand, order[:, :, None], axis=1)
    d = np.take_along_axis(d, order, axis=1)
    valid = rng.random((B, C)) > 0.1

    keep = np.asarray(graph_ops.rng_select(
        jnp.asarray(nodes), jnp.asarray(cand), jnp.asarray(d),
        jnp.asarray(valid), m, 0, 1))

    for b in range(B):
        kept = []
        for j in range(C):
            if not valid[b, j] or len(kept) >= m:
                continue
            occ = any(((cand[b, g] - cand[b, j]) ** 2).sum() <= d[b, j]
                      for g in kept)
            if not occ:
                kept.append(j)
        fill = [j for j in range(C)
                if valid[b, j] and j not in kept][:m - len(kept)]
        want = kept + fill + [-1] * (m - len(kept) - len(fill))
        assert keep[b].tolist() == want, (b, keep[b].tolist(), want)


def test_candidates_find_true_neighbors():
    data = _corpus(n=400)
    g = RelativeNeighborhoodGraph(neighborhood_size=8, tpt_number=6,
                                  tpt_leaf_size=64, neighborhood_scale=2,
                                  tpt_samples=100)
    cand_ids, cand_d = g.build_candidates(data, metric=0, base=1, seed=5)
    assert cand_ids.shape == (400, 16)
    # ascending distances, no self, no duplicates
    for row in range(0, 400, 37):
        ids = cand_ids[row][cand_ids[row] >= 0]
        assert row not in ids
        assert len(np.unique(ids)) == len(ids)
        d = cand_d[row][cand_ids[row] >= 0]
        assert np.all(np.diff(d) >= 0)
    # recall of candidate lists vs exact 5-NN
    diff = data[:, None, :] - data[None, :, :]
    exact = np.sum(diff * diff, axis=-1)
    np.fill_diagonal(exact, np.inf)
    truth = np.argsort(exact, axis=1)[:, :5]
    hits = np.mean([len(set(cand_ids[i].tolist())
                        & set(truth[i].tolist())) / 5
                    for i in range(400)])
    assert hits > 0.9, hits


def test_full_build_accuracy():
    data = _corpus(n=400)
    g = RelativeNeighborhoodGraph(neighborhood_size=8, tpt_number=6,
                                  tpt_leaf_size=64, neighborhood_scale=2,
                                  refine_iterations=1, cef=32,
                                  tpt_samples=100)
    g.build(data, metric=0, base=1, search_fn_factory=None, seed=5)
    assert g.graph.shape == (400, 8)
    acc = g.accuracy_estimation(data, metric=0, base=1, samples=50)
    assert acc > 0.5, acc


def test_refine_accuracy_guard_rolls_back_degrading_pass(caplog):
    """Round-5 guardrail (measured at 10M): a refine
    pass whose search returns garbage must be rolled back instead of
    replacing the TPT candidate edges."""
    import logging

    data = _corpus(n=400)
    bad = np.random.default_rng(3)

    def bad_factory(graph, final):
        # budget-starved refine stand-in: near-random neighbor ids
        def fn(queries, k):
            ids = bad.integers(0, data.shape[0], (queries.shape[0], k))
            d = bad.random((queries.shape[0], k)).astype(np.float32)
            return d, ids
        return fn

    kw = dict(neighborhood_size=8, tpt_number=6, tpt_leaf_size=64,
              neighborhood_scale=2, refine_iterations=1, cef=32,
              tpt_samples=100)
    g_on = RelativeNeighborhoodGraph(refine_accuracy_guard=True, **kw)
    with caplog.at_level(logging.WARNING, logger="sptag_tpu.graph.rng"):
        g_on.build(data, metric=0, base=1, search_fn_factory=bad_factory,
                   seed=5)
    assert any("DEGRADED" in r.message for r in caplog.records)
    assert g_on.graph.shape == (400, 8)        # rollback re-narrowed to m

    g_off = RelativeNeighborhoodGraph(refine_accuracy_guard=False, **kw)
    g_off.build(data, metric=0, base=1, search_fn_factory=bad_factory,
                seed=5)
    acc_on = g_on.accuracy_estimation(data, metric=0, base=1, samples=50)
    acc_off = g_off.accuracy_estimation(data, metric=0, base=1, samples=50)
    assert acc_on > acc_off + 0.02, (acc_on, acc_off)
