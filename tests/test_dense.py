"""Dense tree-partition search tests (TPU-first fast path, algo/dense.py)."""

import jax
import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.algo import dense
from sptag_tpu.algo.dense import DenseTreeSearcher, partition_from_tree
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.trees.bktree import BKTree


def _corpus(n=800, d=12, seed=5):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((16, d)).astype(np.float32) * 4
    data = (centers[rng.integers(0, 16, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    return data


def test_partition_covers_every_id_once():
    data = _corpus()
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    centers, clusters = partition_from_tree(tree, len(data), 64)
    all_ids = np.concatenate(clusters)
    assert sorted(all_ids.tolist()) == list(range(len(data)))
    assert len(centers) == len(clusters)
    # clusters respect the target within the k-means branching slack
    assert max(len(c) for c in clusters) <= 64 + 8


def test_dense_search_recall():
    data = _corpus()
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    centers, clusters = partition_from_tree(tree, len(data), 64)
    searcher = DenseTreeSearcher(data, centers, clusters, None,
                                 DistCalcMethod.L2, 1)
    rng = np.random.default_rng(0)
    queries = data[rng.integers(0, len(data), 32)] \
        + rng.standard_normal((32, data.shape[1])).astype(np.float32) * 0.05
    d, ids = searcher.search(queries, k=10, max_check=512)

    diff = queries[:, None, :] - data[None, :, :]
    exact = np.sum(diff * diff, axis=-1)
    truth = np.argsort(exact, axis=1)[:, :10]
    recall = np.mean([len(set(ids[q].tolist()) & set(truth[q].tolist())) / 10
                      for q in range(32)])
    assert recall >= 0.95, recall
    assert np.all(np.diff(d, axis=1) >= -1e-4)


def test_dense_search_excludes_deleted():
    data = _corpus(n=300)
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    centers, clusters = partition_from_tree(tree, len(data), 64)
    deleted = np.zeros(len(data), bool)
    deleted[:10] = True
    searcher = DenseTreeSearcher(data, centers, clusters, deleted,
                                 DistCalcMethod.L2, 1)
    d, ids = searcher.search(data[:10], k=3, max_check=300)
    assert not np.isin(ids, np.arange(10)).any()


def test_bkt_dense_after_add_covers_new_rows():
    data = _corpus(n=400)
    index = sp.create_instance("BKT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BKTKmeansK", "8"), ("TPTNumber", "4"),
                        ("TPTLeafSize", "64"), ("NeighborhoodSize", "16"),
                        ("CEF", "64"), ("AddCEF", "32"),
                        ("MaxCheckForRefineGraph", "128"),
                        ("MaxCheck", "512"), ("RefineIterations", "1"),
                        ("Samples", "100"), ("SearchMode", "dense"),
                        ("DenseClusterSize", "64"),
                        ("AddCountForRebuild", "1000")]:
        assert index.set_parameter(name, value)
    assert index.build(data) == sp.ErrorCode.Success
    rng = np.random.default_rng(3)
    new = data[:8] + rng.standard_normal((8, 12)).astype(np.float32) * 0.01
    # AddCountForRebuild=1000 -> tree NOT rebuilt; dense path must still
    # cover the appended rows via nearest-centroid assignment
    assert index.add(new) == sp.ErrorCode.Success
    _, ids = index.search_batch(new, 2)
    hit = np.mean([(400 + q) in ids[q] for q in range(8)])
    assert hit >= 0.9, (hit, ids)


def test_dense_grouped_probing():
    """Query-grouped probing (DenseQueryGroup) must match or beat the
    per-query kernel's recall at the same MaxCheck (each query is scored
    against the group union's U >= nprobe blocks) and handle non-multiple
    batch sizes via padding."""
    data = _corpus(n=2000, d=16, seed=9)
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    centers, clusters = partition_from_tree(tree, len(data), 64)
    searcher = DenseTreeSearcher(data, centers, clusters, None,
                                 DistCalcMethod.L2, 1)
    rng = np.random.default_rng(1)
    # dense enough over the ~31 blocks that the adaptive cap keeps G >= the
    # f32 tile floor (8); deliberately not a padding bucket so the padding
    # mask (nq_valid) is exercised too
    nq = 131
    queries = data[rng.integers(0, len(data), nq)] \
        + rng.standard_normal((nq, 16)).astype(np.float32) * 0.05

    exact = ((queries ** 2).sum(1)[:, None] + (data ** 2).sum(1)[None, :]
             - 2.0 * (queries @ data.T))
    truth = np.argsort(exact, axis=1)[:, :10]

    def recall(ids):
        return np.mean([len(set(ids[q].tolist()) & set(truth[q].tolist()))
                        / 10 for q in range(nq)])

    d0, i0 = searcher.search(queries, k=10, max_check=256)
    # union_factor=8 drives U to the full block count (~31 here), so every
    # query is scored against EVERY block its ungrouped probe set covered
    # (and more): recall can only match or improve, structurally
    d1, i1 = searcher.search(queries, k=10, max_check=256,
                             group=8, union_factor=8)
    assert np.all(np.diff(d1, axis=1) >= -1e-4)
    r0, r1 = recall(i0), recall(i1)
    assert r1 >= r0 - 1e-9, (r0, r1)
    # the tighter default union (factor 2) trades a little per-query probe
    # coverage for speed — recall must stay in the same band
    _, i3 = searcher.search(queries, k=10, max_check=256,
                            group=8, union_factor=2)
    assert recall(i3) >= r0 - 0.05, (r0, recall(i3))
    # self-queries through the GROUPED path (batch dense enough that the
    # adaptive cap keeps G=8).  Only a query's rank-0 block is guaranteed
    # to survive the union cut, and a row's own block is not always its
    # nearest-centroid block — assert a high hit RATE, not exactness
    d_self, i_self = searcher.search(data[:128], k=1, group=8,
                                     max_check=256, union_factor=4)
    assert searcher.last_effective_group == 8
    hit = np.mean(i_self[:, 0] == np.arange(128))
    assert hit >= 0.95, (hit, i_self[:, 0])
    # a sparse 3-query batch demotes grouping (adaptive cap below the tile
    # floor) and still returns correct shapes through the per-query kernel
    d2, i2 = searcher.search(queries[:3], k=5, group=64, union_factor=2)
    assert searcher.last_effective_group == 0
    assert i2.shape == (3, 5) and (i2[:, 0] >= 0).all()
    # oversized union factor WITH grouping active: U is clamped to the
    # rank buffer's width (G*nprobe) and the cluster count — no top_k crash
    d4, i4 = searcher.search(queries, k=5, max_check=256,
                             group=16, union_factor=50)
    assert searcher.last_effective_group > 1
    assert i4.shape == (nq, 5) and (i4[:, 0] >= 0).all()


def test_dense_grouped_power_of_two_validation():
    data = _corpus(n=300)
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    centers, clusters = partition_from_tree(tree, len(data), 64)
    searcher = DenseTreeSearcher(data, centers, clusters, None,
                                 DistCalcMethod.L2, 1)
    import pytest

    with pytest.raises(ValueError):
        searcher.search(data[:4], k=2, group=12)


def test_dense_replicas_closure_assignment():
    """DenseReplicas=2 packs boundary rows into their nearest other block
    (capped), improving recall at fixed MaxCheck without duplicate ids in
    results."""
    data = _corpus(n=3000, d=24)
    truth_d = (data ** 2).sum(1)[None, :] - 2.0 * (data[:64] @ data.T)
    truth = np.argsort(truth_d, axis=1)[:, :10]

    def build(reps):
        index = sp.create_instance("BKT", "Float")
        for name, value in [("DistCalcMethod", "L2"), ("BKTKmeansK", "8"),
                            ("TPTNumber", "2"), ("TPTLeafSize", "100"),
                            ("NeighborhoodSize", "8"), ("CEF", "32"),
                            ("MaxCheckForRefineGraph", "64"),
                            ("RefineIterations", "1"), ("Samples", "100"),
                            ("DenseClusterSize", "64"),
                            ("DenseReplicas", str(reps)),
                            ("MaxCheck", "256")]:
            index.set_parameter(name, value)
        assert index.build(data) == sp.ErrorCode.Success
        return index

    def recall(index):
        _, ids = index.search_batch(data[:64], 10)
        for row in ids:
            real = [x for x in row if x >= 0]
            assert len(real) == len(set(real)), row    # dedup holds
        return np.mean([len(set(ids[i]) & set(truth[i])) / 10
                        for i in range(64)])

    i1, i2 = build(1), build(2)
    r1, r2 = recall(i1), recall(i2)
    # the recall effect is corpus-dependent (P grows, nprobe shrinks, so
    # FEWER distinct blocks are probed at the same budget) — assert sane
    # floors and the mechanical invariants, not universal improvement
    assert r1 >= 0.9 and r2 >= 0.85, (r1, r2)
    # capped growth: padded block size at most ~2x the replica-free one
    d1 = i1._get_dense()
    d2 = i2._get_dense()
    assert d2.cluster_size <= 2 * d1.cluster_size + 32, (
        d1.cluster_size, d2.cluster_size)
    # replicas really are present: total occupied slots grow
    occ1 = int(np.asarray((d1.member_ids >= 0).sum()))
    occ2 = int(np.asarray((d2.member_ids >= 0).sum()))
    assert occ2 > occ1, (occ1, occ2)


def test_dense_param_change_after_search_takes_effect():
    """Dense-affecting params set AFTER a dense search must invalidate the
    materialized dense snapshot (VERDICT r4 item 3): before the fix,
    DenseReplicas/DenseClusterSize changes silently no-opped until the
    next unrelated mutation (the same silent-no-op class the beam engine
    params had — reference SetParameter semantics re-read config live,
    inc/Core/VectorIndex.h SetParameter)."""
    data = _corpus(n=3000, d=24)
    index = sp.create_instance("BKT", "Float")
    for name, value in [("DistCalcMethod", "L2"), ("BKTKmeansK", "8"),
                        ("TPTNumber", "2"), ("TPTLeafSize", "100"),
                        ("NeighborhoodSize", "8"), ("CEF", "32"),
                        ("MaxCheckForRefineGraph", "64"),
                        ("RefineIterations", "1"), ("Samples", "100"),
                        ("DenseClusterSize", "64"),
                        ("SearchMode", "dense"),
                        ("MaxCheck", "256")]:
        assert index.set_parameter(name, value)
    assert index.build(data) == sp.ErrorCode.Success

    _, ids1 = index.search_batch(data[:32], 10)
    snap1 = index._get_dense()
    assert snap1.replicas == 1
    occ1 = int(np.asarray((snap1.member_ids >= 0).sum()))

    # post-search knob change: snapshot must be dropped and rebuilt
    assert index.set_parameter("DenseReplicas", "2")
    assert index._dense is None, "DenseReplicas change must drop snapshot"
    _, ids2 = index.search_batch(data[:32], 10)
    snap2 = index._get_dense()
    assert snap2 is not snap1
    assert snap2.replicas == 2
    occ2 = int(np.asarray((snap2.member_ids >= 0).sum()))
    assert occ2 > occ1, (occ1, occ2)

    # DenseClusterSize is baked into the partition: same invalidation
    assert index.set_parameter("DenseClusterSize", "128")
    assert index._dense is None
    _, _ = index.search_batch(data[:32], 10)
    snap3 = index._get_dense()
    assert snap3 is not snap2
    assert snap3.cluster_size != snap2.cluster_size or (
        snap3.centers.shape != snap2.centers.shape)

    # live-read knobs need NO invalidation: setting them must not drop
    # the snapshot (rebuilds are expensive; only baked params pay it)
    assert index.set_parameter("DenseQueryGroup", "8")
    assert index._dense is snap3


# ---------------------------------------------------------------------------
# a candidate's tombstone arrives with its block (PR 49): the per-slot dead
# table against the per-id formula `deleted[ids] | ids < 0`
# ---------------------------------------------------------------------------

def _blocks(data, target=64):
    tree = BKTree(tree_number=1, kmeans_k=8, leaf_size=8, samples=100)
    tree.build(data)
    return partition_from_tree(tree, len(data), target)


def _rows_of_block(searcher, block):
    ids = np.asarray(searcher.member_ids)[block]
    return ids[ids >= 0]


def _by_id_formula(plain, deleted, queries, k, **route):
    """What the per-id formula answers: EVERY candidate the route scores
    for a query, nearest first (a tombstone-free searcher over the same
    layout, asked for as many answers as it has candidates), masked in
    numpy by `deleted[ids] | ids < 0`, the first k kept.  The distances
    are the program's own float32 values, so the comparison is bit for
    bit."""
    width = plain.num_clusters * plain.cluster_size
    d_all, i_all = plain._scan_topk(queries, width, **route)
    out_d = np.full((len(queries), k), dense.MAX_DIST, np.float32)
    out_i = np.full((len(queries), k), -1, np.int32)
    for q, (d_q, i_q) in enumerate(zip(d_all, i_all)):
        live = ~(deleted[np.maximum(i_q, 0)] | (i_q < 0))
        got = min(k, int(live.sum()))
        out_d[q, :got] = d_q[live][:got]
        out_i[q, :got] = i_q[live][:got]
    return out_d, out_i


def _f32_case(**kw):
    def make():
        data = _corpus(n=2000, d=16, seed=9)
        rng = np.random.default_rng(1)
        queries = data[rng.integers(0, len(data), 131)] \
            + rng.standard_normal((131, 16)).astype(np.float32) * 0.05
        return data, queries, DistCalcMethod.L2, 1
    return dict(make=make, **kw)


def _int8_case(**kw):
    def make():
        rng = np.random.default_rng(4)
        data = np.clip(_corpus(n=1500, d=16, seed=2) * 12, -127, 127
                       ).astype(np.int8)
        return (data, data[rng.integers(0, len(data), 40)],
                DistCalcMethod.L2, 127)
    return dict(make=make, **kw)


def _random_tenth(searcher, rng):
    return rng.random(searcher.n) < 0.1


def _one_whole_block(searcher, rng):
    mask = np.zeros(searcher.n, bool)
    # the block most queries of the corpus rank first is as good as any
    mask[_rows_of_block(searcher, 3)] = True
    return mask


CASES = {
    "no_tombstones": _f32_case(mask=lambda s, rng: np.zeros(s.n, bool)),
    "several_blocks": _f32_case(mask=_random_tenth),
    "one_block_all_dead": _f32_case(mask=_one_whole_block),
    "replicas_2_dedup": _f32_case(mask=_random_tenth, replicas=2),
    "grouped": _f32_case(mask=_random_tenth, route=dict(group=8),
                         group=8),
    "chunked": _f32_case(mask=_random_tenth, chunk=16),
    "grouped_chunked": _f32_case(mask=_random_tenth, route=dict(group=8),
                                 group=8, chunk=32),
    "int8_blocks": _int8_case(mask=_random_tenth),
    "cascade": _f32_case(mask=_random_tenth,
                         cascade={"tier": "device", "rerank_budget": 0}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tombstones_by_slot_answer_as_the_per_id_formula(monkeypatch, case):
    """ids AND float32 distances, bit for bit, through every route of the
    dense program: the new per-slot table and the parent's per-id gather
    are the same set of dead candidates."""
    spec = CASES[case]
    data, queries, metric, base = spec["make"]()
    centers, clusters = _blocks(data)
    kw = dict(replicas=spec.get("replicas", 1),
              cascade_cfg=spec.get("cascade"))
    plain = DenseTreeSearcher(data, centers, clusters, None, metric, base,
                              **kw)
    deleted = spec["mask"](plain, np.random.default_rng(7))
    marked = DenseTreeSearcher(data, centers, clusters, deleted, metric,
                               base, **kw)
    np.testing.assert_array_equal(np.asarray(marked.member_ids),
                                  np.asarray(plain.member_ids))
    # the table itself is the formula over the layout's ids
    mids = np.asarray(plain.member_ids)
    np.testing.assert_array_equal(
        np.asarray(marked.dead_slot),
        deleted[np.maximum(mids, 0)] | (mids < 0))
    if spec.get("cascade"):
        # the cascade's int8 blocks hold x / scale (the scan's queries too)
        queries = queries / np.float32(plain.scale)
    route = dict(max_check=256, **spec.get("route", {}))
    if "chunk" in spec:
        per_query = 4 * plain.cluster_size * data.shape[1] * 4
        # grouped: U = 2 x nprobe blocks a group of 8, reckoned per query
        if spec.get("group"):
            per_query = 2 * per_query // spec["group"]
        monkeypatch.setattr(dense, "gather_budget",
                            lambda: spec["chunk"] * per_query)
        twin = ("_dense_search_grouped_chunked" if spec.get("group")
                else "_dense_search_chunked")
        chunked, runs = getattr(dense, twin), []
        monkeypatch.setattr(
            dense, twin,
            lambda *a, **k: runs.append(a[6].shape) or chunked(*a, **k))
    want_d, want_i = _by_id_formula(plain, deleted, queries, 10, **route)
    got_d, got_i = marked._scan_topk(queries, 10, **route)
    assert marked.last_effective_group == spec.get("group", 0)
    if "chunk" in spec:         # both searchers took the lax.map twin
        assert len(runs) == 2 and runs[1][1] == spec["chunk"], runs
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d.view(np.uint32),
                                  want_d.view(np.uint32))
    assert not deleted[got_i[got_i >= 0]].any()
    assert (got_i[:, 0] >= 0).all()
    if spec.get("cascade"):
        # and the public search re-ranks a tombstone-free shortlist
        _, ids = marked.search(queries * np.float32(plain.scale), 10,
                               max_check=256)
        assert (ids[:, 0] >= 0).all()
        assert not deleted[ids[ids >= 0]].any()


@pytest.mark.parametrize("replicas", [1, 2])
def test_set_deleted_after_placement_and_a_later_smaller_mask(replicas):
    """`set_deleted` takes the FULL row mask each time: the next search
    honours it, and a mask with fewer bits (a refine dropped tombstones)
    brings the rows back, in every block that holds a replica."""
    data = _corpus(n=1200, d=16, seed=11)
    centers, clusters = _blocks(data)
    searcher = DenseTreeSearcher(data, centers, clusters, None,
                                 DistCalcMethod.L2, 1, replicas=replicas)
    queries = data[:24]      # every block probed: a row finds itself
    d0, i0 = searcher.search(queries, 5, max_check=4096)
    assert (i0[:, 0] == np.arange(24)).all()
    mask = np.zeros(len(data), bool)
    mask[:24] = True
    searcher.set_deleted(mask)
    mids = np.asarray(searcher.member_ids)
    np.testing.assert_array_equal(np.asarray(searcher.dead_slot),
                                  (mids < 0) | ((mids >= 0) & (mids < 24)))
    _, i1 = searcher.search(queries, 5, max_check=4096)
    assert not np.isin(i1, np.arange(24)).any()
    mask[:12] = False                       # fewer bits than the last
    searcher.set_deleted(mask)
    _, i2 = searcher.search(queries, 5, max_check=4096)
    assert (i2[:12, 0] == np.arange(12)).all()
    assert not np.isin(i2, np.arange(12, 24)).any()
    searcher.set_deleted(np.zeros(len(data), bool))
    d3, i3 = searcher.search(queries, 5, max_check=4096)
    np.testing.assert_array_equal(i3, i0)
    np.testing.assert_array_equal(d3.view(np.uint32), d0.view(np.uint32))


def _gathers(jaxpr):
    """Every `gather` equation of a jaxpr, the nested ones (pjit, while,
    cond, custom calls) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


@pytest.mark.parametrize("grouped", [False, True])
def test_the_dense_programs_fetch_no_tombstone_by_candidate_id(grouped):
    """Structural guard: the tombstones reach the program as the (C, P)
    slot table and leave it by whole block rows - no gather reads a
    rank-1 mask, and none fetches one element for each of the
    Q x nprobe x P candidates (8.4M a batch in `bkt_deep10m.saturate`)."""
    C, P, D, Q, nprobe, G = 12, 16, 16, 16, 2, 8
    s = jax.ShapeDtypeStruct
    args = (s((C, P, D), np.float32), s((C, P), np.int32),
            s((C, P), np.float32), s((C, D), np.float32),
            s((C,), np.float32), s((C, P), np.bool_),
            s((Q, D), np.float32))
    if grouped:
        U = 2 * nprobe
        closed = jax.make_jaxpr(
            lambda *a: dense._dense_search_grouped_kernel(
                *a, k=5, nprobe=nprobe, U=U, G=G,
                metric=int(DistCalcMethod.L2), base=1))(
                    *args, s((), np.int32))
        candidates, fetches = Q * U * P, (Q // G) * U
    else:
        closed = jax.make_jaxpr(
            lambda *a: dense._dense_search_kernel(
                *a, k=5, nprobe=nprobe, metric=int(DistCalcMethod.L2),
                base=1))(*args)
        candidates, fetches = Q * nprobe * P, Q * nprobe
    gathers = list(_gathers(closed.jaxpr))
    of_masks = [e for e in gathers
                if e.invars[0].aval.dtype == np.bool_]
    assert of_masks, "the slot table is fetched somewhere"
    for eqn in of_masks:
        operand = eqn.invars[0].aval
        assert operand.shape == (C, P)
        assert tuple(eqn.params["slice_sizes"]) == (1, P)
        assert int(np.prod(eqn.outvars[0].aval.shape)) == fetches * P
    for eqn in gathers:
        if int(np.prod(eqn.params["slice_sizes"])) == 1:
            assert int(np.prod(eqn.outvars[0].aval.shape)) < candidates
