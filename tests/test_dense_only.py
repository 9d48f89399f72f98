"""Dense-only build (BuildGraph=0): a framework extension that skips the
RNG graph so the index serves the MXU partition scan alone.

The reference always builds its graph (BuildIndex, BKTIndex.cpp:279-306);
BuildGraph=0 exists for dense-mode-only deployments where the graph's
TPT + refine passes are pure build cost (the partition scan never reads
it) — it is what lets one chip build and serve 10M rows inside a run
(benchmark cell `bkt_deep10m.saturate`; the served path of such a folder
is tests/test_dense_only_served.py).
"""

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.utils import metrics


def _corpus(n=3000, d=32, nq=64, seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((32, d)).astype(np.float32) * 3.0
    data = (centers[rng.integers(0, 32, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    queries = (centers[rng.integers(0, 32, nq)]
               + rng.standard_normal((nq, d)).astype(np.float32))
    return data, queries


def _truth(data, queries, k):
    d = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    return np.argsort(d, axis=1)[:, :k]


def _build(data, **params):
    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, val in dict({"BuildGraph": "0", "BKTLeafSize": "64",
                           "DenseClusterSize": "128",
                           "MaxCheck": "1024"}, **params).items():
        idx.set_parameter(name, str(val))
    idx.build(data)
    return idx


def test_dense_only_build_and_search():
    data, queries = _corpus()
    idx = _build(data)
    truth = _truth(data, queries, 10)
    _, ids = idx.search_batch(queries, 10)
    recall = np.mean([len(set(ids[i]) & set(truth[i])) / 10
                      for i in range(len(queries))])
    assert recall > 0.9, recall
    # no graph was built: the adjacency is all sentinels
    assert (idx._graph.graph == -1).all()


def test_dense_only_beam_refuses():
    data, _ = _corpus(n=500, nq=1)
    idx = _build(data)
    idx.set_parameter("SearchMode", "beam")
    with pytest.raises(RuntimeError, match="BuildGraph=0"):
        idx.search_batch(data[:4], 5)


def test_dense_only_save_load_roundtrip(tmp_path):
    data, queries = _corpus(n=2000)
    idx = _build(data)
    folder = str(tmp_path / "dense_only")
    idx.save_index(folder)
    loaded = sp.load_index(folder)
    assert loaded.params.build_graph == 0
    d0, i0 = idx.search_batch(queries, 10)
    d1, i1 = loaded.search_batch(queries, 10)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(d0, d1, rtol=1e-6)


@pytest.mark.slow   # 8-device mesh build (tiered suite, ISSUE 6)
def test_dense_only_sharded_mesh():
    """BuildGraph=0 flows through the mesh build: dense search works over
    8 shards, beam refuses — the 8-shard dense-only program is exactly
    BASELINE config 3's topology (one chip's dense-only form is measured
    by the benchmark cell `bkt_deep10m.saturate`)."""
    from sptag_tpu.parallel.sharded import ShardedBKTIndex

    data, queries = _corpus(n=4000)
    truth = _truth(data, queries, 10)
    idx = ShardedBKTIndex.build(
        data, dense=True,
        params={"BuildGraph": "0", "BKTLeafSize": "64",
                "DenseClusterSize": "128", "MaxCheck": "1024"})
    _, ids = idx.search_dense(queries, 10)
    recall = np.mean([len(set(ids[i]) & set(truth[i])) / 10
                      for i in range(len(queries))])
    assert recall > 0.85, recall
    with pytest.raises(RuntimeError, match="BuildGraph=0"):
        idx.search(queries[:4], 5)


def test_dense_only_add_delete():
    data, queries = _corpus(n=2000)
    idx = _build(data)
    extra, _ = _corpus(n=64, seed=9)
    begin = idx.num_samples
    idx.add(extra)
    assert idx.num_samples == begin + 64
    # appended rows are reachable through nearest-center assignment
    _, ids = idx.search_batch(extra[:8], 3)
    found = set(ids.ravel().tolist())
    assert any(v >= begin for v in found)
    # delete-by-content (exact match) tombstones the row out of results
    victim = int(ids[0, 0])
    assert idx.delete(idx.get_sample(victim)[None, :]) == sp.ErrorCode.Success
    _, ids2 = idx.search_batch(extra[:8], 3)
    assert victim not in set(ids2.ravel().tolist())


def test_tombstone_table_is_rebuilt_once_a_swap_and_counts_its_dead():
    """The per-slot dead table (PR 49): computed once where the layout is
    placed and once a delete-then-search, never a search."""
    data, queries = _corpus(n=2000)
    idx = _build(data)
    metrics.reset()
    idx.search_batch(queries, 10)                 # places the layout
    assert metrics.counter_value("dense.tombstone_rebuilds") == 1
    assert metrics.gauge_value("dense.dead_slots") == 0
    idx.search_batch(queries, 10)
    assert metrics.counter_value("dense.tombstone_rebuilds") == 1
    victims = [5, 700, 1999]
    # by content, one call: its own search runs before any row is marked
    assert idx.delete_rows(data[victims]) == (sp.ErrorCode.Success, 3)
    assert metrics.counter_value("dense.tombstone_rebuilds") == 1
    _, ids = idx.search_batch(data[victims], 3)   # ONE swap for the three
    assert not np.isin(ids, victims).any()
    assert metrics.counter_value("dense.tombstone_rebuilds") == 2
    assert metrics.gauge_value("dense.dead_slots") == len(victims)
    idx.search_batch(queries, 10)
    assert metrics.counter_value("dense.tombstone_rebuilds") == 2
