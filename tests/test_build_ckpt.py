"""Resumable-build checkpoints (utils/build_ckpt.py).

The reference build restarts from scratch on any failure (BuildIndex,
reference src/Core/BKT/BKTIndex.cpp:279-306 — cheap on a local CPU).  The
TPU build's remote backend can die mid-build, so the pipeline checkpoints
each stage; these tests pin:

* a checkpointed build equals a plain build (same stages, same stream);
* an interrupted build resumes WITHOUT re-running completed stages;
* the checkpoint is fingerprint-bound (other data/params never match);
* a successful build clears its checkpoint subfolder.
"""

import os

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.graph.rng import RelativeNeighborhoodGraph
from sptag_tpu.trees.bktree import BKTree
from sptag_tpu.utils.build_ckpt import BuildCheckpoint, build_fingerprint


def _mk_data(n=600, d=24, seed=3):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def _mk_index():
    index = sp.create_instance("BKT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for k, v in (("BKTNumber", "1"), ("BKTKmeansK", "8"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "64"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("MaxCheckForRefineGraph", "64"), ("RefineIterations", "2"),
                 ("MaxCheck", "256")):
        index.set_parameter(k, v)
    return index


def test_checkpointed_build_matches_plain_build(tmp_path):
    data = _mk_data()
    plain = _mk_index()
    plain.build(data)
    ckpt = _mk_index()
    ckpt.build(data, checkpoint_dir=str(tmp_path / "ck"))
    assert np.array_equal(plain._graph.graph, ckpt._graph.graph)
    # success clears the fingerprint subfolder
    root = tmp_path / "ck"
    assert not any(p.is_dir() for p in root.iterdir()) \
        if root.exists() else True
    q = data[:5]
    dp, ip = plain.search_batch(q, 3)
    dc, ic = ckpt.search_batch(q, 3)
    assert np.array_equal(ip, ic)


def test_interrupted_build_resumes_completed_stages(tmp_path, monkeypatch):
    data = _mk_data()
    ck_dir = str(tmp_path / "ck")

    # interrupt the first build at the first refine (post-candidates) pass
    calls = {"n": 0}
    real_refine = RelativeNeighborhoodGraph.refine_once

    def dying_refine(self, *a, **kw):
        calls["n"] += 1
        raise RuntimeError("backend died")

    monkeypatch.setattr(RelativeNeighborhoodGraph, "refine_once",
                        dying_refine)
    first = _mk_index()
    with pytest.raises(RuntimeError):
        first.build(data, checkpoint_dir=ck_dir)
    assert calls["n"] == 1
    monkeypatch.setattr(RelativeNeighborhoodGraph, "refine_once",
                        real_refine)

    # stage files survived the crash: tree + candidates (the cheap
    # initial prune is recomputed on resume; refine passes checkpoint
    # after they complete — the crash was in the first one)
    sub = [p for p in (tmp_path / "ck").iterdir() if p.is_dir()]
    assert len(sub) == 1
    names = {p.name for p in sub[0].iterdir()}
    assert "tree.bin" in names
    assert "candidates.npz" in names

    # the resumed build must not re-run the tree stage nor any TPT tree's
    # all-pairs work (build_candidates itself runs again but serves every
    # tree from the checkpoint)
    def no_tree_build(self, *a, **kw):
        raise AssertionError("tree stage re-ran on resume")

    def no_tree_candidates(self, *a, **kw):
        raise AssertionError("TPT all-pairs re-ran on resume")

    monkeypatch.setattr(BKTree, "build", no_tree_build)
    monkeypatch.setattr(RelativeNeighborhoodGraph, "_tree_candidates",
                        no_tree_candidates)
    resumed = _mk_index()
    assert resumed.build(data, checkpoint_dir=ck_dir) == sp.ErrorCode.Success
    assert resumed.build_resumed
    monkeypatch.undo()

    # and its result equals an uninterrupted build's
    plain = _mk_index()
    plain.build(data)
    assert not plain.build_resumed
    assert np.array_equal(plain._graph.graph, resumed._graph.graph)
    dp, ip = plain.search_batch(data[:8], 5)
    dr, ir = resumed.search_batch(data[:8], 5)
    assert np.array_equal(ip, ir)


def test_kdt_interrupted_build_resumes(tmp_path, monkeypatch):
    """KDT inherits the resumable _build — its checkpointed tree must load
    back as a KDTree (KDTIndex overrides _load_tree), not a BKTree."""
    from sptag_tpu.trees.kdtree import KDTree

    data = _mk_data()
    ck_dir = str(tmp_path / "ck")
    index = sp.create_instance("KDT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for k, v in (("KDTNumber", "1"), ("TPTNumber", "2"),
                 ("TPTLeafSize", "64"), ("NeighborhoodSize", "8"),
                 ("CEF", "32"), ("MaxCheckForRefineGraph", "64"),
                 ("RefineIterations", "2"), ("MaxCheck", "256")):
        index.set_parameter(k, v)

    real_refine = RelativeNeighborhoodGraph.refine_once

    def dying_refine(self, *a, **kw):
        raise RuntimeError("backend died")

    monkeypatch.setattr(RelativeNeighborhoodGraph, "refine_once",
                        dying_refine)
    with pytest.raises(RuntimeError):
        index.build(data, checkpoint_dir=ck_dir)
    monkeypatch.setattr(RelativeNeighborhoodGraph, "refine_once",
                        real_refine)

    def no_tree_build(self, *a, **kw):
        raise AssertionError("KDT tree stage re-ran on resume")

    monkeypatch.setattr(KDTree, "build", no_tree_build)
    resumed = sp.create_instance("KDT", "Float")
    resumed.set_parameter("DistCalcMethod", "L2")
    for k, v in (("KDTNumber", "1"), ("TPTNumber", "2"),
                 ("TPTLeafSize", "64"), ("NeighborhoodSize", "8"),
                 ("CEF", "32"), ("MaxCheckForRefineGraph", "64"),
                 ("RefineIterations", "2"), ("MaxCheck", "256")):
        resumed.set_parameter(k, v)
    assert resumed.build(data, checkpoint_dir=ck_dir) == sp.ErrorCode.Success
    assert resumed.build_resumed
    assert isinstance(resumed._tree, KDTree)
    _, ids = resumed.search_batch(data[:8], 5)
    assert (ids[:, 0] == np.arange(8)).all()


def test_fingerprint_binds_data_and_params(tmp_path):
    data = _mk_data()
    other = _mk_data(seed=4)
    assert build_fingerprint(data, "cfg") != build_fingerprint(other, "cfg")
    assert build_fingerprint(data, "cfg") != build_fingerprint(data, "cfg2")
    # distinct fingerprints key distinct subfolders -> no cross-talk
    a = BuildCheckpoint(str(tmp_path), build_fingerprint(data, "cfg"))
    b = BuildCheckpoint(str(tmp_path), build_fingerprint(other, "cfg"))
    a.put_bytes("tree", b"A")
    assert b.get_bytes("tree") is None
    assert a.get_bytes("tree") == b"A"
    assert a.resumed and not b.resumed


def test_corrupt_stage_file_is_ignored(tmp_path):
    ck = BuildCheckpoint(str(tmp_path), "f" * 40)
    ck.put_arrays("candidates", cand_ids=np.zeros((4, 2), np.int32),
                  cand_d=np.zeros((4, 2), np.float32),
                  trees_done=np.int64(1))
    path = os.path.join(ck.folder, "candidates.npz")
    with open(path, "wb") as f:
        f.write(b"not an npz")
    assert ck.get_arrays("candidates") is None


def test_gc_runs_only_on_clear_and_age_is_configurable(tmp_path,
                                                       monkeypatch):
    """Orphan GC (ADVICE r3): constructing a checkpoint must NOT reap
    stale siblings (a suspended build requeued late keeps its stages);
    GC runs from clear() — the single retire point — with an
    env-configurable age, and <= 0 disables it."""
    import time

    root = str(tmp_path)
    stale = os.path.join(root, "stalebuild")
    os.makedirs(stale)
    old = time.time() - 9 * 24 * 3600
    os.utime(stale, (old, old))

    # constructor leaves the stale sibling alone
    ck = BuildCheckpoint(root, "a" * 40)
    assert os.path.isdir(stale)

    # GC disabled: clear() keeps it too
    monkeypatch.setenv("SPTAG_TPU_BUILD_CKPT_GC_AGE_S", "0")
    ck.put_bytes("tree", b"x")
    ck.clear()
    assert os.path.isdir(stale)

    # configurable age: one hour -> the 9-day-old sibling is reaped,
    # a fresh sibling survives
    fresh = os.path.join(root, "freshbuild")
    os.makedirs(fresh)
    monkeypatch.setenv("SPTAG_TPU_BUILD_CKPT_GC_AGE_S", "3600")
    ck2 = BuildCheckpoint(root, "b" * 40)
    ck2.clear()
    assert not os.path.isdir(stale)
    assert os.path.isdir(fresh)


def test_sharded_build_keeps_checkpoints_until_all_shards_done(
        tmp_path, monkeypatch):
    """Multi-shard resume (round 4): a finished shard's checkpoint must
    survive until EVERY shard succeeds — per-shard clear-on-success made
    a death in shard s rebuild shards [0, s) from scratch.  Pin:
    keep_checkpoint=True defers the clear to the caller, the sharded
    build retires all checkpoints only at the end, and build_resumed
    aggregates the per-shard signals."""
    from sptag_tpu.core.types import DistCalcMethod
    from sptag_tpu.parallel.sharded import ShardedBKTIndex, make_mesh

    monkeypatch.setenv("SPTAG_TPU_BUILD_CKPT", str(tmp_path))
    data = _mk_data(n=400, d=16, seed=9)
    params = {"BKTNumber": 1, "BKTKmeansK": 8, "TPTNumber": 2,
              "TPTLeafSize": 64, "NeighborhoodSize": 8, "CEF": 24,
              "MaxCheckForRefineGraph": 64, "RefineIterations": 1,
              "MaxCheck": 128}

    # single-index keep_checkpoint contract
    idx = _mk_index()
    assert idx.build(data, keep_checkpoint=True) == sp.ErrorCode.Success
    ck = idx.last_checkpoint
    assert ck is not None and os.path.isdir(ck.folder)
    ck.clear()

    # sharded build: end state has NO leftover checkpoints (all retired
    # after success) and build_resumed False on a cold build
    index = ShardedBKTIndex.build(data, DistCalcMethod.L2,
                                  mesh=make_mesh(), params=params)
    assert index.build_resumed is False
    leftovers = [d for d in os.listdir(tmp_path)
                 if os.path.isdir(os.path.join(tmp_path, d))]
    assert leftovers == [], leftovers
