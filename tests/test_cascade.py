"""Tiered corpus cascade (ISSUE 14, ops/cascade.py): correctness of the
sketch -> int8 -> fp pipeline and its beyond-HBM host tiers.

Contracts pinned here:

* budget semantics — validated, power-of-two quantized, a budget
  covering the corpus composes the tier out;
* budget-sweep recall floors vs the exact oracle (Wilson CIs);
* tombstone + delta-shard visibility through EVERY tier;
* host-tier fp fetch bit-identical to the device-resident re-rank;
* off-parity — CascadeSearch=0 builds nothing, results and serve bytes
  byte-identical (the ci_check.sh standalone pass keys on "off_parity"
  / "parity" in these names);
* cost-ledger crosscheck ±15% for the new ops.cascade kernel families;
* qualmon tier triage verdicts (sketch_budget / int8_budget /
  host_fetch_drop);
* SketchRerank calibration persistence (save/load skips the
  recalibration scan; mutation invalidates).
"""

import socket

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import cascade
from sptag_tpu.utils import devmem, qualmon

jnp = pytest.importorskip("jax.numpy")


def _dataset(n=3000, d=48, nq=64, seed=7):
    rng = np.random.default_rng(seed)
    # mild clustering so the sketch tier has structure to exploit
    centers = rng.standard_normal((16, d)).astype(np.float32) * 2.0
    data = (centers[rng.integers(0, 16, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    queries = (centers[rng.integers(0, 16, nq)]
               + rng.standard_normal((nq, d)).astype(np.float32))
    return data.astype(np.float32), queries.astype(np.float32)


def _flat(data, **params):
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for k, v in params.items():
        idx.set_parameter(k, str(v))
    idx.build(data)
    return idx


def _recall(ids, truth, k):
    hits = sum(len(set(map(int, ids[r][:k])) & set(map(int, truth[r][:k])))
               for r in range(len(ids)))
    return hits / float(len(ids) * k)


# ---------------------------------------------------------------------------
# budget + tier validation
# ---------------------------------------------------------------------------

def test_budget_resolution_and_validation():
    # auto budgets: pow2, ordered, clamped
    b1, b2 = cascade.resolve_budgets(0, 0, 10, 4096)
    assert b1 & (b1 - 1) == 0 and b2 & (b2 - 1) == 0
    assert 10 <= b2 <= b1 <= 4096
    # explicit budgets quantize UP, never shrink below k
    b1, b2 = cascade.resolve_budgets(300, 33, 10, 4096)
    assert (b1, b2) == (512, 64)
    # b2 is clamped to b1, both to n
    b1, b2 = cascade.resolve_budgets(100000, 100000, 10, 4096)
    assert (b1, b2) == (4096, 4096)
    with pytest.raises(ValueError):
        cascade.resolve_budgets(-1, 0, 10, 4096)
    with pytest.raises(ValueError):
        cascade.resolve_budgets(0, -5, 10, 4096)
    with pytest.raises(ValueError):
        cascade.normalize_tier("hbm")
    assert cascade.normalize_tier(" Host ") == "host"


def test_int8_quantization_contract():
    rng = np.random.default_rng(0)
    data = rng.standard_normal((64, 16)).astype(np.float32)
    q, scale = cascade.quantize_int8(data)
    assert q.dtype == np.int8
    np.testing.assert_allclose(q.astype(np.float32) * scale, data,
                               atol=scale)
    with pytest.raises(ValueError):
        cascade.quantize_int8(np.zeros((4, 4), np.int8))


# ---------------------------------------------------------------------------
# budget-sweep recall floors vs the exact oracle (Wilson CI)
# ---------------------------------------------------------------------------

def test_budget_sweep_recall_floors():
    data, queries = _dataset()
    k = 10
    base = _flat(data)
    truth_d, truth_i = base.search_batch(queries, k)
    last = 0.0
    for b1, b2, floor in [(256, 64, 0.55), (1024, 256, 0.80),
                          (3072, 1024, 0.90)]:
        idx = _flat(data, CascadeSearch=1, TierBudgetSketch=b1,
                    TierBudgetInt8=b2)
        _, ids = idx.search_batch(queries, k)
        rec = _recall(ids, truth_i, k)
        trials = len(queries) * k
        lo, hi = qualmon.wilson(rec * trials, trials)
        assert hi >= floor, (b1, b2, rec, lo, hi)
        # recall is monotone-ish in budget: generous budgets must not
        # fall below what starved ones achieved (allow CI slack)
        assert rec >= last - 0.05, (b1, b2, rec, last)
        last = rec
    # budgets covering the corpus = exact scan, recall 1.0 bit-exact
    idx = _flat(data, CascadeSearch=1, TierBudgetSketch=100000,
                TierBudgetInt8=100000)
    d, ids = idx.search_batch(queries, k)
    assert _recall(ids, truth_i, k) == 1.0
    np.testing.assert_array_equal(ids, truth_i)
    np.testing.assert_allclose(d, truth_d, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tier parity: host fetch bit-identical to device-resident re-rank
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", ["host", "host_all"])
def test_host_tier_bit_identical_to_device(tier):
    data, queries = _dataset(n=2000, nq=32)
    dev = _flat(data, CascadeSearch=1, TierBudgetSketch=512,
                TierBudgetInt8=128)
    d0, i0 = dev.search_batch(queries, 10)
    host = _flat(data, CascadeSearch=1, TierBudgetSketch=512,
                 TierBudgetInt8=128, CorpusTier=tier)
    d1, i1 = host.search_batch(queries, 10)
    np.testing.assert_array_equal(i0, i1)
    # the fp re-rank is ONE traced function for both tiers
    # (cascade.rerank_gathered) — distances agree bit for bit
    assert d0.tobytes() == d1.tobytes()


def test_host_tier_zero_fp_device_residency():
    data, queries = _dataset(n=2000, nq=32)
    devmem.reset()
    try:
        idx = _flat(data, CascadeSearch=1, CorpusTier="host")
        idx.search_batch(queries, 10)
        comp = devmem.component_bytes()
        # sketches + int8 on device, the fp corpus host-side ONLY
        assert "corpus" not in comp, comp
        assert comp.get("int8_blocks", 0) > 0
        assert comp.get("sketch", 0) > 0
        assert comp.get("host_corpus", 0) >= data.nbytes
        # host_all additionally evicts the int8 blocks
        devmem.reset()
        idx2 = _flat(data, CascadeSearch=1, CorpusTier="host_all")
        idx2.search_batch(queries, 10)
        comp2 = devmem.component_bytes()
        assert "corpus" not in comp2 and "int8_blocks" not in comp2, comp2
        assert comp2.get("host_corpus", 0) > comp.get("host_corpus", 0)
    finally:
        devmem.reset()


def test_host_tier_oracle_streams_blocks():
    """exact_search_batch on a host-tier index is exact (equal to the
    device oracle) and never materializes the fp corpus."""
    data, queries = _dataset(n=2000, nq=16)
    base = _flat(data)
    td, ti = base.exact_search_batch(queries, 10)
    host = _flat(data, CascadeSearch=1, CorpusTier="host")
    hd, hi = host.exact_search_batch(queries, 10)
    np.testing.assert_array_equal(ti, hi)
    np.testing.assert_allclose(td, hd, rtol=1e-5, atol=1e-5)
    # streamed merge with a tiny block size crosses block boundaries
    st = host._cascade_state()
    bd, bi = cascade.host_exact_scan(
        st.fp_host, np.asarray(st.invalid_d), queries, 10,
        int(DistCalcMethod.L2), 1, block_rows=257)
    np.testing.assert_array_equal(bi, ti)


# ---------------------------------------------------------------------------
# tombstones + delta shard through every tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", ["device", "host", "host_all"])
def test_tombstones_visible_through_tiers(tier):
    data, queries = _dataset(n=1500, nq=16)
    idx = _flat(data, CascadeSearch=1, TierBudgetSketch=512,
                TierBudgetInt8=128, CorpusTier=tier)
    _, before = idx.search_batch(queries, 10)
    victims = sorted({int(v) for v in before[:, :3].ravel()
                      if v >= 0})[:16]
    assert idx.delete(data[victims]) == sp.ErrorCode.Success
    _, after = idx.search_batch(queries, 10)
    assert not (set(victims) & {int(v) for v in after.ravel()}), victims
    # exact oracle agrees the deletes are gone
    _, oracle = idx.exact_search_batch(queries, 10)
    assert not (set(victims) & {int(v) for v in oracle.ravel()})


@pytest.mark.parametrize("tier", ["device", "host"])
def test_delta_shard_adds_visible_through_tiers(tier):
    data, queries = _dataset(n=1500, nq=8)
    idx = _flat(data, CascadeSearch=1, CorpusTier=tier,
                DeltaShardCapacity=64)
    # plant rows identical to queries: they MUST surface at rank 0
    assert idx.add(queries[:4]) == sp.ErrorCode.Success
    d, ids = idx.search_batch(queries[:4], 5)
    n0 = 1500
    for r in range(4):
        assert ids[r, 0] >= n0, (r, ids[r])
        assert d[r, 0] <= 1e-4


# ---------------------------------------------------------------------------
# off-parity: CascadeSearch=0 is byte-identical and builds nothing
# ---------------------------------------------------------------------------

def test_cascade_off_parity_results_and_state():
    data, queries = _dataset(n=1200, nq=16)
    plain = _flat(data)
    d0, i0 = plain.search_batch(queries, 10)
    devmem.reset()
    try:
        off = _flat(data)       # defaults: CascadeSearch=0
        assert str(off.get_parameter("CascadeSearch")) == "0"
        assert str(off.get_parameter("CorpusTier")) == "device"
        d1, i1 = off.search_batch(queries, 10)
        assert d0.tobytes() == d1.tobytes()
        assert i0.tobytes() == i1.tobytes()
        comp = devmem.component_bytes()
        assert "int8_blocks" not in comp and "host_corpus" not in comp
        assert off._cascade is None
    finally:
        devmem.reset()


def test_cascade_off_parity_golden_wire_bytes():
    """Default knobs: a served response is byte-identical to the
    reference wire layout (the pattern every off-by-default subsystem
    carries; tools/ci_check.sh standalone)."""
    from conftest import ServerThread
    from sptag_tpu.serve import wire
    from sptag_tpu.serve.server import SearchServer
    from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                         ServiceSettings)

    rng = np.random.default_rng(13)
    data = rng.standard_normal((200, 12)).astype(np.float32)
    flat = sp.create_instance("FLAT", "Float")
    flat.set_parameter("DistCalcMethod", "L2")
    flat.build(data)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("f", flat)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = ServerThread(server)
    t.start()
    host, port = t.wait_ready()
    try:
        qtext = "|".join(str(x) for x in data[3])
        expected_result = SearchExecutor(ctx).execute(qtext)
        expected_result.request_id = ""
        expected_body = expected_result.pack()
        expected = wire.PacketHeader(
            wire.PacketType.SearchResponse, wire.PacketProcessStatus.Ok,
            len(expected_body), 1, 99).pack() + expected_body
        body = wire.RemoteQuery(qtext).pack()
        s = socket.create_connection((host, port), timeout=10)
        s.sendall(wire.PacketHeader(
            wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
            len(body), 0, 99).pack() + body)
        s.settimeout(10)
        got = b""
        while len(got) < len(expected):
            chunk = s.recv(65536)
            if not chunk:
                break
            got += chunk
        s.close()
        assert got == expected
    finally:
        t.stop()


# ---------------------------------------------------------------------------
# qualmon tier triage
# ---------------------------------------------------------------------------

def test_classify_low_recall_names_starved_tier():
    v, _ = qualmon.classify_low_recall(
        "", "flat", cascade={"sketch_dropped": 3, "int8_dropped": 1,
                             "host_dropped": 0})
    assert v == "sketch_budget"
    v, _ = qualmon.classify_low_recall(
        "", "flat", cascade={"sketch_dropped": 1, "int8_dropped": 4,
                             "host_dropped": 0})
    assert v == "int8_budget"
    # a MEASURED budget starvation outranks the lifetime fetch-drop
    # counter (the triage re-ran this query's shortlists; host_dropped
    # is historical and must not mask the budget root cause)
    v, _ = qualmon.classify_low_recall(
        "", "flat", cascade={"sketch_dropped": 5, "int8_dropped": 0,
                             "host_dropped": 2})
    assert v == "sketch_budget"
    # shortlists clean + drops recorded -> the fetch is the suspect
    v, _ = qualmon.classify_low_recall(
        "", "flat", cascade={"sketch_dropped": 0, "int8_dropped": 0,
                             "host_dropped": 2})
    assert v == "host_fetch_drop"
    # all tiers clean -> fall through to the legacy verdicts
    v, _ = qualmon.classify_low_recall(
        "", "flat", cascade={"sketch_dropped": 0, "int8_dropped": 0,
                             "host_dropped": 0})
    assert v == "unknown"


def test_cascade_triage_counts_tier_drops():
    data, queries = _dataset(n=2000, nq=4)
    idx = _flat(data, CascadeSearch=1, TierBudgetSketch=64,
                TierBudgetInt8=16)
    _, truth = idx.exact_search_batch(queries[:1], 10)
    tri = idx.cascade_triage(queries[0], truth[0], 10)
    assert set(tri) == {"sketch_dropped", "int8_dropped", "host_dropped"}
    assert all(v >= 0 for v in tri.values())
    # off index reports nothing
    off = _flat(data)
    assert off.cascade_triage(queries[0], truth[0], 10) is None


# ---------------------------------------------------------------------------
# SketchRerank calibration persistence (save/load satellite)
# ---------------------------------------------------------------------------

def test_sketch_calibration_persisted_across_save_load(tmp_path):
    data, queries = _dataset(n=1500, nq=8)
    idx = _flat(data, SketchPrefilter=True)
    idx.search_batch(queries, 10)            # triggers the calibration
    with idx._lock:
        cal = idx._sketch[3]
    assert cal and cal > 0
    folder = str(tmp_path / "idx")
    assert idx.save_index(folder) == sp.ErrorCode.Success

    from sptag_tpu.algo.flat import FlatIndex
    from sptag_tpu.core.index import load_index

    loaded = load_index(folder)
    assert loaded._loaded_cal is not None
    assert loaded._loaded_cal[2] == cal
    # a warm start consumes the persisted value WITHOUT re-running the
    # calibration scan
    calls = []
    orig = FlatIndex._calibrate

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    FlatIndex._calibrate = spy
    try:
        loaded.search_batch(queries, 10)
        assert not calls, "persisted calibration must skip the scan"
        with loaded._lock:
            assert loaded._sketch[3] == cal
        # mutation invalidates: the next cold calibration re-runs
        assert loaded.add(queries[:1]) == sp.ErrorCode.Success
        assert loaded._loaded_cal is None
        loaded.search_batch(queries, 10)
        assert calls, "mutated corpus must recalibrate"
    finally:
        FlatIndex._calibrate = orig


def test_calibration_blob_absent_by_default(tmp_path):
    data, _ = _dataset(n=1200, nq=4)
    idx = _flat(data)
    folder = str(tmp_path / "plain")
    assert idx.save_index(folder) == sp.ErrorCode.Success
    import os

    assert not os.path.exists(os.path.join(folder, "sketch_cal.bin"))


# ---------------------------------------------------------------------------
# graph engines: dense + beam cascade (device vs host parity)
# ---------------------------------------------------------------------------

def _bkt(data, **params):
    idx = sp.create_instance("BKT", "Float")
    for k, v in {"DistCalcMethod": "L2", "BKTKmeansK": "8",
                 "TPTNumber": "2", "RefineIterations": "1",
                 "FinalRefineSearchMode": "dense", **params}.items():
        idx.set_parameter(k, str(v))
    idx.build(data)
    return idx


def test_dense_cascade_device_host_parity_and_recall():
    data, queries = _dataset(n=1200, d=32, nq=16)
    idx = _bkt(data, SearchMode="dense", BuildGraph=0)
    _, truth = idx.exact_search_batch(queries, 10)
    _, ids_off = idx.search_batch(queries, 10, max_check=1024)
    rec_off = _recall(ids_off, truth, 10)
    idx.set_parameter("CascadeSearch", "1")
    idx.set_parameter("TierBudgetInt8", "128")
    d1, i1 = idx.search_batch(queries, 10, max_check=1024)
    rec_on = _recall(i1, truth, 10)
    assert rec_on >= rec_off - 0.1, (rec_on, rec_off)
    idx.set_parameter("CorpusTier", "host")
    d2, i2 = idx.search_batch(queries, 10, max_check=1024)
    np.testing.assert_array_equal(i1, i2)
    assert d1.tobytes() == d2.tobytes()


def test_beam_cascade_host_tier_parity():
    data, queries = _dataset(n=1200, d=32, nq=16)
    idx = _bkt(data, SearchMode="beam")
    _, truth = idx.exact_search_batch(queries, 10)
    idx.set_parameter("CascadeSearch", "1")
    idx.set_parameter("CorpusTier", "host")
    devmem.reset()
    try:
        d1, i1 = idx.search_batch(queries, 10, max_check=512)
        assert _recall(i1, truth, 10) >= 0.8
        comp = devmem.component_bytes()
        assert "corpus" not in comp, comp          # int8-only device
        assert comp.get("host_corpus", 0) > 0
        # host-tier oracle stays exact
        _, hi = idx.exact_search_batch(queries, 10)
        np.testing.assert_array_equal(hi, truth)
        # segmented execution parity (the scheduler contract)
        idx.set_parameter("BeamSegmentIters", "3")
        d2, i2 = idx.search_batch(queries, 10, max_check=512)
        np.testing.assert_array_equal(i1, i2)
        assert d1.tobytes() == d2.tobytes()
        # continuous-batching scheduler parity
        idx.set_parameter("BeamSegmentIters", "0")
        idx.set_parameter("ContinuousBatching", "1")
        d3, i3 = idx.search_batch(queries, 10, max_check=512)
        np.testing.assert_array_equal(i1, i3)
        assert d1.tobytes() == d3.tobytes()
    finally:
        devmem.reset()
        idx.close()


def test_kdt_seeded_cascade_both_tiers():
    """The KDT walk seeds from per-query kd-descent rows gathered off
    `data` — on the DEVICE tier those rows are fp and must NOT be
    dequantized (only the walk's int8 shadow is scaled); on the HOST
    tier they are int8 and MUST be.  Regression for both directions of
    the seed-scaling bug."""
    data, queries = _dataset(n=1000, d=32, nq=12)
    idx = sp.create_instance("KDT", "Float")
    for k, v in {"DistCalcMethod": "L2", "TPTNumber": "2",
                 "RefineIterations": "1",
                 "FinalRefineSearchMode": "dense"}.items():
        idx.set_parameter(k, str(v))
    idx.build(data)
    _, truth = idx.exact_search_batch(queries, 10)
    _, i0 = idx.search_batch(queries, 10, max_check=512)
    rec0 = _recall(i0, truth, 10)
    for tier in ("device", "host"):
        idx.set_parameter("CascadeSearch", "1")
        idx.set_parameter("CorpusTier", tier)
        _, i1 = idx.search_batch(queries, 10, max_check=512)
        assert _recall(i1, truth, 10) >= rec0 - 0.1, tier
    idx.close()


def test_mesh_cascade_scheduler_vs_monolithic_parity(host_mesh):
    from sptag_tpu.parallel.sharded import ShardedBKTIndex

    data, queries = _dataset(n=600, d=32, nq=8)
    sh = ShardedBKTIndex.build(
        data, params={"DistCalcMethod": "L2", "BKTKmeansK": "8",
                      "TPTNumber": "2", "RefineIterations": "1",
                      "FinalRefineSearchMode": "dense",
                      "CascadeSearch": "1"},
        mesh=host_mesh(2))
    assert sh.data_score is not None and sh.score_scale > 0
    d1, i1 = sh.search(queries, 10, max_check=256)
    sh.enable_continuous_batching(slots=32)
    futs = sh.submit_batch(queries, 10, max_check=256)
    res = [f.result() for f in futs]
    i2 = np.stack([r[1] for r in res])
    d2 = np.stack([r[0] for r in res])
    np.testing.assert_array_equal(i1, i2)
    assert d1.tobytes() == d2.tobytes()
    sh.retire_scheduler()


def test_mesh_rejects_host_tier(host_mesh):
    from sptag_tpu.parallel.sharded import ShardedBKTIndex

    data, _ = _dataset(n=400, d=32, nq=4)
    with pytest.raises(ValueError, match="single-chip"):
        ShardedBKTIndex.build(
            data, params={"DistCalcMethod": "L2", "BKTKmeansK": "8",
                          "TPTNumber": "2", "RefineIterations": "1",
                          "FinalRefineSearchMode": "dense",
                          "CascadeSearch": "1", "CorpusTier": "host"},
            mesh=host_mesh(2))
