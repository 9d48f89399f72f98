"""ISSUE 4 — segmented beam walk + continuous-batching slot scheduler.

The contract under test (DESIGN.md §10): the segmented execution of the
walk — fixed-S compiled segments over checkpointed loop-carried state,
with or without the slot scheduler's retire/compact/refill on top — must
return results BIT-IDENTICAL to the monolithic `lax.while_loop` walk for
every query, regardless of what shares its batch/slots.  That exactness
is what lets the scheduler retire converged queries early and refill
their slots without changing any answer.

Corpora are tiny (hundreds of rows): what is under test is parity,
scheduling and compile counts, not recall — the tier-1 budget is
compile-bound (tests/conftest.py)."""

import threading

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.utils import recompile_guard as rg


def _build_bkt(data, max_check=64):
    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BKTNumber", "1"), ("BKTKmeansK", "8"),
                        ("Samples", "200"), ("TPTNumber", "2"),
                        ("TPTLeafSize", "50"), ("NeighborhoodSize", "8"),
                        ("CEF", "64"), ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"), ("SearchMode", "beam"),
                        ("MaxCheck", str(max_check))]:
        assert idx.set_parameter(name, value), name
    assert idx.build(data) == sp.ErrorCode.Success
    return idx


@pytest.fixture(scope="module")
def bkt_setup():
    rng = np.random.default_rng(7)
    data = rng.standard_normal((400, 16)).astype(np.float32)
    queries = rng.standard_normal((20, 16)).astype(np.float32)
    idx = _build_bkt(data)
    yield idx, data, queries
    idx.close()


# ---- parity: segmented == monolithic, bit for bit -------------------------

# (max_check, beam_width, nbp_limit, dynamic_pivots) — budget/width/nbp
# spread, with and without mid-walk spare-pivot injection
_CONFIGS = [(32, 4, 1, 0), (32, 4, 3, 4), (64, 8, 3, 4), (128, 4, 2, 0)]


# what the body scores against, and with it whose norm an in-loop
# distance carries (PR 44: the scored row's own): the float32 rows, their
# bfloat16 shadow, the int8 cascade tier's dequantised rows
# name -> (parameter, value, its default, the shadow's dtype)
_SCORING = {"f32": ("BeamScoreDtype", "f32", "auto", None),
            "bf16": ("BeamScoreDtype", "bf16", "auto", "bfloat16"),
            "int8": ("CascadeSearch", "1", "0", "int8")}


@pytest.fixture(params=list(_SCORING))
def scored_engine(bkt_setup, request):
    idx, _, queries = bkt_setup
    name, value, default, shadow = _SCORING[request.param]
    assert idx.set_parameter(name, value)
    eng = idx._get_engine()
    assert (eng.data_score is None if shadow is None
            else str(eng.data_score.dtype) == shadow)
    assert bool(eng.score_scale) == (request.param == "int8")
    yield eng, queries
    assert idx.set_parameter(name, default)


@pytest.mark.parametrize("mc,bw,nbp,dp", _CONFIGS)
def test_segmented_parity_pivot_seeded(scored_engine, mc, bw, nbp, dp,
                                       monkeypatch):
    eng, queries = scored_engine
    d0, i0 = eng.search(queries, 5, max_check=mc, beam_width=bw,
                        nbp_limit=nbp, dynamic_pivots=dp)
    for s in (1, 3):
        d1, i1 = eng.search(queries, 5, max_check=mc, beam_width=bw,
                            nbp_limit=nbp, dynamic_pivots=dp,
                            segment_iters=s)
        assert np.array_equal(i0, i1), (mc, bw, nbp, dp, s)
        assert np.array_equal(d0, d1), (mc, bw, nbp, dp, s)
    # the chunked driver (`lax.map` over chunks of 8) is the same twin
    monkeypatch.setattr(type(eng), "chunk_size", lambda self: 8)
    d2, i2 = eng.search(queries, 5, max_check=mc, beam_width=bw,
                        nbp_limit=nbp, dynamic_pivots=dp)
    assert np.array_equal(i0, i2), (mc, bw, nbp, dp, "chunked")
    assert np.array_equal(d0, d2), (mc, bw, nbp, dp, "chunked")


def test_segmented_parity_seeded_path(bkt_setup):
    """KDT-style per-query seeding (seeds override pivots) through the
    same segmented machinery."""
    idx, data, queries = bkt_setup
    eng = idx._get_engine()
    rng = np.random.default_rng(11)
    seeds = rng.integers(0, data.shape[0], (len(queries), 6)).astype(
        np.int32)
    # unseeded-looking duplicates + -1 pads exercise the seed dedupe
    seeds[:, 3] = seeds[:, 0]
    seeds[0, 5] = -1
    for mc, nbp in [(32, 2), (64, 3)]:
        d0, i0 = eng.search(queries, 5, max_check=mc, beam_width=4,
                            nbp_limit=nbp, seeds=seeds)
        d1, i1 = eng.search(queries, 5, max_check=mc, beam_width=4,
                            nbp_limit=nbp, seeds=seeds, segment_iters=2)
        assert np.array_equal(i0, i1)
        assert np.array_equal(d0, d1)


def test_index_level_segment_param(bkt_setup):
    """BeamSegmentIters routes index searches through the segmented walk
    with identical results (INI-parity knob, core/params.py)."""
    idx, _, queries = bkt_setup
    d0, i0 = idx.search_batch(queries, 5, max_check=64)
    assert idx.set_parameter("BeamSegmentIters", "2")
    try:
        d1, i1 = idx.search_batch(queries, 5, max_check=64)
    finally:
        idx.set_parameter("BeamSegmentIters", "0")
    assert np.array_equal(i0, i1)
    assert np.array_equal(d0, d1)


# ---- the slot scheduler ---------------------------------------------------

def test_scheduler_matches_monolithic_and_drains(bkt_setup):
    """Scheduled results return the monolithic walk's ids; distances are
    compared with allclose because the scheduler seeds/walks at QUANTIZED
    refill-bucket shapes — XLA tiles reductions per batch shape, so a
    (8, P) seed matmul can differ from the monolithic (32, P) one in the
    last ulp.  At EQUAL shapes the walk is bit-identical (the parity
    tests above assert exact equality)."""
    idx, _, queries = bkt_setup
    d0, i0 = idx.search_batch(queries, 5, max_check=64)
    for name, value in [("ContinuousBatching", "1"), ("BeamSlots", "8"),
                        ("BeamSegmentIters", "2")]:
        assert idx.set_parameter(name, value)
    try:
        d1, i1 = idx.search_batch(queries, 5, max_check=64)
        futs = idx.submit_batch(queries, 5, max_check=64)
        for row, f in enumerate(futs):
            fd, fi = f.result(timeout=60)
            assert np.array_equal(fi, i1[row])
            np.testing.assert_allclose(fd, d1[row], rtol=1e-6)
        stats = idx._scheduler.stats()
    finally:
        idx.set_parameter("ContinuousBatching", "0")
    assert np.array_equal(i0, i1)
    np.testing.assert_allclose(d0, d1, rtol=1e-6)
    assert stats["live"] == 0 and stats["pending"] == 0, stats


def test_scheduler_hammer_mixed_maxcheck(bkt_setup):
    """Concurrent submitters with MIXED MaxCheck budgets: every query is
    answered exactly once with the monolithic walk's exact result, and a
    full drain leaves no occupied slot (mirrors test_threadpool.py's
    accepted-jobs-run-exactly-once idiom)."""
    idx, _, queries = bkt_setup
    budgets = (32, 128)
    # reference results from the monolithic path, per (query, budget)
    ref = {}
    for mc in budgets:
        d, ids = idx.search_batch(queries, 5, max_check=mc)
        for qi in range(len(queries)):
            ref[(qi, mc)] = (d[qi], ids[qi])
    for name, value in [("ContinuousBatching", "1"), ("BeamSlots", "8"),
                        ("BeamSegmentIters", "1")]:
        assert idx.set_parameter(name, value)
    try:
        answers = []
        answers_lock = threading.Lock()
        errors = []

        def submitter(seed):
            rng = np.random.default_rng(seed)
            for _ in range(12):
                qi = int(rng.integers(0, len(queries)))
                mc = int(budgets[rng.integers(0, len(budgets))])
                try:
                    res = idx.search(queries[qi], 5, max_check=mc)
                    got = (qi, mc, res.dists.copy(), res.ids.copy())
                except Exception as e:           # noqa: BLE001
                    errors.append(e)
                    return
                with answers_lock:
                    answers.append(got)

        threads = [threading.Thread(target=submitter, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert len(answers) == 4 * 12          # every submit answered once
        for qi, mc, d, ids in answers:
            rd, rids = ref[(qi, mc)]
            assert np.array_equal(ids, rids), (qi, mc)
            # distances allclose, not equal: refill-bucket shapes retile
            # the reductions (see test_scheduler_matches_monolithic)
            np.testing.assert_allclose(d, rd, rtol=1e-6)
        stats = idx._scheduler.stats()
        assert stats["live"] == 0, stats       # no slot leak
        assert stats["pending"] == 0, stats
    finally:
        idx.set_parameter("ContinuousBatching", "0")


def test_scheduler_warm_mints_no_compiles(bkt_setup):
    """A warmed scheduler runs refill/segment/retire/compact cycles with
    ZERO fresh XLA compiles: slot capacity and refill sizes are bucketed
    (BeamSlots=8 admits only the {1, 8} buckets), budgets ride traced
    t_limit vectors.  The recompile-guard acceptance for the tentpole."""
    idx, _, queries = bkt_setup
    for name, value in [("ContinuousBatching", "1"), ("BeamSlots", "8"),
                        ("BeamSegmentIters", "2")]:
        assert idx.set_parameter(name, value)
    try:
        # warm both capacity buckets and both budgets
        for mc in (32, 128):
            idx.search(queries[0], 5, max_check=mc)         # bucket 1
            idx.search_batch(queries, 5, max_check=mc)      # bucket 8
        with rg.no_recompiles("scheduler.steady") as log:
            idx.search(queries[3], 5, max_check=32)
            idx.search_batch(queries[::-1].copy(), 5, max_check=128)
            idx.search_batch(queries[:7], 5, max_check=32)
        assert log.count == 0
    finally:
        idx.set_parameter("ContinuousBatching", "0")


def test_scheduler_retire_drains_in_flight(bkt_setup):
    """retire() — the engine-snapshot-swap path — rejects NEW queries but
    completes everything already submitted (in-flight searches must not
    surface as failures just because a mutation swapped the snapshot)."""
    from sptag_tpu.algo.scheduler import BeamSlotScheduler, SchedulerStopped

    idx, _, queries = bkt_setup
    sched = BeamSlotScheduler(idx._get_engine(), slots=8, segment_iters=1)
    futs = [sched.submit(queries[i], 5, 128) for i in range(8)]
    sched.retire()
    for f in futs:
        f.result(timeout=60)              # drained, not failed
    with pytest.raises(SchedulerStopped):
        sched.submit(queries[0], 5, 128)


def test_scheduler_stop_fails_pending(bkt_setup):
    """stop() resolves outstanding futures with SchedulerStopped instead
    of leaving waiters blocked forever."""
    from sptag_tpu.algo.scheduler import BeamSlotScheduler, SchedulerStopped

    idx, _, queries = bkt_setup
    sched = BeamSlotScheduler(idx._get_engine(), slots=8, segment_iters=1)
    fut = sched.submit(queries[0], 5, 64)
    fut.result(timeout=60)                    # let the worker warm up
    sched.stop()
    with pytest.raises(SchedulerStopped):
        sched.submit(queries[0], 5, 64)


# ---- serve-tier streaming -------------------------------------------------

@pytest.mark.parametrize("backing", ["resolved", "scheduler"])
def test_execute_batch_on_ready_streams_per_query(bkt_setup, backing):
    """SearchExecutor.execute_batch(on_ready=...) answers a single-index
    group at the granularity its futures resolve at.  `resolved` (FLAT:
    submit_batch returns finished futures): nothing is streamed, the
    returned list is complete and equal to the plain call's.  `scheduler`
    (BKT beam, ContinuousBatching=1: more queries than slots, so futures
    are pending when submit_batch returns): every successful result goes
    through the callback exactly once, identical to the returned list —
    the surface server._serve_batch streams from."""
    from sptag_tpu.serve.service import SearchExecutor, ServiceContext
    from sptag_tpu.utils import metrics

    if backing == "resolved":
        rng = np.random.default_rng(3)
        data = rng.standard_normal((64, 8)).astype(np.float32)
        index = sp.create_instance("FLAT", "Float")
        index.set_parameter("DistCalcMethod", "L2")
        assert index.build(data) == sp.ErrorCode.Success
        rows = 5
    else:
        index, data, _ = bkt_setup
        for name, value in [("ContinuousBatching", "1"), ("BeamSlots", "8"),
                            ("BeamSegmentIters", "2")]:
            assert index.set_parameter(name, value)
        rows = 20
    ctx = ServiceContext()
    ctx.add_index("t", index)
    ex = SearchExecutor(ctx)
    texts = ["|".join(str(x) for x in data[i]) for i in range(rows)]
    texts.append("1|2")                       # dim mismatch -> failure row
    got = {}

    def on_ready(i, result):
        assert i not in got, "double delivery"
        got[i] = result
    try:
        plain = ex.execute_batch(texts)
        metrics.reset()
        streamed = ex.execute_batch(texts, on_ready=on_ready)
    finally:
        if backing == "scheduler":
            index.set_parameter("ContinuousBatching", "0")
    if backing == "resolved":
        assert got == {}
        assert metrics.counter_value("service.batched_results") == rows
        assert metrics.counter_value("service.streamed_results") == 0
    else:
        assert sorted(got) == list(range(rows))   # failures: not streamed
        assert metrics.counter_value("service.streamed_results") == rows
        assert metrics.counter_value("service.batched_results") == 0
        for i, r in got.items():
            assert streamed[i] is r
    for i in range(rows):
        assert streamed[i].status == plain[i].status
        assert streamed[i].results[0].ids == plain[i].results[0].ids
        assert streamed[i].results[0].ids[0] == i
    assert streamed[rows].status == plain[rows].status   # failure returned


def test_kdt_scheduler_parity():
    """KDT rides the scheduler with its per-query kd-tree seeds."""
    rng = np.random.default_rng(5)
    data = rng.standard_normal((200, 12)).astype(np.float32)
    queries = rng.standard_normal((10, 12)).astype(np.float32)
    idx = sp.create_instance("KDT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, value in [("KDTNumber", "1"), ("Samples", "100"),
                        ("TPTNumber", "2"), ("TPTLeafSize", "50"),
                        ("NeighborhoodSize", "8"), ("CEF", "64"),
                        ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"), ("MaxCheck", "64")]:
        assert idx.set_parameter(name, value), name
    assert idx.build(data) == sp.ErrorCode.Success
    try:
        d0, i0 = idx.search_batch(queries, 5, max_check=64)
        for name, value in [("ContinuousBatching", "1"),
                            ("BeamSlots", "8")]:
            assert idx.set_parameter(name, value)
        d1, i1 = idx.search_batch(queries, 5, max_check=64)
        assert np.array_equal(i0, i1)
        np.testing.assert_allclose(d0, d1, rtol=1e-6)
    finally:
        idx.close()
