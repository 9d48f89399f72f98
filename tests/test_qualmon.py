"""Search-quality observatory (ISSUE 7): canonical recall math pinned
against hand-computed fixtures, estimator correctness on a planted
corpus, shadow-queue overflow/budget drop semantics (never blocks),
exact-scan oracle parity, index-health metrics, the aggregator+2-shard
end-to-end (gauge within its Wilson CI of offline truth, budget-starved
triage verdict + flight dump), [Service] ini plumbing / set_parameter
live-apply, and the QualitySampleRate=0 byte-parity / one-flag-test
contract (the ci_check.sh standalone pass)."""

import asyncio  # noqa: F401  (referenced via test_serve harness)
import json
import logging
import os
import socket
import threading
import time

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.serve import wire
from sptag_tpu.serve.aggregator import (AggregatorContext,
                                        AggregatorService, RemoteServer)
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import flightrec, metrics, qualmon

from tests.test_serve import _ServerThread


# ---------------------------------------------------------------------------
# canonical recall math (the one definition, hand-computed fixtures)
# ---------------------------------------------------------------------------

def test_recall_row_hand_computed_fixture():
    """Reference CalcRecall parity on a worked example: per truth slot,
    a hit is an id match — |{1}|/3 and |{4,5}|/3."""
    assert qualmon.recall_row([1, 2, 3], [1, 9, 8], 3) == \
        pytest.approx(1 / 3)
    assert qualmon.recall_row([4, 5, -1], [4, 5, 6], 3) == \
        pytest.approx(2 / 3)
    # padding on either side never counts; k bounds both lists — a
    # served id past position k is NOT a hit (it was not returned in
    # the top-k), and truth entries past k are not demanded
    assert qualmon.recall_row([-1, -1], [-1, -1], 2) == 0.0
    assert qualmon.recall_row([7, 1, 2], [7], 1) == 1.0
    assert qualmon.recall_row([1, 2, 3, 7], [7, 0, 9], 3) == 0.0
    assert qualmon.recall_row([7, 1, 2], [9, 7, 0], 3) == \
        pytest.approx(1 / 3)


def test_recall_row_distance_tie_handling():
    """The CalcRecall distance clause: a served id NOT in the truth set
    still hits when its distance equals a truth distance within
    tolerance — two distinct vectors tied at the same distance are
    equally correct answers (id 20 at dist 0.5 covers truth id 9)."""
    ids, dists = [1, 20, 3], [0.0, 0.5, 0.9]
    truth_ids, truth_dists = [1, 9, 8], [0.0, 0.5, 2.0]
    assert qualmon.recall_row(ids, truth_ids, 3) == pytest.approx(1 / 3)
    assert qualmon.recall_row(ids, truth_ids, 3, dists=dists,
                              truth_dists=truth_dists) == \
        pytest.approx(2 / 3)
    # tolerance is relative: 0.5 vs 0.5000001 matches, 0.5 vs 0.6 not
    assert qualmon.recall_row([20], [9], 1, dists=[0.5000001],
                              truth_dists=[0.5]) == 1.0
    assert qualmon.recall_row([20], [9], 1, dists=[0.6],
                              truth_dists=[0.5]) == 0.0


def test_recall_at_k_batch_and_container_shapes():
    """The bench/IndexSearcher surface: rows as ndarrays, truth as sets
    or lists — one definition for all consumers."""
    ids_all = np.array([[1, 2, 3], [4, 5, -1]])
    assert qualmon.recall_at_k(ids_all, [{1, 9, 8}, {4, 5, 6}], 3) == \
        pytest.approx(0.5)
    assert qualmon.recall_at_k(ids_all, np.array([[1, 9, 8], [4, 5, 6]]),
                               3) == pytest.approx(0.5)
    assert qualmon.recall_at_k([], [], 3) == 0.0


def test_wilson_interval():
    lo, hi = qualmon.wilson(50, 100)
    assert lo == pytest.approx(0.4038, abs=1e-3)
    assert hi == pytest.approx(0.5962, abs=1e-3)
    assert qualmon.wilson(0, 0) == (0.0, 1.0)
    lo, hi = qualmon.wilson(10, 10)
    assert lo > 0.6 and hi == 1.0
    lo, hi = qualmon.wilson(0, 10)
    assert lo == 0.0 and hi < 0.4


def test_dist_recall_greedy_match():
    """Distance-only recall (the aggregator merge check): greedy
    one-to-one matching with relative tolerance."""
    assert qualmon.dist_recall([0.1, 0.2, 0.3], [0.1, 0.2, 0.3], 3) == 1.0
    assert qualmon.dist_recall([0.1, 0.3], [0.1, 0.2], 2) == 0.5
    # one served 0.1 cannot cover two truth 0.1 slots
    assert qualmon.dist_recall([0.1, 5.0], [0.1, 0.1], 2) == 0.5


def test_bench_and_cli_delegate_to_qualmon():
    """One recall: the CLI delegates to the canonical function, and the
    benchmark's reference (which imports nothing of the program, so it
    cannot delegate) gives the same value -- `recall_at_10` is what a
    `qps` of the ledger is stated at."""
    from benchmark.harness import reference
    from sptag_tpu.tools import index_searcher

    ids_all = np.array([[1, 2, 3], [4, 5, -1]])
    truth = [{1, 9, 8}, {4, 5, 6}]
    expect = qualmon.recall_at_k(ids_all, truth, 3)
    # the harness takes the reference's (Q, k) id rows, not sets
    ref_ids = np.array([[1, 9, 8], [4, 5, 6]])
    assert reference.recall_at_k(ids_all, ref_ids, 3) == \
        pytest.approx(expect)
    assert index_searcher.calc_recall(ids_all, truth, 3) == \
        pytest.approx(expect)


# ---------------------------------------------------------------------------
# estimator on a planted corpus (true recall known analytically)
# ---------------------------------------------------------------------------

@pytest.fixture()
def flat_corpus():
    rng = np.random.default_rng(1)
    data = rng.standard_normal((64, 6)).astype(np.float32)
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    idx.build(data)
    return idx, data


def test_estimator_planted_recall(flat_corpus):
    """Half the sampled queries serve their exact top-k, half serve
    garbage — the window recall is analytically 0.5 and the Wilson CI
    straddles it."""
    idx, data = flat_corpus
    qualmon.configure(sample_rate=1.0)
    k = 4
    for i in range(16):
        ex_d, ex_ids = idx.exact_search_batch(data[i], k)
        if i % 2 == 0:
            served = list(ex_ids[0])
        else:
            served = [-1] * k          # total miss
        r = qualmon.recall_row(served, ex_ids[0], k)
        qualmon.record_sample("flat", "main", r, k)
    agg = qualmon.aggregate_stats()
    assert agg["recall"] == pytest.approx(0.5)
    assert agg["lo"] < 0.5 < agg["hi"]
    assert agg["trials"] == 16 * k
    ws = qualmon.window_stats()["flat|main"]
    assert ws["samples"] == 16 and ws["recall"] == pytest.approx(0.5)
    # the labeled exposition carries the same numbers
    text = qualmon.render_prometheus()
    assert 'sptag_tpu_quality_recall_at_k{mode="flat",shard="main"} 0.5' \
        in text


def test_exact_oracle_ignores_approximations(flat_corpus):
    """The shadow oracle must be exact even when the index is configured
    to serve approximately — otherwise it would inherit the very error
    it is supposed to measure."""
    idx, data = flat_corpus
    dn = ((data[:5, None, :] - data[None, :, :]) ** 2).sum(-1)
    true = np.argsort(dn, axis=1)[:, :3].astype(np.int32)
    idx.set_parameter("SketchPrefilter", "1")
    idx.set_parameter("ApproxTopK", "1")
    _, ids = idx.exact_search_batch(data[:5], 3)
    assert np.array_equal(ids, true)


def test_exact_oracle_graph_index_and_deletes():
    """BKT/KDT run the oracle off the engine snapshot's resident arrays;
    deleted rows are excluded like search_batch."""
    rng = np.random.default_rng(2)
    data = rng.standard_normal((80, 6)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0")]:
        assert idx.set_parameter(p, v), p
    idx.build(data)
    try:
        _, ids = idx.exact_search_batch(data[:4], 1)
        assert list(ids[:, 0]) == [0, 1, 2, 3]
        idx.delete(data[:1])
        _, ids = idx.exact_search_batch(data[:1], 1)
        assert ids[0, 0] != 0
    finally:
        idx.close()


# ---------------------------------------------------------------------------
# shadow queue: overflow drops, budget drops — never blocks
# ---------------------------------------------------------------------------

def test_shadow_queue_overflow_drops_never_blocks():
    qualmon.configure(sample_rate=1.0, queue_cap=2)
    release = threading.Event()
    ran = []

    def slow_job():
        release.wait(5)
        ran.append(1)

    # first job may be picked up immediately; saturate queue + worker
    accepted = sum(qualmon.submit(slow_job) for _ in range(8))
    t0 = time.perf_counter()
    dropped = [qualmon.submit(slow_job) for _ in range(16)]
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.5                 # drop path never blocks
    assert not all(dropped)
    c = qualmon.counters()
    assert c["queue_drops"] >= 1
    assert c["submitted"] == accepted + sum(dropped)
    release.set()
    assert qualmon.drain(10)
    assert len(ran) == c["submitted"]


def test_shadow_budget_drops_counted():
    """QualityShadowBudget bounds estimated device FLOPs: an oversized
    job is dropped and counted, zero-cost jobs still flow."""
    qualmon.configure(sample_rate=1.0, shadow_budget_gflops=0.001)
    big = 1e12                           # 1 TFLOP against a 1 MFLOP/s cap
    assert not qualmon.submit(lambda: None, est_flops=big)
    c = qualmon.counters()
    assert c["budget_drops"] == 1
    assert metrics.counter_value("quality.shadow_budget_drops") == 1
    assert qualmon.submit(lambda: None, est_flops=0.0)
    assert qualmon.drain(5)


@pytest.mark.parametrize("shapes,fits", [
    (((50, 8),), True),
    (((50, 8), (30, 8)), True),
    (((400, 16),), False),
])
def test_server_charges_a_sample_two_n_d_flops_a_served_index(shapes, fits):
    """What `_queue_quality_sample` charges against QualityShadowBudget:
    2 x num_samples x feature_dim flops a served index, the dots of one
    exact scan of one query.  The bucket holds two seconds of budget and
    never more: one half a flop wider than the charge admits the sample
    (and the shadow gauge reads the charge), one half a flop narrower
    drops it and counts the drop."""
    rng = np.random.default_rng(len(shapes))
    ctx = ServiceContext(ServiceSettings(default_max_result=3))
    names = []
    for i, (n, d) in enumerate(shapes):
        index = sp.create_instance("FLAT", "Float")
        index.set_parameter("DistCalcMethod", "L2")
        index.build(rng.standard_normal((n, d)).astype(np.float32))
        names.append("idx%d" % i)
        ctx.add_index(names[-1], index)
    charge = sum(2.0 * n * d for n, d in shapes)
    capacity = charge + (0.5 if fits else -0.5)
    qualmon.configure(sample_rate=1.0,
                      shadow_budget_gflops=capacity / 2.0 / 1e9)
    server = SearchServer(ctx, batch_window_ms=1.0)
    text = ("$indexname:%s " % ",".join(names)
            + "|".join(["0.5"] * shapes[0][1]))
    result = SearchExecutor(ctx).execute(text)
    assert [r.index_name for r in result.results] == names
    server._queue_quality_sample("budget-rid", text, result)
    c = qualmon.counters()
    if fits:
        assert (c["submitted"], c["budget_drops"]) == (1, 0)
        assert metrics.gauge_value("quality.shadow_gflops") == \
            pytest.approx(charge / 1e9, rel=1e-9)
        assert metrics.counter_value("quality.shadow_budget_drops") == 0
    else:
        assert (c["submitted"], c["budget_drops"]) == (0, 1)
        assert metrics.counter_value("quality.shadow_budget_drops") == 1
    assert qualmon.drain(10)


def test_shadow_worker_error_is_counted_not_fatal():
    qualmon.configure(sample_rate=1.0)

    def bad():
        raise RuntimeError("boom")

    assert qualmon.submit(bad)
    assert qualmon.submit(lambda: qualmon.inc("after_error"))
    assert qualmon.drain(5)
    assert qualmon.counters()["shadow_errors"] == 1
    assert qualmon.snapshot()["quality_counters"]["after_error"] == 1


def test_sampling_rate_gate_deterministic():
    qualmon.configure(sample_rate=0.25)
    picks = [qualmon.maybe_sample() for _ in range(16)]
    assert sum(picks) == 4
    assert picks == [False, False, False, True] * 4


# ---------------------------------------------------------------------------
# triage classification
# ---------------------------------------------------------------------------

def test_classify_low_recall_verdicts():
    flightrec.note_query_stats("q-budget", iters=8, t_budget=8)
    code, detail = qualmon.classify_low_recall("q-budget", "beam")
    assert code == "beam_budget" and "beam terminated early" in detail
    flightrec.note_query_stats("q-early", iters=2, t_budget=8)
    assert qualmon.classify_low_recall("q-early", "beam")[0] == \
        "beam_converged_early"
    assert qualmon.classify_low_recall("none", "dense")[0] == \
        "dense_prefilter"
    assert qualmon.classify_low_recall("none", "flat", sketch=True)[0] == \
        "sketch_prefilter"
    assert qualmon.classify_low_recall("none", "flat")[0] == "unknown"
    # rids are client-supplied and reusable: a dense query sharing a rid
    # with an earlier budget-starved beam query must NOT inherit its
    # iteration counters (scheduler stats only apply to beam-capable
    # modes)
    assert qualmon.classify_low_recall("q-budget", "dense")[0] == \
        "dense_prefilter"


def test_note_query_stats_merges_producers():
    """The scheduler writes retire numbers; the quality monitor adds its
    verdict later — keys merge, neither producer erases the other."""
    flightrec.note_query_stats("rid-m", segments=3, iters=5, t_budget=8)
    flightrec.note_query_stats("rid-m", quality_recall=0.4,
                               quality_verdict="beam_budget")
    st = flightrec.query_stats("rid-m")
    assert st["segments"] == 3 and st["quality_verdict"] == "beam_budget"


def test_low_recall_sample_triages_and_dumps(tmp_path, caplog):
    """A sample below the floor: request-id-stamped warning with the
    verdict, stats merged under the rid, flight auto-dump written."""
    dump_dir = str(tmp_path / "dumps")
    flightrec.configure(enabled=True, dump_dir=dump_dir)
    qualmon.configure(sample_rate=1.0, recall_floor=0.9)
    flightrec.note_query_stats("rid-low", iters=4, t_budget=4)
    verdict, detail = qualmon.classify_low_recall("rid-low", "beam")
    with caplog.at_level(logging.WARNING, "sptag_tpu.utils.qualmon"):
        qualmon.record_sample("beam", "s0", 0.3, 10, rid="rid-low",
                              verdict=verdict, detail=detail)
    msgs = [r.getMessage() for r in caplog.records]
    assert any("low-recall query rid=rid-low" in m
               and "verdict=beam_budget" in m
               and "beam terminated early" in m for m in msgs), msgs
    st = flightrec.query_stats("rid-low")
    assert st["quality_verdict"] == "beam_budget"
    assert st["quality_recall"] == pytest.approx(0.3)
    dumps = [f for f in os.listdir(dump_dir) if f.endswith(".json")]
    assert dumps, "low-recall flight dump missing"
    with open(os.path.join(dump_dir, dumps[0])) as f:
        assert json.load(f)["otherData"]["reason"] == "low_recall"
    assert qualmon.counters()["low_recall"] == 1


# ---------------------------------------------------------------------------
# index health metrics
# ---------------------------------------------------------------------------

def test_graph_health_metrics():
    """Hand-checkable graph: 0->1->2 chain plus an isolated node 3;
    seeds at 0 reach {0,1,2} of 4 live nodes."""
    graph = np.array([[1, -1], [2, -1], [1, -1], [-1, -1]], np.int32)
    h = qualmon.graph_health(graph, None, np.array([0]))
    assert h["nodes"] == 4
    assert h["degree_min"] == 0 and h["degree_max"] == 1
    assert h["degree_hist"] == [1, 3, 0]     # one 0-degree, three 1-degree
    assert h["reachable_fraction"] == pytest.approx(0.75)
    # edges: 0->1 (1->0? no), 1->2 (2->1? yes), 2->1 (1->2? yes) -> 2/3
    assert h["reciprocal_fraction"] == pytest.approx(2 / 3, abs=1e-3)
    # deleting the isolated node makes the seeds cover every live node
    h2 = qualmon.graph_health(graph, np.array([0, 0, 0, 1], bool),
                              np.array([0]))
    assert h2["reachable_fraction"] == pytest.approx(1.0)
    assert h2["deleted_fraction"] == pytest.approx(0.25)


def test_index_health_published_on_mutation():
    rng = np.random.default_rng(3)
    data = rng.standard_normal((60, 6)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0")]:
        assert idx.set_parameter(p, v), p
    qualmon.configure(sample_rate=1.0)
    idx.build(data)
    try:
        idx.publish_quality_health(shard="shardX")
        h = qualmon.snapshot()["health"]["shardX"]
        for key in ("degree_hist", "reciprocal_fraction",
                    "reachable_fraction", "deleted_fraction", "samples"):
            assert key in h, key
        assert h["samples"] == 60 and h["deleted_fraction"] == 0.0
        # mutation republishes under the sticky label (on the shadow
        # worker — drain before reading)
        idx.delete(data[:1])
        assert qualmon.drain()
        h = qualmon.snapshot()["health"]["shardX"]
        assert h["deleted"] == 1
        assert h["deleted_fraction"] == pytest.approx(1 / 60, abs=1e-3)
        text = qualmon.render_prometheus()
        assert 'quality_graph_reachable_fraction{mode="",shard="shardX"}' \
            in text
    finally:
        idx.close()


def test_health_off_is_no_op():
    """With the monitor off, mutation-path health hooks publish nothing
    (the one-flag-test contract extends to build/add/delete)."""
    rng = np.random.default_rng(4)
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    idx.build(rng.standard_normal((16, 4)).astype(np.float32))
    assert qualmon.snapshot()["health"] == {}


# ---------------------------------------------------------------------------
# params: ini plumbing + set_parameter live-apply
# ---------------------------------------------------------------------------

def test_quality_params_ini_parity(tmp_path):
    ini = tmp_path / "svc.ini"
    ini.write_text("[Service]\nQualitySampleRate=0.25\n"
                   "QualityRecallFloor=0.8\nQualityShadowBudget=2.5\n"
                   "QualityWindow=128\n")
    s = ServiceContext.from_ini(str(ini)).settings
    assert s.quality_sample_rate == 0.25
    assert s.quality_recall_floor == 0.8
    assert s.quality_shadow_budget == 2.5
    assert s.quality_window == 128
    a = AggregatorContext.from_ini(str(ini))
    assert a.quality_sample_rate == 0.25
    assert a.quality_recall_floor == 0.8
    assert a.quality_shadow_budget == 2.5
    assert a.quality_window == 128
    # defaults: off
    ini2 = tmp_path / "empty.ini"
    ini2.write_text("[Service]\n")
    assert ServiceContext.from_ini(str(ini2)) \
        .settings.quality_sample_rate == 0.0
    assert AggregatorContext.from_ini(str(ini2)).quality_sample_rate == 0.0


def test_quality_params_live_apply_via_set_parameter():
    """The flight-recorder pattern: Index.QualitySampleRate etc. apply
    DIRECTLY to the process monitor on a warm index — both ways — and
    each knob maps to its own configure field."""
    idx = sp.create_instance("FLAT", "Float")
    assert not qualmon.enabled()
    assert idx.set_parameter("QualitySampleRate", "0.5")
    assert qualmon.enabled()
    assert idx.set_parameter("QualityRecallFloor", "0.75")
    assert qualmon.recall_floor() == 0.75
    assert idx.set_parameter("QualityWindow", "32")
    cfg = qualmon.snapshot()["config"]
    assert cfg == {"sample_rate": 0.5, "recall_floor": 0.75,
                   "shadow_budget_gflops": 0.0, "window": 32,
                   "queue_cap": qualmon.DEFAULT_QUEUE_CAP}
    assert idx.set_parameter("QualityShadowBudget", "1.5")
    assert qualmon.snapshot()["config"]["shadow_budget_gflops"] == 1.5
    assert idx.set_parameter("QualitySampleRate", "0")
    assert not qualmon.enabled()
    # BKT carries the same registry entries (INI save/load parity)
    bkt = sp.create_instance("BKT", "Float")
    assert bkt.get_parameter("QualitySampleRate") == "0"
    assert "QualitySampleRate=0" in bkt.params.save_config()


# ---------------------------------------------------------------------------
# end-to-end: aggregator over two shards
# ---------------------------------------------------------------------------

def _http_get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    return resp.status, body


def _scrape_gauge(text, name):
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    return None


@pytest.fixture(scope="module")
def beam_index():
    """Tiny continuous-batching BKT shared by the e2e test (the
    test_flightrec pattern — builds dominate suite cost)."""
    rng = np.random.default_rng(7)
    data = rng.standard_normal((120, 8)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0"), ("SearchMode", "beam"),
                 ("MaxCheck", "16"), ("BeamSegmentIters", "2"),
                 ("ContinuousBatching", "1")]:
        assert idx.set_parameter(p, v), p
    idx.build(data)
    idx.search_batch(data[:1], 3)
    yield idx, data
    idx.close()


def test_quality_e2e_aggregator_two_shards(beam_index, tmp_path):
    """THE acceptance loop: two shard servers + aggregator with
    QualitySampleRate=1 on a seeded corpus.  The scraped
    quality.recall_at_k gauge agrees with offline exact recall within
    its published Wilson CI; a deliberately budget-starved query
    (MaxCheck=16 -> T=1 walk iteration) lands a "beam terminated early"
    triage verdict on the request-id-stamped log and a flight dump; and
    /debug/quality serves windows + per-shard health on both tiers."""
    idx, data = beam_index
    dump_dir = str(tmp_path / "dumps")
    qset = dict(default_max_result=3, quality_sample_rate=1.0,
                quality_recall_floor=1.01)   # triage EVERY sample
    ctx_a = ServiceContext(ServiceSettings(**qset))
    ctx_a.add_index("shard_a", idx)
    ctx_b = ServiceContext(ServiceSettings(**qset))
    ctx_b.add_index("shard_b", idx)
    srv_a = SearchServer(ctx_a, batch_window_ms=1.0, metrics_port=-1,
                         flight_recorder=True, flight_dump_dir=dump_dir,
                         flight_tier="server_a")
    srv_b = SearchServer(ctx_b, batch_window_ms=1.0,
                         flight_recorder=True, flight_dump_dir=dump_dir,
                         flight_tier="server_b")
    ta, tb = _ServerThread(srv_a), _ServerThread(srv_b)
    ta.start()
    tb.start()
    (ha, pa), (hb, pb) = ta.wait_ready(60), tb.wait_ready(60)
    agg_ctx = AggregatorContext(search_timeout_s=30.0, metrics_port=-1,
                                merge_top_k=True,
                                quality_sample_rate=1.0,
                                quality_recall_floor=1.01)
    agg_ctx.servers = [RemoteServer(ha, pa), RemoteServer(hb, pb)]
    agg = AggregatorService(agg_ctx)
    tg = _ServerThread(agg)
    tg.start()
    hg, pg = tg.wait_ready(60)

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    qlog = logging.getLogger("sptag_tpu.utils.qualmon")
    capture = Capture()
    qlog.addHandler(capture)
    try:
        from sptag_tpu.serve.client import AnnClient

        client = AnnClient(hg, pg, timeout_s=30.0)
        client.connect()
        served = {}
        k = 3
        nq = 6
        for i in range(nq):
            rid = "qual-e2e-%03d" % i
            qtext = ("$indexname:shard_a,shard_b $maxcheck:16 "
                     + "|".join(str(x) for x in data[i]))
            res = client.search(qtext, request_id=rid)
            assert res.status == wire.ResultStatus.Success
            served[i] = res
        client.close()

        # every sampled query replays in the background; samples are
        # queued just AFTER each response hits the wire, so wait for
        # the expected submissions (2 shard replays + 1 merge check per
        # query), then for the shadow queue to drain
        deadline = time.time() + 30
        while time.time() < deadline and \
                qualmon.counters()["submitted"] < 3 * nq:
            time.sleep(0.05)
        assert qualmon.counters()["submitted"] >= 3 * nq, \
            qualmon.counters()
        assert qualmon.drain(30)

        # labeled gauge vs offline truth: the shard_a window must agree
        # with offline exact recall (served merged entries vs the exact
        # oracle — both shards serve the same index object) within its
        # published Wilson interval, and closely in value at rate=1.
        offline = []
        for i in range(nq):
            ex_d, ex_ids = idx.exact_search_batch(data[i], k)
            for r in served[i].results:
                if r.index_name != "shard_a":
                    continue
                offline.append(qualmon.recall_row(
                    [v for v in r.ids], ex_ids[0], k,
                    dists=[d for d in r.dists], truth_dists=ex_d[0]))
        assert len(offline) == nq
        status, text = _http_get(srv_a._metrics_http.port, "/metrics")
        assert status == 200
        lbl = '{mode="beam",shard="shard_a"}'
        g = _scrape_gauge(text, "sptag_tpu_quality_recall_at_k" + lbl)
        lo = _scrape_gauge(text, "sptag_tpu_quality_recall_at_k_lo" + lbl)
        hi = _scrape_gauge(text, "sptag_tpu_quality_recall_at_k_hi" + lbl)
        assert g is not None and lo is not None and hi is not None
        shard_mean = float(np.mean(offline))
        assert lo - 1e-9 <= shard_mean <= hi + 1e-9, (lo, shard_mean, hi)
        assert g == pytest.approx(shard_mean, abs=0.01)
        # the aggregate (unlabeled) gauge exists too and sits in [0, 1]
        agg_g = metrics.gauge("quality.recall_at_k").value
        assert 0.0 <= agg_g <= 1.0

        # budget-starved triage: MaxCheck=16 -> one walk iteration ->
        # iters == t_budget -> "beam terminated early" on the log
        deadline = time.time() + 10
        while time.time() < deadline and not any(
                "beam terminated early" in m for m in records):
            time.sleep(0.05)
        assert any("low-recall query rid=qual-e2e-" in m
                   and "verdict=beam_budget" in m
                   and "beam terminated early" in m
                   for m in records), records[:5]
        # ... and the flight dump rode along
        deadline = time.time() + 10
        dumps = []
        while time.time() < deadline and not dumps:
            dumps = ([f for f in os.listdir(dump_dir)
                      if f.endswith(".json")]
                     if os.path.isdir(dump_dir) else [])
            time.sleep(0.05)
        assert dumps, "no flight dump for low-recall queries"

        # /debug/quality on the shard tier: windows + per-shard health
        status, body = _http_get(srv_a._metrics_http.port,
                                 "/debug/quality")
        assert status == 200
        q = json.loads(body)
        assert q["enabled"] is True
        assert any(w["shard"] == "shard_a" for w in q["windows"].values())
        assert "shard_a" in q["health"]
        assert "reachable_fraction" in q["health"]["shard_a"]
        # aggregator tier (shared process): merged view includes both
        # shards' windows plus its own merge-agreement samples
        status, body = _http_get(agg._metrics_http.port, "/debug/quality")
        assert status == 200
        qa = json.loads(body)
        shards = {w["shard"] for w in qa["windows"].values()}
        assert {"shard_a", "shard_b"} <= shards
        assert "aggregator" in shards     # the merge check sampled too
    finally:
        qlog.removeHandler(capture)
        tg.stop()
        ta.stop()
        tb.stop()


# ---------------------------------------------------------------------------
# QualitySampleRate=0: byte parity + one flag test
# ---------------------------------------------------------------------------

def test_quality_off_parity_serve_bytes_and_zero_work():
    """With the monitor off (the default), the serve path produces
    byte-identical wire responses to the reference layout and performs
    no quality work — zero samples, zero threads, zero series (the
    ci_check.sh standalone parity pass, mirroring flightrec's)."""
    rng = np.random.default_rng(0)
    data = rng.standard_normal((50, 8)).astype(np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("main", index)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    host, port = t.wait_ready()
    try:
        assert not qualmon.enabled()
        qtext = "|".join(str(x) for x in data[7])
        expected_result = SearchExecutor(ctx).execute(qtext)
        expected_result.request_id = ""
        expected_body = expected_result.pack()
        expected = wire.PacketHeader(
            wire.PacketType.SearchResponse, wire.PacketProcessStatus.Ok,
            len(expected_body), 1, 77).pack() + expected_body

        body = wire.RemoteQuery(qtext).pack()
        s = socket.create_connection((host, port), timeout=10)
        s.sendall(wire.PacketHeader(
            wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
            len(body), 0, 77).pack() + body)
        s.settimeout(10)
        got = b""
        while len(got) < len(expected):
            chunk = s.recv(65536)
            if not chunk:
                break
            got += chunk
        s.close()
        assert got == expected
        assert qualmon.counters() == {
            "enabled": 0, "seen": 0, "sampled": 0, "submitted": 0,
            "queue_drops": 0, "budget_drops": 0, "shadow_errors": 0,
            "low_recall": 0, "shadow_gflops": 0.0}
        assert qualmon.render_prometheus() == ""
        assert qualmon.snapshot()["windows"] == {}
    finally:
        t.stop()
