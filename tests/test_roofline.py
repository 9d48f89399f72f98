"""Capability registry + roofline wiring (ISSUE 6): table lookup, the
disk-cached micro-probe, roofline_row math, engine gauges, and THE
acceptance e2e — an aggregator over two shard servers whose /metrics exposes engine.roofline_pct_peak and
memory.device_bytes, /debug/memory answers, the slow-query log carries
per-query GFLOP/s, and serve wire bytes stay byte-identical with the
new knobs at their defaults."""

import json
import logging
import socket
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
from sptag_tpu.utils import metrics, roofline

from tests.test_serve import _ServerThread


def _http_get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    return resp.status, body


# ---------------------------------------------------------------------------
# capability registry
# ---------------------------------------------------------------------------

def test_tpu_table_lookup_known_generations():
    for kind, bf16, gbps in [("TPU v5 lite", 197e12, 819.0),
                             ("TPU v4", 275e12, 1228.0),
                             ("TPU v3", 123e12, 900.0)]:
        cap = roofline._table_lookup(kind, "tpu")
        assert cap is not None and cap.source == "table"
        assert cap.peak_flops_bf16 == bf16
        assert cap.peak_flops_f32 == bf16 / 4.0
        assert cap.hbm_gbps == gbps


def test_v5p_not_shadowed_by_v5e():
    cap = roofline._table_lookup("TPU v5p", "tpu")
    assert cap.peak_flops_bf16 == 459e12


def test_int8_peak_uses_doubled_path_where_it_exists():
    """v5e-class chips run int8 matmuls at 2x the bf16 rate — scoring
    int8 kernels against the bf16 peak would overstate %-of-peak ~2x."""
    v5e = roofline._table_lookup("TPU v5 lite", "tpu")
    assert v5e.peak_flops("int8") == 2 * v5e.peak_flops_bf16
    v4 = roofline._table_lookup("TPU v4", "tpu")
    assert v4.peak_flops("int8") == v4.peak_flops_bf16
    # probe capabilities have no int8 measurement: fall back to bf16/f32
    probe = roofline.Capability("cpu", "cpu", 1e11, 1e11, 10.0, "probe")
    assert probe.peak_flops("int8") == 1e11


def test_unknown_kind_without_probe_has_no_peaks():
    assert roofline._table_lookup("cpu", "cpu") is None
    cap = roofline.Capability("cpu", "cpu", None, None, None, "none")
    assert cap.pct_of_peak(1e9, 1e9) is None
    assert cap.peak_flops("bf16") is None


def test_probe_outcome_is_disk_cached(tmp_path, monkeypatch):
    """The measured fallback runs device work ONCE per (kind, jax
    version): the second capability() resolves from disk (the PR-4
    probe-cache pattern)."""
    monkeypatch.setenv("SPTAG_TPU_ROOFLINE_CACHE", str(tmp_path))
    calls = []

    def fake_probe():
        calls.append(1)
        return {"peak_flops_f32": 1e11, "hbm_gbps": 10.0}

    monkeypatch.setattr(roofline, "_run_probe", fake_probe)
    roofline.reset()
    cap1 = roofline.capability(probe=True)
    roofline.reset()
    cap2 = roofline.capability(probe=True)
    roofline.reset()
    assert cap1.source == "probe" and cap2.source == "probe"
    assert cap1.peak_flops_f32 == 1e11 == cap2.peak_flops_f32
    assert len(calls) == 1                  # second hit came from disk
    # probe-flag DOWNGRADE live-applies: with probe=False the cached
    # probed capability must not leak through (RooflineProbe=0 turns
    # %-of-peak off on unknown kinds)
    cap3 = roofline.capability(probe=False)
    assert cap3.source == "none" and cap3.peak_flops_f32 is None
    roofline.reset()


def test_roofline_row_math_and_binding_resource():
    cap = roofline.Capability("x", "cpu", 1e12, 1e12, 100.0, "table")
    # compute-bound: high flops per byte
    row = roofline.roofline_row("f", 1e9, 1e3, qps=100.0, cap=cap)
    assert row["achieved_gflops"] == pytest.approx(100.0)
    assert row["pct_peak_flops"] == pytest.approx(10.0)
    assert row["bound"] == "compute"
    # bandwidth-bound: high bytes per flop
    row = roofline.roofline_row("f", 1e3, 1e9, qps=50.0, cap=cap)
    assert row["achieved_gbps"] == pytest.approx(50.0)
    assert row["pct_peak_hbm"] == pytest.approx(50.0)
    assert row["bound"] == "bandwidth"
    assert row["pct_peak"] == row["pct_peak_hbm"]


def test_engine_resolves_capability_without_sampling():
    """The capability resolves at snapshot build even with device-time
    sampling OFF (its default), so the scheduler's slow-query pct_peak
    classification does not silently depend on the sampler."""
    from sptag_tpu.algo.engine import GraphSearchEngine
    from sptag_tpu.core.types import DistCalcMethod

    rng = np.random.default_rng(0)
    data = rng.standard_normal((64, 8)).astype(np.float32)
    graph = rng.integers(0, 64, (64, 4)).astype(np.int32)
    eng = GraphSearchEngine(data, graph, np.arange(8, dtype=np.int32),
                            None, DistCalcMethod.L2, 1, score_dtype="f32")
    assert eng.device_sample_rate == 0.0
    assert eng._capability is not None      # "none"-source at worst


def test_engine_gauges_published_on_sampled_segments():
    """FlightDeviceSampleRate=1 + RooflineProbe: every segment dispatch
    publishes achieved GFLOP/s / GB/s and %-of-peak gauges."""
    rng = np.random.default_rng(0)
    data = rng.standard_normal((120, 8)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0"), ("SearchMode", "beam"),
                 ("MaxCheck", "64"), ("BeamSegmentIters", "2"),
                 ("FlightDeviceSampleRate", "1"), ("RooflineProbe", "1")]:
        assert idx.set_parameter(p, v), p
    idx.build(data)
    idx.search_batch(data[:4], 3)
    assert metrics.gauge("engine.achieved_gflops").value > 0
    assert metrics.gauge("engine.achieved_gbps").value > 0
    # RooflineProbe=1 guarantees a capability on every platform (table
    # on TPU, measured probe here on CPU) -> the pct gauge exists
    assert metrics.gauge("engine.roofline_pct_peak").value > 0
    idx.close()


# ---------------------------------------------------------------------------
# acceptance e2e: aggregator + 2 shards
# ---------------------------------------------------------------------------

@pytest.fixture()
def roofline_serving(tmp_path):
    rng = np.random.default_rng(0)
    data = rng.standard_normal((120, 8)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0"), ("SearchMode", "beam"),
                 ("MaxCheck", "64"), ("BeamSegmentIters", "2"),
                 ("FlightDeviceSampleRate", "1"), ("RooflineProbe", "1"),
                 ("ContinuousBatching", "1")]:
        assert idx.set_parameter(p, v), p
    idx.build(data)
    idx.search_batch(data[:1], 3)
    yield idx, data
    idx.close()


def test_roofline_e2e_aggregator_two_shards(roofline_serving):
    """ISSUE 6 acceptance: scrape engine.roofline_pct_peak and
    memory.device_bytes from /metrics, fetch /debug/memory, and find the
    per-query GFLOP/s attribution in the slow-query log."""
    idx, data = roofline_serving
    ctx_a = ServiceContext(ServiceSettings(default_max_result=3))
    ctx_a.add_index("shard_a", idx)
    ctx_b = ServiceContext(ServiceSettings(default_max_result=3))
    ctx_b.add_index("shard_b", idx)
    srv_a = SearchServer(ctx_a, batch_window_ms=1.0, metrics_port=-1,
                         slow_query_threshold_ms=1e-6,
                         flight_recorder=True, flight_tier="server_a")
    srv_b = SearchServer(ctx_b, batch_window_ms=1.0,
                         slow_query_threshold_ms=1e-6,
                         flight_recorder=True, flight_tier="server_b")
    ta, tb = _ServerThread(srv_a), _ServerThread(srv_b)
    ta.start()
    tb.start()
    (ha, pa), (hb, pb) = ta.wait_ready(60), tb.wait_ready(60)
    agg_ctx = AggregatorContext(search_timeout_s=30.0)
    agg_ctx.servers = [RemoteServer(ha, pa), RemoteServer(hb, pb)]
    agg = AggregatorService(agg_ctx)
    tg = _ServerThread(agg)
    tg.start()
    hg, pg = tg.wait_ready(60)

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    shard_log = logging.getLogger("sptag_tpu.serve.server")
    capture = Capture()
    shard_log.addHandler(capture)
    rid = "e2e-roofline-007"
    try:
        from sptag_tpu.serve.client import AnnClient

        client = AnnClient(hg, pg, timeout_s=30.0)
        client.connect()
        qtext = ("$indexname:shard_a,shard_b $maxcheck:64 "
                 + "|".join(str(x) for x in data[5]))
        res = client.search(qtext, request_id=rid)
        assert res.status == wire.ResultStatus.Success
        client.close()

        # /metrics: the roofline gauges and the memory.device_bytes
        # component gauges, plus the flight health gauges (satellite:
        # they were counters()-only before)
        deadline = time.time() + 10
        text = ""
        while time.time() < deadline:
            status, text = _http_get(srv_a._metrics_http.port, "/metrics")
            assert status == 200
            if "sptag_tpu_engine_roofline_pct_peak" in text:
                break
            time.sleep(0.05)
        assert "sptag_tpu_engine_roofline_pct_peak" in text
        assert "sptag_tpu_engine_achieved_gflops" in text
        assert 'sptag_tpu_memory_device_bytes{component="corpus"}' in text
        assert 'sptag_tpu_memory_device_bytes{component="graph"}' in text
        assert "sptag_tpu_flight_recorded" in text
        assert "sptag_tpu_flight_dump_ratelimited" in text

        # /debug/memory: the ledger snapshot with the live-arrays
        # cross-check, on BOTH tiers
        status, body = _http_get(srv_a._metrics_http.port, "/debug/memory")
        assert status == 200
        snap = json.loads(body)
        assert snap["components"].get("corpus", 0) > 0
        assert snap["ledger_device_bytes"] <= snap["live_arrays_bytes"]

        # slow-query log: per-query achieved GFLOP/s (+ %-of-peak via
        # the probe capability) classifies the slow query
        deadline = time.time() + 10
        while time.time() < deadline:
            if any(("rid=%s" % rid) in m and "gflops=" in m
                   for m in records):
                break
            time.sleep(0.05)
        hits = [m for m in records
                if ("rid=%s" % rid) in m and "gflops=" in m]
        assert hits, records
        assert any("pct_peak=" in m for m in hits), hits
    finally:
        shard_log.removeHandler(capture)
        tg.stop()
        ta.stop()
        tb.stop()


def test_serve_bytes_identical_with_new_knobs_at_defaults():
    """RooflineProbe / DeviceBytesLedger / the gauges never touch the
    wire path: with the knobs at their defaults the serve response is
    byte-identical to the reference layout (the same golden-bytes
    construction as the flight off-parity gate)."""
    rng = np.random.default_rng(0)
    data = rng.standard_normal((50, 8)).astype(np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    assert index.get_parameter("RooflineProbe") == "0"
    assert index.get_parameter("DeviceBytesLedger") == "1"
    index.build(data)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("main", index)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = _ServerThread(server)
    t.start()
    host, port = t.wait_ready(60)
    try:
        qtext = "|".join(str(x) for x in data[7])
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
