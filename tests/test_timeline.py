"""Serving timeline + SLO burn-rate engine + ground-truth canary + mesh
skew telemetry (ISSUE 15).

Units: ring bounds and coarse downsampling, counter→rate math,
histogram extraction, the unified labeled-family sampling surface, the
SLO state machine under a fake clock (ok → warn → page → ok with flight
events), canary probe ground-truth parity vs the oracle, the
shard_skew triage verdict, and canary admission isolation.

E2e: a server-tier canary measuring exact recall 1.0 through the full
serve path; THE acceptance drill — an aggregator over two shards with a
fault-injected slow shard driving the latency objective to ``page``,
visible on /debug/slo, /metrics (slo_* gauges) and a flightrec
transition event, with the backend-skew family naming the slow shard;
and the mesh scheduler's per-shard iteration series in /debug/timeline.

Off-parity: with every ISSUE 15 knob at its default the serve wire
bytes are byte-identical, no sampler/prober thread exists and the
timeline counters read zero (the ci_check.sh standalone pass).
"""

import json
import logging
import socket
import threading
import time

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.serve import canary as canary_mod
from sptag_tpu.serve import protocol, slo, wire
from sptag_tpu.serve.aggregator import (AggregatorContext,
                                        AggregatorService, RemoteServer)
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import flightrec, metrics, qualmon, timeline

from conftest import ServerThread


def _http_get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    return resp.status, body


def _flat_index(n=60, d=8, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n, d)).astype(np.float32)
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    idx.build(data)
    return idx, data


# ---------------------------------------------------------------------------
# timeline store units
# ---------------------------------------------------------------------------

def test_ring_bounds_and_coarse_downsampling():
    """Fine rings are hard-bounded; every `coarse_every` fine points
    fold into one (mean, min, max) aggregate covering a longer horizon
    at the same fixed memory."""
    timeline.configure(enabled=True, capacity=8, coarse_every=4)
    for i in range(50):
        timeline.record("t.series", float(i), now=float(i))
    fine = timeline.points("t.series")
    assert len(fine) == 8                      # ring bound
    assert fine[-1] == (49.0, 49.0)
    coarse = timeline.points("t.series", coarse=True)
    assert 0 < len(coarse) <= 8
    # each coarse point is the mean of its 4-sample window
    t, mean = coarse[0][0], coarse[0][1]
    assert mean == pytest.approx(t - 1.5)      # mean(i-3..i) = i - 1.5


def test_counter_rate_and_histogram_extraction():
    """Counters become per-second rates against the previous tick;
    histograms contribute p50/p99 (ms) and an observation rate; gauges
    sample as-is."""
    timeline.configure(enabled=True)
    metrics.inc("t.ctr", 5)
    metrics.set_gauge("t.gauge", 7.5)
    assert timeline.sample_now(now=0.0) > 0
    assert timeline.latest("t.ctr.rate") is None     # first tick: no rate
    metrics.inc("t.ctr", 10)
    metrics.observe("t.lat", 0.050)
    timeline.sample_now(now=2.0)
    assert timeline.latest("t.ctr.rate") == pytest.approx(5.0)
    assert timeline.latest("t.gauge") == 7.5
    assert timeline.latest("t.lat.p99_ms") == pytest.approx(50.0)


def test_window_values_extend_into_coarse_ring():
    """A query window longer than the fine ring's span is covered by
    coarse means — the slow burn window's long-horizon path."""
    timeline.configure(enabled=True, capacity=8, coarse_every=2)
    for i in range(50):
        timeline.record("t.w", float(i), now=float(i))
    short = timeline.window_values("t.w", 5.0, now=49.0)
    assert short == [44.0, 45.0, 46.0, 47.0, 48.0, 49.0]
    long = timeline.window_values("t.w", 40.0, now=49.0)
    assert len(long) > 8                       # coarse entries prepended
    st = timeline.window_stats("t.w", 5.0, now=49.0)
    assert st["last"] == 49.0 and st["n"] == 6


def test_labeled_families_sampled_into_series():
    """The timeline samples the SAME labeled-series provider surface
    /metrics renders (the ISSUE 15 dedupe contract): a devmem component
    appears as its labeled series key."""
    from sptag_tpu.utils import devmem

    class Owner:
        pass

    o = Owner()
    devmem.track("corpus", o, 4096)
    timeline.configure(enabled=True)
    timeline.sample_now(now=1.0)
    key = 'memory.device_bytes{component="corpus"}'
    assert timeline.latest(key) == 4096.0
    assert key in timeline.snapshot()["series"]


def test_series_cap_counts_overflow():
    timeline.configure(enabled=True)
    base = timeline.counters()["series"]
    for i in range(timeline.MAX_SERIES + 5 - base):
        timeline.record("t.cap", float(i), label="i=%d" % i, now=0.0)
    c = timeline.counters()
    assert c["series"] <= timeline.MAX_SERIES
    assert c["series_dropped"] >= 5


def test_timeline_cli_sparkline_and_report():
    from sptag_tpu.tools import timeline as tlcli

    assert tlcli.sparkline([]) == ""
    assert tlcli.sparkline([1.0, 1.0]) == "▄▄"
    line = tlcli.sparkline(list(range(100)), width=10)
    assert len(line) == 10 and line[0] == "▁" and line[-1] == "█"
    timeline.configure(enabled=True)
    timeline.record("t.cli", 1.0, now=0.0)
    timeline.record("t.cli", 9.0, now=1.0)
    lines = tlcli.report(timeline.snapshot())
    assert any("t.cli" in ln and "max 9" in ln for ln in lines)


# ---------------------------------------------------------------------------
# SLO burn-rate state machine (fake clock)
# ---------------------------------------------------------------------------

def test_slo_burn_rate_state_machine_fake_clock():
    """Multi-window burn: warn needs BOTH windows over warn_burn, page
    needs both over page_burn, recovery drains through the fast window
    — each transition emits a flight event and bumps the counter."""
    timeline.configure(enabled=True, capacity=256)
    flightrec.configure(enabled=True)
    cfg = slo.SloConfig(availability_target=0.95, fast_window_s=10.0,
                        slow_window_s=30.0, warn_burn=1.0, page_burn=4.0)
    eng = slo.SloEngine(cfg, tier="server", clock=lambda: 0.0)
    # budget = 1 - 0.95 = 0.05 violating-sample fraction
    for t in range(30):
        timeline.record("canary.ok", 1.0, now=float(t))
    eng.evaluate(now=29.0)
    objs = eng.snapshot()["objectives"]
    assert objs["availability"]["state"] == "ok"
    # partial outage: 4 bad samples -> fast burn high, slow burn ~2.6
    for t in range(30, 34):
        timeline.record("canary.ok", 0.0, now=float(t))
    eng.evaluate(now=33.0)
    assert eng.snapshot()["objectives"]["availability"]["state"] == "warn"
    # sustained outage -> both windows past page_burn
    for t in range(34, 46):
        timeline.record("canary.ok", 0.0, now=float(t))
    eng.evaluate(now=45.0)
    snap = eng.snapshot()["objectives"]["availability"]
    assert snap["state"] == "page"
    assert snap["burn_fast"] >= cfg.page_burn
    assert snap["burn_slow"] >= cfg.page_burn
    # recovery: healthy long enough that BOTH windows drain
    for t in range(46, 90):
        timeline.record("canary.ok", 1.0, now=float(t))
    eng.evaluate(now=89.0)
    snap = eng.snapshot()["objectives"]["availability"]
    assert snap["state"] == "ok"
    assert snap["transitions"] == 3
    kinds = [e["payload"] for e in flightrec.collect()
             if e["kind"] == "slo_transition"]
    assert [(p["from"], p["to"]) for p in kinds] == [
        ("ok", "warn"), ("warn", "page"), ("page", "ok")]
    assert metrics.counter_value("slo.transitions") == 3
    # the labeled exposition carries the per-objective state
    text = metrics.render_provider_families()
    assert 'sptag_tpu_slo_state{objective="availability",tier="server"} 0' \
        in text


def test_slo_insufficient_samples_holds_state():
    """Too few fast-window samples must not flap the verdict — no data
    is not a page."""
    timeline.configure(enabled=True)
    eng = slo.SloEngine(slo.SloConfig(availability_target=0.99,
                                      fast_window_s=10.0,
                                      slow_window_s=30.0, min_samples=3),
                        clock=lambda: 0.0)
    timeline.record("canary.ok", 0.0, now=0.0)
    eng.evaluate(now=1.0)
    assert eng.snapshot()["objectives"]["availability"]["state"] == "ok"


def test_slo_config_from_settings_duck_types_both_tiers():
    s = ServiceSettings(slo_p99_ms=125.0, slo_fast_window_s=5.0)
    cfg = slo.config_from_settings(s)
    assert cfg.p99_ms == 125.0 and cfg.fast_window_s == 5.0
    assert slo.armed(cfg)
    assert not slo.armed(slo.config_from_settings(ServiceSettings()))
    a = AggregatorContext(slo_recall_floor=0.8)
    assert slo.armed(slo.config_from_settings(a))


# ---------------------------------------------------------------------------
# canary: ground truth parity + isolation
# ---------------------------------------------------------------------------

def test_canary_probe_truth_matches_oracle_exactly():
    """Pinned truth == the oracle's answer, and the probe text
    round-trips the exact float32 vector (the parity satellite).

    Bit for bit against the oracle called as the pinning calls it (all
    probes in one batch).  A probe is REPLAYED alone, and a one-row scan
    rounds |q|^2 + |x|^2 - 2 q.x otherwise than a six-row one (a
    self-distance reads 0.0 in one and 1 ulp of the norms, 3.8e-6, in
    the other): there the ids must be equal and the distances equal by
    the rule the prober itself judges with (qualmon.recall_row's
    DEFAULT_DIST_TOL) — this test used to ask 1e-6 relative of a 0.0."""
    idx, data = _flat_index(n=50, d=8)
    ctx = ServiceContext(ServiceSettings())
    ctx.add_index("main", idx)
    probes = canary_mod.probes_from_context(ctx, count=6, k=5)
    assert len(probes) == 6
    vecs = []
    for p in probes:
        parsed = protocol.parse_query(p.text)
        vec = parsed.extract_vector(idx.value_type, "|")
        assert vec is not None
        vecs.append(vec)
        ex_d, ex_ids = idx.exact_search_batch(vec.reshape(1, -1), 5)
        assert p.truth_ids == [int(v) for v in ex_ids[0]]
        for got, want in zip(p.truth_dists, ex_d[0]):
            assert abs(got - float(want)) <= \
                qualmon.DEFAULT_DIST_TOL * max(abs(float(want)), 1.0)
        assert parsed.result_num == 5          # $resultnum pins served k
    # the text round trip is exact: every probe is a corpus row
    rows = [int(v) for v in np.linspace(0, 49, num=6, dtype=np.int64)]
    np.testing.assert_array_equal(np.stack(vecs), data[rows])
    ex_d, ex_ids = idx.exact_search_batch(np.stack(vecs), 5)
    assert [p.truth_ids for p in probes] == ex_ids.tolist()
    assert [p.truth_dists for p in probes] == \
        [[float(d) for d in row] for row in ex_d]


def test_admission_canary_exempt_from_fair_shares():
    """A canary-flagged admit rides the state ladder but never charges
    the fair-share table (the isolation contract's admission half)."""
    from sptag_tpu.serve.admission import (ADMIT, DEGRADE,
                                           AdmissionConfig,
                                           AdmissionController)

    clock = [0.0]
    ctrl = AdmissionController(AdmissionConfig(), clock=lambda: clock[0])
    assert ctrl.admit("probe", canary=True) == ADMIT
    assert "probe" not in ctrl._clients        # never share-charged
    ctrl._state = 1                            # degrade state
    assert ctrl.admit("probe", canary=True) == DEGRADE
    assert "probe" not in ctrl._clients


def test_classify_low_recall_shard_skew_verdict():
    """A budget-exhausted sample whose per-shard iteration counters
    show a straggler is triaged shard_skew, naming the shard; balanced
    counters keep the beam_budget verdict."""
    flightrec.note_query_stats("rid-skew", iters=128, t_budget=128,
                               shard_imbalance=2.1, slow_shard=3)
    verdict, detail = qualmon.classify_low_recall("rid-skew", "beam")
    assert verdict == "shard_skew"
    assert "shard 3" in detail
    flightrec.note_query_stats("rid-flat", iters=128, t_budget=128,
                               shard_imbalance=1.05, slow_shard=0)
    verdict, _ = qualmon.classify_low_recall("rid-flat", "beam")
    assert verdict == "beam_budget"


def test_canary_e2e_server_tier_exact_recall_and_isolation(tmp_path):
    """Canary armed on a real server: probes replay through the full
    serve path, exact recall lands at 1.0 in the timeline and families,
    and — with qualmon armed at rate 1 — the live quality windows see
    ZERO samples (the isolation contract's qualmon half)."""
    idx, data = _flat_index()
    ctx = ServiceContext(ServiceSettings(default_max_result=5,
                                         canary_probes=4))
    ctx.add_index("main", idx)
    server = SearchServer(ctx, batch_window_ms=1.0,
                          timeline_interval_ms=50.0,
                          canary_interval_ms=30.0,
                          quality_sample_rate=1.0)
    t = ServerThread(server)
    t.start()
    t.wait_ready(60)
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if metrics.counter_value("canary.probes") >= 5:
                break
            time.sleep(0.05)
        assert metrics.counter_value("canary.probes") >= 5
        assert metrics.counter_value("canary.failures") == 0
        assert timeline.latest("canary.recall") == 1.0
        assert timeline.latest("canary.ok") == 1.0
        snap = server._canary.snapshot()
        assert snap["indexes"]["main"]["recall_mean"] == 1.0
        # canary rids excluded from the live quality windows
        qualmon.drain()
        assert qualmon.window_stats() == {}
        text = metrics.render_provider_families()
        assert ('sptag_tpu_canary_recall{index="main",tier="server"} 1.0'
                in text)
    finally:
        t.stop()
    # the prober thread died with the server
    assert not any(th.name == "canary-prober"
                   for th in threading.enumerate())


# ---------------------------------------------------------------------------
# THE acceptance drill: fault-injected slow shard -> page
# ---------------------------------------------------------------------------

def _boot_shard(idx, name, fault_spec=None):
    ctx = ServiceContext(ServiceSettings(default_max_result=3))
    ctx.add_index(name, idx)
    srv = SearchServer(ctx, batch_window_ms=1.0, fault_spec=fault_spec)
    t = ServerThread(srv)
    t.start()
    return t, t.wait_ready(60)


@pytest.mark.locksan_ok
def test_e2e_drill_slow_shard_drives_page(tmp_path):
    """ISSUE 15 acceptance: a fault-injected slow shard drives the
    aggregator's latency objective to page — visible on /debug/slo,
    /metrics (slo_* gauges) and a flightrec transition event — while
    the backend-skew family names the slow shard."""
    idx, data = _flat_index(n=40, d=8)
    ta, (ha, pa) = _boot_shard(idx, "main")
    tb, (hb, pb) = _boot_shard(idx, "main",
                               fault_spec="delay@server.respond:ms=250,p=1")
    probe_file = tmp_path / "probes.txt"
    probe_file.write_text(
        "$resultnum:3 " + "|".join(repr(float(x)) for x in data[7]) + "\n")
    agg_ctx = AggregatorContext(
        search_timeout_s=30.0, metrics_port=-1,
        flight_recorder=True,
        timeline_interval_ms=100.0,
        slo_p99_ms=60.0, slo_fast_window_s=1.0, slo_slow_window_s=2.5,
        slo_warn_burn=1.0, slo_page_burn=4.0,
        canary_interval_ms=50.0, canary_probe_file=str(probe_file))
    agg_ctx.servers = [RemoteServer(ha, pa), RemoteServer(hb, pb)]
    agg = AggregatorService(agg_ctx)
    tg = ServerThread(agg)
    tg.start()
    tg.wait_ready(60)
    mport = agg._metrics_http.port
    try:
        deadline = time.time() + 30
        state = ""
        while time.time() < deadline:
            status, body = _http_get(mport, "/debug/slo")
            assert status == 200
            snap = json.loads(body)
            state = snap.get("objectives", {}).get(
                "latency_p99", {}).get("state", "")
            if state == "page":
                break
            time.sleep(0.1)
        assert state == "page", snap
        # canary picture rides the same page
        assert snap["canary"]["indexes"]["aggregator"]["probes"] > 0
        # /metrics: the slo_* gauges say page (code 2)
        status, text = _http_get(mport, "/metrics")
        assert status == 200
        assert ('sptag_tpu_slo_state{objective="latency_p99",'
                'tier="aggregator"} 2') in text
        # the backend-skew family names the slow shard as straggler
        slow = "%s:%d" % (hb, pb)
        assert ('sptag_tpu_aggregator_backend_straggler{backend="%s"} 1'
                % slow) in text
        # the flight ring carries the transition event
        status, body = _http_get(mport, "/debug/flight")
        assert status == 200
        trace_json = json.loads(body)
        trans = [e for e in trace_json["flightEvents"]
                 if e["kind"] == "slo_transition"]
        assert any(e["payload"]["to"] == "page" for e in trans)
        # /debug/timeline serves the canary + slo series history
        status, body = _http_get(mport, "/debug/timeline?series=canary")
        assert status == 200
        tl = json.loads(body)
        assert any(k.startswith("canary.latency_ms")
                   for k in tl["series"])
    finally:
        tg.stop()
        ta.stop()
        tb.stop()


# ---------------------------------------------------------------------------
# mesh shard-skew series
# ---------------------------------------------------------------------------

def test_mesh_scheduler_publishes_shard_skew_series(host_mesh):
    """The mesh scheduler's (cap, n_shards) iteration counters surface
    as per-shard labeled series the timeline records (the /debug/
    timeline acceptance surface) plus skew/straggler gauges, and every
    retired rid carries its per-shard imbalance stats."""
    from sptag_tpu.algo.scheduler import gather_futures
    from sptag_tpu.parallel.sharded import ShardedBKTIndex

    rng = np.random.default_rng(3)
    data = rng.standard_normal((128, 16)).astype(np.float32)
    index = ShardedBKTIndex.build(
        data, DistCalcMethod.L2, mesh=host_mesh(2),
        params={"BKTNumber": 1, "BKTKmeansK": 4, "TPTNumber": 2,
                "TPTLeafSize": 32, "NeighborhoodSize": 8, "CEF": 16,
                "MaxCheckForRefineGraph": 64, "RefineIterations": 1,
                "MaxCheck": 64, "SearchMode": "beam"})
    timeline.configure(enabled=True)
    index.enable_continuous_batching(slots=32)
    rids = ["skew-%d" % i for i in range(6)]
    futs = index.submit_batch(data[:6, :], 5, rids=rids)
    gather_futures(futs, 5)
    fams = {f.name: f for f in metrics.collect_families()}
    assert "scheduler.shard_iters" in fams
    shards = {lbl["shard"] for lbl, _v in
              fams["scheduler.shard_iters"].samples}
    assert shards == {"0", "1"}
    timeline.sample_now(now=1.0)
    keys = [k for k in timeline.series_names()
            if k.startswith("scheduler.shard_iters{")]
    assert len(keys) == 2
    st = flightrec.query_stats("skew-0")
    assert st is not None and "shard_imbalance" in st
    assert st["slow_shard"] in (0, 1)
    assert metrics.gauge_value("scheduler.shard_skew") >= 0.0


# ---------------------------------------------------------------------------
# off-parity: everything default == byte-identical + zero work
# ---------------------------------------------------------------------------

def test_timeline_off_parity_serve_bytes_and_no_threads():
    """With every ISSUE 15 knob at its default the serve path produces
    byte-identical wire responses, the timeline counters read zero and
    no sampler/prober thread exists (the ci_check.sh standalone parity
    pass)."""
    idx, data = _flat_index(n=50, d=8)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("main", idx)
    server = SearchServer(ctx, batch_window_ms=1.0)
    t = ServerThread(server)
    t.start()
    host, port = t.wait_ready(60)
    try:
        assert not timeline.enabled()
        assert server._slo is None and server._canary is None
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
        assert timeline.counters() == {
            "enabled": 0, "samples": 0, "recorded": 0, "series": 0,
            "series_dropped": 0, "listener_errors": 0}
        names = {th.name for th in threading.enumerate()}
        assert "timeline-sampler" not in names
        assert "canary-prober" not in names
        # record() with the store off is a no-op flag test
        timeline.record("t.off", 1.0)
        assert timeline.series_names() == []
    finally:
        t.stop()
