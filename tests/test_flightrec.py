"""Flight recorder (ISSUE 5): ring semantics, epoch-swap drain under
threads, Chrome-trace golden schema, scheduler/engine attribution, the
aggregator+2-shard end-to-end trace with flow arrows, the merge CLI,
and the FlightRecorder=off byte-parity / zero-work contract."""

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
from sptag_tpu.tools import flight as flight_cli
from sptag_tpu.utils import flightrec, metrics

from tests.test_serve import _ServerThread


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_recorder_off_is_zero_work():
    """Off (the default): record() is a flag test — no events, no
    thread-local buffers minted, counters stay zero."""
    assert not flightrec.enabled()
    for _ in range(100):
        flightrec.record("server", "decode", "rid", dur_ns=5)
    with flightrec.span("server", "execute"):
        pass
    c = flightrec.counters()
    assert c == {"enabled": 0, "recorded": 0, "dropped": 0, "threads": 0,
                 "dump_errors": 0, "dump_ratelimited": 0}
    assert flightrec.collect() == []


def test_ring_overflow_drops_oldest_never_blocks():
    flightrec.configure(enabled=True, max_events=64)
    t0 = time.perf_counter()
    for i in range(1000):
        flightrec.record("t", "ev", payload={"seq": i})
    dt = time.perf_counter() - t0
    assert dt < 2.0                       # appends, not blocking waits
    evs = flightrec.collect()
    assert len(evs) == 64
    seqs = [e["payload"]["seq"] for e in evs]
    assert seqs == list(range(936, 1000))       # newest survive, in order
    c = flightrec.counters()
    assert c["recorded"] == 1000
    assert c["dropped"] == 936


def test_reset_restores_defaults():
    flightrec.configure(enabled=True, max_events=8, dump_dir="/tmp/x")
    flightrec.record("t", "ev")
    flightrec.note_query_stats("r", segments=1)
    flightrec.reset()
    assert not flightrec.enabled()
    assert flightrec.collect() == []
    assert flightrec.query_stats("r") is None
    c = flightrec.counters()
    assert c["recorded"] == 0 and c["threads"] == 0


def test_thread_hammer_epoch_swap_drain():
    """8 writers hammer the per-thread buffers while the main thread
    drains concurrently: nothing deadlocks, nothing is delivered twice,
    and accounting closes (delivered + dropped == recorded).

    Hardened (ISSUE 15 satellite): the drainer is DEADLINE-PACED on the
    stop event instead of spinning — a free-spinning drainer performs
    thousands of epoch swaps, and the documented race ("a writer racing
    the swap can strand at most ONE in-flight append per thread PER
    SWAP") then loses more than the old fixed `n_threads` slack allowed
    on a loaded suite host.  The loss bound below is the TRUE invariant
    — swaps-while-writing × writers — so the test cannot flake without
    a real recorder bug, and the pacing keeps the expected loss tiny."""
    n_threads, per_thread = 8, 2000
    flightrec.configure(enabled=True, max_events=4 * n_threads * per_thread)
    stop = threading.Event()
    drained = []
    drains = [0]

    def writer(t):
        for i in range(per_thread):
            flightrec.record("hammer", "ev", payload={"t": t, "i": i})

    def drainer():
        # wait() (deadline-based, stop-aware) rather than a bare
        # sleep/spin: stop takes effect immediately and each tick is
        # one epoch swap, counted for the loss bound
        while not stop.wait(0.002):
            drains[0] += 1
            drained.extend(flightrec.drain())
    dthread = threading.Thread(target=drainer)
    dthread.start()
    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    swaps_while_writing = drains[0] + 1   # +1: a tick mid-join race
    stop.set()
    dthread.join()
    # final drain AFTER every writer joined cannot strand anything
    drained.extend(flightrec.drain())
    c = flightrec.counters()
    assert c["recorded"] == n_threads * per_thread
    keys = [(e["payload"]["t"], e["payload"]["i"]) for e in drained]
    assert len(keys) == len(set(keys))          # exactly-once delivery
    # accounting never invents events, and the loss is bounded by the
    # race's real geometry: at most one in-flight append per thread per
    # concurrent swap
    assert len(keys) + c["dropped"] <= c["recorded"]
    assert len(keys) >= (c["recorded"] - c["dropped"]
                         - swaps_while_writing * n_threads)


# ---------------------------------------------------------------------------
# Chrome-trace export schema
# ---------------------------------------------------------------------------

def test_chrome_trace_export_golden_schema():
    flightrec.configure(enabled=True)
    flightrec.record("aggregator", "request", "rid-1", dur_ns=5000)
    flightrec.record("server_a", "execute", "rid-1", dur_ns=3000,
                     payload={"batch": 2})
    flightrec.record("server_a", "enqueue", "rid-1")          # instant
    flightrec.record("scheduler", "segment", dur_ns=1000)     # untagged
    trace = flightrec.export_chrome_trace()
    evs = trace["traceEvents"]
    # process metadata: one pid per tier, named
    meta = {e["args"]["name"]: e["pid"] for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert set(meta) == {"aggregator", "server_a", "scheduler"}
    assert len(set(meta.values())) == 3
    # complete spans carry ts + dur (microseconds); instants are ph=i
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"request", "execute", "segment"}
    for e in spans:
        assert e["dur"] > 0 and e["ts"] > 0 and "pid" in e and "tid" in e
    ex = next(e for e in spans if e["name"] == "execute")
    assert ex["args"]["rid"] == "rid-1" and ex["args"]["batch"] == 2
    assert any(e["ph"] == "i" and e["name"] == "enqueue" for e in evs)
    # flow arrows: s -> t -> f chain (3 rid-tagged events) sharing one id
    flows = [e for e in evs if e.get("cat") == "flight.flow"]
    assert {f["ph"] for f in flows} == {"s", "t", "f"}
    assert len({f["id"] for f in flows}) == 1
    # raw events ride along for the merge CLI
    assert len(trace["flightEvents"]) == 4
    assert trace["otherData"]["counters"]["recorded"] == 4
    json.dumps(trace)                     # the whole artifact serializes


def test_dump_dir_is_ringed(tmp_path):
    d = str(tmp_path / "dumps")
    flightrec.configure(enabled=True, dump_dir=d, dump_max_files=3,
                        dump_min_interval_s=0)
    flightrec.record("t", "ev")
    paths = [flightrec.dump_to_file("slow", "r%d" % i) for i in range(7)]
    assert all(p for p in paths)
    left = sorted(fn for fn in os.listdir(d) if fn.endswith(".json"))
    assert len(left) == 3
    assert os.path.basename(paths[-1]) in left      # newest kept
    with open(os.path.join(d, left[-1])) as f:
        data = json.load(f)
    assert data["otherData"]["reason"] == "slow"
    assert data["otherData"]["pid"] == os.getpid()


def test_dump_failure_is_counted_not_raised(tmp_path):
    """An unwritable dump dir must be visible (the serve tiers fire
    dumps from discarded executor futures): dump_to_file returns None,
    counts the failure, and never raises."""
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    flightrec.configure(enabled=True, dump_dir=str(blocker / "sub"))
    flightrec.record("t", "ev")
    assert flightrec.dump_to_file("slow", "r1") is None
    assert flightrec.counters()["dump_errors"] == 1


def test_merge_same_process_dumps_share_one_tier(tmp_path):
    """Two ringed dumps of ONE process (same otherData.pid) must not be
    split into two Perfetto processes; the same tier name from two
    DIFFERENT pids must."""
    flightrec.configure(enabled=True)
    flightrec.record("server", "request", "r1", dur_ns=100)
    flightrec.record("server", "request", "r2", dur_ns=100)
    raw = flightrec.collect()
    d1, d2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    with open(d1, "w") as f:          # two overlapping dumps, one pid
        json.dump({"flightEvents": raw[:1],
                   "otherData": {"pid": 1234}}, f)
    with open(d2, "w") as f:
        json.dump({"flightEvents": raw,
                   "otherData": {"pid": 1234}}, f)
    merged = flight_cli.merge_traces([d1, d2])
    tiers = {e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert tiers == {"server"}
    # same tier, different pid -> split with a source suffix
    with open(d2, "w") as f:
        json.dump({"flightEvents": raw[1:],
                   "otherData": {"pid": 5678}}, f)
    merged = flight_cli.merge_traces([d1, d2])
    tiers = {e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert tiers == {"server#pid1234", "server#pid5678"}


# ---------------------------------------------------------------------------
# params / ini parity
# ---------------------------------------------------------------------------

def test_flight_params_ini_parity(tmp_path):
    """The four FlightX parameters exist in the core registry (offline
    CLI passthrough) AND parse from [Service] on both serve tiers."""
    from sptag_tpu.core.params import BKTParams, KDTParams

    for cls in (BKTParams, KDTParams):
        p = cls()
        assert p.set_param("FlightRecorder", "1")
        assert p.set_param("FlightRecorderEvents", "4096")
        assert p.set_param("FlightDeviceSampleRate", "0.25")
        assert p.set_param("FlightDumpOnSlowQuery", "/tmp/fl")
        assert p.flight_recorder == 1
        assert p.flight_recorder_events == 4096
        assert p.flight_device_sample_rate == 0.25
        assert p.flight_dump_on_slow_query == "/tmp/fl"
        assert p.get_param("FlightDeviceSampleRate") == "0.25"
    ini = tmp_path / "svc.ini"
    ini.write_text("[Service]\nFlightRecorder=1\n"
                   "FlightRecorderEvents=2048\n"
                   "FlightDumpOnSlowQuery=/tmp/fdump\n")
    s = ServiceContext.from_ini(str(ini)).settings
    assert s.flight_recorder is True
    assert s.flight_recorder_events == 2048
    assert s.flight_dump_on_slow_query == "/tmp/fdump"
    a = AggregatorContext.from_ini(str(ini))
    assert a.flight_recorder is True
    assert a.flight_recorder_events == 2048
    assert a.flight_dump_on_slow_query == "/tmp/fdump"
    # defaults: everything off
    d = ServiceSettings()
    assert not d.flight_recorder and d.flight_dump_on_slow_query == ""


# ---------------------------------------------------------------------------
# scheduler + engine attribution (shared tiny beam index)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def beam_index():
    """One tiny continuous-batching BKT index shared by the scheduler
    and e2e tests (builds dominate suite cost — reuse warmed shapes)."""
    rng = np.random.default_rng(0)
    data = rng.standard_normal((120, 8)).astype(np.float32)
    idx = sp.create_instance("BKT", "Float")
    for p, v in [("DistCalcMethod", "L2"), ("BKTKmeansK", "4"),
                 ("TPTNumber", "2"), ("TPTLeafSize", "16"),
                 ("NeighborhoodSize", "8"), ("CEF", "32"),
                 ("RefineIterations", "0"), ("SearchMode", "beam"),
                 ("MaxCheck", "64"), ("BeamSegmentIters", "2"),
                 ("FlightDeviceSampleRate", "1"),
                 ("ContinuousBatching", "1")]:
        assert idx.set_parameter(p, v), p
    idx.build(data)
    idx.search_batch(data[:1], 3)         # warm the Q=1 bucket shapes
    yield idx, data
    idx.close()


def test_scheduler_flight_events_and_rid_stats(beam_index):
    idx, data = beam_index
    flightrec.configure(enabled=True)
    rids = ["rid-%02d" % i for i in range(4)]
    futs = idx.submit_batch(data[:4], 3, rids=rids)
    # ISSUE 5 small fix: by the time ANY future is readable, the retire
    # path has already published that batch's scheduler metrics — a
    # completion-triggered sample must not undercount its own query
    retired_at_cb = []
    futs[0].add_done_callback(
        lambda f: retired_at_cb.append(
            metrics.counter_value("scheduler.retired")))
    for f in futs:
        f.result()
    assert retired_at_cb and retired_at_cb[0] >= 1
    kinds = {(e["tier"], e["kind"]) for e in flightrec.collect()}
    for want in [("scheduler", "pending"), ("scheduler", "slot_assign"),
                 ("scheduler", "segment"), ("scheduler", "retire"),
                 ("engine", "segment_device")]:
        assert want in kinds, (want, kinds)
    # per-rid stats feed the slow-query log (and survive recorder off)
    st = flightrec.query_stats("rid-00")
    assert st is not None
    assert st["segments"] >= 1 and st["slot_wait_ms"] >= 0.0
    assert "refills" in st
    h = metrics.histogram_or_none("engine.segment_device_ns")
    assert h is not None and h.count >= 1 and h.max > 0


def test_sampled_segment_keeps_its_time_and_event_without_rate_gauges(
        beam_index):
    """FlightDeviceSampleRate=1 on the segmented driver (no scheduler):
    every segment is timed to completion into engine.segment_device_ns
    and one `segment_device` event with the rows and the iteration cap;
    no achieved-rate or share-of-peak gauge is derived from it (the
    kernels' rooflines are the benchmark's, from traced device time)."""
    idx, data = beam_index
    assert idx.set_parameter("ContinuousBatching", "0")
    try:
        flightrec.configure(enabled=True)
        idx.search_batch(data[:4], 3)
        assert metrics.counter_value("beam.segmented") >= 1
        h = metrics.histogram_or_none("engine.segment_device_ns")
        assert h is not None and h.count >= 1 and h.max > 0
        events = [e for e in flightrec.collect()
                  if (e["tier"], e["kind"]) == ("engine", "segment_device")]
        assert len(events) == h.count
        for e in events:
            assert e["dur_ns"] > 0
            assert set(e["payload"]) == {"rows", "iters"}
            assert e["payload"]["iters"] == 2       # BeamSegmentIters
        gauges = metrics.snapshot()["gauges"]
        assert not [n for n in gauges if n.startswith("engine.")], gauges
    finally:
        assert idx.set_parameter("ContinuousBatching", "1")


def test_flight_params_apply_on_warm_index(beam_index):
    """set_parameter on a WARM index must not be a silent no-op: the
    recorder knobs apply directly to the process recorder (both ways —
    enable AND disable), and the engine-baked sample rate invalidates
    the engine snapshot."""
    idx, data = beam_index
    assert not flightrec.enabled()
    assert idx.set_parameter("FlightRecorder", "1")
    assert flightrec.enabled()
    assert idx.set_parameter("FlightRecorder", "0")
    assert not flightrec.enabled()
    idx._get_engine()
    assert idx.set_parameter("FlightDeviceSampleRate", "0.5")
    assert idx._engine is None          # baked in -> snapshot invalidated
    assert idx.set_parameter("FlightDeviceSampleRate", "1")
    assert idx._get_engine().device_sample_rate == 1.0


def test_configure_resize_preserves_buffered_events():
    """Resizing the ring folds live thread buffers first — counters
    never go backwards and buffered events are not lost."""
    flightrec.configure(enabled=True)
    flightrec.record("t", "ev", payload={"seq": 1})
    flightrec.configure(max_events=4096)
    assert flightrec.counters()["recorded"] == 1
    assert [e["payload"]["seq"] for e in flightrec.collect()] == [1]


# ---------------------------------------------------------------------------
# end-to-end: aggregator over two shards, flows + device time + dumps
# ---------------------------------------------------------------------------

def _http_get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    return resp.status, body


def test_flight_e2e_aggregator_two_shards(beam_index, tmp_path):
    """THE acceptance loop: aggregator over two shard servers with the
    recorder on — one request id yields flow-connected spans on all
    three tiers, at least one engine segment carries sampled device
    time, the slow-query log carries the scheduler numbers, slow
    queries auto-dump, and the merge CLI joins per-tier dumps into one
    trace with globally recomputed flow arrows."""
    idx, data = beam_index
    dump_dir = str(tmp_path / "dumps")
    ctx_a = ServiceContext(ServiceSettings(default_max_result=3))
    ctx_a.add_index("shard_a", idx)
    ctx_b = ServiceContext(ServiceSettings(default_max_result=3))
    ctx_b.add_index("shard_b", idx)       # same snapshot, two tiers
    srv_a = SearchServer(ctx_a, batch_window_ms=1.0, metrics_port=-1,
                         slow_query_threshold_ms=1e-6,
                         flight_recorder=True, flight_dump_dir=dump_dir,
                         flight_tier="server_a")
    srv_b = SearchServer(ctx_b, batch_window_ms=1.0,
                         slow_query_threshold_ms=1e-6,
                         flight_recorder=True, flight_dump_dir=dump_dir,
                         flight_tier="server_b")
    ta, tb = _ServerThread(srv_a), _ServerThread(srv_b)
    ta.start()
    tb.start()
    # generous readiness timeouts: in-suite CPU contention (XLA compile
    # threads from earlier modules) can stall loop startup past the
    # harness default — the known flake mode of the PR-2 observability
    # e2e
    (ha, pa), (hb, pb) = ta.wait_ready(60), tb.wait_ready(60)
    agg_ctx = AggregatorContext(search_timeout_s=30.0,
                                flight_recorder=True,
                                flight_dump_on_slow_query=dump_dir,
                                slow_query_threshold_ms=1e-6)
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
    rid = "e2e-flight-0042"
    try:
        from sptag_tpu.serve.client import AnnClient

        client = AnnClient(hg, pg, timeout_s=30.0)
        client.connect()
        qtext = ("$indexname:shard_a,shard_b $maxcheck:64 "
                 + "|".join(str(x) for x in data[5]))
        res = client.search(qtext, request_id=rid)
        assert res.status == wire.ResultStatus.Success
        assert res.request_id == rid
        client.close()

        # slow-query enrichment: the shard log line carries the per-rid
        # scheduler numbers next to the per-stage timings.  The shard
        # logs AFTER its response is already on the wire, so the client
        # can return first — poll briefly.
        deadline = time.time() + 10
        while time.time() < deadline:
            if any(("rid=%s" % rid) in m and "slot_wait=" in m
                   and "segments=" in m and "refills=" in m
                   for m in records):
                break
            time.sleep(0.05)
        assert any(("rid=%s" % rid) in m and "slot_wait=" in m
                   and "segments=" in m and "refills=" in m
                   for m in records), records

        # /debug/flight serves the Perfetto artifact; one rid spans all
        # three tiers (same post-response race: poll)
        deadline = time.time() + 10
        while time.time() < deadline:
            status, body = _http_get(srv_a._metrics_http.port,
                                     "/debug/flight")
            assert status == 200
            trace = json.loads(body)
            evs = trace["traceEvents"]
            rid_tiers = {e.get("cat") for e in evs
                         if e.get("args", {}).get("rid") == rid
                         and e["ph"] in ("X", "i")}
            if {"aggregator", "server_a", "server_b"} <= rid_tiers:
                break
            time.sleep(0.05)
        assert {"aggregator", "server_a", "server_b"} <= rid_tiers, rid_tiers
        # client + scheduler attribution ride the same trace in-process
        assert "client" in rid_tiers and "scheduler" in rid_tiers
        # flow arrows stitch the rid across tiers: one chain, one id
        flows = [e for e in evs if e.get("cat") == "flight.flow"
                 and e["id"] == flightrec._flow_id(rid)]
        assert {"s", "f"} <= {f["ph"] for f in flows}
        flow_pids = {f["pid"] for f in flows}
        pid_names = {e["pid"]: e["args"]["name"] for e in evs
                     if e["ph"] == "M" and e["name"] == "process_name"}
        assert {"aggregator", "server_a", "server_b"} <= \
            {pid_names[p] for p in flow_pids}
        # sampled device time: an engine segment with a real duration
        dev = [e for e in evs if e["ph"] == "X"
               and e["name"] == "segment_device" and e["cat"] == "engine"]
        assert dev and all(e["dur"] > 0 for e in dev)
        # server stages all present for the rid
        stage_names = {e["name"] for e in evs
                       if e.get("args", {}).get("rid") == rid}
        for want in ("decode", "queue_wait", "encode", "drain", "request",
                     "fanout", "merge", "send"):
            assert want in stage_names, (want, stage_names)

        # FlightDumpOnSlowQuery: the 1e-6 threshold makes every request
        # slow -> at least one ringed auto-dump lands on disk
        deadline = time.time() + 10
        while time.time() < deadline:
            dumps = [fn for fn in os.listdir(dump_dir)
                     if fn.endswith(".json")] if os.path.isdir(dump_dir) \
                else []
            if dumps:
                break
            time.sleep(0.05)
        assert dumps, "no auto-dump written"

        # merge CLI: split the ring into PER-TIER dumps (what separate
        # processes would produce), merge, and check the flow chain is
        # recomputed globally — no single input could contain it
        raw = flightrec.collect()
        ins = []
        for i, tiers in enumerate((("aggregator", "client"),
                                   ("server_a", "scheduler", "engine"),
                                   ("server_b",))):
            part = [e for e in raw if e["tier"] in tiers]
            assert part, tiers
            p = str(tmp_path / ("tier%d.json" % i))
            with open(p, "w") as f:
                json.dump({"traceEvents": [], "flightEvents": part}, f)
            ins.append(p)
        out = str(tmp_path / "merged.json")
        assert flight_cli.main(["-o", out, "--rid", rid] + ins) == 0
        with open(out) as f:
            merged = json.load(f)
        mevs = merged["traceEvents"]
        mtiers = {e.get("cat") for e in mevs
                  if e.get("args", {}).get("rid") == rid}
        assert {"aggregator", "server_a", "server_b"} <= mtiers
        mflows = [e for e in mevs if e.get("cat") == "flight.flow"]
        assert {"s", "f"} <= {f["ph"] for f in mflows}
        assert len({f["pid"] for f in mflows}) >= 3
        # --rid filter dropped untagged pool events (e.g. segment)
        assert all(e.get("args", {}).get("rid") == rid
                   for e in mevs if e["ph"] in ("X", "i"))
    finally:
        shard_log.removeHandler(capture)
        tg.stop()
        ta.stop()
        tb.stop()


def test_merge_cli_rejects_non_dump(tmp_path):
    p = str(tmp_path / "plain.json")
    with open(p, "w") as f:
        json.dump({"traceEvents": []}, f)
    assert flight_cli.main(["-o", "-", p]) == 1


# ---------------------------------------------------------------------------
# FlightRecorder=off: byte parity + zero hot-path work
# ---------------------------------------------------------------------------

def test_flight_off_parity_serve_bytes_and_zero_work():
    """With the recorder off (the default), the serve path produces
    byte-identical wire responses to the reference layout (golden bytes
    constructed from the executor + header spec) and performs zero
    recorder work — no events, no buffers (the ci_check.sh standalone
    parity pass)."""
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
        assert not flightrec.enabled()
        qtext = "|".join(str(x) for x in data[7])
        # golden response bytes: executor result (rid stays empty), the
        # documented header fields (first connection -> cid 1)
        expected_result = SearchExecutor(ctx).execute(qtext)
        expected_result.request_id = ""
        expected_body = expected_result.pack()
        expected = wire.PacketHeader(
            wire.PacketType.SearchResponse, wire.PacketProcessStatus.Ok,
            len(expected_body), 1, 77).pack() + expected_body

        body = wire.RemoteQuery(qtext).pack()        # minor version 0
        assert body[2:4] == b"\x00\x00"
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
        c = flightrec.counters()
        assert c == {"enabled": 0, "recorded": 0, "dropped": 0,
                     "threads": 0, "dump_errors": 0,
                     "dump_ratelimited": 0}
    finally:
        t.stop()
