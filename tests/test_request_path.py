"""A request's latency closes on one clock (ISSUE 38).

Every stamp a request gets is on `time.perf_counter()` — CLOCK_MONOTONIC
on Linux, the same clock in every process of the machine — so a caller
that stamps its own sends and reads can split its mean latency, exactly,
into way in + `server.request` + way back: the server sums, per BATCH,
its requests' enqueue instants (`server.arrival_clock`) and the instants
their replies were with the socket (`server.departure_clock`), counted
from the gauge `server.clock_origin_s`, through `trace.record_sum`.
Beside them: `server.batch_reply` (executed -> last reply written), the
event loop's heartbeat (`server.loop_lag`) and the two threads' CPU
seconds a batch (`server.loop_cpu`, `server.executor_cpu`).  Under test: a
real SearchServer over a tiny FLAT index and a raw-socket caller in this
process.
"""

import os
import socket
import statistics
import subprocess
import sys
import time

import pytest

from conftest import ServerThread
from sptag_tpu.serve import server as server_module
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.utils import metrics, trace
from test_batched_responses import _flat_context, _read_packets, _request

#: what the parent recorded once a request and still does: the enqueue ->
#: assembled wait, the wire decode, the whole stay in the server
PER_REQUEST = {"server.queue_wait", "server.decode", "server.request"}
PER_BATCH_NEW = {"server.arrival_clock", "server.departure_clock",
                 "server.batch_reply", "server.loop_cpu",
                 "server.executor_cpu"}


# ---- trace.record_sum ------------------------------------------------------

def test_record_sum_adds_count_and_total_and_feeds_no_histogram():
    trace.record_sum("t.sum", 12.5, 5)
    before = trace.report()
    assert before["t.sum"] == {"count": 5, "total_s": 12.5, "mean_s": 2.5,
                               "max_s": 0.0}          # no percentiles
    trace.record_sum("t.sum", 7.25, 3)
    after = trace.report()["t.sum"]
    assert (after["count"], after["total_s"]) == (8, 19.75)
    assert metrics.histogram_or_none("t.sum") is None
    # the window's delta, as benchmark/run.py::span_deltas takes it
    assert after["count"] - before["t.sum"]["count"] == 3
    assert after["total_s"] - before["t.sum"]["total_s"] == 7.25


def test_record_sum_keeps_a_large_sum_of_instants_to_the_microsecond():
    """2e5 requests 400 s after the origin: the mean comes back to well
    under a microsecond (report() rounds totals to 1e-6 s)."""
    total, n = 0.0, 200_000
    for i in range(200):
        batch = [400.0 + (1000 * i + j) * 1e-6 for j in range(1000)]
        trace.record_sum("t.clock", sum(batch), len(batch))
        total += sum(batch)
    rec = trace.report()["t.clock"]
    assert rec["count"] == n
    assert abs(rec["total_s"] / n - total / n) < 1e-9


# ---- one clock across processes --------------------------------------------

def test_perf_counter_is_one_clock_across_processes():
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-c",
         "import time; print(repr(time.perf_counter()))"],
        capture_output=True, text=True, timeout=60, check=True)
    t1 = time.perf_counter()
    assert t0 <= float(out.stdout) <= t1


# ---- the served path -------------------------------------------------------

class _Served:
    """One server (its first batch is full at `max_batch`), one raw
    connection; `sends` and `reads` are this caller's perf_counter
    stamps: a request just BEFORE its bytes go to the socket, a reply
    once it is read whole."""

    def __init__(self, max_batch, batch_window_ms=500.0, executor=None):
        ctx, self.data = _flat_context()
        self.server = SearchServer(ctx, batch_window_ms=batch_window_ms,
                                   max_batch=max_batch)
        if executor is not None:
            self.server.executor = executor(self.server.executor)
        self.thread = ServerThread(self.server)
        self.thread.start()
        self.sock = socket.create_connection(self.thread.wait_ready(),
                                             timeout=20)
        self.sock.settimeout(20)
        self._buf = b""
        self.sends, self.reads = [], []

    def send(self, count):
        for row in range(len(self.sends), len(self.sends) + count):
            packet = _request(self.data, row % len(self.data), row,
                              f"rid-{row}")
            self.sends.append(time.perf_counter())
            self.sock.sendall(packet)

    def read(self, count):
        for _ in range(count):
            _packets, self._buf = _read_packets(self.sock, 1, self._buf)
            self.reads.append(time.perf_counter())

    def wait_departed(self, count, timeout=20.0):
        """Until `count` replies are with their sockets AND their
        per-request duties done (the departure sum is recorded last)."""
        end = time.perf_counter() + timeout
        while time.perf_counter() < end:
            rec = trace.report().get("server.departure_clock")
            if rec and rec["count"] >= count:
                return
            time.sleep(0.002)
        raise AssertionError(f"{count} replies never left: "
                             f"{trace.report().get('server.departure_clock')}")

    def close(self):
        self.sock.close()
        self.thread.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def test_mean_latency_is_way_in_plus_server_request_plus_way_back():
    n = 24
    with _Served(max_batch=8) as s:
        for _ in range(n // 8):
            s.send(8)
            s.wait_departed(len(s.sends))
            # a reply left unread is on its way back all the while
            time.sleep(0.03)
            s.read(8)
        spans = trace.report()
        origin = metrics.gauge_value("server.clock_origin_s")
    arrived, departed = (spans["server.arrival_clock"],
                         spans["server.departure_clock"])
    request = spans["server.request"]
    assert arrived["count"] == departed["count"] == request["count"] == n
    assert 0 < origin <= min(s.sends)
    way_in = origin + arrived["total_s"] / n - statistics.fmean(s.sends)
    way_back = statistics.fmean(s.reads) - (origin + departed["total_s"] / n)
    latency = statistics.fmean([r - t for r, t in zip(s.reads, s.sends)])
    assert way_in >= 0
    assert way_back >= 0.03
    assert abs(latency - (way_in + request["total_s"] / n + way_back)) \
        < 0.2e-3
    # the reply: one record a batch, and no request's stay in the server
    # ends before its batch's last reply has left
    assert spans["server.batch_reply"]["count"] == n // 8
    assert 0 < spans["server.batch_reply"]["max_s"] <= request["max_s"]


def test_a_blocked_loop_shows_in_the_heartbeats_lag():
    beat = server_module.HEARTBEAT_S
    with _Served(max_batch=8) as s:
        time.sleep(5 * beat)
        quiet = trace.report()["server.loop_lag"]["count"]
        assert quiet >= 2
        s.thread.loop.call_soon_threadsafe(time.sleep, 0.03)
        time.sleep(0.03 + 5 * beat)
        lag = trace.report()["server.loop_lag"]
    # a beat was due within HEARTBEAT_S of the block's start
    assert lag["max_s"] >= 0.03 - beat - 0.002
    assert lag["count"] > quiet
    # stop() cancelled it: nothing beats on a stopped server
    stopped = trace.report()["server.loop_lag"]["count"]
    time.sleep(3 * beat)
    assert trace.report()["server.loop_lag"]["count"] == stopped


class _BusyExecutor:
    """Burns `BURN_S` of its own thread's CPU before every batch."""
    BURN_S = 0.05

    def __init__(self, inner):
        self.inner = inner

    def execute_batch(self, texts, **kw):
        c0 = time.thread_time()
        while time.thread_time() - c0 < self.BURN_S:
            pass
        return self.inner.execute_batch(texts, **kw)


def test_a_busy_executor_shows_in_its_own_cpu_and_not_in_the_loops():
    batches = 3
    with _Served(max_batch=4, executor=_BusyExecutor) as s:
        for _ in range(batches):
            s.send(4)
            s.read(4)
        s.wait_departed(4 * batches)
        spans = trace.report()
    busy, loop, cycle = (spans["server.executor_cpu"],
                         spans["server.loop_cpu"],
                         spans["server.batch_cycle"])
    assert busy["count"] == batches
    assert busy["total_s"] >= batches * _BusyExecutor.BURN_S
    # one record beside each batch_cycle: none for the first batch
    assert loop["count"] == cycle["count"] == batches - 1
    assert loop["total_s"] < 0.5 * (batches - 1) * _BusyExecutor.BURN_S
    assert loop["total_s"] <= cycle["total_s"]


# ---- what a request costs with no trace live --------------------------------

class _CountedClock:
    """`time`, counting the clock reads serve/server.py makes."""

    def __init__(self):
        self.calls = {"perf_counter": 0, "thread_time": 0,
                      "monotonic_ns": 0}

    def __getattr__(self, name):
        if name in self.calls:
            self.calls[name] += 1
        return getattr(time, name)


def _one_batch_counted(monkeypatch, n):
    """One batch of `n` requests on one connection -> (registry updates
    by name, the server module's clock reads by function)."""
    updates = {}
    record, record_sum = trace.record, trace.record_sum

    def counted_record(name, seconds):
        updates[name] = updates.get(name, 0) + 1
        record(name, seconds)

    def counted_sum(name, total_s, count):
        updates[name] = updates.get(name, 0) + 1
        record_sum(name, total_s, count)

    clock = _CountedClock()
    with monkeypatch.context() as m:
        m.setattr(trace, "record", counted_record)
        m.setattr(trace, "record_sum", counted_sum)
        m.setattr(server_module, "time", clock)
        with _Served(max_batch=n) as s:
            s.send(n)
            s.read(n)
            s.wait_departed(n)
        assert trace.report()["server.execute_batch"]["count"] == 1
    return updates, clock.calls


def test_a_batch_of_n_adds_a_constant_number_of_updates(monkeypatch):
    """With no trace live a request adds no registry update, lock or
    clock read the parent did not make: what grows with the batch is the
    parent's three records and its one `t_enq` stamp a request."""
    small, small_clock = _one_batch_counted(monkeypatch, 2)
    trace.reset()
    metrics.reset()
    large, large_clock = _one_batch_counted(monkeypatch, 16)
    for name in PER_REQUEST:
        assert (small[name], large[name]) == (2, 16), name
    for name in PER_BATCH_NEW - {"server.loop_cpu"}:
        assert small[name] == large[name] == 1, name
    assert "server.loop_cpu" not in large        # one batch: no cycle yet
    # (the heartbeat goes by the clock; a rung this process had not
    # compiled yet is recorded by utils/recompile_guard.py)
    grew = {name for name in large if large[name] != small.get(name)
            and name != "server.loop_lag" and not name.startswith("xla.")}
    assert grew == PER_REQUEST
    assert large_clock["perf_counter"] - small_clock["perf_counter"] == 14
    assert large_clock["thread_time"] == small_clock["thread_time"] == 3
    assert large_clock["monotonic_ns"] == small_clock["monotonic_ns"] == 0


@pytest.mark.parametrize("name", sorted(PER_BATCH_NEW | {"server.loop_lag"}))
def test_docs_list_every_new_record(name):
    docs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "TELEMETRY.md")
    with open(docs) as f:
        assert f"`{name}`" in f.read()
