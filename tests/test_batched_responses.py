"""Answers that arrive as a batch leave as a batch (ISSUE 26).

A group whose futures are all resolved when `submit_batch` returns is
built in bulk with no `on_ready` hand-off, and `_respond_batch` makes one
write per connection of that connection's packets joined.  Under test:
the bytes on the wire are `_respond_one`'s, the per-request duties stay
per request, injected faults still hit single responses, and one dead
connection costs the others nothing.
"""

import asyncio
import concurrent.futures as cf
import socket
import time

import numpy as np
import pytest

import sptag_tpu as sp
from conftest import ServerThread
from sptag_tpu.serve import admission, wire
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import metrics

K = 5
PER_CONN = 3          # requests a connection; two connections a batch
N = 2 * PER_CONN


def _flat_context(names=("main",), n=200, d=8):
    data = np.random.default_rng(0).standard_normal((n, d)).astype(
        np.float32)
    ctx = ServiceContext(ServiceSettings(default_max_result=K))
    for name in names:
        index = sp.create_instance("FLAT", "Float")
        index.set_parameter("DistCalcMethod", "L2")
        index.build(data)
        ctx.add_index(name, index)
    return ctx, data


def _text(data, row):
    return "|".join(str(x) for x in data[row])


def _request(data, row, resource_id, rid):
    body = wire.RemoteQuery(_text(data, row), request_id=rid).pack()
    return wire.PacketHeader(wire.PacketType.SearchRequest,
                             wire.PacketProcessStatus.Ok, len(body), 0,
                             resource_id).pack() + body


def _read_packets(sock, count, buf=b""):
    """-> (`count` raw packets, header and body, in arrival order; the
    bytes read past them)."""
    got = []
    while len(got) < count:
        while len(buf) >= wire.HEADER_SIZE and len(got) < count:
            head = wire.PacketHeader.unpack(buf[:wire.HEADER_SIZE])
            end = wire.HEADER_SIZE + head.body_length
            if len(buf) < end:
                break
            got.append(buf[:end])
            buf = buf[end:]
        if len(got) < count:
            chunk = sock.recv(65536)
            assert chunk, "server closed the connection"
            buf += chunk
    return got, buf


def _rid(row):
    """Every request carries an id but row 1's (the plain trailer-less
    body must survive the joined write too)."""
    return "" if row == 1 else f"rid-{row}"


def _serve_one_batch(data, ctx, closing=False, expect=None, **server_kw):
    """One batch of N requests, PER_CONN on each of two connections,
    through a real SearchServer whose batch is full at N.  -> per
    connection the raw packets received, in arrival order.  `closing`:
    the first connection closes right after its requests are written.
    `expect`: packets each connection gets (all of its own, if None).
    Returns once the server has counted every response it sent (a
    client can read the bytes before the server's epilogue runs)."""
    thread = ServerThread(SearchServer(ctx, batch_window_ms=500.0,
                                       max_batch=N, **server_kw))
    thread.start()
    host, port = thread.wait_ready()
    socks = []
    try:
        for _ in range(2):
            s = socket.create_connection((host, port), timeout=20)
            s.settimeout(20)
            socks.append(s)
        for c, s in enumerate(socks):
            rows = range(c * PER_CONN, (c + 1) * PER_CONN)
            s.sendall(b"".join(_request(data, row, 100 + row, _rid(row))
                               for row in rows))
            if closing and c == 0:
                s.close()
        out = []
        for c, s in enumerate(socks):
            if closing and c == 0:
                out.append([])
                continue
            want = PER_CONN if expect is None else expect[c]
            packets, rest = _read_packets(s, want)
            assert rest == b""
            out.append(packets)
        counted = N if expect is None else sum(expect)
        deadline = time.monotonic() + 10
        while metrics.counter_value("server.responses") < counted:
            assert time.monotonic() < deadline, "responses not counted"
            time.sleep(0.01)
        return out
    finally:
        for s in socks:
            s.close()
        thread.stop()


def _reference_packet(data, index, row, cid, degraded):
    """`_respond_one`'s bytes for this row, built from the index's own
    answer to the same batch (a batch of one may differ in the last ulp)
    by the per-element loop the bulk conversion replaced."""
    dists, ids = index.search_batch(data[:N], K)
    result = wire.RemoteSearchResult(
        wire.ResultStatus.Success,
        [wire.IndexSearchResult("main", [int(v) for v in ids[row]],
                                [float(d) for d in dists[row]], None)],
        _rid(row), [wire.MARKER_DEGRADED] if degraded else [])
    body = result.pack()
    return wire.PacketHeader(wire.PacketType.SearchResponse,
                             wire.PacketProcessStatus.Ok, len(body), cid,
                             100 + row).pack() + body


@pytest.mark.parametrize("degraded", [False, True],
                         ids=["plain", "degraded"])
def test_joined_write_carries_respond_ones_bytes(degraded):
    ctx, data = _flat_context()
    index = ctx.indexes["main"]

    def controller():
        # a permanently degrading controller marks every Success; no
        # connection can exceed a fair share of 1, so none is shed
        return (admission.AdmissionController(
            admission.AdmissionConfig(fair_share=1.0),
            signals=lambda: {"queue_frac": 0.6}) if degraded else None)

    metrics.reset()
    batched = _serve_one_batch(data, ctx, admission=controller())
    counters = dict(metrics.snapshot()["counters"])
    assert counters["server.responses"] == N
    assert counters["service.batched_results"] == N
    assert "service.streamed_results" not in counters
    assert "server.streamed_responses" not in counters
    assert counters.get("server.degraded_responses", 0) == (
        N if degraded else 0)
    # a live injector that never fires: the per-response path
    one_by_one = _serve_one_batch(data, ctx, admission=controller(),
                                  fault_spec="drop:p=0")
    for c in range(2):
        rows = range(c * PER_CONN, (c + 1) * PER_CONN)
        # batch order on a connection is the order it wrote them in
        heads = [wire.PacketHeader.unpack(p[:wire.HEADER_SIZE])
                 for p in batched[c]]
        assert [h.resource_id for h in heads] == [100 + r for r in rows]
        assert batched[c] == one_by_one[c]
        for packet, head, row in zip(batched[c], heads, rows):
            assert packet == _reference_packet(
                data, index, row, head.connection_id, degraded)
            got = wire.RemoteSearchResult.unpack(
                packet[wire.HEADER_SIZE:])
            assert got.request_id == _rid(row)
            assert got.degraded is degraded
            assert got.results[0].ids[0] == row


def test_live_injector_sends_one_by_one_and_a_drop_loses_one_response():
    ctx, data = _flat_context()
    metrics.reset()
    # the second response decided on is dropped: the first connection
    # reads two of its three, the second connection all of its own
    got = _serve_one_batch(
        data, ctx, expect=[PER_CONN - 1, PER_CONN],
        fault_spec="drop@server.respond:p=1,n=1,after=1")
    counters = dict(metrics.snapshot()["counters"])
    assert counters["faultinject.drops"] == 1
    assert counters["server.responses"] == N - 1
    ids = [[wire.PacketHeader.unpack(p[:wire.HEADER_SIZE]).resource_id
            for p in conn] for conn in got]
    assert ids == [[100, 102], [103, 104, 105]]


def test_a_connection_closed_mid_batch_costs_the_others_nothing():
    ctx, data = _flat_context()
    metrics.reset()
    got = _serve_one_batch(data, ctx, closing=True)
    index = ctx.indexes["main"]
    rows = range(PER_CONN, N)
    assert len(got[1]) == PER_CONN
    for packet, row in zip(got[1], rows):
        cid = wire.PacketHeader.unpack(
            packet[:wire.HEADER_SIZE]).connection_id
        assert packet == _reference_packet(data, index, row, cid, False)
    assert metrics.counter_value("server.batch_failures") == 0
    assert metrics.counter_value("server.response_task_errors") == 0


class _Writer:
    """A StreamWriter's surface as `_send` uses it."""

    def __init__(self, broken=False):
        self.broken = broken
        self.writes = []
        self.aborted = False
        self.transport = self

    def write(self, payload):
        if self.broken:
            raise ConnectionResetError("peer went away")
        self.writes.append(payload)

    async def drain(self):
        pass

    def abort(self):
        self.aborted = True


def test_a_failing_write_drops_that_client_and_the_rest_are_written():
    """The first connection's write raises: it is evicted and counted,
    the second connection still gets its packets in ONE write."""
    ctx, data = _flat_context()
    server = SearchServer(ctx)
    texts = [_text(data, row) for row in range(N)]
    results = SearchExecutor(ctx).execute_batch(texts)
    batch = [(1 + row // PER_CONN,
              wire.PacketHeader(wire.PacketType.SearchRequest,
                                wire.PacketProcessStatus.Ok, 0, 0,
                                100 + row),
              wire.RemoteQuery(texts[row], request_id=_rid(row)),
              0.0, None, False) for row in range(N)]
    broken, sound = _Writer(broken=True), _Writer()
    metrics.reset()

    async def respond():
        server._conns = {1: (broken, asyncio.Lock()),
                         2: (sound, asyncio.Lock())}
        await server._respond_batch(batch, results, set(), 0.0, 0.0)

    asyncio.run(respond())
    assert broken.aborted and 1 not in server._conns
    assert metrics.counter_value("server.send_errors") == 1
    assert len(sound.writes) == 1
    index = ctx.indexes["main"]
    assert sound.writes[0] == b"".join(
        _reference_packet(data, index, row, 2, False)
        for row in range(PER_CONN, N))
    # every request keeps its per-request duties, as with _respond_one
    assert metrics.counter_value("server.responses") == N


# ------------------------------------------------------------- executor

class _Resolved:
    """An index whose submit_batch hands back finished futures, one of
    them failed: the resolved group must answer that row FailedExecute
    and the others in bulk."""

    feature_dim = 8
    value_type = sp.VectorValueType.Float
    metadata = None

    def __init__(self, inner, fail_row):
        self.inner = inner
        self.fail_row = fail_row

    def submit_batch(self, queries, k, max_check=None, search_mode=None,
                     rids=None):
        dists, ids = self.inner.search_batch(queries, k)
        futs = []
        for row in range(len(queries)):
            f = cf.Future()
            if row == self.fail_row:
                f.set_exception(RuntimeError("row failed"))
            else:
                f.set_result((dists[row], ids[row]))
            futs.append(f)
        return futs


def test_resolved_group_with_a_failed_row_makes_no_hand_off():
    ctx, data = _flat_context()
    ctx.indexes["main"] = _Resolved(ctx.indexes["main"], fail_row=2)
    texts = [_text(data, row) for row in range(N)]
    calls = []
    metrics.reset()
    out = SearchExecutor(ctx).execute_batch(
        texts, on_ready=lambda i, r: calls.append(i))
    assert calls == []
    assert [r.status for r in out] == [
        wire.ResultStatus.FailedExecute if row == 2
        else wire.ResultStatus.Success for row in range(N)]
    assert [r.results[0].ids[0] for row, r in enumerate(out)
            if row != 2] == [0, 1, 3, 4, 5]
    assert metrics.counter_value("service.batched_results") == N - 1
    assert metrics.counter_value("service.search_errors") == 1
    assert metrics.counter_value("service.streamed_results") == 0


def test_fan_out_counts_a_query_once_and_converts_in_bulk():
    """A two-index group keeps batch granularity (as before) and gains
    the bulk conversion: plain Python ints and floats, one count a
    query."""
    ctx, data = _flat_context(names=("a", "b"))
    texts = ["$indexname:a,b " + _text(data, row) for row in range(N)]
    calls = []
    metrics.reset()
    out = SearchExecutor(ctx).execute_batch(
        texts, on_ready=lambda i, r: calls.append(i))
    assert calls == []
    assert metrics.counter_value("service.batched_results") == N
    for row, r in enumerate(out):
        assert [x.index_name for x in r.results] == ["a", "b"]
        for x in r.results:
            assert x.ids[0] == row
            assert all(type(v) is int for v in x.ids)
            assert all(type(d) is float for d in x.dists)
