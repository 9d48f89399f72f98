"""The batcher waits its window only where waiting can bring company,
judged by what the last window brought (ISSUE 31).

An arrival at an idle server waits `batch_window_ms`
(`server.gather_window`), unless the last such window, waited in full,
closed on that one request (`server.gather_lone`); a batch of more than
one undoes that, and so does anything queued when the executor frees.
What is queued when the executor frees waits the window too, until one
brings back fewer than half as many as were just answered: the next
TRICKLE_BATCHES backlogs leave at once, each as one batch
(`server.gather_backlog`), after the finished batch's replies, before
one waits the window again.  Past its window a gather waits for the
callers just answered where PATIENCE_SHARE of the time their batch held
the executor, less the window, is one more window at least, that share
of its time at most (`server.gather_patient`; ISSUE 32).  Under test: a real SearchServer over
a tiny FLAT index, its executor wrapped so a test can hold it busy and read
the batches it was handed.
"""

import asyncio
import socket
import threading
import time

import pytest

from conftest import ServerThread
from sptag_tpu.serve import server as server_module
from sptag_tpu.serve import wire
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.utils import metrics
from test_batched_responses import _flat_context, _read_packets, _request

WINDOW_S = 0.3
GATHERS = ("server.gather_backlog", "server.gather_window",
           "server.gather_lone")


class _HeldExecutor:
    """The server's executor, recording each batch's size.  After
    `hold()` a batch stays on the executor thread until `step()` lets
    one go or `release()` all; every batch stays there `pad_s` at
    least."""

    def __init__(self, inner, pad_s=0.0):
        self.inner = inner
        self.pad_s = pad_s
        self.sizes = []
        self.answered = []      # `server.responses` as each batch arrived
        self._holding = False
        self._steps = 0
        self._changed = threading.Condition()

    def hold(self):
        with self._changed:
            self._holding = True

    def step(self):
        with self._changed:
            self._steps += 1
            self._changed.notify_all()

    def release(self):
        with self._changed:
            self._holding = False
            self._changed.notify_all()

    def execute_batch(self, texts, **kw):
        self.sizes.append(len(texts))
        self.answered.append(metrics.counter_value("server.responses"))
        with self._changed:
            assert self._changed.wait_for(
                lambda: not self._holding or self._steps, 20), \
                "the test never released the executor"
            if self._holding:
                self._steps -= 1
        time.sleep(self.pad_s)
        return self.inner.execute_batch(texts, **kw)


class _Served:
    """One server, one or more raw connections, requests by row."""

    def __init__(self, batch_window_ms=1e3 * WINDOW_S, max_batch=64,
                 connections=1, pad_s=0.0, **server_kw):
        ctx, self.data = _flat_context()
        self.server = SearchServer(ctx, batch_window_ms=batch_window_ms,
                                   max_batch=max_batch, **server_kw)
        self.held = self.server.executor = _HeldExecutor(
            self.server.executor, pad_s)
        self.thread = ServerThread(self.server)
        self.thread.start()
        addr = self.thread.wait_ready()
        self.socks = []
        for _ in range(connections):
            s = socket.create_connection(addr, timeout=20)
            s.settimeout(20)
            self.socks.append(s)
        self._bufs = [b""] * connections
        self._sent = 0

    def send(self, count=1, conn=0):
        rows = range(self._sent, self._sent + count)
        self._sent += count
        self.socks[conn].sendall(b"".join(
            _request(self.data, row % len(self.data), row, f"rid-{row}")
            for row in rows))

    def read(self, count=1, conn=0):
        packets, self._bufs[conn] = _read_packets(
            self.socks[conn], count, self._bufs[conn])
        for p in packets:
            body = wire.RemoteSearchResult.unpack(p[wire.HEADER_SIZE:])
            assert body.status == wire.ResultStatus.Success
        return packets

    def ask(self, conn=0):
        """One request, its reply read: -> seconds the caller waited."""
        t0 = time.perf_counter()
        self.send(1, conn)
        self.read(1, conn)
        return time.perf_counter() - t0

    def wait_for(self, what, said):
        deadline = time.monotonic() + 10
        while not what():
            assert time.monotonic() < deadline, said
            time.sleep(0.005)

    def wait_batches(self, count):
        self.wait_for(lambda: len(self.held.sizes) >= count,
                      f"batch {count} never reached the executor")

    def hold_one(self):
        """Hold the executor and send one request: returns with it on
        the executor thread (the server must be in its lone state, or
        the caller has waited out the window)."""
        self.held.hold()
        before = len(self.held.sizes)
        self.send(1)
        self.wait_batches(before + 1)

    def trickling(self, backlog=4):
        """Busy, and taught that a window brings nothing: `backlog`
        requests queue behind a held one, wait the window alone, and
        are on the executor thread, held, when this returns.  Batches
        so far [1, 1, backlog]; gathers window 2, lone 1."""
        self.ask()                       # the window, waited for nothing
        self.hold_one()                  # lone
        self.queue(backlog)
        self.held.step()                 # the held one leaves; a window
        self.wait_batches(3)
        assert self.held.sizes == [1, 1, backlog]

    def queue(self, count):
        """`count` requests queued behind the held one."""
        self.send(count)
        self.wait_for(lambda: self.server._queue.qsize() == count,
                      "the requests never queued")

    def close(self):
        self.held.release()
        for s in self.socks:
            s.close()
        self.thread.stop()


@pytest.fixture
def served():
    made = []

    def make(**kw):
        made.append(_Served(**kw))
        return made[-1]
    yield make
    for s in made:
        s.close()


def _gathers():
    return {n.rsplit("_", 1)[1]: metrics.counter_value(n) for n in GATHERS}


@pytest.mark.parametrize("later", [1, 6])
def test_lone_caller_pays_one_window_then_none(served, later):
    """(a) One caller: the first request waits the window for company
    that cannot come; the batcher remembers, and every later one leaves
    at once."""
    s = served()
    first = s.ask()
    rest = [s.ask() for _ in range(later)]
    assert first >= WINDOW_S
    assert min(rest) < WINDOW_S / 2, rest
    assert _gathers() == {"backlog": 0, "window": 1, "lone": later}
    assert s.held.sizes == [1] * (1 + later)
    # the window actually spent, on the server's clock (a loaded
    # sandbox can hold any one reply up): one whole, then none
    gather = metrics.histogram("server.batch_gather")
    assert gather.count == 1 + later
    assert WINDOW_S <= gather.sum < 1.5 * WINDOW_S


@pytest.mark.parametrize("taught,queued,max_batch,batches", [
    (4, 3, 64, [3]),
    (4, 5, 64, [5]),
    (4, 7, 64, [7]),
    (6, 11, 7, [7, 4]),
])
def test_backlog_leaves_as_one_batch_without_the_window(
        served, taught, queued, max_batch, batches):
    """(b) Once a window has brought a backlog nothing, what queues
    while the executor is busy leaves the moment it frees, up to
    `max_batch` a batch, the remainder in the next, and no batch of it
    waits the window."""
    s = served(max_batch=max_batch)
    s.trickling(taught)
    s.queue(queued)
    s.held.release()
    s.read(1 + taught + queued)
    assert s.held.sizes == [1, 1, taught] + batches
    assert _gathers() == {"backlog": len(batches), "window": 2, "lone": 1}
    # two windows in all, the first request's and the first backlog's
    # (the server's clock: a new batch size compiles, which a caller's
    # clock would count)
    gather = metrics.histogram("server.batch_gather")
    assert gather.count == 3 + len(batches)
    assert 2 * WINDOW_S <= gather.sum < 2.5 * WINDOW_S


def test_a_backlog_waits_the_window_again_after_a_while(
        served, monkeypatch):
    """What a window brought one backlog is not known for good: a
    counted number of backlogs leave at once, then one waits again."""
    monkeypatch.setattr(server_module, "TRICKLE_BATCHES", 3)
    s = served()
    s.trickling(4)
    for nth in range(1, 5):
        s.queue(2)
        s.held.step()                    # the held batch leaves
        s.wait_batches(3 + nth)          # and the two queued are held
        assert _gathers() == {"backlog": min(nth, 3),
                              "window": 2 + (nth > 3), "lone": 1}
    s.held.release()
    s.read(1 + 4 + 8)
    assert s.held.sizes == [1, 1, 4, 2, 2, 2, 2]


def test_replies_are_written_before_the_backlog_is_dispatched(served):
    """The finished batch's replies are with the socket before the next
    batch reaches the executor thread (whose parse would hold the
    interpreter lock against the loop thread that writes them)."""
    s = served()
    s.trickling(4)
    s.queue(3)
    s.held.release()
    s.read(8)
    assert s.held.sizes == [1, 1, 4, 3]
    assert s.held.answered == [0, 1, 2, 6]


@pytest.mark.parametrize("company", ["backlog", "window"])
def test_a_batch_of_more_than_one_arms_the_window_again(served, company):
    """(c) After a batch of more than one, however it came about, a lone
    arrival at the idle server waits again, and a request sent inside
    that window shares its batch."""
    s = served(connections=2)
    if company == "backlog":
        s.trickling(4)
        s.queue(3)
        s.held.release()
        s.read(8)
        before = {"backlog": 1, "window": 2, "lone": 1}
        sizes = [1, 1, 4, 3]
    else:
        s.send(2)                        # one write: company in a window
        s.read(2)
        before = {"backlog": 0, "window": 1, "lone": 0}
        sizes = [2]
    assert _gathers() == before
    assert s.held.sizes == sizes
    t0 = time.perf_counter()
    s.send(1, conn=0)
    time.sleep(WINDOW_S / 3)
    s.send(1, conn=1)
    s.read(1, conn=0)
    s.read(1, conn=1)
    assert time.perf_counter() - t0 >= WINDOW_S
    assert s.held.sizes == sizes + [2]
    assert _gathers() == dict(before, window=before["window"] + 1)
    # and a lone caller after that pays one window to find itself alone
    assert s.ask() >= WINDOW_S
    s.ask()
    assert _gathers() == dict(before, window=before["window"] + 2,
                              lone=before["lone"] + 1)


def test_a_lone_arrival_takes_what_is_queued_with_it(served):
    """In the lone state requests that arrive in one write leave in one
    batch (whatever `get_nowait()` finds), and that batch arms the
    window again."""
    s = served()
    s.ask()
    s.ask()
    s.send(3)
    s.read(3)
    assert s.held.sizes == [1, 1, 3]
    assert _gathers() == {"backlog": 0, "window": 1, "lone": 2}
    assert s.ask() >= WINDOW_S
    assert _gathers() == {"backlog": 0, "window": 2, "lone": 2}


@pytest.mark.parametrize("joins", [False, True])
def test_one_queued_request_waits_the_window_for_the_second_caller(
        served, joins):
    """ONE request queued when the executor frees proves a second
    caller, and is no company yet: it arms the window again and waits
    it, and the caller whose reply has just left meets it there.  (Sent
    on alone, the two would alternate in batches of one for good.)"""
    s = served(connections=2)
    s.ask()                              # window, futile
    s.hold_one()                         # lone, held on the executor
    s.send(1, conn=1)
    s.wait_for(lambda: s.server._queue.qsize() == 1, "never queued")
    s.held.release()
    s.read(1)                            # the held one's reply
    if joins:
        s.send(1)                        # its caller is back, in the window
    s.read(1, conn=1)
    if joins:
        s.read(1)
    assert s.held.sizes == [1, 1, 1 + joins]
    assert _gathers() == {"backlog": 0, "window": 2, "lone": 1}
    gather = metrics.histogram("server.batch_gather")
    assert 2 * WINDOW_S <= gather.sum < 2.5 * WINDOW_S
    # company in that window keeps it armed; none makes it futile again
    before = len(s.held.sizes)
    s.ask()
    assert _gathers() == {"backlog": 0, "window": 2 + joins,
                          "lone": 2 - joins}
    assert s.held.sizes[before:] == [1]


@pytest.mark.parametrize("callers", [2, 4])
def test_callers_that_join_during_an_execution_coalesce(served, callers):
    """Closed-loop callers that join one at a time, each while a batch
    executes, end up in ONE batch a cycle, as they would from a common
    start: where they join decides nothing for good."""
    window_s = pad_s = 0.04
    s = served(batch_window_ms=1e3 * window_s, connections=callers,
               pad_s=pad_s)
    stop = threading.Event()

    def loop(conn):
        while not stop.is_set():
            s.ask(conn)
    threads = [threading.Thread(target=loop, args=(c,), daemon=True,
                                name=f"caller-{c}")
               for c in range(callers)]

    def batches_more(n, said):
        want = len(s.held.sizes) + n
        s.wait_for(lambda: len(s.held.sizes) >= want, said)
    try:
        threads[0].start()
        batches_more(3, "the first caller is not served")
        for t in threads[1:]:
            batches_more(1, "no batch to join during")   # it executes NOW
            t.start()
            batches_more(4, "the callers are not served")
        batches_more(6, "the callers are not served")
        tail = s.held.sizes[-6:]
    finally:
        stop.set()
        for t in threads:
            t.join(20)
    # a caller that the sandbox holds up for a whole window misses one
    # batch and is back in the next: all but one of the last six
    assert sum(size == callers for size in tail) >= 5, s.held.sizes


@pytest.mark.parametrize("held_up", ["send", "stuck_send", "fault_delay"])
def test_a_backlog_waits_for_replies_that_take_their_time(served, held_up):
    """The order is the batcher's own, not the event loop's: replies
    whose write suspends are still out before the backlog is dispatched,
    if they are out within a window (a connection that does not read
    holds no batch longer).  An injected delay is a fault of ONE
    response and holds no batch."""
    kw = {}
    if held_up == "fault_delay":
        kw["fault_spec"] = "delay@server.respond:ms=150,p=1"
    s = served(**kw)
    if held_up != "fault_delay":
        send = s.server._send
        slow_s = WINDOW_S / 2 if held_up == "send" else 3 * WINDOW_S

        async def slow_send(cid, payload):
            await asyncio.sleep(slow_s)
            await send(cid, payload)
        s.server._send = slow_send
    s.trickling(4)
    s.queue(3)
    s.held.release()
    s.read(8)
    assert s.held.sizes == [1, 1, 4, 3]
    # the four replies of the batch before it: out, or not waited for
    assert (s.held.answered[3] == 6) == (held_up == "send")
    waited = metrics.histogram("server.batch_reply_wait")
    assert waited.count == 1
    low, high = {"send": (WINDOW_S / 2, WINDOW_S),
                 "stuck_send": (WINDOW_S, 2 * WINDOW_S),
                 "fault_delay": (0, WINDOW_S / 3)}[held_up]
    assert low <= waited.sum < high
    assert metrics.counter_value("server.batch_reply_timeouts") == (
        held_up == "stuck_send")


@pytest.mark.parametrize("back_after", ["window", "never", "short", "band"])
def test_a_long_batch_earns_its_callers_a_wait_past_the_window(
        served, back_after):
    """A batch that held the executor 1.6 s (a graph walk's, scaled up
    for a sandbox's clock) earns its four callers PATIENCE_SHARE (a
    quarter) of that: back 0.1 s after a 0.04 s window they share ONE
    batch with what queued behind them, sent on the moment the last is
    back.  Never back, the wait ends after 0.4 s and the trickle rule
    takes over; after a batch of 0.1 s no gather goes past its window
    at all, nor after one of 0.24 s (`band`: a quarter of it less the
    window is half a window, not one more)."""
    window_s = 0.04
    pad_s = {"short": 0.1, "band": 0.24}.get(back_after, 1.6)
    s = served(batch_window_ms=1e3 * window_s, pad_s=pad_s,
               connections=2)
    s.send(4)                            # one write: one window, one batch
    s.wait_batches(1)
    s.send(2, conn=1)                    # queue while it executes
    s.read(4)
    t_read = time.perf_counter()
    if back_after != "never":
        time.sleep(0.1)                  # past the window
        s.send(4)
    s.read(2, conn=1)
    if back_after != "never":
        s.read(4)
    patient = metrics.counter_value("server.gather_patient")
    gather = metrics.histogram("server.batch_gather")
    if back_after == "window":
        assert s.held.sizes == [4, 6]
        assert patient == 1
        # all six were there 0.1 s in: the rest of the 0.4 s was not
        # waited (the server's clock, less the first batch's window)
        assert 0.08 <= gather.sum - window_s < 0.19
    elif back_after == "never":
        assert s.held.sizes == [4, 2]
        assert patient == 1
        earned = pad_s * server_module.PATIENCE_SHARE
        assert time.perf_counter() - t_read >= earned - 0.01
        assert earned - 0.01 <= gather.sum - window_s < earned + 0.2
    else:
        assert s.held.sizes == [4, 2, 4]
        assert patient == 0
        assert gather.sum < 4 * window_s
    got = _gathers()
    # (after the short batch the four come back while the two execute
    # or after: a backlog sent on at once, or a window)
    assert got["window"] + got["backlog"] == len(s.held.sizes)
    assert got["window"] >= 2 and got["lone"] == 0


@pytest.mark.parametrize("share,whole", [(server_module.PATIENCE_SHARE,
                                          True), (0.0, False)])
def test_callers_out_of_step_behind_long_batches_end_up_in_one(
        served, monkeypatch, share, whole):
    """Eight closed-loop callers that join 60 ms apart and take 12 ms
    to come back with their next request (a generator process with 128
    of them takes 15-40), behind batches of 0.24 s and a window of 4 ms:
    with the wait past the window they are ONE batch a cycle within a
    few cycles; without it (the share set to nothing: the batcher before
    ISSUE 32) they go on in the groups they joined in, taking turns on
    the executor."""
    monkeypatch.setattr(server_module, "PATIENCE_SHARE", share)
    callers = 8
    s = served(batch_window_ms=4.0, connections=callers, pad_s=0.24)
    stop = threading.Event()

    def loop(conn):
        time.sleep(0.06 * conn)
        while not stop.is_set():
            s.ask(conn)
            time.sleep(0.012)
    threads = [threading.Thread(target=loop, args=(c,), daemon=True,
                                name=f"caller-{c}")
               for c in range(callers)]
    try:
        for t in threads:
            t.start()
        s.wait_for(lambda: len(s.held.sizes) >= 14,
                   "the callers are not served")
        tail = s.held.sizes[8:14]
    finally:
        stop.set()
        for t in threads:
            t.join(20)
    if whole:
        assert sum(size == callers for size in tail) >= 5, s.held.sizes
        assert metrics.counter_value("server.gather_patient") >= 1
    else:
        assert max(tail) < callers, s.held.sizes
        assert metrics.counter_value("server.gather_patient") == 0


def test_a_gather_without_a_window_is_never_patient(served):
    """`batch_window_ms` 0 asks for no waiting: none is added."""
    s = served(batch_window_ms=0.0, pad_s=0.8, connections=2)
    s.send(4)
    s.wait_batches(1)
    s.send(2, conn=1)
    s.read(4)
    s.read(2, conn=1)
    assert metrics.counter_value("server.gather_patient") == 0
    assert metrics.histogram("server.batch_gather").sum < 0.05


@pytest.mark.parametrize("callers", [1, 3, 8])
def test_gather_counters_sum_to_the_executed_batches(served, callers):
    """(d) Every gathered batch is counted once, under the reason it
    left: the three counters sum to the `server.execute_batch` spans."""
    per_caller = 20
    s = served(batch_window_ms=2.0, max_batch=4, connections=callers)

    def loop(conn):
        for _ in range(per_caller):
            s.socks[conn].sendall(_request(s.data, conn, conn, ""))
            s.read(1, conn)
    threads = [threading.Thread(target=loop, args=(c,), daemon=True,
                                name=f"caller-{c}")
               for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert sum(s.held.sizes) == callers * per_caller
    assert max(s.held.sizes) <= 4
    assert sum(_gathers().values()) == len(s.held.sizes) \
        == metrics.histogram("server.execute_batch").count
    if callers == 1:
        assert _gathers() == {"backlog": 0, "window": 1,
                              "lone": per_caller - 1}
