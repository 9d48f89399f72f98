"""`closed_writer`: `closed`'s searchers beside ONE writer connection.

The searchers ARE `loops/closed.py` (its `run`, its record keys: `qps`, the
latencies, `loadgen.*` and `path.*` read searches alone).  The writer is a
thread of its own with `writer_connections` = 1 socket and one operation
in flight, so the mutations have a serial order:

* **warm-up, before `ready`** (at most `WARM_LIMIT_S` of new steps):
  `warm_steps` steps, step w = `$admin:add` of block w, then
  `$admin:delete` (by content) of block w - 1; the last block is deleted
  too, so the window opens on the base rows alone and every program a
  step runs has run.
* **the window**: at each `writer_period_ms` boundary one step = add of
  the next block and, on its reply, delete of the block added
  `delete_lag_steps` steps earlier.  One operation is in flight, so a
  step whose predecessor is still running at its boundary starts when
  that one ends, late; a boundary whose whole period has passed by then
  is skipped and counted (`w_steps_skipped`): the writer never runs more
  than one period behind and never offers more than a step a period.
  (A hiccup of the machine, a batch that takes 130 ms where 17 is the
  rule, delays a step; only a server that cannot keep the pace loses
  steps.)  No step starts after the window's end, and every socket wait
  ends by the window's end + `closed.GRACE_S`.

Block s is `harness/runbook.streamed_rows(queries, s, rows_per_add,
stream_sigma)`, the queries parsed back from their texts; it goes over the
wire as a base64 float32 block (`#...`).  The writer's arrays (`w_*`) are
one entry an operation, warm-up's included (`w_t_send` < 0), on the
searchers' clock: `w_t_send` is stamped BEFORE the write and counted from
`window_t0`, `w_latency` ends after the reply is read.  A check reads them
through `benchmark/checks/exact_ids_live.py::writer_ops`.
"""

import base64
import threading
import time

import numpy as np

from benchmark.harness import runbook
from benchmark.loadgen import load_by_name

ADD, DELETE = 0, 1
WARM_LIMIT_S = 60.0


class Writer:
    def __init__(self, spec: dict, texts: list, closed, wire):
        self.closed, self.wire = closed, wire
        self.traffic, self.seconds = spec["traffic"], spec["seconds"]
        t = self.traffic
        assert t["writer_connections"] == 1 \
            and t["rows_per_delete"] == t["rows_per_add"]
        self.name = runbook.index_name(texts[0])
        self.queries = np.stack([runbook.query_vector(x) for x in texts])
        steps = t["warm_steps"] + int(
            self.seconds * 1e3 // t["writer_period_ms"]) + 1
        self.bodies = []               # per step: (add body, delete body)
        for s in range(steps):
            block = base64.b64encode(runbook.streamed_rows(
                self.queries, s, t["rows_per_add"],
                t["stream_sigma"]).tobytes()).decode()
            self.bodies.append(tuple(
                wire.RemoteQuery(f"$admin:{op} $indexname:{self.name} "
                                 f"#{block}").pack()
                for op in ("add", "delete")))
        self.sock, self.cid = closed._dial(spec["host"], spec["port"], wire)
        self.ops = []      # (kind, step, t_send, latency, ok, count)
        self.rid = 0
        self.deadline = None
        self.steps_done = self.steps_skipped = 0
        self.thread = None

    def _op(self, kind: int, step: int) -> bool:
        """One operation, its reply awaited -> whether it was
        acknowledged `ok` in time."""
        wire, rows = self.wire, self.traffic["rows_per_add"]
        body = self.bodies[step][kind]
        self.rid += 1
        packet = wire.PacketHeader(
            wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
            len(body), self.cid, self.rid).pack() + body
        t_send = time.perf_counter()
        latency, ok, count = float("nan"), False, -1
        try:
            self.sock.settimeout(max(0.05, self.deadline - t_send))
            self.sock.sendall(packet)
            while True:
                self.sock.settimeout(
                    max(0.05, self.deadline - time.perf_counter()))
                header, reply = self.closed._read_packet(self.sock, wire)
                if header.packet_type == wire.PacketType.SearchResponse \
                        and header.resource_id == self.rid:
                    break
            latency = time.perf_counter() - t_send
            res = wire.RemoteSearchResult.unpack(reply)
            if res is not None and len(res.results) == 1:
                first = res.results[0]
                ok = (int(res.status) == int(wire.ResultStatus.Success)
                      and first.index_name.startswith("admin:ok:"))
                count = int(first.ids[0]) if len(first.ids) else -1
        except OSError:            # a timeout is one: no reply in time
            pass
        self.ops.append((kind, step, rows, t_send, latency, ok, count))
        return ok

    def warm(self) -> None:
        t0 = time.perf_counter()
        self.deadline = t0 + 2 * WARM_LIMIT_S
        last = None
        for w in range(self.traffic["warm_steps"]):
            if time.perf_counter() - t0 > WARM_LIMIT_S:
                break
            if not self._op(ADD, w):
                break
            last, before = w, last
            if before is not None and not self._op(DELETE, before):
                break
        if last is not None:
            self._op(DELETE, last)

    def start(self) -> None:
        self.thread = threading.Thread(target=self._window, daemon=True)
        self.thread.start()

    def _window(self) -> None:
        t = self.traffic
        period = t["writer_period_ms"] / 1e3
        t0 = time.perf_counter()
        t_end = t0 + self.seconds
        self.deadline = t_end + self.closed.GRACE_S
        first = t["warm_steps"]
        step, boundary = first, 0
        while True:
            now = time.perf_counter()
            # boundaries whose whole period is over are lost
            current = int((now - t0) // period)
            if current > boundary:
                self.steps_skipped += current - boundary
                boundary = current
            due = t0 + boundary * period
            if due >= t_end or now >= t_end:
                break
            if now < due:
                time.sleep(due - now)
            if not self._op(ADD, step):
                break
            self.steps_done += 1
            if step - t["delete_lag_steps"] >= first \
                    and not self._op(DELETE, step - t["delete_lag_steps"]):
                break
            step += 1
            boundary += 1

    def record(self, window_t0: float) -> dict:
        if self.thread is not None:
            self.thread.join()
        self.sock.close()
        col = list(zip(*self.ops)) if self.ops else [[]] * 7
        t = self.traffic
        return {"w_kind": np.asarray(col[0], np.int64),
                "w_step": np.asarray(col[1], np.int64),
                "w_rows": np.asarray(col[2], np.int64),
                "w_t_send": np.asarray(col[3], np.float64) - window_t0,
                "w_latency": np.asarray(col[4], np.float64),
                "w_ok": np.asarray(col[5], bool),
                "w_count": np.asarray(col[6], np.int64),
                "w_steps_done": np.int64(self.steps_done),
                "w_steps_skipped": np.int64(self.steps_skipped),
                "w_sigma": np.float64(t["stream_sigma"]),
                "w_period_ms": np.float64(t["writer_period_ms"]),
                "w_window_s": np.float64(self.seconds)}


def run(spec: dict, texts: list, ready, go) -> dict:
    """Connect, run the warm steps, call `ready()`, block in `go()`, drive
    the window.  Returns `closed`'s per-request arrays (the searches) and
    the writer's (`w_*`)."""
    from sptag_tpu.serve import wire

    closed = load_by_name("loops", "closed")
    writer = Writer(spec, texts, closed, wire)

    def ready_when_warm():
        writer.warm()
        ready()

    def go_and_write():
        go()
        writer.start()

    record = closed.run(spec, texts, ready_when_warm, go_and_write)
    record.update(writer.record(float(record["window_t0"])))
    return record
