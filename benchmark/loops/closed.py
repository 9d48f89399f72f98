"""`closed`: every caller sends its next query when its reply arrives.

`callers` logical callers share `connections` sockets; caller i cycles the
distinct queries from offset i * distinct / callers.  A slow server
receives less load: the mix states no rate.

One thread per connection carries its callers: it reads a reply, records
it, and writes that caller's next request — so the generator stays a
small, steady share of one core at rates where a thread per caller (the
program's PipelinedAnnClient under 128 threads: 124 % of a core at 2,300
queries/s, first chip run of PR 24) would compete with the server it
measures.  It speaks the program's wire format through
`sptag_tpu.serve.wire` (register handshake, SearchRequest with the
reference-exact body, SearchResponse matched by resource id) and takes
nothing else of the program.
"""

import gc
import socket
import threading
import time

import numpy as np

MALFORMED = -1          # a Success reply that is not one list of k ids
NO_REPLY = -2           # nothing came back by the window's end + GRACE_S
GRACE_S = 5.0


def _read_exact(sock, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(n)
        if not chunk:
            raise OSError("connection closed")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def _read_packet(sock, wire):
    header = wire.PacketHeader.unpack(_read_exact(sock, wire.HEADER_SIZE))
    body = _read_exact(sock, header.body_length) if header.body_length \
        else b""
    return header, body


def _dial(host, port, wire):
    sock = socket.create_connection((host, port), timeout=60.0)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.sendall(wire.PacketHeader(wire.PacketType.RegisterRequest).pack())
    header, _ = _read_packet(sock, wire)
    cid = header.connection_id \
        if header.packet_type == wire.PacketType.RegisterResponse \
        else wire.INVALID_CONNECTION_ID
    return sock, cid


def run(spec: dict, texts: list, ready, go) -> dict:
    """Connect, call `ready()`, block in `go()`, drive the window.
    Returns the per-request arrays the parent reduces."""
    from sptag_tpu.serve import wire

    # the arrays below grow by a few objects a request and hold no cycle:
    # a full collection over them stalls every caller at once, longer the
    # longer the window runs (PR 24: 30 s windows read 3-14 % fewer
    # queries/s than 10 s windows until this was off)
    gc.disable()
    traffic, k, seconds = spec["traffic"], spec["k"], spec["seconds"]
    callers, conns = traffic["callers"], traffic["connections"]
    ok = int(wire.ResultStatus.Success)
    bodies = [wire.RemoteQuery(t).pack() for t in texts]
    socks = [_dial(spec["host"], spec["port"], wire) for _ in range(conns)]
    rows = [[] for _ in range(conns)]
    start = threading.Barrier(conns + 1)
    window = {}

    def connection(c: int):
        sock, cid = socks[c]
        out = rows[c]
        mine = range(c, callers, conns)
        at = {i: i * len(texts) // callers for i in mine}
        sent = {}                       # resource id -> (caller, t_send)
        rid = 0

        def send(i, t_read):
            nonlocal rid
            rid += 1
            body = bodies[at[i]]
            packet = wire.PacketHeader(
                wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
                len(body), cid, rid).pack() + body
            sock.sendall(packet)
            t = time.perf_counter()
            sent[rid] = (i, t, 0.0 if t_read is None else t - t_read)

        start.wait()
        t0, t_end = window["t0"], window["t0"] + seconds
        for i in mine:
            send(i, None)
        try:
            while sent:
                sock.settimeout(max(0.05, t_end + GRACE_S
                                    - time.perf_counter()))
                header, body = _read_packet(sock, wire)
                t_read = time.perf_counter()
                if header.packet_type != wire.PacketType.SearchResponse \
                        or header.resource_id not in sent:
                    continue
                i, t_send, turnaround = sent.pop(header.resource_id)
                res = wire.RemoteSearchResult.unpack(body)
                status, ids, dists = MALFORMED, None, None
                if res is not None:
                    status = int(res.status)
                    if status == ok:
                        if len(res.results) == 1 \
                                and len(res.results[0].ids) == k \
                                and len(res.results[0].dists) == k:
                            ids = res.results[0].ids
                            dists = res.results[0].dists
                        else:
                            status = MALFORMED
                out.append((at[i], t_send - t0, t_read - t_send, status,
                            turnaround, ids, dists))
                at[i] = (at[i] + 1) % len(texts)
                if t_read < t_end:
                    send(i, t_read)
        except OSError:                 # a timeout is one: no reply in time
            pass
        for i, t_send, turnaround in sent.values():
            out.append((at[i], t_send - t0, float("nan"), NO_REPLY,
                        turnaround, None, None))

    threads = [threading.Thread(target=connection, args=(c,), daemon=True)
               for c in range(conns)]
    for t in threads:
        t.start()
    ready()
    go()
    cpu0 = time.process_time()
    window["t0"] = time.perf_counter()
    start.wait()
    for t in threads:
        t.join()
    wall = time.perf_counter() - window["t0"]
    cpu = time.process_time() - cpu0
    for sock, _ in socks:
        sock.close()

    flat = [r for conn_rows in rows for r in conn_rows]
    n = len(flat)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan, np.float32)
    for j, r in enumerate(flat):
        if r[3] == ok:
            ids[j], dists[j] = r[5], r[6]
    return {"query": np.asarray([r[0] for r in flat], np.int64),
            "t_send": np.asarray([r[1] for r in flat], np.float64),
            "latency": np.asarray([r[2] for r in flat], np.float64),
            "status": np.asarray([r[3] for r in flat], np.int64),
            "turnaround": np.asarray([r[4] for r in flat], np.float64),
            "ids": ids, "dists": dists,
            "success_status": np.int64(ok),
            "window_t0": np.float64(window["t0"]),
            "generator_cpu_s": np.float64(cpu),
            "generator_wall_s": np.float64(wall)}
