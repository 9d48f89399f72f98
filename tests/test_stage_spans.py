"""Per-batch stage spans inside `server.execute_batch`, the batcher's cycle
records, `trace.annotate` / `trace.start_trace`, and the named device
stages of the served programs (ISSUE 25).

What is under test is WHERE and HOW OFTEN each name is recorded — once a
batch, never per query — and that naming the device stages changes no
arithmetic.  Durations are only compared with one another.
"""

import contextlib
import socket

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sptag_tpu as sp
from conftest import ServerThread
from sptag_tpu import utils
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.serve import server as server_mod
from sptag_tpu.serve import wire
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import metrics, trace

N = 6                 # queries a batch: max_batch, so a burst is ONE batch
BATCHES = 2
PER_BATCH = ("server.execute_batch", "index.search", "index.readback",
             "service.results", "server.batch_gather",
             "server.batch_resume")
RUNGS = {b: f"server.batches_q{b}" for b in utils.QUERY_BUCKETS}


def _flat_context(n=200, d=8):
    data = np.random.default_rng(0).standard_normal((n, d)).astype(
        np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    ctx = ServiceContext(ServiceSettings(default_max_result=5))
    ctx.add_index("main", index)
    return ctx, data


def _burst(sock, data, rows) -> list:
    """Write len(rows) requests in one send, read as many responses."""
    out = b""
    for rid, row in enumerate(rows):
        body = wire.RemoteQuery("|".join(str(x) for x in data[row])).pack()
        out += wire.PacketHeader(wire.PacketType.SearchRequest,
                                 wire.PacketProcessStatus.Ok, len(body), 0,
                                 rid).pack() + body
    sock.sendall(out)
    got, buf = [], b""
    while len(got) < len(rows):
        chunk = sock.recv(65536)
        assert chunk, "server closed the connection"
        buf += chunk
        while len(buf) >= wire.HEADER_SIZE:
            head = wire.PacketHeader.unpack(buf[:wire.HEADER_SIZE])
            end = wire.HEADER_SIZE + head.body_length
            if len(buf) < end:
                break
            got.append(wire.RemoteSearchResult.unpack(
                buf[wire.HEADER_SIZE:end]))
            buf = buf[end:]
    return got


@pytest.fixture(scope="module")
def served():
    """Two bursts of N queries through a real SearchServer whose batch is
    full at N (the 300 ms window is never waited out) -> the span report
    and the counters after exactly BATCHES batches."""
    trace.reset()
    metrics.reset()
    ctx, data = _flat_context()
    thread = ServerThread(SearchServer(ctx, batch_window_ms=300.0,
                                       max_batch=N))
    thread.start()
    host, port = thread.wait_ready()
    try:
        with socket.create_connection((host, port), timeout=20) as sock:
            sock.settimeout(20)
            for b in range(BATCHES):
                rows = list(range(b * N, (b + 1) * N))
                answers = _burst(sock, data, rows)
                # resolved futures: one joined write, in batch order
                assert [a.results[0].ids[0] for a in answers] == rows
    finally:
        thread.stop()
    return {"spans": trace.report(),
            "counters": dict(metrics.snapshot()["counters"]),
            "gauges": dict(metrics.snapshot()["gauges"])}


@pytest.mark.parametrize("name", PER_BATCH)
def test_stage_occurs_once_a_served_batch(served, name):
    assert served["spans"][name]["count"] == BATCHES


def test_parse_occurs_twice_a_batch_within_execute_batch(served):
    """The option scan of every text, then the group's vectors: read as
    total / batches."""
    spans = served["spans"]
    assert spans["service.parse"]["count"] == 2 * BATCHES
    assert 0 < spans["service.parse"]["total_s"] \
        <= spans["server.execute_batch"]["total_s"]


def test_stages_nest_and_do_not_exceed_the_batch(served):
    spans = served["spans"]
    total = {n: spans[n]["total_s"] for n in spans}
    assert 0 < total["index.readback"] <= total["index.search"]
    assert (total["service.parse"] + total["index.search"]
            + total["service.results"]) <= total["server.execute_batch"]


def test_batch_cycle_is_absent_for_the_first_batch(served):
    cycle = served["spans"]["server.batch_cycle"]
    assert cycle["count"] == BATCHES - 1
    assert cycle["total_s"] >= served["spans"][
        "server.execute_batch"]["total_s"] / BATCHES


def test_batcher_records_are_non_negative(served):
    for name in ("server.batch_gather", "server.batch_resume",
                 "server.batch_cycle"):
        assert served["spans"][name]["total_s"] >= 0.0
        assert served["spans"][name]["max_s"] >= 0.0
    # both batches were full before the window ran out
    assert served["spans"]["server.batch_gather"]["max_s"] < 0.3


def test_batches_are_counted_by_padding_rung_not_gauged(served):
    assert served["counters"][RUNGS[8]] == BATCHES
    assert not [n for n in served["counters"]
                if n.startswith("server.batches_q") and n != RUNGS[8]]
    assert "server.last_batch_size" not in served["gauges"]


def test_per_request_sites_record_nothing(served):
    """`server.dispatch` and `server.stream_response` are annotations."""
    assert "server.dispatch" not in served["spans"]
    assert "server.stream_response" not in served["spans"]
    assert served["spans"]["server.queue_wait"]["count"] == BATCHES * N


@pytest.mark.parametrize("size,rung", [(1, 1), (2, 8), (8, 8), (9, 32),
                                       (128, 128), (129, 256),
                                       (1024, 1024), (5000, 1024)])
def test_count_batch_follows_the_shared_padding_ladder(size, rung):
    assert rung == utils.query_bucket(size, utils.QUERY_BUCKETS[-1])
    server_mod._count_batch(size)
    assert metrics.snapshot()["counters"] == {RUNGS[rung]: 1}


# ------------------------------------------------ executor, dense program

def test_non_streaming_branch_records_the_same_stages():
    ctx, data = _flat_context()
    texts = ["|".join(str(x) for x in data[i]) for i in range(N)]
    out = SearchExecutor(ctx).execute_batch(texts)        # no on_ready
    assert [r.results[0].ids[0] for r in out] == list(range(N))
    spans = trace.report()
    assert spans["service.parse"]["count"] == 2
    for name in ("index.search", "index.readback", "service.results"):
        assert spans[name]["count"] == 1


def _dense_index(n=600, d=16):
    data = np.random.default_rng(3).standard_normal((n, d)).astype(
        np.float32)
    index = sp.create_instance("BKT", "Float")
    for key, value in {
            "DistCalcMethod": "L2", "BKTKmeansK": "8", "TPTNumber": "2",
            "TPTLeafSize": "200", "NeighborhoodSize": "8", "CEF": "32",
            "MaxCheckForRefineGraph": "64", "RefineIterations": "1",
            "FinalRefineSearchMode": "same", "SearchMode": "dense",
            "DenseClusterSize": "64", "MaxCheck": "128"}.items():
        index.set_parameter(key, value)
    assert index.build(data) == sp.ErrorCode.Success
    return index, data


def test_dense_search_is_one_search_one_readback_and_says_what_it_scores():
    index, data = _dense_index()
    index.search_batch(data[:8], 5)                       # warm
    trace.reset()
    metrics.reset()
    _, ids = index.search_batch(data[:N], 5)
    spans = trace.report()
    assert spans["index.search"]["count"] == 1
    assert spans["index.readback"]["count"] == 1
    assert spans["index.readback"]["total_s"] \
        <= spans["index.search"]["total_s"]
    dense = index._dense
    blocks = -(-128 // dense.cluster_size)                # MaxCheck 128
    gauges = metrics.snapshot()["gauges"]
    assert gauges["dense.rows_per_query"] == blocks * dense.cluster_size
    assert gauges["dense.centroids_per_query"] == dense.num_clusters
    assert (ids[:, 0] == np.arange(N)).mean() >= 0.5


# ------------------------------------------------------- utils/trace.py

def test_annotate_is_one_shared_noop_and_touches_no_registry():
    assert not trace._trace_active
    first = trace.annotate("server.dispatch")
    assert first is trace.annotate("server.stream_response")
    with first:
        with trace.annotate("server.dispatch"):           # re-entrant
            pass
    assert trace.report() == {}
    snap = metrics.snapshot()
    assert not snap["counters"] and not snap["histograms"]


def test_annotate_is_a_trace_annotation_while_a_trace_is_live(monkeypatch):
    monkeypatch.setattr(trace, "_trace_active", True)
    ann = trace.annotate("server.dispatch")
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    with ann:
        pass
    assert trace.report() == {}


@pytest.mark.parametrize("asked,level", [(None, 0), (False, 0), (True, 1)])
def test_start_trace_keeps_the_python_tracer_off_unless_asked(
        monkeypatch, tmp_path, asked, level):
    seen = {}

    def fake_start(logdir, **kwargs):
        seen["logdir"], seen["kwargs"] = logdir, kwargs

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    if asked is None:
        trace.start_trace(str(tmp_path))
    else:
        trace.start_trace(str(tmp_path), python_tracer=asked)
    try:
        assert trace._trace_active
        assert seen["logdir"] == str(tmp_path)
        assert seen["kwargs"]["profiler_options"].python_tracer_level \
            == level
    finally:
        trace.stop_trace()
    assert not trace._trace_active


# ------------------------------------------- named device stages: no-ops

def _jit_again(kernel, arrays: int, statics: int):
    """The program's source behind a NEW function object (jax caches
    traces by function), its trailing `statics` arguments static."""
    return jax.jit(lambda *a: kernel.__wrapped__(*a),
                   static_argnums=tuple(range(arrays, arrays + statics)))


def _without_scopes(monkeypatch) -> None:
    """`jax.named_scope` a no-op from here on: what is traced after this
    is the program as it was before its stages were named."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


def _stage_names(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {s for s in ("flat.distance", "flat.topk", "dense.centroids",
                        "dense.gather", "dense.probe", "dense.mask",
                        "dense.topk") if f"/{s}/" in text}


def test_flat_program_names_its_stages_and_computes_the_same(monkeypatch):
    from sptag_tpu.algo import flat

    rng = np.random.default_rng(11)
    data = jnp.asarray(rng.standard_normal((256, 16)).astype(np.float32))
    sqnorm = jnp.sum(data * data, axis=1)
    invalid = jnp.asarray(rng.random(256) < 0.1)
    static = (5, int(DistCalcMethod.L2), 1)
    scoped = _jit_again(flat._flat_search_kernel, 4, 3)
    qs = {q: jnp.asarray(rng.standard_normal((q, 16)).astype(np.float32))
          for q in (8, 32)}
    assert _stage_names(scoped.lower(data, sqnorm, invalid, qs[8],
                                     *static)) \
        == {"flat.distance", "flat.topk"}
    want = {q: scoped(data, sqnorm, invalid, x, *static)
            for q, x in qs.items()}
    scoped(data, sqnorm, invalid, qs[8] + 1.0, *static)   # same bucket
    _without_scopes(monkeypatch)
    plain = _jit_again(flat._flat_search_kernel, 4, 3)
    assert not _stage_names(plain.lower(data, sqnorm, invalid, qs[8],
                                        *static))
    for q, x in qs.items():
        got = plain(data, sqnorm, invalid, x, *static)
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[q].dists))
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[q].ids))
    plain(data, sqnorm, invalid, qs[8] + 1.0, *static)
    # one program per query-count bucket, named or not
    assert scoped._cache_size() == plain._cache_size() == len(qs)


@pytest.mark.parametrize("grouped", [False, True])
def test_dense_programs_name_their_stages_and_compute_the_same(
        monkeypatch, grouped):
    from sptag_tpu.algo import dense

    rng = np.random.default_rng(12)
    C, P, D, Q, n = 8, 16, 16, 16, 100
    perm = rng.standard_normal((C, P, D)).astype(np.float32)
    ids = rng.permutation(C * P).reshape(C, P).astype(np.int32)
    ids[ids >= n] = -1                                    # block padding
    args = (jnp.asarray(perm), jnp.asarray(ids),
            jnp.asarray((perm * perm).sum(-1)),
            jnp.asarray(perm.mean(1)),
            jnp.asarray((perm.mean(1) ** 2).sum(-1)),
            jnp.asarray((ids < 0) | (rng.random((C, P)) < 0.1)),  # dead_slot
            jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32)))
    if grouped:
        kernel, shape = dense._dense_search_grouped_kernel, (8, 6)
        args += (jnp.int32(Q - 3), 5, 2, 4, 8, int(DistCalcMethod.L2), 1)
    else:
        kernel, shape = dense._dense_search_kernel, (7, 4)
        args += (5, 2, int(DistCalcMethod.L2), 1)
    scoped = _jit_again(kernel, *shape)
    assert _stage_names(scoped.lower(*args)) == {
        "dense.centroids", "dense.gather", "dense.probe", "dense.mask",
        "dense.topk"}
    want = scoped(*args)
    _without_scopes(monkeypatch)
    plain = _jit_again(kernel, *shape)
    assert not _stage_names(plain.lower(*args))
    got = plain(*args)
    assert np.array_equal(np.asarray(got.dists), np.asarray(want.dists))
    assert np.array_equal(np.asarray(got.ids), np.asarray(want.ids))
    assert (np.asarray(want.ids) >= 0).any()
