"""The seven readers of PR 38, each on a hand-made `run`: the two `path.*`
readers place the program's mean arrival and departure instants
(`server.arrival_clock`, `server.departure_clock`, counted from gauge
`server.clock_origin_s`) on the generator's own clock; the other five
read one record each.  None, never a raise, where the parent's program
has no such record, a request had no reply, or the counts differ."""

import json
import os

import numpy as np
import pytest

from benchmark.loadgen import load_by_name
from sptag_tpu.utils import metrics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("path.way_in_ms", "path.way_back_ms", "server.request_ms",
       "server.reply_ms", "loop.lag_ms", "loop.cpu_share",
       "executor.cpu_share")

ORIGIN = 5000.0            # the server's perf_counter() at start()
T0 = 5100.0                # the generator's at the window's start
#: four requests sent 1, 2, 3, 4 s into the window; each is 2 ms on its
#: way in, 10 ms in the server and 3 ms on its way back
T_SEND = np.array([1.0, 2.0, 3.0, 4.0])
LATENCY = np.full(4, 0.015)
OK = 0
SPANS = {
    "server.arrival_clock": {
        "count": 4, "total_s": float((T0 - ORIGIN + T_SEND + 0.002).sum())},
    "server.departure_clock": {
        "count": 4, "total_s": float((T0 - ORIGIN + T_SEND + 0.012).sum())},
    "server.request": {"count": 4, "total_s": 0.040},
    "server.batch_reply": {"count": 2, "total_s": 0.003},
    "server.loop_lag": {"count": 400, "total_s": 0.100},
    "server.batch_cycle": {"count": 2, "total_s": 2.0},
    "server.loop_cpu": {"count": 2, "total_s": 0.5},
    "server.executor_cpu": {"count": 3, "total_s": 0.8},
}
WANT = {"path.way_in_ms": 2.0, "path.way_back_ms": 3.0,
        "server.request_ms": 10.0, "server.reply_ms": 1.5,
        "loop.lag_ms": 0.25, "loop.cpu_share": 25.0,
        "executor.cpu_share": 40.0}
#: the parent of PR 38 records these of the names above, and no others
PARENT = ("server.request", "server.batch_cycle")


def _requests(**changed):
    r = {"t_send": T_SEND, "latency": LATENCY,
         "status": np.full(4, OK), "success_status": np.int64(OK),
         "window_t0": np.float64(T0)}
    r.update(changed)
    return r


@pytest.fixture
def origin():
    metrics.set_gauge("server.clock_origin_s", ORIGIN)
    yield
    metrics.reset()


def _read(metric, spans=None, **changed):
    return load_by_name("layer_metrics", metric).read(
        {"spans": SPANS if spans is None else spans,
         "requests": _requests(**changed)})


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_a_hand_made_run(origin, metric):
    assert _read(metric) == pytest.approx(WANT[metric], abs=1e-6)


def test_the_three_terms_make_up_the_mean_latency(origin):
    terms = sum(_read(m) for m in ("path.way_in_ms", "server.request_ms",
                                   "path.way_back_ms"))
    assert terms == pytest.approx(1e3 * LATENCY.mean(), abs=1e-6)


@pytest.mark.parametrize("metric", NEW)
def test_reader_has_nothing_to_read_on_the_parents_spans(origin, metric):
    older = {n: s for n, s in SPANS.items() if n in PARENT}
    got = _read(metric, spans=older)
    if metric == "server.request_ms":       # the parent records it too
        assert got == pytest.approx(10.0)
    else:
        assert got is None
    assert load_by_name("layer_metrics", metric).read(
        {"spans": {}, "requests": _requests()}) is None


@pytest.mark.parametrize("metric", ("path.way_in_ms", "path.way_back_ms"))
def test_path_readers_want_the_same_requests_on_both_sides(origin, metric):
    # one request had no reply (loops/closed.py: NO_REPLY, latency NaN)
    lost = LATENCY.copy()
    lost[3] = np.nan
    assert _read(metric, latency=lost,
                 status=np.array([OK, OK, OK, -2])) is None
    # a request the program shed, expired or dropped is in one sum only
    for name in ("server.arrival_clock", "server.departure_clock"):
        fewer = {**SPANS, name: {"count": 3,
                                 "total_s": SPANS[name]["total_s"]}}
        assert _read(metric, spans=fewer) is None
    # nothing was written
    assert _read(metric, t_send=T_SEND[:0], latency=LATENCY[:0],
                 status=np.zeros(0, np.int64)) is None


@pytest.mark.parametrize("metric", ("path.way_in_ms", "path.way_back_ms"))
def test_path_readers_want_the_origin_and_the_generators_record(metric):
    metrics.reset()                          # no server.clock_origin_s
    assert _read(metric) is None
    metrics.set_gauge("server.clock_origin_s", ORIGIN)
    try:
        read = load_by_name("layer_metrics", metric).read
        # tier-1's served run has `spans` alone
        assert read({"spans": SPANS}) is None
        assert read({"spans": SPANS, "requests": None}) is None
    finally:
        metrics.reset()


def test_the_new_metrics_are_appended_with_every_cell_listed():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    at = [m["name"] for m in bench["per_layer"]].index(NEW[0])
    added = bench["per_layer"][at:at + len(NEW)]
    assert tuple(m["name"] for m in added) == NEW
    for m in added:
        # the eight cells PR 38 found; a later cell opts in by name
        assert m["workloads"][:8] == cells[:8]
        assert set(m["workloads"]) <= set(cells)
        assert m["source"] == ("host_clock" if m["name"].startswith("path.")
                               else "program_span")
        assert os.path.exists(os.path.join(
            HERE, "layer_metrics", m["name"] + ".py"))
