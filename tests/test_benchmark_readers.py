"""The benchmark's per-layer readers against the program itself.

`python3 -m benchmark.run` is the one way this repo is measured, and its
per-layer metrics read the program's span names and counters.  The
readers' own tests (benchmark/tests/, run by hand) feed them hand-made
runs; nothing there notices a program PR that renames `server.batch_cycle`
— the metric would read null on the chip and stay so.  Here a tiny FLAT
index is served on the CPU through `SearchServer`, the `run` dict is
filled the way benchmark/run.py::run_cell fills it (`spans` = the window's
delta of `trace.report()`, `compiles_in_window` from
utils/recompile_guard.py), and every reader BENCHMARK.json lists with a
`source` that needs no chip must return a number.

The cases come from BENCHMARK.json: a metric a later PR adds is held too.
Durations are CPU times and are never compared with anything.
"""

import json
import math
import os

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.harness import serving
from benchmark.loadgen import load_by_name
from benchmark.run import span_deltas
from conftest import ServerThread
from sptag_tpu.serve.client import AnnClientPool
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import ServiceContext, ServiceSettings
from sptag_tpu.utils import recompile_guard, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what a run off the chip can fill; `device_trace` needs the chip's
#: profile, `host_clock` the load generator's own record
OFF_CHIP_SOURCES = ("program_span", "program_counter")
K, BURST, BURSTS = 5, 8, 3

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = [m["name"] for m in json.load(_f)["per_layer"]
                 if m["source"] in OFF_CHIP_SOURCES]


@pytest.fixture(scope="module")
def run():
    """A warm-up burst (set-up: it compiles the rung), then a window of
    BURSTS bursts -> the keys of run_cell's `run` that need no chip."""
    data = np.random.default_rng(0).standard_normal((200, 8)).astype(
        np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    ctx = ServiceContext(ServiceSettings(default_max_result=K))
    ctx.add_index("main", index)
    thread = ServerThread(SearchServer(ctx, batch_window_ms=20.0,
                                       max_batch=BURST))
    thread.start()
    host, port = thread.wait_ready()
    texts = [serving.query_text("main", K, row) for row in data[:BURST]]
    try:
        with AnnClientPool(host, port, connections=2, timeout_s=60.0,
                           max_workers=BURST) as pool:
            def burst():
                answers = [f.result() for f in
                           [pool.search_async(t) for t in texts]]
                assert [a.results[0].ids[0] for a in answers] \
                    == list(range(BURST))

            burst()
            before = trace.report()
            with recompile_guard.track_compiles("benchmark.window") as log:
                for _ in range(BURSTS):
                    burst()
            spans = span_deltas(before, trace.report())
    finally:
        thread.stop()
    return {"spans": spans, "compiles_in_window": log.count}


def test_benchmark_lists_readers_that_need_no_chip():
    """An empty list would hold nothing and fail nowhere (a `source`
    renamed in BENCHMARK.json)."""
    assert PER_LAYER and len(set(PER_LAYER)) == len(PER_LAYER)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reader_returns_a_number_from_the_served_program(run, metric):
    value = load_by_name("layer_metrics", metric).read(run)
    assert isinstance(value, (int, float)), (
        f"{metric} read {value!r}: the span or counter it reads is not "
        f"recorded under that name any more (spans seen: "
        f"{sorted(run['spans'])})")
    assert math.isfinite(value) and value >= 0
