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
from sptag_tpu.utils import metrics, recompile_guard, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what a run off the chip can fill; `device_trace` needs the chip's
#: profile, `host_clock` the load generator's own record
OFF_CHIP_SOURCES = ("program_span", "program_counter")
K, BURST, BURSTS = 5, 8, 3

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)


def _walks(cell: str) -> bool:
    """Whether the cell's configuration serves `SearchMode=beam`."""
    cells = {w["name"]: w for w in _BENCH["workloads"]}
    files = {c["name"]: c["file"] for c in _BENCH["configs"]}
    with open(os.path.join(REPO, files[cells[cell]["config"]])) as f:
        return json.load(f)["index_params"].get("SearchMode") == "beam"


#: metrics listed for beam cells alone read what the walk publishes: held
#: against a served BKT index in SearchMode=beam, further down
BEAM_ONLY = [m["name"] for m in _BENCH["per_layer"]
             if m.get("workloads") and all(map(_walks, m["workloads"]))]


def _mutates(cell: str) -> bool:
    """Whether the cell's traffic has a writer beside its searchers."""
    cells = {w["name"]: w for w in _BENCH["workloads"]}
    with open(os.path.join(REPO, "benchmark", "traffic",
                           cells[cell]["traffic"] + ".json")) as f:
        return "writer_connections" in json.load(f)


#: metrics listed for cells with a writer alone read what a mutation
#: records: held against a served index that is mutated, further down
LIVE_ONLY = [m["name"] for m in _BENCH["per_layer"]
             if m.get("workloads") and all(map(_mutates, m["workloads"]))]
#: and of those, the ones listed for four-chip cells alone read what a
#: mesh placement records: held against a mutated mesh index, at the end
MESH_LIVE_ONLY = [
    m["name"] for m in _BENCH["per_layer"] if m["name"] in LIVE_ONLY
    and all({w["name"]: w for w in _BENCH["workloads"]}[c]["chips"] == 4
            for c in m["workloads"])]


def _packs_a_tree(cell: str) -> bool:
    """Whether the cell's configuration is a dense-only BKT index (the
    tree, its cut into blocks and no graph)."""
    cells = {w["name"]: w for w in _BENCH["workloads"]}
    files = {c["name"]: c["file"] for c in _BENCH["configs"]}
    with open(os.path.join(REPO, files[cells[cell]["config"]])) as f:
        return json.load(f)["index_params"].get("BuildGraph") == "0"


#: metrics listed for dense-only cells alone read the stages of such an
#: index's bring-up: held against one that is built and searched, at the end
DENSE_ONLY = [m["name"] for m in _BENCH["per_layer"]
              if m.get("workloads")
              and all(map(_packs_a_tree, m["workloads"]))]
PER_LAYER = [m["name"] for m in _BENCH["per_layer"]
             if m["source"] in OFF_CHIP_SOURCES
             and m["name"] not in BEAM_ONLY + LIVE_ONLY + DENSE_ONLY]


def _served_bursts(index, rows, warm: int, bursts: int) -> dict:
    """`warm` bursts of one query a row of `rows` through a SearchServer
    in front of `index` (set-up: they compile the rung), then a window of
    `bursts` more -> the keys of run_cell's `run` that need no chip.
    Every answer's first id is its row's own."""
    ctx = ServiceContext(ServiceSettings(default_max_result=K))
    ctx.add_index("main", index)
    thread = ServerThread(SearchServer(ctx, batch_window_ms=20.0,
                                       max_batch=BURST))
    thread.start()
    host, port = thread.wait_ready()
    texts = [serving.query_text("main", K, row) for row in rows]
    try:
        with AnnClientPool(host, port, connections=2, timeout_s=120.0,
                           max_workers=BURST) as pool:
            def burst():
                answers = [f.result() for f in
                           [pool.search_async(t) for t in texts]]
                assert [a.results[0].ids[0] for a in answers] \
                    == list(range(len(texts)))

            for _ in range(warm):
                burst()
            before = trace.report()
            with recompile_guard.track_compiles("benchmark.window") as log:
                for _ in range(bursts):
                    burst()
            spans = span_deltas(before, trace.report())
    finally:
        thread.stop()
    return {"spans": spans, "compiles_in_window": log.count}


@pytest.fixture(scope="module")
def run():
    """A tiny FLAT index: a warm-up burst, then a window of BURSTS."""
    data = np.random.default_rng(0).standard_normal((200, 8)).astype(
        np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    return _served_bursts(index, data[:BURST], 1, BURSTS)


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


# ---- the walk's readers (PR 32) -------------------------------------------

@pytest.fixture(scope="module")
def walked_index():
    """A tiny BKT index whose parameters say SearchMode=beam."""
    data = np.random.default_rng(1).standard_normal((400, 16)).astype(
        np.float32)
    index = sp.create_instance("BKT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BKTNumber", "1"), ("BKTKmeansK", "8"),
                        ("Samples", "200"), ("TPTNumber", "2"),
                        ("TPTLeafSize", "50"), ("NeighborhoodSize", "8"),
                        ("CEF", "64"), ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"), ("SearchMode", "beam"),
                        ("MaxCheck", "256")]:
        assert index.set_parameter(name, value), name
    assert index.build(data) == sp.ErrorCode.Success
    yield index, data
    index.close()


def _walked_run(index, data) -> dict:
    """Two bursts through a SearchServer in front of the beam index.
    Counters are read live by the readers, so this runs INSIDE each test
    (conftest empties the registries before every test)."""
    return _served_bursts(index, data[:BURST], 0, 2)


def test_benchmark_lists_the_walks_readers():
    assert {"kernel.beam_walk_roofline",
            "kernel.beam_trips_per_batch"} <= set(BEAM_ONLY)


def test_trips_reader_returns_a_number_from_the_served_walk(walked_index):
    run = _walked_run(*walked_index)
    value = load_by_name("layer_metrics",
                         "kernel.beam_trips_per_batch").read(run)
    plan = walked_index[0]._get_engine().walk_plan(K, 256)
    assert isinstance(value, float) and 1 <= value <= plan[3]
    # the span the walk's device wait is read from (index.device_wait_ms)
    assert load_by_name("layer_metrics",
                        "index.device_wait_ms").read(run) > 0
    assert load_by_name("layer_metrics", "index.dispatch_ms").read(run) > 0


def test_roofline_reader_finds_what_the_walk_publishes(walked_index):
    """The chip's trace aside, kernel.beam_walk_roofline needs the walk's
    two totals (rows scored over real queries), three gauges and the
    beam programs' names."""
    from sptag_tpu.algo import engine

    reader = load_by_name("layer_metrics", "kernel.beam_walk_roofline")
    assert reader.walked_per_query() is None        # nothing walked yet
    run = _walked_run(*walked_index)
    eng = walked_index[0]._get_engine()
    _, L, B, T, _ = eng.walk_plan(K, 256)
    rows, pool, pivots, itemsize = reader.walked_per_query()
    assert 0 < rows <= T * B * 8 and pool == L and itemsize == 4
    assert rows == metrics.counter_value("beam.rows_scored_total") \
        / metrics.counter_value("beam.queries_total")
    assert pivots == eng.pivot_ids.shape[0]
    for program in reader.ENTRY + reader.REST:
        assert program.startswith("jit_")
        assert hasattr(engine, program[len("jit_"):]), program
    # a hand-made slice: two batches of 8 queries in 1 ms of device time
    run.update(trace={"programs": {"jit__beam_search_kernel":
                                   {"runs": 2, "seconds": 1e-3}}},
               config={"dim": 16},
               peaks={"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9})
    assert 0 < reader.read(run) <= 100
    assert reader.read({**run, "trace": None}) is None


def test_the_walks_readers_read_nothing_without_a_walk(run):
    """The parent's program, or a cell that walks no graph: None, no
    raise."""
    for metric in BEAM_ONLY:
        assert load_by_name("layer_metrics", metric).read(
            {**run, "trace": {"programs": {}}, "config": {"dim": 8},
             "peaks": {}}) is None


# ---- the mutations' readers (PR 40) ----------------------------------------

LIVE_OFF_CHIP = [m for m in LIVE_ONLY if m not in MESH_LIVE_ONLY
                 and {x["name"]: x for x in _BENCH["per_layer"]}[m]["source"]
                 in OFF_CHIP_SOURCES]


@pytest.fixture()
def mutated_run(tmp_path):
    """A tiny FLAT index with its log armed: a warm add and delete, then
    a window of two adds and one delete by content (what `run_cell`'s
    `spans` would hold of them)."""
    rng = np.random.default_rng(2)
    data = rng.standard_normal((300, 8)).astype(np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.set_parameter("WalEnabled", "1")
    index.build(data)
    assert index.save_index(str(tmp_path / "idx")) == sp.ErrorCode.Success
    index = sp.load_index(str(tmp_path / "idx"))
    index.search_batch(data[:1], K)                 # place the block
    blocks = [rng.standard_normal((4, 8)).astype(np.float32)
              for _ in range(3)]
    index.add(blocks[0])
    assert index.delete_rows(blocks[0]) == (sp.ErrorCode.Success, 4)
    before = trace.report()
    index.add(blocks[1])
    index.add(blocks[2])
    assert index.delete_rows(blocks[1]) == (sp.ErrorCode.Success, 4)
    return {"spans": span_deltas(before, trace.report())}


def test_benchmark_lists_the_mutations_readers():
    assert {"mutation.add_ms", "mutation.delete_ms",
            "mutation.block_update_ms", "mutation.upload_bytes_per_row",
            "mutation.wal_appends_per_op"} <= set(LIVE_OFF_CHIP)
    assert "kernel.live_scan_roofline" in LIVE_ONLY


@pytest.mark.parametrize("metric", LIVE_OFF_CHIP)
def test_reader_returns_a_number_from_the_mutated_index(mutated_run, metric):
    value = load_by_name("layer_metrics", metric).read(mutated_run)
    assert isinstance(value, (int, float)), (
        f"{metric} read {value!r} (spans seen: "
        f"{sorted(mutated_run['spans'])})")
    assert math.isfinite(value) and value > 0
    if metric == "mutation.wal_appends_per_op":
        assert value == 1.0
    if metric == "mutation.upload_bytes_per_row":
        # two adds of 4 rows on the 8-row rung, one delete of 4 rows
        assert value == (2 * (8 * 33 + 4) + 8 * 4) / 12


def test_the_mutations_readers_read_nothing_without_a_mutation(run):
    """The parent's program, or a cell that mutates nothing: None, no
    raise."""
    for metric in LIVE_ONLY:
        assert load_by_name("layer_metrics", metric).read(
            {**run, "trace": {"programs": {}},
             "config": {"algo": "FLAT", "rows": 200, "dim": 8},
             "peaks": {}}) is None


def test_live_roofline_arithmetic_against_a_hand_count(run):
    """5M x 100 f32 read once a run at 819 GB/s is 2.442 ms: 64 queries
    a run in 15.5 ms of device time read 15.75 %, bound by the HBM."""
    reader = load_by_name("layer_metrics", "kernel.live_scan_roofline")
    spans = {"server.queue_wait": {"count": 640, "total_s": 1.0},
             "server.execute_batch": {"count": 10, "total_s": 1.0}}
    got = reader.bound({
        "spans": spans, "peaks": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9},
        "config": {"algo": "FLAT", "rows": 5_000_000, "dim": 100},
        "trace": {"programs": {reader.PROGRAM: {"runs": 10,
                                                "seconds": 0.155}}}})
    least, seconds = got
    assert least["bound"] == "hbm" and seconds == 0.155
    assert math.isclose(least["seconds"], 10 * 2e9 / 819e9)
    assert math.isclose(100 * least["seconds"] / seconds, 15.7549,
                        rel_tol=1e-4)
    # the program the reader names is the program the index runs
    from sptag_tpu.algo import flat
    assert hasattr(flat, reader.PROGRAM[len("jit_"):])


# ---- the int8 scan's roofline (PR 34) -------------------------------------

def test_int8_roofline_reader_finds_what_the_scan_publishes(run):
    """The chip's trace aside, kernel.int8_scan_roofline needs the two
    server spans (queries a program run) and the scan program's name; the
    work comes from the configuration's file."""
    from sptag_tpu.algo import flat

    reader = load_by_name("layer_metrics", "kernel.int8_scan_roofline")
    assert reader.PROGRAM.startswith("jit_")
    assert hasattr(flat, reader.PROGRAM[len("jit_"):])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "flat_msmarco_i8_cosine.json")) as f:
        config = json.load(f)
    peaks = serving.peaks_for("TPU v5 lite")
    # a hand-made slice: BURSTS program runs in 30 ms of device time
    traced = {**run, "config": config, "peaks": peaks, "trace": {
        "programs": {reader.PROGRAM: {"runs": BURSTS, "seconds": 30e-3}}}}
    least, seconds = reader.bound(traced)
    assert seconds == 30e-3 and least["bound"] == "hbm"     # 8 a run
    assert least["seconds"] == pytest.approx(
        BURSTS * 8841823 * 384 / 819e9)
    assert reader.read(traced) == pytest.approx(
        100 * least["seconds"] / 30e-3)
    assert 0 < reader.read(traced) <= 100
    # nothing to read: no trace, no such program, rows of another type
    assert reader.read({**traced, "trace": None}) is None
    assert reader.read({**traced, "trace": {"programs": {}}}) is None
    assert reader.read({**traced, "config": {**config,
                                             "value_type": "Float"}}) is None
    assert reader.read({**traced, "spans": {}}) is None


@pytest.mark.parametrize("queries,bound,seconds", [
    # 10 runs of 32 queries over 1M x 384 one-byte rows: the read of the
    # rows (3.84e9 bytes at 819e9 a second) outlasts the products
    # (2.4576e11 operations at 393e12 a second)
    (32, "hbm", 10 * 1_000_000 * 384 / 819e9),
    # at 512 queries the products take longer: 2 x 1M x 384 x 512 x 10
    # = 3.93216e12 operations
    (512, "ops", 3.93216e12 / 393e12),
])
def test_int8_roofline_arithmetic_against_a_hand_count(queries, bound,
                                                       seconds):
    from benchmark.harness import roofline_int8

    got = roofline_int8.int8_scan_least_seconds(
        10, queries, 1_000_000, 384,
        {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9})
    assert got["bound"] == bound
    assert got["seconds"] == pytest.approx(seconds, rel=1e-12)
    assert got["hbm_seconds"] == pytest.approx(3.84e9 / 819e9, rel=1e-12)
    assert got["op_seconds"] == pytest.approx(
        2 * 1e6 * 384 * queries * 10 / 393e12, rel=1e-12)
    assert got["seconds"] == max(got["hbm_seconds"], got["op_seconds"])


# ---- the living mesh's readers (PR 43) -------------------------------------

def test_benchmark_lists_the_living_meshs_readers():
    assert set(MESH_LIVE_ONLY) == {"kernel.sharded_live_scan_roofline",
                                   "mutation.devices_per_write"}
    cell = {w["name"]: w for w in _BENCH["workloads"]}[
        "sharded_live20m.stream"]
    assert cell["chips"] == 4 and cell["traffic"] == "stream128"
    listed = {m["name"] for m in _BENCH["per_layer"]
              if cell["name"] in m.get("workloads", ())}
    assert set(LIVE_OFF_CHIP) | set(MESH_LIVE_ONLY) | {
        "kernel.topk_ms_per_batch", "kernel.mesh_merge_ms_per_batch",
        "server.reply_ms", "loop.lag_ms", "loop.cpu_share",
        "executor.cpu_share"} == listed


def test_devices_per_write_reads_a_mutated_mesh_index(host_mesh):
    """Three adds of a rung each and a delete of one of them, on four
    shards: one device written an operation, whichever shard its turn
    gave it; and the accepted mutation readers read the mesh index's
    spans as they read the one-chip index's."""
    from sptag_tpu.core.types import DistCalcMethod
    from sptag_tpu.parallel.sharded import ShardedFlatIndex

    rng = np.random.default_rng(3)
    data = rng.standard_normal((300, 8)).astype(np.float32)
    index = ShardedFlatIndex(data, DistCalcMethod.L2, 1, mesh=host_mesh(4))
    blocks = [rng.standard_normal((4, 8)).astype(np.float32)
              for _ in range(4)]
    index.add(blocks[0])                            # the growth
    assert index.delete_rows(blocks[0]) == (sp.ErrorCode.Success, 4)
    before = trace.report()
    for block in blocks[1:]:
        index.add(block)
    assert index.delete_rows(blocks[2]) == (sp.ErrorCode.Success, 4)
    run = {"spans": span_deltas(before, trace.report())}
    reader = load_by_name("layer_metrics", "mutation.devices_per_write")
    assert reader.read(run) == 1.0
    assert run["spans"]["mesh.write_devices"]["count"] == 4
    assert load_by_name("layer_metrics",
                        "mutation.upload_bytes_per_row").read(run) \
        == (3 * (8 * 33 + 4) + 8 * 4) / 16
    for metric in ("mutation.add_ms", "mutation.delete_ms",
                   "mutation.block_update_ms"):
        assert load_by_name("layer_metrics", metric).read(run) > 0
    # a delete whose rows lie on two shards: two devices written
    before = trace.report()
    assert index.delete_rows(data[[0, 299]]) == (sp.ErrorCode.Success, 2)
    assert reader.read({"spans": span_deltas(before, trace.report())}) == 2.0


def test_the_living_meshs_readers_read_nothing_on_one_chip(mutated_run, run):
    """The parent's program, or a one-chip index that mutates: None, no
    raise."""
    reader = load_by_name("layer_metrics", "mutation.devices_per_write")
    assert reader.read(mutated_run) is None and reader.read(run) is None
    roofline = load_by_name("layer_metrics",
                            "kernel.sharded_live_scan_roofline")
    traced = {**run, "peaks": {"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9},
              "trace": {"programs": {roofline.PROGRAM: {"runs": 1,
                                                        "seconds": 1.0}}}}
    for config in ({"algo": "FLAT", "rows": 200, "dim": 8},
                   {"algo": "FLAT", "rows": 200, "dim": 8, "chips": 1},
                   {"algo": "BKT", "rows": 200, "dim": 8, "chips": 4}):
        assert roofline.read({**traced, "config": config}) is None
    assert roofline.read({**run, "trace": {"programs": {}}, "peaks": {},
                          "config": {"algo": "FLAT", "rows": 200, "dim": 8,
                                     "chips": 4}}) is None


def test_sharded_live_roofline_arithmetic_against_a_hand_count():
    """A chip's share of 20M x 100 f32 (5M rows, 2.0 GB) read once a run
    at 819 GB/s is 2.442 ms: 64 queries a run in 7.0 ms of device time on
    the busiest plane read 34.89 %, bound by the HBM; the reserve and the
    merge are not counted."""
    reader = load_by_name("layer_metrics",
                          "kernel.sharded_live_scan_roofline")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sharded_flat_live_msturing20m_f32_l2.json")) as f:
        config = json.load(f)
    spans = {"server.queue_wait": {"count": 640, "total_s": 1.0},
             "server.execute_batch": {"count": 10, "total_s": 1.0}}
    least, seconds = reader.bound({
        "spans": spans, "config": config,
        "peaks": serving.peaks_for("TPU v5 lite"),
        "trace": {"programs": {reader.PROGRAM: {"runs": 10,
                                                "seconds": 0.070}}}})
    assert least["bound"] == "hbm" and seconds == 0.070
    assert math.isclose(least["seconds"], 10 * 2e9 / 819e9)
    assert math.isclose(100 * least["seconds"] / seconds, 34.886,
                        rel_tol=1e-4)
    # at 128 queries a run the dots still take less than the read
    assert least["flop_seconds"] * 2 < least["hbm_seconds"]
    from sptag_tpu.parallel import sharded
    assert hasattr(sharded, reader.PROGRAM[len("jit_"):])



@pytest.mark.parametrize("mode,correct", [("bits23", True), ("bits7", False)])
def test_the_living_control_puts_the_plain_scan_through_the_rule(
        mode, correct, capsys):
    """benchmark/tools/control_reference_live.py at a test's size: a
    serial record gives every answer ONE admissible state, so the plain
    scan in float32 is correct to the list, and with its inputs cut to
    bfloat16's mantissa it is not, by `dist_err_ulps_rms`."""
    from benchmark.tools import control_reference_live

    assert control_reference_live.main(
        ["sharded_flat_live_msturing20m_f32_l2", "7", mode, "3000"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is correct and line["rows"] == 3000
    assert line["answers_with_streamed_row"] > line["answers_compared"] / 2
    compared = line["compared"]
    assert compared["writer_steps_done_share"] == 1.0
    assert compared["mutations_failed"] == compared["invalid_lists"] == 0
    if correct:
        assert compared["stale_or_wrong_lists"] == 0
    assert (compared["dist_err_ulps_rms"] <= 5.0) is correct


# ---- the dense-only bring-up's readers (PR 48) ------------------------------

DENSE_OFF_CHIP = [
    m for m in DENSE_ONLY
    if {x["name"]: x for x in _BENCH["per_layer"]}[m]["source"]
    in OFF_CHIP_SOURCES]


def test_benchmark_lists_the_bring_ups_readers():
    assert {"build.tree_seconds",
            "build.dense_pack_seconds"} <= set(DENSE_OFF_CHIP)
    assert {"kernel.dense_mask_ms_per_batch",
            "kernel.dense_probe_ms_per_batch"} <= set(DENSE_ONLY)


@pytest.mark.parametrize("metric", DENSE_OFF_CHIP)
def test_reader_returns_a_number_from_a_dense_only_index(metric):
    """The build lies before the window, so these read the program's own
    report: a dense-only BKT index built and searched once in this
    process leaves both spans."""
    reader = load_by_name("layer_metrics", metric)
    assert reader.read({"spans": {}}) is None           # nothing built yet
    data = np.random.default_rng(3).standard_normal((600, 8)).astype(
        np.float32)
    index = sp.create_instance("BKT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BuildGraph", "0"), ("BKTKmeansK", "8"),
                        ("BKTLeafSize", "16"), ("DenseClusterSize", "32"),
                        ("MaxCheck", "128")]:
        assert index.set_parameter(name, value), name
    assert index.build(data) == sp.ErrorCode.Success
    index.search_batch(data[:4], K)
    value = reader.read({"spans": {}})
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    assert value == trace.report()[{
        "build.tree_seconds": "build.bkt_tree",
        "build.dense_pack_seconds": "build.dense_pack"}[metric]]["total_s"]


def test_the_bring_ups_readers_read_nothing_on_another_index(run):
    """The parent's program (no `build.dense_pack`), a served FLAT index
    (no tree), a run with no chip's trace: None, no raise."""
    for metric in DENSE_ONLY:
        assert load_by_name("layer_metrics", metric).read(
            {**run, "trace": None, "workload": "flat_1m.saturate",
             "config": {"dim": 8}, "peaks": {}}) is None
