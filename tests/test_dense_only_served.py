"""A dense-only BKT index (`BuildGraph=0`, `SearchMode=dense`) as a served
deployment (PR 48): the builder CLI's main() with `Index.BuildGraph=0` ->
saved folder -> `ServiceContext.from_ini` -> answers through
`SearchExecutor.execute_batch`, held against the exact numpy scan
(benchmark/harness/reference.py, which imports nothing of the program).

The configuration's own `index_params` (benchmark/configs/
bkt_deep10m_f32_l2_denseonly.json) and width, on a few thousand rows with a
small MaxCheck: what is under test is the path and what it leaves behind
(the ini, the graph-less adjacency, the span and gauges the benchmark's
readers take), not a recall figure for the record.
"""

import json
import os

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.harness import compare, reference, serving
from benchmark.loadgen import load_by_name
from sptag_tpu.serve.service import SearchExecutor, ServiceContext
from sptag_tpu.serve.wire import ResultStatus
from sptag_tpu.utils import metrics, trace

NAME, ROWS, QUERIES, MAX_CHECK = "blocks", 6000, 48, 2048
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "configs",
                       "bkt_deep10m_f32_l2_denseonly.json")) as _f:
    CELL = json.load(_f)
K, DIM = CELL["k"], CELL["dim"]
CONFIG = {**CELL, "rows": ROWS,
          "index_params": {**CELL["index_params"],
                           "MaxCheck": str(MAX_CHECK)}}


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """(folder, rows, queries) — built once by the builder CLI's main()."""
    work = str(tmp_path_factory.mktemp("dense_only_served"))
    data, queries = load_by_name("datasets", CELL["dataset"]).make(
        2**31 + 48, ROWS, DIM, QUERIES)
    folder = os.path.join(work, "index")
    trace.reset()
    serving.build_index(work, folder, data, CONFIG)
    assert trace.report()["build.bkt_tree"]["count"] == 1
    assert "build.rng_graph" not in trace.report()
    return folder, data, queries


def _context(folder, tmp_path) -> ServiceContext:
    ini = os.path.join(str(tmp_path), "service.ini")
    with open(ini, "w") as f:
        f.write(f"[Service]\n[QueryConfig]\nDefaultMaxResultNumber={K}\n"
                f"[Index]\nList={NAME}\n[Index_{NAME}]\n"
                f"IndexFolder={folder}\n")
    ctx = ServiceContext.from_ini(ini)
    assert NAME in ctx.indexes          # from_ini skips a folder it cannot load
    return ctx


def _answers(ctx, queries):
    texts = [serving.query_text(NAME, K, q) for q in queries]
    results = SearchExecutor(ctx).execute_batch(texts)
    assert all(r.status == ResultStatus.Success for r in results)
    return (np.array([r.results[0].ids for r in results]),
            np.array([r.results[0].dists for r in results], np.float32))


def test_the_saved_folder_says_dense_only_and_holds_no_graph(saved):
    folder = saved[0]
    with open(os.path.join(folder, "indexloader.ini")) as f:
        ini = f.read()
    assert "BuildGraph=0" in ini and "SearchMode=dense" in ini
    assert f"MaxCheck={MAX_CHECK}" in ini and "DenseClusterSize=256" in ini
    index = sp.load_index(folder)
    assert index._graph.graph.shape[0] == ROWS
    assert (index._graph.graph == -1).all()


def test_served_answers_against_the_exact_scan(saved, tmp_path):
    folder, data, queries = saved
    trace.reset()
    ids, dists = _answers(_context(folder, tmp_path), queries)
    exact, _ = reference.exact_topk(data, queries, K)
    recall = reference.recall_at_k(ids, exact, K)
    assert recall >= CELL["check"]["limits"]["recall_at_10_min"], recall
    # ids distinct and in range; distances nearest first, and each the
    # float32 distance of the id it came with (the check's ulp rule)
    assert compare.invalid_lists(ids, ROWS) == 0
    assert np.all(np.diff(dists, axis=1) >= 0)
    err = compare.dist_err_ulps(data, queries, np.arange(QUERIES), ids,
                                dists)
    assert np.sqrt(np.mean(err ** 2)) \
        <= CELL["check"]["limits"]["dist_err_ulps_rms"], err.max()
    assert err.max() <= 4.0
    # what the first search after a load leaves the benchmark's readers:
    # the pack's span (build.dense_pack_seconds), the placed geometry and
    # what a query scored (kernel.dense_scan_roofline)
    assert trace.report()["build.dense_pack"]["count"] == 1
    blocks = metrics.gauge_value("dense.blocks")
    assert metrics.gauge_value("dense.block_rows") == 256
    assert ROWS / 256 <= blocks <= 2 * ROWS / 256
    assert metrics.gauge_value("dense.pad_share") \
        == pytest.approx(1 - ROWS / (blocks * 256))
    assert metrics.gauge_value("dense.rows_per_query") == MAX_CHECK
    # the per-slot tombstone table, computed where the layout was placed
    assert metrics.counter_value("dense.tombstone_rebuilds") >= 1
    assert metrics.gauge_value("dense.dead_slots") == 0
    assert metrics.gauge_value("dense.centroids_per_query") == blocks


def test_a_second_batch_packs_nothing(saved, tmp_path):
    ctx = _context(saved[0], tmp_path)
    first = _answers(ctx, saved[2])
    trace.reset()
    again = _answers(ctx, saved[2])
    assert "build.dense_pack" not in trace.report()
    assert np.array_equal(first[0], again[0])
    assert np.array_equal(first[1], again[1])


def test_beam_is_refused_on_the_served_folder(saved, tmp_path):
    """No graph to walk: `$searchmode:beam`-style overrides and a
    parameter set to beam end in an error, never in a walk of -1 rows."""
    index = sp.load_index(saved[0])
    index.set_parameter("SearchMode", "beam")
    with pytest.raises(RuntimeError, match="BuildGraph=0"):
        index.search_batch(saved[2][:4], K)
    index.set_parameter("SearchMode", "dense")
    _, ids = index.search_batch(saved[2][:4], K)
    assert compare.invalid_lists(np.asarray(ids), ROWS) == 0
