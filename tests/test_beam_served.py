"""`SearchMode=beam` as a served deployment (PR 32): a small BKT folder
that the builder CLI saved with `SearchMode=beam` in its ini, loaded and
served through `SearchServer` the way `python -m sptag_tpu.serve.server`
does (benchmark/harness/serving.py).

Held here: recall against the exact scan and against the plain walk over
the SAME saved graph (benchmark/harness/reference_walk.py: SPTAG's own
search in numpy, imports nothing of the program); distances exact for the
ids returned; the span and the `beam.*` gauges and counters the
benchmark's readers use, present after one served batch; trips within
`walk_plan`'s T; and each of the three walk drivers counted where it runs,
with one answer.

The corpus is small (2,000 x 32, 32 neighbours a row, MaxCheck 2048 as
in the cell): what is under test is the served path and its telemetry,
not a recall figure for the record.
"""

import os

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.harness import reference, reference_walk, serving
from benchmark.loadgen import load_by_name
from sptag_tpu.serve.client import AnnClientPool
from sptag_tpu.serve.wire import ResultStatus
from sptag_tpu.utils import metrics, trace

NAME, K, ROWS, DIM, QUERIES, MAX_CHECK = "walked", 10, 2000, 32, 48, 2048
CONFIG = {"algo": "BKT", "value_type": "Float", "metric": "L2", "k": K,
          "service": {},
          "index_params": {
              "BKTNumber": "1", "BKTKmeansK": "32", "TPTNumber": "4",
              "TPTLeafSize": "500", "NeighborhoodSize": "32", "CEF": "64",
              "MaxCheckForRefineGraph": "128", "RefineIterations": "1",
              "MaxCheck": str(MAX_CHECK), "FinalRefineSearchMode": "same",
              "SearchMode": "beam"}}
# The program may read this far under the plain walk on the same graph.
# It is as a rule ABOVE it (a trip pops 64 nodes where the plain walk pops
# one, so it scores 30 times the rows); the margin is for the other way
# round: the program seeds from a shared pivot table (a BFS of the tree's
# top levels), the plain walk descends the tree for every query, so on a
# query the pivots cover badly the plain walk can start nearer.  Two
# lists of ten among 48 queries is 0.04.
MARGIN = 0.04


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """(folder, rows, queries, exact ids) — built once by the builder
    CLI's main()."""
    work = str(tmp_path_factory.mktemp("beam_served"))
    data, queries = load_by_name("datasets", "clustered_f32").make(
        2**31 + 32, ROWS, DIM, QUERIES)
    folder = os.path.join(work, "index")
    serving.build_index(work, folder, data, CONFIG)
    return folder, data, queries, reference.exact_topk(data, queries, K)[0]


def _served_answers(saved, tmp_path):
    """One burst of every query through a SearchServer on a socket ->
    ((Q, K) ids, (Q, K) distances)."""
    folder, _, queries, _ = saved
    texts = [serving.query_text(NAME, K, q) for q in queries]
    with serving.served(str(tmp_path), NAME, folder, CONFIG) as (_, addr):
        with AnnClientPool(addr[0], addr[1], connections=2,
                           timeout_s=300.0, max_workers=QUERIES) as pool:
            answers = [f.result() for f in
                       [pool.search_async(t) for t in texts]]
    assert all(a.status == ResultStatus.Success for a in answers)
    return (np.array([a.results[0].ids for a in answers]),
            np.array([a.results[0].dists for a in answers]))


def test_the_saved_ini_says_beam(saved):
    with open(os.path.join(saved[0], "indexloader.ini")) as f:
        ini = f.read()
    assert "SearchMode=beam" in ini and f"MaxCheck={MAX_CHECK}" in ini


def test_served_recall_against_the_scan_and_the_plain_walk(saved, tmp_path):
    folder, data, queries, exact = saved
    ids, _ = _served_answers(saved, tmp_path)
    served = reference.recall_at_k(ids, exact, K)
    graph, tree = reference_walk.from_folder(folder)
    assert graph.shape == (ROWS, 32)
    plain_ids, _, scored = reference_walk.walk_all(
        data, graph, queries, K, MAX_CHECK, tree=tree)
    plain = reference.recall_at_k(plain_ids, exact, K)
    assert plain >= 0.80, plain          # the reference is a sound walk
    assert scored.max() <= MAX_CHECK + 32 and scored.mean() > 3 * K
    assert served >= plain - MARGIN, (served, plain)
    # the seeded form (a caller with a pivot list and no tree): started
    # beside the answer, the walk finds it
    ids0, dists0, _ = reference_walk.walk(data, graph, queries[0], K,
                                          MAX_CHECK, seeds=exact[0][:4])
    assert ids0[0] == exact[0][0] and np.all(np.diff(dists0) >= 0)
    assert served >= 0.85, served
    assert all(len(set(row)) == K and min(row) >= 0 and max(row) < ROWS
               for row in ids.tolist())


def test_served_distances_are_exact_for_the_ids_returned(saved, tmp_path):
    _, data, queries, _ = saved
    ids, dists = _served_answers(saved, tmp_path)
    true = reference.exact_scores(data, queries, ids)
    assert np.all(np.diff(dists, axis=1) >= 0)
    ulp = reference.F32_EPS * reference.ulp_scale(data, queries)[:, None]
    assert np.abs(dists - true).max() <= 4 * ulp.max()


def test_one_served_batch_leaves_the_readers_their_names(saved, tmp_path):
    """What benchmark/layer_metrics/index.device_wait_ms,
    kernel.beam_trips_per_batch and kernel.beam_walk_roofline read."""
    _served_answers(saved, tmp_path)
    spans = trace.report()
    assert spans["index.readback"]["count"] >= 1
    assert spans["index.search"]["total_s"] \
        >= spans["index.readback"]["total_s"]
    index = sp.load_index(saved[0])
    k_eff, L, B, T, _ = index._get_engine().walk_plan(K, MAX_CHECK)
    assert (L, B, T) == (320, 64, 32)           # the cell's plan
    batches = metrics.counter_value("beam.monolithic")
    assert batches >= 1
    assert metrics.counter_value("beam.chunked") == 0
    assert metrics.counter_value("beam.segmented") == 0
    trips = metrics.gauge_value("beam.trips")
    assert 1 <= trips <= T
    assert trips <= metrics.counter_value("beam.trips_total") <= T * batches
    assert metrics.gauge_value("beam.pool") == L
    assert 0 < metrics.gauge_value("beam.rows_scored_per_query") \
        <= T * B * 32
    # the totals a reader forms its ratio from: every real query once
    assert metrics.counter_value("beam.queries_total") >= len(saved[2])
    assert 0 < metrics.counter_value("beam.rows_scored_total") \
        <= T * B * 32 * metrics.counter_value("beam.queries_total")
    assert metrics.gauge_value("beam.pivots") \
        == index._get_engine().pivot_ids.shape[0]
    assert metrics.gauge_value("beam.score_itemsize") == 4      # on a CPU
    index.close()


@pytest.mark.parametrize("driver", ["monolithic", "chunked", "segmented"])
def test_each_walk_driver_is_counted_where_it_runs(saved, driver,
                                                   monkeypatch):
    """The same queries through each driver of GraphSearchEngine.search:
    one counter moves, and the answer is the monolithic program's."""
    from sptag_tpu.algo.engine import GraphSearchEngine

    folder, _, queries, _ = saved
    index = sp.load_index(folder)
    want_d, want_ids = index.search_batch(queries, K)
    trips = metrics.gauge_value("beam.trips")
    rows = metrics.gauge_value("beam.rows_scored_per_query")
    metrics.reset()
    if driver == "chunked":
        monkeypatch.setattr(GraphSearchEngine, "chunk_size",
                            lambda self: 16)
    elif driver == "segmented":
        assert index.set_parameter("BeamSegmentIters", "5")
    d, ids = index.search_batch(queries, K)
    for name in ("monolithic", "chunked", "segmented"):
        assert metrics.counter_value("beam." + name) == (name == driver)
    # every driver traces the exact body with the row-gather layout here:
    # its visited / de-duplicate ensemble runs in sorted-id order (PR 33)
    assert metrics.counter_value("beam.dedup_sorted") == 1
    assert metrics.counter_value("beam.dedup_positional") == 0
    # and took its candidates' norms from the rows it gathered (PR 44)
    assert metrics.counter_value("beam.norm_from_rows") == 1
    assert np.array_equal(ids, want_ids) and np.array_equal(d, want_d)
    # three chunks of 16 walk one after the other: their trips (each
    # chunk's `live.max()`) add up, and no chunk walks longer than the
    # one program that held them all
    got = metrics.gauge_value("beam.trips")
    assert got == metrics.counter_value("beam.trips_total")
    if driver == "chunked":
        assert trips <= got <= 3 * trips
    else:
        assert got == trips
    assert metrics.gauge_value("beam.rows_scored_per_query") == rows
    assert metrics.counter_value("beam.queries_total") == len(queries)
    assert metrics.counter_value("beam.rows_scored_total") \
        == rows * len(queries)
    assert trace.report()["index.readback"]["count"] >= 1
    index.close()


def test_beam_programs_name_their_outputs():
    """jax's persistent compile cache keys a program with its locations
    stripped: the output names are what gives each beam program a key of
    its own (PR 25)."""
    import jax

    import __graft_entry__ as graft

    fn, args = graft.entry()
    text = jax.jit(fn).lower(*args).as_text()
    for field in ("dists", "ids", "live"):
        assert f'jax.result_info = "result.{field}"' in text
    for scope in ("beam.seed", "beam.gather", "beam.score", "beam.merge",
                  "beam.finalize"):
        assert scope in jax.jit(fn).lower(*args).as_text(debug_info=True)
