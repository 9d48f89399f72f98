"""The beam cell, tiny, on the CPU, through benchmark.run: traced and
untraced; the plain walk (harness/reference_walk.py) beside the program on
the tiny index; and a broken timed path — the re-rank's distances
perturbed, the walk kept from leaving its seeds — must come out
`correct: false`.
Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Widths, metric, k, MaxCheck, the neighbourhood and the served path are the
cell's own; rows, checked queries and callers are cut and the build knobs
are rehearse.py's (default knobs take minutes at 10k rows on a CPU).  The
pivot table is cut WITH the rows (`NumberOfInitialDynamicPivots=2` leaves
the n / 24 rule the cell's 100,000 rows fall under: 166 pivots of 4,000
rows as the cell has 4,166; the default's floor of 1,600 pivots would seed
a tiny walk with two fifths of the corpus).  Then the whole walk finds
0.78-0.90 of the neighbours, ONE trip 0.46-0.58 and a walk that never
leaves its seeds 0.13 (five seeds, 32 queries; seed 2**31 + 33, 64
queries; my CPU runs, PR 32).  The fault planted here is the last: the
committed cell's floor (0.60: PERF.md section 2 says why it is that low)
catches it too — at 100k rows the seeds alone hold about one neighbour in
24 — where a walk cut to one trip reads 0.77-0.79 there and passes.  What
NO floor detects is a walk at a quarter of its budget (MaxCheck 512 reads
within 0.01 of 2048): the configuration's file promises nothing about the
budget.
"""

import json
import os

import jax
import pytest

from benchmark import run
from benchmark.tests import rehearse
from benchmark.tools import control_walk

CELL = "bkt_100k_beam.saturate"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"config": {"rows": 4_000,
                   "index_params": {
                       **rehearse.BKT["config"]["index_params"],
                       "SearchMode": "beam",
                       "NumberOfInitialDynamicPivots": "2"},
                   "index_cache": False,
                   "check": {"rule": "recall_and_exact_dists",
                             "queries": 32,
                             "limits": {"recall_at_10_min": 0.70,
                                        "dist_err_ulps_rms": 4.0}}},
        "traffic": {"callers": 16, "connections": 2,
                    "distinct_queries": 64}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.beam_walk_roofline"}


@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearses(traced):
    from sptag_tpu.utils import metrics

    r = run.run_cell(CELL, 2**31 + 32, 2.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    # the folder the builder CLI wrote says beam, and the server walked
    folder = os.path.join(run.WORK, CELL, "index")
    with open(os.path.join(folder, "indexloader.ini")) as f:
        assert "SearchMode=beam" in f.read()
    assert metrics.counter_value("beam.monolithic") > 0
    assert metrics.gauge_value("beam.pool") == 320
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    if traced:
        values = {k: v["value"] for k, v in r["rehearsal_values"].items()}
        assert 1 <= values["kernel.beam_trips_per_batch"] <= 32
        # index.dispatch_ms / index.device_wait_ms list their cells and
        # this one is not among them: the parent's walk has no
        # `index.readback` span and a traced line has to be whole on
        # both sides (tests/test_benchmark_readers.py holds the program
        # to the span)
        assert not {"index.device_wait_ms", "index.dispatch_ms"} & want
    else:
        # recall is the check's (the line's `compared`); as an
        # end-to-end metric it waits for a parent whose recall does not
        # swing by seed (PERF.md section 7)
        recall = {n["name"]: n["value"] for n in r["compared"]}
        assert recall["recall_at_10"] >= 0.70
    assert r["seen"]["compiles_in_window"] == 0
    assert r["seen"]["serve_errors"] == {
        n: 0 for n in r["seen"]["serve_errors"]}


def test_the_plain_walk_beside_the_program():
    """What tools/control_walk.py prints on the chip, tiny: the plain
    walk finds most of the neighbours within its budget of scored rows,
    the program is not far under it (it is as a rule above: a trip pops
    64 nodes where the plain walk pops one, so it scores many times the
    rows), and a quarter of the budget reads no higher than the whole."""
    _, _, config, config_path, _ = run.find_cell(CELL)
    config = {**config, **TINY["config"],
              "check": {**TINY["config"]["check"], "queries": 64}}
    line = control_walk.control("bkt_100k_f32_l2_beam", config,
                                config_path, 2**31 + 33)
    json.dumps(line)
    plain, program = line["plain"], line["program"]
    assert plain["recall"] >= 0.70
    assert 10 <= plain["rows_scored"] <= 2048 + 32
    assert program["2048"]["recall"] >= plain["recall"] - 0.05
    assert program["512"]["recall"] <= program["2048"]["recall"] + 0.01
    assert program["2048"]["trips"] <= 32
    assert program["2048"]["rows_scored"] <= 32 * 64 * 32
    # the same budget, the walk cut to one trip, and to none: the
    # control the floor is set against
    assert program["one_trip"]["trips"] == 1
    assert program["one_trip"]["rows_scored"] <= 64 * 32
    assert program["one_trip"]["recall"] \
        < program["2048"]["recall"] - 0.15
    assert program["no_trip"]["trips"] == 0
    assert program["no_trip"]["rows_scored"] == 0
    assert program["no_trip"]["recall"] < 0.30


def _broken(monkeypatch, name, replacement):
    """Run the cell with `engine.<name>` replaced underneath the whole
    served path; the broken programs must not outlive the test."""
    from sptag_tpu.algo import engine

    jax.clear_caches()
    monkeypatch.setattr(engine, name, replacement)
    try:
        return run.run_cell(CELL, 7, 2.0, False, rehearse=TINY)
    finally:
        jax.clear_caches()


def test_a_rerank_that_is_off_is_not_correct(monkeypatch):
    """Every returned distance a part in ten thousand too large (what a
    re-rank in lower precision does): ids, order and recall stay."""
    from sptag_tpu.algo import engine
    sound = engine._finalize

    def perturbed(*a, **kw):
        d, ids = sound(*a, **kw)
        return d * 1.0001, ids

    r = _broken(monkeypatch, "_finalize", perturbed)
    assert r["failed"] == 0 and r["correct"] is False
    assert {n["name"] for n in r["compared"] if not n["ok"]} \
        == {"dist_err_ulps_rms"}


def test_a_walk_that_never_leaves_its_seeds_is_not_correct(monkeypatch):
    """No trip at all (the pool is the nearest pivots, re-ranked): valid
    lists, exact distances, too few of the neighbours."""
    from sptag_tpu.algo.engine import GraphSearchEngine
    sound = GraphSearchEngine.walk_plan

    def no_trip(self, *a, **kw):
        k_eff, L, B, _, limit = sound(self, *a, **kw)
        return k_eff, L, B, 0, limit

    monkeypatch.setattr(GraphSearchEngine, "walk_plan", no_trip)
    r = run.run_cell(CELL, 7, 2.0, False, rehearse=TINY)
    assert r["failed"] == 0 and r["correct"] is False
    assert {n["name"] for n in r["compared"] if not n["ok"]} \
        == {"recall_at_10"}
