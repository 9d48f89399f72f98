"""`flat_msmarco_i8.saturate` and `bkt_100k.single` (PR 34), tiny, on the
CPU, through benchmark.run, traced and untraced; and the int8 cell's timed
path broken underneath — every distance off by one, two ids of a list
swapped for far rows — must come out `correct: false`.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_int8.py -q

Widths (384, int8, cosine, k=10), the check and its limits are the cell's
own; rows, checked queries and callers are cut.  At 20,000 rows the scan
selects in one stage (the two-stage select engages from 128,000 columns:
tests/test_flat_int8_cosine.py runs it over ties at that width).
"""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.tests import rehearse

CELL = "flat_msmarco_i8.saturate"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"config": {"rows": 20_000, "check": {
            "rule": "exact_ids_int_cosine", "queries": 32,
            "limits": {"id_lists_wrong": 0, "invalid_lists": 0,
                       "dist_err_max": 0}}},
        "traffic": {"callers": 16, "connections": 2,
                    "distinct_queries": 64}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.int8_scan_roofline",
               "kernel.dense_scan_roofline"}


def _want(cell, traced):
    return {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", cell)}


@pytest.mark.parametrize("traced", [False, True])
def test_int8_cell_rehearses(traced):
    from sptag_tpu.utils import metrics

    native = metrics.counter_value("flat.dot_int8_native")
    r = run.run_cell(CELL, 2**31 + 34, 2.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    compared = {n["name"]: n["value"] for n in r["compared"]}
    assert compared["id_lists_wrong"] == 0 and compared["dist_err_max"] == 0
    assert compared["invalid_lists"] == 0
    # what the program says of the scan it served
    assert metrics.counter_value("flat.dot_int8_native") > native
    assert metrics.gauge_value("flat.rows_resident") == 20_000
    assert metrics.gauge_value("flat.row_itemsize") == 1
    want = _want(CELL, traced)
    assert "kernel.int8_scan_roofline" in want or not traced
    assert "recall_at_10" not in want and "kernel.flat_scan_roofline" \
        not in want
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    assert r["seen"]["compiles_in_window"] == 0


def _broken(monkeypatch, alter):
    from sptag_tpu.algo.flat import FlatIndex
    sound = FlatIndex._search_batch

    def broken(self, queries, k, *a, **kw):
        dists, ids = sound(self, queries, k, *a, **kw)
        return alter(np.array(dists), np.array(ids), self.num_samples)

    monkeypatch.setattr(FlatIndex, "_search_batch", broken)
    r = run.run_cell(CELL, 2**31 + 35, 2.0, False, rehearse=TINY)
    assert r["failed"] == 0 and r["correct"] is False
    return {n["name"] for n in r["compared"] if not n["ok"]}


def test_distances_off_by_one_are_not_correct(monkeypatch):
    """The ids are the exact scan's; every distance is one too large."""
    bad = _broken(monkeypatch, lambda d, i, n: (d + 1.0, i))
    assert bad == {"dist_err_max"}


def test_a_swapped_id_is_not_correct(monkeypatch):
    """Rank 3 of every list names another row (its distance stays put)."""
    def alter(d, i, n):
        i[:, 3] = (i[:, 3] + n // 2) % n
        return d, i
    bad = _broken(monkeypatch, alter)
    assert "id_lists_wrong" in bad and "dist_err_max" in bad


@pytest.mark.parametrize("traced", [False, True])
def test_bkt_single_rehearses(traced):
    """`bkt_100k.single`: one caller on the dense BKT index."""
    cell = "bkt_100k.single"
    tiny = {"config": rehearse.BKT["config"],
            "traffic": {"distinct_queries": 64}}
    r = run.run_cell(cell, 2**31 + 36, 2.0, traced, rehearse=tiny)
    json.dumps(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = _want(cell, traced)
    assert "recall_at_10" in want or traced
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    if traced:
        # one request a batch: the lone path
        assert r["rehearsal_values"]["batcher.batch_size"]["value"] == 1.0
    assert r["seen"]["compiles_in_window"] == 0


@pytest.mark.parametrize("mode,correct", [("int32", True), ("bf16", False)])
def test_the_control_in_the_programs_place(mode, correct):
    """tools/control_reference_int8.py's comparison, tiny: the plain
    jax.numpy scan through the cell's own rule — sound with int32
    accumulation, not correct by `dist_err_max` with a bfloat16 result."""
    from benchmark.harness import compare, reference_int8_cosine
    from benchmark.loadgen import load_by_name

    config = {**run.load_json(run.ROOT, "benchmark/configs/"
                              "flat_msmarco_i8_cosine.json"),
              **TINY["config"]}
    data, queries = load_by_name("datasets", config["dataset"]).make(
        2**31 + 37, config["rows"], config["dim"], 32)
    ids, dists = reference_int8_cosine.device_answers(
        data, queries, config["k"], mode, block=8_192)
    got = load_by_name("checks", config["check"]["rule"]).check(
        data, queries, np.arange(32),
        compare.answers_as_window(ids, dists), config)
    bad = {n["name"] for n in got["numbers"] if not n["ok"]}
    assert (not bad) == correct, got
    assert correct or "dist_err_max" in bad
