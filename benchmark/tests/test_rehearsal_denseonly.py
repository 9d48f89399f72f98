"""The dense-only cell (`bkt_deep10m.saturate`), tiny, on the CPU, through
benchmark.run: traced and untraced; and a broken timed path — the probe
cut to one block, the re-rank's distances kept at a lower precision — must
come out `correct: false`, each by ONE number.
Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_denseonly.py -q

Width (96), metric, k, the block size, the build parameters (BuildGraph=0,
BKTLeafSize 128, DenseClusterSize 256) and the served path are the cell's
own; rows, checked queries, callers and MaxCheck are cut: 8,000 rows are
15 Gaussian clusters in ~36 blocks, and MaxCheck 2,048 (8 blocks a query)
finds 0.99 of the neighbours there where ONE block finds 0.5-0.6 (my CPU
runs, PR 48).  The generator's refusal (`datasets/clustered_f32_dense.py`)
applies above 2M rows only, so the rehearsal runs on any program.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import run

CELL = "bkt_deep10m.saturate"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"config": {"rows": 8_000,
                   "index_params": {
                       "BuildGraph": "0", "SearchMode": "dense",
                       "BKTNumber": "1", "BKTKmeansK": "32",
                       "BKTLeafSize": "128", "DenseClusterSize": "256",
                       "MaxCheck": "2048"},
                   "check": {"rule": "recall_and_exact_dists",
                             "queries": 32,
                             "limits": {"recall_at_10_min": 0.90,
                                        "dist_err_ulps_rms": 4.0}}},
        "traffic": {"callers": 16, "connections": 2,
                    "distinct_queries": 64}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.dense_scan_roofline",
               "kernel.dense_mask_ms_per_batch",
               "kernel.dense_probe_ms_per_batch"}


@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearses(traced):
    from sptag_tpu.utils import metrics, trace

    trace.reset()           # the build's spans are read over the process
    r = run.run_cell(CELL, 2**31 + 48, 2.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    # the folder the builder CLI wrote says dense and no graph, and the
    # server scanned the 8 blocks MaxCheck 2,048 stands for
    folder = os.path.join(run.WORK, CELL, "index")
    with open(os.path.join(folder, "indexloader.ini")) as f:
        ini = f.read()
    assert "SearchMode=dense" in ini and "BuildGraph=0" in ini
    assert metrics.gauge_value("dense.rows_per_query") == 2048
    assert metrics.gauge_value("dense.block_rows") == 256
    assert 8_000 / 256 <= metrics.gauge_value("dense.blocks") <= 48
    assert 0 <= metrics.gauge_value("dense.pad_share") < 0.35
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    values = {k: v["value"] for k, v in r["rehearsal_values"].items()}
    if traced:
        # the build's stages, read from the program's own report
        assert 0 < values["build.tree_seconds"] < values["build.seconds"]
        assert values["build.dense_pack_seconds"] > 0
    else:
        assert values["recall_at_10"] >= 0.90
    assert r["seen"]["built_this_run"] is True
    assert r["seen"]["compiles_in_window"] == 0
    assert r["seen"]["serve_errors"] == {
        n: 0 for n in r["seen"]["serve_errors"]}


def _broken(monkeypatch, patch):
    """Run the cell with the dense programs re-traced over `patch`'s
    change; the broken programs must not outlive the test."""
    jax.clear_caches()
    patch()
    try:
        return run.run_cell(CELL, 7, 2.0, False, rehearse=TINY)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_a_probe_cut_to_one_block_is_not_correct(monkeypatch):
    """Every query scans the ONE block whose mean is nearest: valid
    lists, exact distances, too few of the neighbours."""
    from sptag_tpu.algo.dense import DenseTreeSearcher
    sound = DenseTreeSearcher.search

    def one_block(self, queries, k, max_check=2048, **kw):
        return sound(self, queries, k, max_check=1, **kw)

    r = _broken(monkeypatch, lambda: monkeypatch.setattr(
        DenseTreeSearcher, "search", one_block))
    assert r["failed"] == 0 and r["correct"] is False
    assert {n["name"] for n in r["compared"] if not n["ok"]} \
        == {"recall_at_10"}


def test_a_rerank_in_lower_precision_is_not_correct(monkeypatch):
    """The distances leave the program with the 8 bits of mantissa a
    bfloat16 keeps: the same ids in the same order, so recall stays."""
    from sptag_tpu.algo import dense
    sound = dense._finalize_topk

    def rounded(*a, **kw):
        d, ids = sound(*a, **kw)
        return d.astype(jnp.bfloat16).astype(jnp.float32), ids

    r = _broken(monkeypatch, lambda: monkeypatch.setattr(
        dense, "_finalize_topk", rounded))
    assert r["failed"] == 0 and r["correct"] is False
    assert {n["name"] for n in r["compared"] if not n["ok"]} \
        == {"dist_err_ulps_rms"}
