"""`sharded_deep10m.single` (PR 32: one caller on the four-chip mesh),
tiny, on four forced CPU devices, through benchmark.run, traced and
untraced.  Run by hand, in a session of its own (test_rehearsal_sharded.py,
imported here, sets XLA_FLAGS for four CPU devices as it is imported):

    JAX_PLATFORMS=cpu python -m pytest \
        benchmark/tests/test_rehearsal_sharded_single.py -q

What needs a chip's trace — the two mesh readers and
kernel.topk_ms_per_batch at Q=1 — is read on the chip (PERF.md section 5).
"""

import json

import pytest

from benchmark import run
from benchmark.tests import test_rehearsal_sharded as saturate

CELL = "sharded_deep10m.single"
# the saturating cell's tiny corpus and limits; one caller
TINY = {"config": saturate.TINY["config"],
        "traffic": {"distinct_queries": 64}}
four_devices = saturate.four_devices


@pytest.mark.parametrize("traced", [False, True])
def test_one_caller_on_four_devices(traced):
    from sptag_tpu.utils import metrics

    r = run.run_cell(CELL, 2**31 + 34, 2.0, traced, rehearse=TINY)
    json.dumps(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] >= 4 and r["rehearsal"] is True
    assert metrics.gauge_value("mesh.shards") == 4
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}
    assert {"kernel.sharded_scan_roofline",
            "kernel.mesh_merge_ms_per_batch"} <= want or not traced
    assert set(r["rehearsal_values"]) == want - saturate.DEVICE_ONLY
    if traced:
        # one request a batch: the lone path
        assert r["rehearsal_values"]["batcher.batch_size"]["value"] == 1.0
    assert r["seen"]["compiles_in_window"] == 0
