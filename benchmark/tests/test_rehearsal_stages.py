"""A traced rehearsal of a FLAT and the BKT cell with the stage metrics of
PR 25: every reader that needs no chip reads something, and the stages do
not add up to more than the batch they lie in.  (test_rehearsal.py's
`device_only` set predates the two device metrics PR 25 added; this file
holds the traced rehearsal to the whole list as it stands.)"""

import pytest

from benchmark import run
from benchmark.tests import rehearse

DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.flat_scan_roofline", "kernel.dense_scan_roofline",
               "kernel.topk_ms_per_batch"}           # need a chip's trace
STAGES = ("service.parse_ms", "service.results_ms", "index.dispatch_ms",
          "index.device_wait_ms")


@pytest.mark.parametrize("cell", ["flat_1m.saturate", "bkt_100k.saturate"])
def test_traced_rehearsal_reads_every_stage(cell):
    r = run.run_cell(cell, 2**31 + 12, 2.0, True,
                     rehearse=rehearse.BY_CELL[cell])
    assert r["correct"] is True, r["compared"]
    values = {k: v["value"] for k, v in r["rehearsal_values"].items()}
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"), "per_layer", cell)}
    assert set(values) == want - DEVICE_ONLY
    assert all(values[m] > 0 for m in STAGES)
    assert sum(values[m] for m in STAGES) <= values["execute.batch_ms"]
    cycle = values["batcher.cycle_ms"]
    assert values["batcher.gather_ms"] >= 0
    assert values["batcher.resume_ms"] >= 0
    assert cycle >= values["execute.batch_ms"]
    print({k: round(v, 3) for k, v in values.items()})
