"""Every cell, tiny, on the CPU, through benchmark.run: the last line's
keys, and that a broken timed path makes `correct` false.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

(not part of tier-1, which collects tests/ only).  A rehearsal skips the
look for a chip and never prints a device metric: its `metrics` is empty.
"""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.tests import rehearse

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", sorted(rehearse.BY_CELL))
@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearses(cell, traced):
    r = run.run_cell(cell, 2**31 + 11, 2.0, traced,
                     rehearse=rehearse.BY_CELL[cell])
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", cell)}
    device_only = {"device.idle_share", "device.busy_ms_per_batch",
                   "kernel.flat_scan_roofline"}      # need a chip's trace
    assert set(r["rehearsal_values"]) == want - device_only
    assert r["seen"]["compiles_in_window"] == 0


def test_same_seed_same_inputs():
    from benchmark.loadgen import load_by_name
    make = load_by_name("datasets", "clustered_f32").make
    a, b = make(2**31 + 5, 2048, 128, 16), make(2**31 + 5, 2048, 128, 16)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (make(6, 2048, 128, 16)[0] == a[0]).all()


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """FLAT: the index swaps the ids of ranks 0 and 9 (their distances
    stay put), underneath the whole served path."""
    from sptag_tpu.algo.flat import FlatIndex
    sound = FlatIndex._search_batch

    def broken(self, queries, k, *a, **kw):
        dists, ids = sound(self, queries, k, *a, **kw)
        ids = np.array(ids)
        ids[:, [0, 9]] = ids[:, [9, 0]]
        return dists, ids

    monkeypatch.setattr(FlatIndex, "_search_batch", broken)
    r = run.run_cell("flat_1m.saturate", 3, 2.0, False,
                     rehearse=rehearse.FLAT)
    assert r["correct"] is False
    bad = {n["name"] for n in r["compared"] if not n["ok"]}
    assert "id_lists_wrong" in bad or "dist_err_ulps_rms" in bad


def test_a_batch_that_fails_is_not_correct(monkeypatch):
    """BKT: every fourth batch raises inside the index, so the server
    answers FailedExecute: counted as failed, and `correct` is false."""
    from sptag_tpu.algo.bkt import BKTIndex
    sound = BKTIndex._search_batch
    calls = {"n": 0}

    def flaky(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] > 12 and calls["n"] % 4 == 0:
            raise RuntimeError("broken on purpose")
        return sound(self, *a, **kw)

    monkeypatch.setattr(BKTIndex, "_search_batch", flaky)
    r = run.run_cell("bkt_100k.saturate", 4, 2.0, False,
                     rehearse=rehearse.BKT)
    assert r["failed"] > 0 and r["correct"] is False
