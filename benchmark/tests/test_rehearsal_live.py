"""`flat_live5m.stream` (PR 40), tiny, on the CPU, through benchmark.run,
traced and untraced; and the timed path broken underneath — an add
acknowledged before its rows are in the device block, a delete
acknowledged whose mask bit is never set, a writer that stalls — must
come out `correct: false`, each by the one number that is there for it.
Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_live.py -q

Widths (100, float32, L2, k=10), the loop, the check and its limits are
the cell's own; rows, checked queries, callers and the rows a step are
cut.  Every fault is armed when the window opens (`Generator.go`): the
warm steps run sound, as they would in a deployment that went wrong
later.
"""

import json
import time

import numpy as np
import pytest

from benchmark import run
from benchmark.harness import reference_live, runbook

CELL = "flat_live5m.stream"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LIMITS = {"stale_or_wrong_lists": 0, "invalid_lists": 0, "tie_ulps": 8,
          "dist_err_ulps_rms": 5.0, "mutations_failed": 0,
          "writer_steps_done_share": 0.95}
TINY = {"config": {"rows": 20_000,
                   "check": {"rule": "exact_ids_live", "queries": 16,
                             "limits": LIMITS}},
        "traffic": {"callers": 16, "connections": 2, "distinct_queries": 64,
                    "rows_per_add": 32, "rows_per_delete": 32}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.live_scan_roofline"}
NEW = {"mutation.add_ms", "mutation.delete_ms", "mutation.block_update_ms",
       "mutation.upload_bytes_per_row", "mutation.wal_appends_per_op",
       "kernel.live_scan_roofline"}


def _want(traced):
    return {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}


@pytest.mark.parametrize("traced", [False, True])
def test_live_cell_rehearses(traced):
    from sptag_tpu.utils import metrics

    grows = metrics.counter_value("flat.block_grows")
    r = run.run_cell(CELL, 2**31 + 40, 4.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    compared = {n["name"]: n["value"] for n in r["compared"]}
    assert compared["stale_or_wrong_lists"] == 0
    assert compared["mutations_failed"] == 0
    assert compared["writer_steps_done_share"] == 1.0
    seen = r["seen"]
    # 16 steps of a 4 s window, 8 warm ones: adds + deletes, in order
    assert seen["writer_steps_done"] == 16 and seen["operations"] == 40
    assert seen["answers_with_streamed_row"] > seen["answers_compared"] / 2
    assert seen["compiles_in_window"] == 0 and seen["warm_passes"] <= 5
    # one growth, at the first warm add
    assert metrics.counter_value("flat.block_grows") - grows == 1
    want = _want(traced)
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    if traced:
        assert NEW <= want
        values = {k: v["value"] for k, v in r["rehearsal_values"].items()}
        assert values["mutation.wal_appends_per_op"] == 1.0
        # the window's 16 adds and 8 deletes; 32 rows ride the 128-row
        # rung here, the cell's 128 fill it
        assert values["mutation.upload_bytes_per_row"] \
            == (16 * (128 * 401 + 4) + 8 * 128 * 4) / (24 * 32)


def _armed_at_go(monkeypatch):
    """-> a dict whose "on" turns true when the window opens."""
    armed = {"on": False}
    go = run.Generator.go

    def arm_and_go(self):
        armed["on"] = True
        go(self)

    monkeypatch.setattr(run.Generator, "go", arm_and_go)
    return armed


def _failed(seed):
    r = run.run_cell(CELL, seed, 4.0, False, rehearse=TINY)
    assert r["failed"] == 0 and r["correct"] is False
    return {n["name"] for n in r["compared"] if not n["ok"]}


def test_an_add_acknowledged_before_it_is_on_the_device(monkeypatch):
    """Each add's rows reach the device block one add late: searches
    sent after its acknowledgement miss them for a period."""
    from sptag_tpu.algo.flat import FlatIndex

    armed = _armed_at_go(monkeypatch)
    sound = FlatIndex._device_append
    pending = []

    def late(self, begin, rows):
        if not armed["on"]:
            return sound(self, begin, rows)
        pending.append((begin, rows.copy()))
        if len(pending) > 1:
            sound(self, *pending.pop(0))

    monkeypatch.setattr(FlatIndex, "_device_append", late)
    assert _failed(2**31 + 41) == {"stale_or_wrong_lists"}


def test_a_delete_acknowledged_whose_mask_bit_is_not_set(monkeypatch):
    """The host tombstones and counts; the device block keeps the rows."""
    from sptag_tpu.algo.flat import FlatIndex

    armed = _armed_at_go(monkeypatch)
    sound = FlatIndex._device_mask
    monkeypatch.setattr(
        FlatIndex, "_device_mask",
        lambda self, vids: None if armed["on"] else sound(self, vids))
    assert _failed(2**31 + 42) == {"stale_or_wrong_lists"}


def test_a_writer_that_stalls(monkeypatch):
    """Every add holds the executor 0.6 s: a third of the steps run."""
    from sptag_tpu.core.index import VectorIndex

    armed = _armed_at_go(monkeypatch)
    sound = VectorIndex.add

    def slow(self, *a, **kw):
        if armed["on"]:
            time.sleep(0.6)
        return sound(self, *a, **kw)

    monkeypatch.setattr(VectorIndex, "add", slow)
    assert _failed(2**31 + 43) == {"writer_steps_done_share"}


def test_the_reference_applies_operations_in_order():
    """`LiveReference` on a corpus a test can read: adds take ids in
    arrival order, a delete by content tombstones exactly its block, and
    a state is the base plus a prefix of the operations."""
    rng = np.random.default_rng(5)
    data = rng.standard_normal((5_000, 16)).astype(np.float32)
    queries = rng.standard_normal((8, 16)).astype(np.float32)
    b0 = runbook.streamed_rows(queries, 0, 4, 0.25)
    b1 = runbook.streamed_rows(queries, 1, 4, 0.25)
    ops = [(reference_live.ADD, b0), (reference_live.ADD, b1),
           (reference_live.DELETE, b0), (reference_live.DELETE, b1)]
    ref = reference_live.LiveReference(data, queries, 3, ops)
    assert list(ref.rows_at) == [5000, 5004, 5008, 5008, 5008]
    assert list(ref.tombstoned) == [0, 0, 4, 4]
    # block s lies nearest to queries 4s..4s+3 while it lives
    assert list(ref.ids[1][:4, 0]) == [5000, 5001, 5002, 5003]
    assert list(ref.ids[2][4:, 0]) == [5004, 5005, 5006, 5007]
    assert (ref.ids[3][:4] < 5000).all() and (ref.ids[4] < 5000).all()
    np.testing.assert_array_equal(ref.ids[0], ref.ids[4])
    assert list(ref.live(2, np.array([5000, 5004, 5008, 3]))) \
        == [True, True, False, True]
    assert list(ref.live(3, np.array([5000, 5004]))) == [False, True]
    # the same rows again from the same queries: what the check leans on
    np.testing.assert_array_equal(
        b1, runbook.streamed_rows(queries, 1, 4, 0.25))
    assert runbook.query_vector("$resultnum:10 $indexname:x 0.5|-1.25")\
        .tolist() == [0.5, -1.25]
    assert runbook.index_name("$resultnum:10 $indexname:x 0.5") == "x"


def test_a_program_that_replaces_the_whole_block_is_refused_at_once(
        monkeypatch):
    """`datasets/clustered_f32_live.py`: at the cell's size a program
    whose FLAT block takes no write in place ends with a HarnessError
    before a row is drawn (exit 2 from `benchmark.run`); a block a
    re-placement handles in milliseconds runs on any program, and the
    rows are `clustered_f32`'s."""
    from benchmark.harness.serving import HarnessError
    from benchmark.loadgen import load_by_name
    from sptag_tpu.algo import flat

    live = load_by_name("datasets", "clustered_f32_live")
    plain = load_by_name("datasets", "clustered_f32")
    live._block_follows_mutations(5_000_000, 100)       # this program
    monkeypatch.delattr(flat, "reserved_slots")
    with pytest.raises(HarnessError, match="reserved_slots"):
        live.make(7, 5_000_000, 100, 512)
    for got, want in zip(live.make(7, 2_000, 100, 8),
                         plain.make(7, 2_000, 100, 8)):
        np.testing.assert_array_equal(got, want)
