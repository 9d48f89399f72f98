"""`sharded_live20m.stream` (PR 43), tiny, on four forced CPU devices,
through benchmark.run, traced and untraced; and the timed path broken
underneath — a write rung put into another shard's slots than the table
of ids says, a delete whose mask bit is never set on one shard, a writer
that stalls — must come out `correct: false`, each by the one number
that is there for it.  Run by hand, in a session of its own (the CPU
backend gets its four devices from XLA_FLAGS, read when it starts):

    JAX_PLATFORMS=cpu python -m pytest \
        benchmark/tests/test_rehearsal_sharded_live.py -q

Widths (100, float32, L2, k=10), the four partitions, the loop, the check
and its limits are the cell's own; rows, checked queries, callers and the
rows a step are cut.  Every fault is armed when the window opens
(`Generator.go`): the warm steps run sound.
"""

import json
import os
import time

_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=4").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import run  # noqa: E402

CELL = "sharded_live20m.stream"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LIMITS = {"stale_or_wrong_lists": 0, "invalid_lists": 0, "tie_ulps": 8,
          "dist_err_ulps_rms": 5.0, "mutations_failed": 0,
          "writer_steps_done_share": 0.90}
TINY = {"config": {"rows": 20_003,
                   "check": {"rule": "exact_ids_live", "queries": 16,
                             "limits": LIMITS}},
        "traffic": {"callers": 16, "connections": 2, "distinct_queries": 64,
                    "rows_per_add": 32, "rows_per_delete": 32}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.mesh_merge_ms_per_batch",
               "kernel.sharded_live_scan_roofline"}
NEW = {"kernel.sharded_live_scan_roofline", "mutation.devices_per_write"}


@pytest.fixture(autouse=True)
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("the CPU backend came up with fewer than 4 devices: "
                    "run this file in a session of its own")


def test_the_configuration_is_the_one_chip_cell_on_a_mesh():
    """The new configuration's limits, guarantees and traffic are the
    one-chip living cell's, but for what the issue changes."""
    _, cell, config, _, traffic = run.find_cell(CELL)
    _, one, config1, _, traffic1 = run.find_cell("flat_live5m.stream")
    assert cell["chips"] == config["chips"] == 4 and one["chips"] == 1
    assert traffic == traffic1
    assert config["index_params"] == {"MeshShardAxis": 4, "WalEnabled": "1",
                                      "WalFsync": "1"}
    assert config["check"]["limits"] == LIMITS
    assert {**config1["check"]["limits"],
            "writer_steps_done_share": 0.90} == LIMITS
    assert config["guarantees"][:3] == config1["guarantees"][:3]
    assert config["rows"] == 20_000_000 and config["dim"] == 100


@pytest.mark.parametrize("traced", [False, True])
def test_mesh_live_cell_rehearses(traced):
    from sptag_tpu.utils import metrics

    grows = metrics.counter_value("flat.block_grows")
    r = run.run_cell(CELL, 2**31 + 43, 4.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    assert r["device"]["count"] >= 4
    compared = {n["name"]: n["value"] for n in r["compared"]}
    assert compared["stale_or_wrong_lists"] == 0
    assert compared["mutations_failed"] == 0
    assert compared["writer_steps_done_share"] == 1.0
    seen = r["seen"]
    # 16 steps of a 4 s window, 8 warm ones: adds + deletes, in order
    assert seen["writer_steps_done"] == 16 and seen["operations"] == 40
    assert seen["answers_with_streamed_row"] > seen["answers_compared"] / 2
    assert seen["compiles_in_window"] == 0 and seen["warm_passes"] <= 5
    assert seen["serve_errors"] == {n: 0 for n in seen["serve_errors"]}
    # one growth of all four blocks, at the first warm add
    assert metrics.counter_value("flat.block_grows") - grows == 1
    assert metrics.gauge_value("mesh.shards") == 4
    # 24 rungs of 32 rows went round the four shards; 16 were deleted
    assert metrics.gauge_value("mesh.live_rows_max") \
        - metrics.gauge_value("mesh.live_rows_min") <= 32
    folder = os.path.join(run.WORK, CELL, "index")
    with open(os.path.join(folder, "sharded.json")) as f:
        manifest = json.load(f)
    assert manifest["algo"] == "FLAT"
    assert manifest["index_params"] == {"WalEnabled": "1"}
    assert os.path.getsize(os.path.join(folder, "wal.bin")) > 1000
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    if traced:
        assert NEW <= want
        values = {k: v["value"] for k, v in r["rehearsal_values"].items()}
        assert values["mutation.wal_appends_per_op"] == 1.0
        assert values["mutation.devices_per_write"] == 1.0
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


def test_a_rung_written_into_the_wrong_shards_slots(monkeypatch):
    """Each add's rung lands in the free slots of ANOTHER shard than the
    one its turn names, where nothing looks for it (masked, no id), and
    reaches its own shard only with the next add: searches sent after
    its acknowledgement miss its rows for a period."""
    from sptag_tpu.parallel import sharded

    armed = _armed_at_go(monkeypatch)
    sound = sharded.ShardedFlatIndex._device_append
    pending = []

    def astray(self, begin, rows):
        if not armed["on"]:
            return sound(self, begin, rows)
        other = (self._next_shard + 1) % len(self._parts)
        rung = np.zeros((128, rows.shape[1]), rows.dtype)
        rung[:len(rows)] = rows
        self._parts[other] = list(sharded._block_write_rows(
            *self._parts[other], rung, np.ones(128, bool),
            np.int32(self._fill[other])))
        self._publish(self._assembled())
        pending.append((begin, rows.copy()))
        if len(pending) > 1:
            sound(self, *pending.pop(0))

    monkeypatch.setattr(sharded.ShardedFlatIndex, "_device_append", astray)
    assert _failed(2**31 + 44) == {"stale_or_wrong_lists"}


def test_a_delete_whose_mask_bit_is_not_set_on_one_shard(monkeypatch):
    """The host tombstones and counts; shard 1's block keeps the rows."""
    from sptag_tpu.parallel import sharded

    armed = _armed_at_go(monkeypatch)
    sound = sharded._block_mask_rows

    def skips_shard_one(invalid, slots):
        on_one = list(invalid.devices())[0].id % 4 == 1
        if armed["on"] and on_one:
            return invalid
        return sound(invalid, slots)

    monkeypatch.setattr(sharded, "_block_mask_rows", skips_shard_one)
    assert _failed(2**31 + 45) == {"stale_or_wrong_lists"}


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
    assert _failed(2**31 + 46) == {"writer_steps_done_share"}


def test_a_program_whose_mesh_index_takes_no_add_is_refused_at_once(
        monkeypatch):
    """`datasets/clustered_f32_live_mesh.py`: a program whose mesh FLAT
    index has no `add` ends with a HarnessError before a row is drawn
    (exit 2 from `benchmark.run`); on this program the rows are
    `clustered_f32`'s."""
    from benchmark.harness.serving import HarnessError
    from benchmark.loadgen import load_by_name
    from sptag_tpu.parallel import sharded

    mesh = load_by_name("datasets", "clustered_f32_live_mesh")
    plain = load_by_name("datasets", "clustered_f32")
    for got, want in zip(mesh.make(7, 2_000, 100, 8),
                         plain.make(7, 2_000, 100, 8)):
        np.testing.assert_array_equal(got, want)

    class BuiltOnce:
        """What the parent's class has: no mutation surface."""

    monkeypatch.setattr(sharded, "ShardedFlatIndex", BuiltOnce)
    with pytest.raises(HarnessError, match="ShardedFlatIndex.add"):
        mesh.make(7, 2_000, 100, 8)
    assert run.main(["--workload", CELL, "--seed", "7", "--seconds", "1"]) \
        == 2
