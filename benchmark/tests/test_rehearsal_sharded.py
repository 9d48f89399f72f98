"""The four-chip cell, tiny, on four forced CPU devices, through
benchmark.run: traced and untraced, and a merge that drops one shard's
candidates must come out `correct: false`.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The CPU backend gets its four devices from XLA_FLAGS, read when the
backend starts: this module sets it as it is imported (collection comes
before any test touches a backend).  Where a backend was already up with
fewer, the tests skip: run this file in a session of its own.
"""

import json
import os

_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

from benchmark import run  # noqa: E402

CELL = "sharded_deep10m.saturate"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# widths, metric, k, shard count and the served path are the cell's own;
# rows (one that four shards of a multiple of 8 do not divide), checked
# queries and callers are cut
TINY = {"config": {"rows": 20_003,
                   "check": {"rule": "exact_ids", "queries": 32,
                             "limits": {"id_lists_wrong": 0, "tie_ulps": 8,
                                        "dist_err_ulps_rms": 4.0}}},
        "traffic": {"callers": 16, "connections": 2,
                    "distinct_queries": 64}}
# need a chip's trace
DEVICE_ONLY = {"device.idle_share", "device.busy_ms_per_batch",
               "kernel.topk_ms_per_batch", "kernel.sharded_scan_roofline",
               "kernel.mesh_merge_ms_per_batch"}


@pytest.fixture(autouse=True)
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("the CPU backend came up with fewer than 4 devices: "
                    "run this file in a session of its own")


@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearses_on_four_devices(traced):
    from sptag_tpu.utils import metrics

    r = run.run_cell(CELL, 2**31 + 27, 2.0, traced, rehearse=TINY)
    json.dumps(r)
    assert KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and r["rehearsal"] is True
    assert r["device"]["count"] >= 4
    # the builder wrote a mesh folder and the server loaded it as one
    folder = os.path.join(run.WORK, CELL, "index")
    with open(os.path.join(folder, "sharded.json")) as f:
        assert json.load(f)["algo"] == "FLAT"
    assert metrics.gauge_value("mesh.shards") == 4
    assert metrics.gauge_value("mesh.rows_per_shard") == 5008
    want = {m["name"] for m in run.metrics_of(
        run.load_json(run.ROOT, "BENCHMARK.json"),
        "per_layer" if traced else "end_to_end", CELL)}
    assert set(r["rehearsal_values"]) == want - DEVICE_ONLY
    assert r["seen"]["compiles_in_window"] == 0
    assert r["seen"]["serve_errors"] == {
        n: 0 for n in r["seen"]["serve_errors"]}


def test_a_merge_that_drops_a_shard_is_not_correct(monkeypatch):
    """Underneath the whole served path the last shard's candidates never
    reach the re-rank: every answer is still a valid id list with true
    distances, and the exact scan of all rows says it is the wrong one."""
    import jax
    import jax.numpy as jnp

    from sptag_tpu.parallel import sharded
    sound = sharded._gather_merge

    def drops_the_last_shard(d, gids, k_final):
        last = jax.lax.axis_index(sharded.SHARD_AXIS) == 3
        return sound(jnp.where(last, jnp.float32(sharded.MAX_DIST), d),
                     gids, k_final)

    jax.clear_caches()
    monkeypatch.setattr(sharded, "_gather_merge", drops_the_last_shard)
    try:
        r = run.run_cell(CELL, 5, 2.0, False, rehearse=TINY)
    finally:
        jax.clear_caches()        # the broken program must not be reused
    assert r["failed"] == 0 and r["correct"] is False
    bad = {n["name"] for n in r["compared"] if not n["ok"]}
    assert bad == {"id_lists_wrong"}
