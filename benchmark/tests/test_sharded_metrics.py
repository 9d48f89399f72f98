"""The two per-layer metrics of the mesh FLAT cell (PR 27), each on a
hand-made input: the sharded roofline's numerator on one worked example,
its reader on a hand-made `run`, and the merge reader on a hand-made
trace."""

import json
import os

import pytest

from benchmark.harness import roofline_sharded, serving, tracered
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sharded_deep10m.saturate"


def config():
    with open(os.path.join(
            HERE, "configs", "sharded_flat_deep10m_f32_l2.json")) as f:
        return json.load(f)


def test_a_chips_share_of_deep10m_at_q128_is_hbm_bound_at_1_17_ms():
    peaks = serving.peaks_for("TPU v5 lite")
    got = roofline_sharded.sharded_scan_least_seconds(
        1, 128, 2_500_000, 96, 4, peaks)
    # one read of 2.5M x 96 x 4 B = 960 MB at 819 GB/s; 2 x 128 x 2.5M x
    # 96 = 61.44 GFLOP at 197 TFLOP/s
    assert got["hbm_seconds"] == pytest.approx(960e6 / 819e9)
    assert got["hbm_seconds"] == pytest.approx(1.1722e-3, rel=1e-3)
    assert got["flop_seconds"] == pytest.approx(61.44e9 / 197e12)
    assert got["bound"] == "hbm" and got["seconds"] == got["hbm_seconds"]
    # a quarter of what one chip would need for the whole corpus
    whole = roofline_sharded.roofline.flat_scan_least_seconds(
        1, 128, 10_000_000, 96, 4, peaks)
    assert got["seconds"] == pytest.approx(whole["seconds"] / 4)


def test_sharded_roofline_reader_on_a_hand_made_run():
    reader = load_by_name("layer_metrics", "kernel.sharded_scan_roofline")
    run = {"config": config(), "peaks": serving.peaks_for("TPU v5 lite"),
           "spans": {"server.queue_wait": {"count": 1280, "total_s": 1.0},
                     "server.execute_batch": {"count": 10, "total_s": 1.0}},
           "trace": {"programs": {
               "jit__sharded_search_kernel": {"runs": 4, "seconds": 0.06},
               "jit__flat_search_kernel": {"runs": 9, "seconds": 9.0}}}}
    least, traced = reader.bound(run, 2_500_000)
    assert traced == pytest.approx(0.06)
    assert least["seconds"] == pytest.approx(4 * 960e6 / 819e9)
    assert 100.0 * least["seconds"] / traced == pytest.approx(7.81, rel=1e-2)
    # no gauge in the program (its parent), no trace, no mesh program
    assert reader.bound(run, None) is None
    assert reader.bound({**run, "trace": None}, 2_500_000) is None
    run["trace"]["programs"].pop("jit__sharded_search_kernel")
    assert reader.bound(run, 2_500_000) is None


def test_sharded_roofline_reads_the_programs_gauge():
    from sptag_tpu.utils import metrics

    reader = load_by_name("layer_metrics", "kernel.sharded_scan_roofline")
    metrics.reset()
    assert reader.rows_per_shard() is None
    assert reader.read({"trace": {"programs": {}}, "config": config(),
                        "spans": {}}) is None
    metrics.set_gauge("mesh.rows_per_shard", 2_500_000)
    assert reader.rows_per_shard() == 2_500_000
    metrics.reset()


def test_merge_reader_sums_the_merge_scope_on_the_busiest_plane():
    reader = load_by_name("layer_metrics", "kernel.mesh_merge_ms_per_batch")
    stack = "jit(_sharded_search_kernel)/jit(main)/shard_map/"
    names = {"%dist": stack + "flat.distance/dot_general",
             "%topk": stack + "flat.topk/top_k",
             "%gather": stack + "mesh.merge/all_gather",
             "%rerank": stack + "mesh.merge/top_k"}

    def program(at, scale=1.0):
        """One run of the program from `at`: distance 4 ms, top-k 10 ms,
        an unnamed copy 0.5 ms (between top-k and gather: not the
        merge's), gather 0.2 ms, an unnamed op 0.1 ms (between gather and
        re-rank: the merge's), re-rank 0.05 ms."""
        t, out = at, []
        for name, ms in (("%dist", 4.0), ("%topk", 10.0), ("%copy", 0.5),
                         ("%gather", 0.2), ("%made", 0.1),
                         ("%rerank", 0.05)):
            out.append((name, t, ms * 1e-3 * scale))
            t += ms * 1e-3 * scale
        return out, ("jit__sharded_search_kernel(7)", at, t - at)

    busy, idle_side = [], []
    modules_busy, modules_idle = [], []
    for at in (0.10, 0.20, 0.95):        # the third lies outside the window
        ops, module = program(at)
        busy += ops
        modules_busy.append(module)
        ops, module = program(at, scale=0.5)
        idle_side += ops
        modules_idle.append(module)
    raw = {"host": [(tracered.WINDOW_SPAN, 0.0, 0.9)],
           "devices": {
               "/device:TPU:0": {"ops": idle_side, "modules": modules_idle},
               "/device:TPU:1": {"ops": busy, "modules": modules_busy}}}
    # two runs in the window on the busiest plane: 0.2 + 0.1 + 0.05 ms each
    assert reader.merge_seconds(raw, names) == pytest.approx(2 * 0.35e-3)
    # a program with no such scope (the parent's), or no device plane
    older = {k: v.replace("mesh.merge/", "") for k, v in names.items()}
    assert reader.merge_seconds(raw, older) is None
    assert reader.merge_seconds({"host": raw["host"], "devices": {}},
                                names) is None
    assert reader.read({"trace": None}) is None


def test_the_new_cell_reports_what_the_benchmark_asks_of_it():
    """Every per-layer metric with no `workloads` list is the new cell's
    too, and the two new ones are its alone."""
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "closed128"
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert entry["reduced"] == config()["reduced"] == []
    assert entry["source"] == config()["source"]
    assert len(entry["source"]) <= 200
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"kernel.sharded_scan_roofline",
                    "kernel.mesh_merge_ms_per_batch"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
