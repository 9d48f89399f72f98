"""The roofline's numerator on one worked example."""

import json
import os

import pytest

from benchmark.harness import roofline, serving


def test_flat_scan_q128_n1m_d128_is_hbm_bound_at_0_63_ms():
    peaks = serving.peaks_for("TPU v5 lite")
    got = roofline.flat_scan_least_seconds(1, 128, 1_000_000, 128, 4, peaks)
    # one read of 512 MB at 819 GB/s; 32.8 GFLOP at 197 TFLOP/s
    assert got["hbm_seconds"] == pytest.approx(0.625e-3, rel=1e-3)
    assert got["flop_seconds"] == pytest.approx(0.1663e-3, rel=1e-3)
    assert got["bound"] == "hbm"
    assert got["seconds"] == got["hbm_seconds"]


def test_past_481_queries_a_batch_the_flops_bound():
    peaks = serving.peaks_for("TPU v5 lite")
    assert roofline.flat_scan_least_seconds(
        1, 512, 1_000_000, 128, 4, peaks)["bound"] == "flops"


def test_a_device_kind_the_table_lacks_is_an_error():
    with pytest.raises(serving.HarnessError):
        serving.peaks_for("cpu")
    with pytest.raises(serving.HarnessError):
        serving.peaks_for("source")


def test_reader_reports_share_of_traced_program_time():
    from benchmark.loadgen import load_by_name
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "flat_1m_f32_l2.json")) as f:
        config = json.load(f)
    run = {"config": config, "peaks": serving.peaks_for("TPU v5 lite"),
           "spans": {"server.queue_wait": {"count": 1280, "total_s": 1.0},
                     "server.execute_batch": {"count": 10, "total_s": 1.0}},
           "trace": {"programs": {"jit__flat_search_kernel":
                                  {"runs": 4, "seconds": 0.25}}}}
    share = load_by_name("layer_metrics", "kernel.flat_scan_roofline").read(
        run)
    assert share == pytest.approx(100.0 * 4 * 0.625e-3 / 0.25, rel=1e-3)
    run["trace"] = None
    assert load_by_name("layer_metrics",
                        "kernel.flat_scan_roofline").read(run) is None
