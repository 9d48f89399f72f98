"""The reduction from trace events to device numbers, on a hand-made
event list through the same functions a chip trace goes through."""

import pytest

from benchmark.harness import tracered as tr

W = tr.WINDOW_SPAN
# one device, window [10, 13]: ops cover [9.5, 10.5] (clipped to 0.5),
# [11, 11.4] and [11.2, 11.8] (overlap -> [11, 11.8]), [12.5, 13.5]
# (clipped to 0.5)
RAW = {
    "devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 9.5, 1.0), ("sort.2", 11.0, 0.4),
                ("fusion.1", 11.2, 0.6), ("sort.2", 12.5, 1.0)],
        "modules": [("jit__flat_search_kernel(123)", 9.5, 1.0),
                    ("jit__flat_search_kernel(123)", 11.0, 0.8),
                    ("jit_other(9)", 12.5, 1.0)]}},
    "host": [(W, 10.0, 3.0),
             ("server.execute_batch", 9.4, 1.2),      # covers gap 1 start
             ("server.encode", 10.55, 0.1),           # later, shorter
             ("server.execute_batch", 10.9, 1.0),
             ("server.drain", 11.85, 0.05)],
    "lines": {},
}


def test_union_of_overlapping_intervals():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 0.5)]
    assert tr.merged(ops) == [(0.0, 1.5), (3.0, 3.5)]
    assert tr.busy_seconds(ops) == pytest.approx(2.0)


def test_clip_drops_what_lies_outside():
    assert tr.clip([("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 5.0, 1.0)],
                   0.8, 2.0) == [("a", 0.8, pytest.approx(0.2)),
                                 ("b", 0.8, pytest.approx(0.7))]


def test_idle_share_and_programs():
    red = tr.reduce_trace(RAW, chips=1)
    assert red["window_s"] == pytest.approx(3.0)
    assert red["busy_s"] == pytest.approx(0.5 + 0.8 + 0.5)
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(0.4)
    assert red["programs"]["jit__flat_search_kernel"]["runs"] == 2
    assert red["programs"]["jit__flat_search_kernel"]["seconds"] == \
        pytest.approx(0.5 + 0.8)
    assert red["host_span_counts"]["server.execute_batch"] == 2
    assert red["device_ops"][0][0] == "fusion.1"


def test_gap_attribution_names_the_innermost_span_over_each_instant():
    red = tr.reduce_trace(RAW, chips=1)
    gaps = dict((n, t) for n, t in red["idle_gaps"])
    # gap [10.5, 11]: execute_batch#1 to 10.55, then encode (started
    # later) to 10.65 though execute_batch#1 runs on to 10.6, nothing to
    # 10.9, execute_batch#2 to 11.  gap [11.8, 12.5]: execute_batch#2 to
    # 11.85, drain to 11.9, nothing after
    assert gaps["server.execute_batch"] == pytest.approx(0.05 + 0.1 + 0.05)
    assert gaps["server.encode"] == pytest.approx(0.1)
    assert gaps["server.drain"] == pytest.approx(0.05)
    assert gaps["(no span)"] == pytest.approx(0.25 + 0.6)
    assert sum(gaps.values()) == pytest.approx(1.2)


def test_gap_with_no_span_over_it():
    gaps = tr.gaps_by_host_span([(1.0, 2.0)], [("x", 3.0, 1.0)])
    assert gaps == [["(no span)", pytest.approx(1.0)]]


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.window_of([("server.encode", 0.0, 1.0)])
