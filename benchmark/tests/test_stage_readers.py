"""The per-layer readers PR 25 added, each on a hand-made `run`; the dense
roofline's numerator on one worked example; the scope reader on a profile
written here byte by byte (tsl's xplane.proto field numbers)."""

import json
import os

import pytest

from benchmark.harness import roofline_dense, scopes, serving, tracered
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPANS = {
    "server.execute_batch": {"count": 10, "total_s": 0.250},
    "server.queue_wait": {"count": 800, "total_s": 12.0},
    "service.parse": {"count": 20, "total_s": 0.080},
    "service.results": {"count": 10, "total_s": 0.070},
    "index.search": {"count": 10, "total_s": 0.075},
    "index.readback": {"count": 10, "total_s": 0.065},
    "server.batch_gather": {"count": 10, "total_s": 0.021},
    "server.batch_resume": {"count": 10, "total_s": 0.060},
    "server.batch_cycle": {"count": 9, "total_s": 0.315},
}
WANT = {"service.parse_ms": 8.0, "service.results_ms": 7.0,
        "index.dispatch_ms": 1.0, "index.device_wait_ms": 6.5,
        "batcher.gather_ms": 2.1, "batcher.resume_ms": 6.0,
        "batcher.cycle_ms": 35.0}
NEEDS = {"service.parse_ms": "service.parse",
         "service.results_ms": "service.results",
         "index.dispatch_ms": "index.readback",
         "index.device_wait_ms": "index.readback",
         "batcher.gather_ms": "server.batch_gather",
         "batcher.resume_ms": "server.batch_resume",
         "batcher.cycle_ms": "server.batch_cycle"}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_on_a_hand_made_run(metric):
    read = load_by_name("layer_metrics", metric).read
    assert read({"spans": SPANS}) == pytest.approx(WANT[metric])
    # the parent of the PR that added the span has nothing to read
    older = {n: s for n, s in SPANS.items() if n != NEEDS[metric]}
    assert read({"spans": older}) is None


def test_four_stages_and_the_rest_make_up_the_batch():
    stages = sum(WANT[m] for m in ("service.parse_ms", "service.results_ms",
                                   "index.dispatch_ms",
                                   "index.device_wait_ms"))
    batch = load_by_name("layer_metrics", "execute.batch_ms").read(
        {"spans": SPANS})
    assert batch - stages == pytest.approx(2.5)      # grouping, admin


def test_every_new_metric_has_its_reader_file():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            HERE, "layer_metrics", m["name"] + ".py")), m["name"]


# ------------------------------------------------------- dense roofline

def test_dense_scan_q128_2048_rows_a_query_is_hbm_bound_at_0_164_ms():
    peaks = serving.peaks_for("TPU v5 lite")
    got = roofline_dense.dense_scan_least_seconds(
        1, 128, 2048, 512, 128, 4, peaks)
    # 128 x 2048 x 512 B = 134,217,728 B of rows + 512 x 512 B of
    # centroids at 819 GB/s; 2 x 128 x 128 x 2560 dot-product operations
    assert got["hbm_seconds"] == pytest.approx(134_479_872 / 819e9)
    assert got["hbm_seconds"] == pytest.approx(0.1642e-3, rel=1e-3)
    assert got["flop_seconds"] == pytest.approx(83_886_080 / 197e12)
    assert got["bound"] == "hbm" and got["seconds"] == got["hbm_seconds"]
    assert roofline_dense.dense_scan_least_seconds(
        3, 128, 2048, 512, 128, 4, peaks)["seconds"] \
        == pytest.approx(3 * got["seconds"])


def test_int8_rows_read_a_quarter_of_the_bytes():
    peaks = serving.peaks_for("TPU v5 lite")
    f32 = roofline_dense.dense_scan_least_seconds(1, 128, 2048, 0, 128, 4,
                                                  peaks)
    i8 = roofline_dense.dense_scan_least_seconds(1, 128, 2048, 0, 128, 1,
                                                 peaks)
    assert i8["hbm_seconds"] == pytest.approx(f32["hbm_seconds"] / 4)


def test_dense_roofline_reader_on_a_hand_made_run():
    reader = load_by_name("layer_metrics", "kernel.dense_scan_roofline")
    with open(os.path.join(HERE, "configs",
                           "bkt_100k_f32_l2_dense.json")) as f:
        config = json.load(f)
    run = {"config": config, "peaks": serving.peaks_for("TPU v5 lite"),
           "spans": {"server.queue_wait": {"count": 1280, "total_s": 1.0},
                     "server.execute_batch": {"count": 10, "total_s": 1.0}},
           "trace": {"programs": {
               "jit__dense_search_kernel": {"runs": 4, "seconds": 0.008},
               "jit__flat_search_kernel": {"runs": 9, "seconds": 9.0}}}}
    least, traced = reader.bound(run, (2048.0, 512.0))
    assert traced == pytest.approx(0.008)
    assert least["seconds"] == pytest.approx(4 * 134_479_872 / 819e9)
    assert 100.0 * least["seconds"] / traced == pytest.approx(8.21, rel=1e-2)
    # no gauges in the program (its parent), no trace, no dense program
    assert reader.bound(run, None) is None
    assert reader.bound({**run, "trace": None}, (2048.0, 512.0)) is None
    run["trace"]["programs"].pop("jit__dense_search_kernel")
    assert reader.bound(run, (2048.0, 512.0)) is None


def test_dense_roofline_reads_the_programs_gauges():
    from sptag_tpu.utils import metrics

    reader = load_by_name("layer_metrics", "kernel.dense_scan_roofline")
    metrics.reset()
    assert reader.scored_per_query() is None
    metrics.set_gauge("dense.rows_per_query", 2048)
    metrics.set_gauge("dense.centroids_per_query", 400)
    assert reader.scored_per_query() == (2048.0, 400.0)
    metrics.reset()


# --------------------------------------------------------- scope reader

def varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def map_entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def event(metadata_id: int, offset_ns: int, duration_ns: int) -> bytes:
    return (field(1, metadata_id) + field(2, offset_ns * 1000)
            + field(3, duration_ns * 1000))


OPS = {1: ("%fusion.3 = f32[128,1000064] fusion(...)",
           "jit(_flat_search_kernel)/flat.distance/dot_general:"),
       2: ('%custom-call = (f32[128,10], s32[128,10]) custom-call(...), '
           'custom_call_target="TopK"',
           "jit(_flat_search_kernel)/flat.topk/top_k:"),
       3: ("%copy.1 = s32[128,10] copy(...)", None),
       4: ("%sort.2 = (f32[1,1280], s32[1,1280]) sort(...)", None)}
MS = 10**6                                               # ns


def profile_bytes() -> bytes:
    """One device plane (stat 7 = `tf_op`; operations 3 and 4 carry none)
    with two runs of the program, and a host plane holding the harness's
    window span [1 s, 4 s].  Run A [0.5, 2.5 s]: fusion 0.5-1.5 s (half
    inside the window), TopK 2.0-2.2, sort 2.2-2.3 (no name stack, between
    two TopK: theirs), TopK 2.3-2.4, copy 2.4-2.5 (no name stack, last of
    its run: nobody's).  Run B [3.0, 3.2 s]: TopK."""
    metas = b""
    for mid, (name, scope) in OPS.items():
        meta = field(1, mid) + field(2, name)
        if scope:
            meta += field(5, field(1, 7) + field(5, scope))
        metas += field(4, map_entry(mid, meta))
    metas += field(4, map_entry(9, field(1, 9) + field(
        2, "jit__flat_search_kernel(123)")))
    ops = field(2, tracered.OPS_LINE) + field(3, 0)
    for mid, start, dur in ((1, 500, 1000), (2, 2000, 200), (4, 2200, 100),
                            (2, 2300, 100), (3, 2400, 100), (2, 3000, 200)):
        ops += field(4, event(mid, start * MS, dur * MS))
    modules = field(2, tracered.MODULES_LINE) + field(3, 0)
    for start, dur in ((500, 2000), (3000, 200)):
        modules += field(4, event(9, start * MS, dur * MS))
    device = (field(2, tracered.DEVICE_PLANE_PREFIX + "0") + field(3, ops)
              + field(3, modules) + metas
              + field(5, map_entry(7, field(1, 7)
                                   + field(2, scopes.SCOPE_STAT))))
    thread = (field(2, "python3") + field(3, 0)
              + field(4, event(1, 1000 * MS, 3000 * MS)))
    host = (field(2, tracered.HOST_PLANE) + field(3, thread)
            + field(4, map_entry(1, field(1, 1)
                                 + field(2, tracered.WINDOW_SPAN))))
    return field(1, device) + field(1, host)


@pytest.fixture
def profile(tmp_path):
    logdir = tmp_path / "plugins" / "profile" / "2026_09_27"
    logdir.mkdir(parents=True)
    path = logdir / "host.xplane.pb"
    path.write_bytes(profile_bytes())
    return str(tmp_path), str(path)


def test_scope_of_an_operation_is_its_metadatas_tf_op_stat(profile):
    got = scopes.op_scopes(profile[1])
    assert got == {name: scope for name, scope in OPS.values() if scope}


def test_an_unnamed_operation_takes_its_neighbours_stage_only_in_its_run():
    names = {"a": "jit(f)/flat.distance/dot:", "b": "jit(f)/flat.topk/x:"}
    ops = [("a", 0.0, 1.0), ("?", 1.0, 1.0), ("b", 2.0, 1.0),
           ("?", 3.0, 1.0), ("b", 5.0, 1.0), ("?", 9.0, 1.0)]
    # one run [0, 4): neighbours disagree, then none after; the next run
    # [5, 6) does not lend its stage backwards; [9, 10) lies in no run
    assert scopes.staged(ops, [("m", 0.0, 4.0), ("m", 5.0, 1.0)], names) \
        == [("flat.distance", 1.0), (None, 1.0), ("flat.topk", 1.0),
            (None, 1.0), ("flat.topk", 1.0), (None, 1.0)]
    assert scopes.staged(ops[2:5], [("m", 2.0, 4.0)], names) \
        == [("flat.topk", 1.0)] * 3


def test_stage_is_the_first_known_component_of_a_name_stack():
    assert scopes.stage_of(
        "jit(_dense_search_kernel)/dense.mask/jit(_where)/select_n:") \
        == "dense.mask"
    assert scopes.stage_of("jit(_flat_search_kernel)/top_k:") is None
    assert scopes.stage_of("") is None


def test_device_seconds_by_stage_clipped_to_the_window(profile):
    raw = tracered.read_xplane(profile[1])
    got = scopes.seconds_by_stage(raw, scopes.op_scopes(profile[1]))
    # the sort between two TopK operations of one run is the top-k's;
    # the copy that ends its run is nobody's
    assert got == {"flat.distance": pytest.approx(0.5),
                   "flat.topk": pytest.approx(0.2 + 0.1 + 0.1 + 0.2),
                   "(no stage)": pytest.approx(0.1)}
    # a program that names no stage (the parent): nothing to read
    assert scopes.seconds_by_stage(raw, {}) == {}


def test_topk_reader_divides_by_the_batches_of_the_slice(profile,
                                                         monkeypatch):
    reader = load_by_name("layer_metrics", "kernel.topk_ms_per_batch")
    monkeypatch.setattr(scopes, "trace_dir", lambda workload: profile[0])
    run = {"workload": "flat_1m.saturate", "trace": {
        "host_span_counts": {"server.execute_batch": 3}}}
    assert reader.read(run) == pytest.approx(1e3 * 0.6 / 3)
    assert reader.read({**run, "trace": None}) is None
    monkeypatch.setattr(scopes, "trace_dir",
                        lambda workload: profile[0] + "/none")
    assert reader.read(run) is None
