"""benchdiff regression sentinel (ISSUE 10): identity pass, doctored
regressions fail with a named metric, noise floors, direction
awareness, platform gating, missing-key/driver-envelope handling."""

import copy
import json
import os

import pytest

from tools import benchdiff

#: a pre-chip CPU artifact of the removed bench.py, kept as the pinned
#: input of this comparer's tests (not a speed record)
R05 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "fixtures", "bench_artifact.json")


def _artifact(**overrides):
    base = {"schema_version": 1, "platform": "cpu", "value": 2000.0,
            "flat_qps": 800.0, "recall_at_10": 0.96,
            "p99_batch_ms": 700.0,
            "loadgen": {"qps_at_slo": 512.0, "p50_ms": 20.0,
                        "p99_ms": 100.0}}
    base.update(overrides)
    return base


def _write(tmp_path, name, obj):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        json.dump(obj, f)
    return p


def test_identity_on_repo_artifact_passes(capsys):
    """THE acceptance command: the pinned repo artifact against itself
    exits 0."""
    assert benchdiff.main([R05, R05]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out


def test_doctored_loadgen_p99_regression_fails(tmp_path, capsys):
    base = _artifact()
    cur = copy.deepcopy(base)
    cur["loadgen"]["p99_ms"] = 120.0           # -20% headroom
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 1
    out = capsys.readouterr().out
    assert "loadgen.p99_ms" in out and "REGRESSED" in out
    assert "FAIL" in out


def test_qps_drop_fails_and_names_metric(tmp_path, capsys):
    base = _artifact()
    cur = _artifact(value=1500.0)              # -25% headline QPS
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 1
    out = capsys.readouterr().out
    assert "value" in out and "REGRESSED" in out


def test_noise_floor_absorbs_small_absolute_wiggle(tmp_path):
    """A big RELATIVE change under the absolute min-delta floor passes:
    a 20->25 QPS beam-stage wiggle is noise, not regression."""
    base = _artifact(beam_qps=22.0)
    cur = _artifact(beam_qps=21.0)             # -4.5% rel, 1.0 abs < 2.0
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0


def test_relative_threshold_absorbs_small_relative_wiggle(tmp_path):
    """A big ABSOLUTE change under the relative threshold passes: 15k
    -> 14.2k dense QPS is -5%, inside the 15% band."""
    base = _artifact(value=15000.0)
    cur = _artifact(value=14200.0)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0


def test_direction_awareness(tmp_path):
    """Latency UP regresses, QPS UP improves — never confused."""
    base = _artifact()
    faster = _artifact(value=3000.0, p99_batch_ms=300.0)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", faster)
    assert benchdiff.main([bp, cp]) == 0
    slower_lat = _artifact(p99_batch_ms=1000.0)
    cp2 = _write(tmp_path, "c2.json", slower_lat)
    assert benchdiff.main([bp, cp2]) == 1


def test_recall_regression_fails_even_across_platforms(tmp_path, capsys):
    base = _artifact()
    cur = _artifact(platform="tpu", value=99999.0, recall_at_10=0.90)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 1
    out = capsys.readouterr().out
    assert "platform mismatch" in out
    assert "recall_at_10" in out and "REGRESSED" in out


def test_platform_mismatch_skips_throughput(tmp_path, capsys):
    base = _artifact()
    cur = _artifact(platform="tpu", value=1.0, flat_qps=1.0)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0
    assert "platform mismatch" in capsys.readouterr().out


def test_missing_stage_keys_are_skipped_not_failed(tmp_path):
    base = _artifact()
    cur = _artifact()
    del cur["loadgen"]                 # stage budget-dropped this run
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0


def test_driver_envelope_unwraps(tmp_path):
    base = {"n": 5, "rc": 0, "parsed": _artifact()}
    cur = {"n": 6, "rc": 0, "parsed": _artifact(value=100.0)}
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 1


def test_schema_version_mismatch_warns_but_diffs(tmp_path, capsys):
    base = _artifact(schema_version=0)
    cur = _artifact()
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0
    assert "schema_version differs" in capsys.readouterr().out


def test_json_output_machine_readable(tmp_path, capsys):
    base = _artifact()
    cur = _artifact(value=1000.0)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main(["--json", bp, cp]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["pass"] is False
    bad = [v for v in out["verdicts"] if v["status"] == "REGRESSED"]
    assert bad and bad[0]["metric"] == "value"


def test_load_errors_exit_2(tmp_path, capsys):
    missing = str(tmp_path / "nope.json")
    assert benchdiff.main([R05, missing]) == 2
    bad = _write(tmp_path, "bad.json", [1, 2, 3])
    assert benchdiff.main([R05, bad]) == 2


def test_resolve_dotted_paths():
    obj = {"a": {"b": {"c": 1.5}}, "x": True, "y": None, "z": "s"}
    assert benchdiff.resolve(obj, "a.b.c") == 1.5
    assert benchdiff.resolve(obj, "a.b.missing") is None
    assert benchdiff.resolve(obj, "x") is None       # bools excluded
    assert benchdiff.resolve(obj, "y") is None
    assert benchdiff.resolve(obj, "z") is None


@pytest.mark.parametrize("base,cur,direction,expect", [
    (100.0, 79.0, benchdiff.HIGHER, "REGRESSED"),   # -21%
    (100.0, 121.0, benchdiff.LOWER, "REGRESSED"),   # +21%
    (100.0, 121.0, benchdiff.HIGHER, "improved"),
    (100.0, 100.0, benchdiff.HIGHER, "ok"),
])
def test_judge_matrix(base, cur, direction, expect):
    m = benchdiff.Metric("m", direction, 0.20, 10.0)
    assert benchdiff.judge(m, base, cur).status == expect


def test_backend_compile_count_regression_fails(tmp_path, capsys):
    """ISSUE 16: per-stage `xla.backend_compile[bench.X]` span counts
    become direction-adjusted `<stage>.backend_compiles` lines — a
    stage minting MORE XLA programs than the baseline is a recompile
    regression even when QPS looks flat."""
    base = _artifact(trace={
        "xla.backend_compile[bench.sweep]": {"count": 4,
                                             "total_s": 2.0},
        "xla.backend_compile[bench.flat_quick]": {"count": 2,
                                                  "total_s": 0.5},
        "bench.sweep": {"count": 1, "total_s": 9.0}})
    cur = copy.deepcopy(base)
    cur["trace"]["xla.backend_compile[bench.sweep]"]["count"] = 12
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 1
    out = capsys.readouterr().out
    assert "bench.sweep.backend_compiles" in out and "REGRESSED" in out
    # the steady stage stays quiet; plain spans never synthesize a line
    assert "bench.flat_quick.backend_compiles REGRESSED" not in out


def test_backend_compile_counts_equal_pass_and_fewer_improve(tmp_path,
                                                             capsys):
    base = _artifact(trace={
        "xla.backend_compile[bench.sweep]": {"count": 8,
                                             "total_s": 2.0}})
    cur = copy.deepcopy(base)
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0
    cur["trace"]["xla.backend_compile[bench.sweep]"]["count"] = 3
    cp = _write(tmp_path, "c2.json", cur)
    assert benchdiff.main([bp, cp]) == 0      # fewer compiles: improved
    # label present on only one side is skipped, not failed
    del cur["trace"]["xla.backend_compile[bench.sweep]"]
    cp = _write(tmp_path, "c3.json", cur)
    assert benchdiff.main([bp, cp]) == 0


def test_backend_compile_lines_are_platform_bound(tmp_path, capsys):
    base = _artifact(trace={
        "xla.backend_compile[bench.sweep]": {"count": 2,
                                             "total_s": 1.0}})
    cur = copy.deepcopy(base)
    cur["platform"] = "tpu"
    cur["trace"]["xla.backend_compile[bench.sweep]"]["count"] = 40
    bp = _write(tmp_path, "b.json", base)
    cp = _write(tmp_path, "c.json", cur)
    assert benchdiff.main([bp, cp]) == 0
    out = capsys.readouterr().out
    assert "platform mismatch" in out
