"""chip_smoke.py off the chip: what can be checked without one.

The script's real run needs a TPU.  Here: importing it initializes no
backend; the data generators and exact references it runs on (the
benchmark's for f32 / L2, its own for int8 cosine) agree with FlatIndex at
a tiny size; the phase lines have the shape the contract names, and on the
CPU it refuses — non-zero exit, `"ok": false` — with and without the
rehearsal switch.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=timeout)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


def test_import_initializes_no_backend():
    code = ("import chip_smoke, sys; "
            "from jax._src import xla_bridge as xb; "
            "assert 'sptag_tpu' not in sys.modules, 'library imported'; "
            "assert not xb._backends, sorted(xb._backends); print('clean')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "clean"


def test_generator_is_seeded_and_shaped():
    from benchmark.datasets import clustered_f32, clustered_int8

    a, qa = clustered_f32.make(3, 2000, 128, 16)
    b, qb = clustered_f32.make(3, 2000, 128, 16)
    c, _ = clustered_f32.make(4, 2000, 128, 16)
    assert a.shape == (2000, 128) and a.dtype == np.float32
    assert qa.shape == (16, 128)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(qa, qb)
    assert not np.array_equal(a, c)
    i8, q8 = clustered_int8.make(3, 2000, 384, 16)
    assert i8.dtype == np.int8 and q8.dtype == np.int8
    assert i8.shape == (2000, 384) and q8.shape == (16, 384)
    np.testing.assert_array_equal(i8, clustered_int8.make(3, 2000, 384, 16)[0])
    norms = np.linalg.norm(i8.astype(np.float64), axis=1)
    assert np.abs(norms - 127.0).max() < 2.0          # unit norm x 127


def test_exact_reference_agrees_with_flat_index():
    """The numpy reference the smoke and the benchmark share and FlatIndex
    answer alike — and the benchmark's `exact_ids` rule refuses an answer
    that is really different."""
    import sptag_tpu as sp
    from benchmark.datasets import clustered_f32
    from benchmark.harness import compare, reference
    from benchmark.loadgen import load_by_name

    data, queries = clustered_f32.make(5, 3000, 128, 24)
    ref_ids, _ = reference.exact_topk(data, queries, 10)
    brute = ((queries[:, None, :].astype(np.float64)
              - data[None, :, :].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_array_equal(
        ref_ids, np.argsort(brute, axis=1, kind="stable")[:, :10])

    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    dists, got = index.search_batch(queries, 10)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "flat_1m_f32_l2.json")) as f:
        config = json.load(f)
    rule, sample = load_by_name("checks", "exact_ids"), np.arange(len(queries))

    def wrong_lists(ids):
        verdict = rule.check(data, queries, sample,
                             compare.answers_as_window(ids, dists), config)
        numbers = {n["name"]: n for n in verdict["numbers"]}
        return numbers["id_lists_wrong"]

    sound = wrong_lists(got)
    assert sound["ok"] and sound["value"] == 0
    wrong = np.array(got)
    wrong[0, 9] = int(np.argmax(brute[0]))       # the farthest row
    altered = wrong_lists(wrong)
    assert not altered["ok"] and altered["value"] == 1


def test_int8_reference_follows_the_integer_cosine_convention():
    import sptag_tpu as sp
    from benchmark.datasets import clustered_int8
    from benchmark.harness import reference, reference_int8_cosine

    data, queries = clustered_int8.make(6, 3000, 384, 16)
    ref_ids, ref_scores = reference_int8_cosine.exact_topk_int8_cosine(
        data, queries, 10)
    # scores are 127^2 - integer dot: integral, ascending
    assert np.array_equal(ref_scores, np.round(ref_scores))
    assert (np.diff(ref_scores, axis=1) >= 0).all()
    index = sp.create_instance("FLAT", "Int8")
    index.set_parameter("DistCalcMethod", "Cosine")
    index.build(data)
    dists, got = index.search_batch(queries, 10)
    # integer ties may order differently; the distances must be the same
    np.testing.assert_array_equal(np.asarray(dists, np.float64), ref_scores)
    assert reference.recall_at_k(got, ref_ids, 10) >= 0.9


def test_device_phase_takes_its_peaks_from_the_benchmarks_table():
    """`phase_device` prints what the benchmark's rooflines divide by:
    benchmark/harness/peaks.json, read and never edited."""
    import chip_smoke

    with open(os.path.join(REPO, "benchmark", "harness", "peaks.json")) as f:
        table = json.load(f)["TPU v5 lite"]
    peaks = chip_smoke.device_peaks("TPU v5 lite")
    assert peaks == {name: table[name] for name in (
        "bf16_flops_per_s", "int8_ops_per_s", "hbm_bytes_per_s")}
    assert all(v > 0 for v in peaks.values())


@pytest.mark.parametrize("kind", ["TPU v9 imagined", "cpu", "source"])
def test_device_phase_refuses_a_kind_the_table_lacks(kind):
    import chip_smoke
    from benchmark.harness.serving import HarnessError

    with pytest.raises(HarnessError, match="peaks.json"):
        chip_smoke.device_peaks(kind)


def test_refuses_on_cpu_without_the_rehearsal_switch():
    proc, lines = _run()
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert "no TPU" in lines[-1]["error"]
    # nothing past the device phase ran
    assert [ln.get("phase") for ln in lines[:-1]] == []


def test_rehearsal_prints_phase_lines_and_never_ok(tmp_path):
    """Tiny beam phase end to end on the CPU (build CLI -> ini -> server
    -> socket clients): phase lines shaped as the contract says, the last
    line the contract's keys, and never `"ok": true`."""
    proc, lines = _run("--rehearse", "--phases", "bkt")
    assert proc.returncode != 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["ok"] is False and last["rehearsal"] is True
    assert "error" not in last, last
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(phases) == ["device", "compile_cache", "bkt.build",
                            "bkt_200k"]
    bkt = phases["bkt_200k"]
    assert (bkt["d"], bkt["k"], bkt["metric"]) == (128, 10, "L2")
    assert bkt["cut"] == f"n 200000 -> {bkt['n']}"
    assert bkt["beam_recall_at_10"] >= 0.8
    assert 0 < bkt["beam_self_first"] <= bkt["self_queries"]
    for key in ("seconds", "build_seconds", "compiles", "compile_seconds",
                "cache_hits"):
        assert key in bkt, key
