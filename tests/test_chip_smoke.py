"""chip_smoke.py off the chip: what can be checked without one.

The script's real run needs a TPU (the driver makes it).  Here: importing
it initializes no backend, its data generator and exact reference agree
with FlatIndex at a tiny size, the phase lines have the shape the
contract names, and on the CPU it refuses — non-zero exit, `"ok": false`
— with and without the rehearsal switch.
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
    import chip_smoke

    a, qa = chip_smoke.make_clustered(3, 2000, 128, 16)
    b, qb = chip_smoke.make_clustered(3, 2000, 128, 16)
    c, _ = chip_smoke.make_clustered(4, 2000, 128, 16)
    assert a.shape == (2000, 128) and a.dtype == np.float32
    assert qa.shape == (16, 128)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(qa, qb)
    assert not np.array_equal(a, c)
    i8, q8 = chip_smoke.make_clustered(3, 2000, 384, 16, np.int8)
    assert i8.dtype == np.int8 and q8.dtype == np.int8
    norms = np.linalg.norm(i8.astype(np.float64), axis=1)
    assert np.abs(norms - 127.0).max() < 2.0          # unit norm x 127


def test_exact_reference_agrees_with_flat_index():
    """The script's numpy reference and FlatIndex answer alike — and
    `compare_exact` refuses an answer that is really different."""
    import chip_smoke
    import sptag_tpu as sp

    data, queries = chip_smoke.make_clustered(5, 3000, 128, 24)
    ref_ids, ref_scores = chip_smoke.exact_topk(data, queries, 10, "L2")
    brute = ((queries[:, None, :].astype(np.float64)
              - data[None, :, :].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_array_equal(
        ref_ids, np.argsort(brute, axis=1, kind="stable")[:, :10])

    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    _, got = index.search_batch(queries, 10)
    same, ties = chip_smoke.compare_exact(data, queries, got, ref_ids,
                                          ref_scores, "L2")
    assert same + ties == len(queries) and same >= len(queries) - 2

    wrong = np.array(got)
    wrong[0, 9] = int(np.argmax(brute[0]))       # the farthest row
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_exact(data, queries, wrong, ref_ids, ref_scores,
                                 "L2")


def test_int8_reference_follows_the_integer_cosine_convention():
    import chip_smoke
    import sptag_tpu as sp

    data, queries = chip_smoke.make_clustered(6, 3000, 384, 16, np.int8)
    ref_ids, ref_scores = chip_smoke.exact_topk(data, queries, 10, "Cosine")
    # scores are 127^2 - integer dot: integral, ascending
    assert np.array_equal(ref_scores, np.round(ref_scores))
    assert (np.diff(ref_scores, axis=1) >= 0).all()
    index = sp.create_instance("FLAT", "Int8")
    index.set_parameter("DistCalcMethod", "Cosine")
    index.build(data)
    dists, got = index.search_batch(queries, 10)
    # integer ties may order differently; the distances must be the same
    np.testing.assert_array_equal(np.asarray(dists, np.float64), ref_scores)
    assert chip_smoke.recall_at_k(got, ref_ids, 10) >= 0.9


def test_refuses_on_cpu_without_the_rehearsal_switch():
    proc, lines = _run()
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert "no TPU" in lines[-1]["error"]
    # nothing past the device phase ran
    assert [ln.get("phase") for ln in lines[:-1]] == []


def test_rehearsal_prints_phase_lines_and_never_ok(tmp_path):
    """Tiny FLAT phase end to end on the CPU (build CLI -> ini -> server
    -> socket clients): phase lines shaped as the contract says, the last
    line the contract's keys, and never `"ok": true`."""
    proc, lines = _run("--rehearse", "--phases", "flat")
    assert proc.returncode != 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["ok"] is False and last["rehearsal"] is True
    assert "error" not in last, last
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(phases) == ["device", "compile_cache", "flat.build",
                            "flat_1m"]
    flat = phases["flat_1m"]
    assert (flat["d"], flat["k"], flat["metric"]) == (128, 10, "L2")
    assert flat["ids_identical"] + flat["ids_tie_resolved"] \
        == flat["queries"] > 0
    for key in ("seconds", "build_seconds", "compiles", "compile_seconds",
                "cache_hits"):
        assert key in flat, key
