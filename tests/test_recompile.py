"""Recompile-guard tier-1 tests — the runtime complement of graftlint.

Asserts the serving invariant directly: the flat and beam search paths
compile ONCE per (query-shape-bucket, dtype) and ZERO times on repeat
queries.  A regression here (a Python scalar sneaking into a traced
argument, an unbucketed shape) would otherwise surface rounds later as
"compile time per request" in a bench, which is the expensive way to
find it.

Corpora are tiny (hundreds of rows) — what is under test is the COMPILE
COUNT, not recall; the counts come from jax.monitoring's
backend-compile event (utils/recompile_guard.py), which fires for real
XLA compilations only (in-process jit cache hits do not).
"""

import os
import sys

import numpy as np
import pytest

import jax

import sptag_tpu as sp
from sptag_tpu.utils import recompile_guard as rg
from sptag_tpu.utils import trace


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Each test owns its warmup: drop in-process executable caches so
    "the warmup compiles, the steady state does not" holds regardless of
    which tests ran before this module."""
    jax.clear_caches()
    yield


def _flat_index(n=96, d=8, value_type="Float", dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == np.float32:
        data = rng.standard_normal((n, d)).astype(dtype)
    else:
        data = rng.integers(-100, 100, (n, d)).astype(dtype)
    idx = sp.create_instance("FLAT", value_type)
    idx.set_parameter("DistCalcMethod", "L2")
    assert idx.build(data) == sp.ErrorCode.Success
    return idx, data


def test_flat_compiles_once_then_never():
    idx, data = _flat_index()
    with rg.track_compiles("flat.warmup") as warm:
        idx.search_batch(data[:8], 5)
    assert warm.count >= 1, "warmup was expected to compile"
    with rg.no_recompiles("flat.steady") as steady:
        idx.search_batch(data[:8], 5)           # identical shape
        idx.search_batch(data[8:16], 5)         # same shape, new values
        idx.search_batch(data[:5], 5)           # same query bucket (8)
    assert steady.count == 0


def test_flat_new_shape_bucket_compiles_once():
    idx, data = _flat_index()
    idx.search_batch(data[:8], 5)               # warm the 8-bucket
    with rg.track_compiles("flat.bucket32") as grow:
        idx.search_batch(data[:20], 5)          # 20 -> bucket 32: one new
    assert grow.count >= 1
    with rg.no_recompiles("flat.bucket32-steady"):
        idx.search_batch(data[:32], 5)          # same bucket again
        idx.search_batch(data[:9], 5)


def test_flat_int8_path_steady_state():
    idx, data = _flat_index(value_type="Int8", dtype=np.int8)
    idx.search_batch(data[:8], 5)               # warmup (int8 programs)
    with rg.no_recompiles("flat.int8-steady"):
        idx.search_batch(data[8:16], 5)


def _beam_index(n=220, d=16, seed=7):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((8, d)).astype(np.float32) * 4
    data = (centers[rng.integers(0, 8, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, value in [("BKTNumber", "1"), ("BKTKmeansK", "8"),
                        ("TPTNumber", "2"), ("TPTLeafSize", "128"),
                        ("NeighborhoodSize", "8"), ("CEF", "32"),
                        ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"), ("Samples", "64"),
                        ("SearchMode", "beam"), ("MaxCheck", "256")]:
        assert idx.set_parameter(name, value)
    assert idx.build(data) == sp.ErrorCode.Success
    return idx, data


def test_beam_walk_zero_recompiles_after_warmup():
    """The engine beam walk — the serving hot path — must be a fixed set
    of compiled programs once warm (ROADMAP north-star; TPU-KNN's
    peak-FLOP/s condition)."""
    idx, data = _beam_index()
    queries = data[:8] + 0.01
    idx.search_batch(queries, 5)                # warmup compiles the walk
    with rg.no_recompiles("beam.steady") as steady:
        idx.search_batch(queries, 5)
        idx.search_batch(data[16:24] + 0.01, 5)  # same shape, new values
        idx.search_batch(data[:6] + 0.01, 5)     # same query bucket
    assert steady.count == 0


def test_beam_walk_per_budget_compile_is_bounded():
    """A distinct (quantized) MaxCheck is a distinct static T — exactly
    one extra program, and repeats at that budget are free."""
    idx, data = _beam_index()
    queries = data[:8] + 0.01
    idx.search_batch(queries, 5, max_check=256)
    idx.search_batch(queries, 5, max_check=512)   # warm second budget
    with rg.no_recompiles("beam.two-budgets"):
        idx.search_batch(queries, 5, max_check=256)
        idx.search_batch(queries, 5, max_check=512)


def test_guard_records_compile_time_into_trace():
    trace.reset()
    idx, data = _flat_index(seed=3)
    with rg.track_compiles("traced") as log:
        idx.search_batch(data[:8], 5)
    assert log.count >= 1
    report = trace.report()
    key = f"{rg.TRACE_SPAN}[traced]"
    assert key in report
    assert report[key]["count"] == log.count
    assert report[key]["total_s"] == pytest.approx(log.total_s, abs=1e-6)


def test_no_recompiles_raises_with_diagnostic():
    idx, data = _flat_index(seed=5)
    with pytest.raises(rg.RecompileError, match="XLA compilation"):
        with rg.no_recompiles("cold-path"):
            idx.search_batch(data[:8], 5)       # cold: must compile


def test_warmup_then_guard_helper():
    idx, data = _flat_index(seed=9)
    d, ids = rg.warmup_then_guard(idx.search_batch, data[:8], 5,
                                  label="helper", repeats=2)
    assert ids.shape == (8, 5)


# ---- where the persistent compile cache goes (utils.enable_compile_cache):
# ---- placed from outside by JAX_COMPILATION_CACHE_DIR, else one fixed
# ---- directory in the checkout

@pytest.fixture
def fresh_cache_switch(monkeypatch):
    import jax

    from sptag_tpu import utils

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setattr(utils, "_cache_enabled", False)
    yield utils
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_compile_cache_dir_from_environment_is_left_alone(
        fresh_cache_switch, monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "mine"))
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    fresh_cache_switch.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"


def test_compile_cache_dir_defaults_to_the_checkout(fresh_cache_switch,
                                                    monkeypatch):
    import os

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fresh_cache_switch.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jax.config.jax_compilation_cache_dir \
        == os.path.join(repo, ".jax_cache")
    # fixed: no salt, pid or time — a second process computes the same
    assert fresh_cache_switch.COMPILE_CACHE_DIR \
        == os.path.join(repo, ".jax_cache")


def test_suite_turns_the_cache_off_with_jaxs_own_switch():
    """conftest.py exports JAX_ENABLE_COMPILATION_CACHE=false before jax
    is imported, so this process and every child it starts run uncached."""
    import os

    import jax

    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert jax.config.jax_enable_compilation_cache is False
