"""FLAT over int8 rows in SPTAG's integer cosine, through the served path
(PR 34: the configuration `flat_msmarco_i8_cosine`, tiny, on the CPU).

Builder CLI `main()` -> saved folder -> `ServiceContext.from_ini` ->
`SearchExecutor.execute_batch` on `|`-separated text queries, held to the
benchmark's plain reference (benchmark/harness/reference_int8_cosine.py:
numpy, imports nothing of the program): the ids of the exact scan and the
exact integers `16129 - dot`.  The corpus is 128,256 rows wide, so the
1-, 8- and 128-query rungs select in two stages (`select_stages`) and the
32-query rung in one, and it is ingested in twelve blocks of
`NORMALIZE_BLOCK_ELEMENTS`.  Durations are CPU times and are compared
with nothing.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.datasets import clustered_int8
from benchmark.harness import compare, serving
from benchmark.harness import reference_int8_cosine as reference
from benchmark.loadgen import load_by_name
from sptag_tpu.algo import flat
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.serve.service import SearchExecutor, ServiceContext
from sptag_tpu.serve.wire import ResultStatus
from sptag_tpu.utils import metrics, trace

ROWS, DIM, K, SEED = 128_256, 384, 10, 2**31 + 34
TWINS = 32          # queries whose nearest row is stored twice: certain ties
CONFIG = {"algo": "FLAT", "value_type": "Int8", "metric": "Cosine",
          "index_params": {}, "k": K,
          "check": {"rule": "exact_ids_int_cosine", "queries": 128,
                    "limits": {"id_lists_wrong": 0, "invalid_lists": 0,
                               "dist_err_max": 0}}}


def _context(work, data) -> ServiceContext:
    """`data` -> BIN file -> index_builder.main -> folder -> from_ini."""
    folder = os.path.join(work, "index")
    serving.build_index(work, folder, data, CONFIG)
    ini = os.path.join(work, "main.ini")
    with open(ini, "w") as f:
        f.write(f"[QueryConfig]\nDefaultMaxResultNumber={K}\n"
                f"[Index]\nList=main\n[Index_main]\nIndexFolder={folder}\n")
    ctx = ServiceContext.from_ini(ini)
    assert "main" in ctx.indexes
    return ctx


def _ask(ctx, queries):
    """Text queries through `execute_batch` -> ((Q, K) ids, float32
    distances as they came back)."""
    out = SearchExecutor(ctx).execute_batch(
        [serving.query_text("main", K, q) for q in queries])
    assert all(r.status == ResultStatus.Success for r in out)
    return (np.array([r.results[0].ids for r in out], np.int64),
            np.array([r.results[0].dists for r in out], np.float32))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    data, queries = clustered_int8.make(SEED, ROWS, DIM, 128)
    # the nearest row of each of the first queries, stored once more in
    # another group of 128 columns: ranks 0 and 1 tie
    nearest = reference.exact_topk_int8_cosine(data, queries[:TWINS], 1)[0]
    spare = np.setdiff1d(np.arange(ROWS - 4_096, ROWS), nearest)[:TWINS]
    data[spare] = data[nearest[:, 0]]
    ctx = _context(str(tmp_path_factory.mktemp("i8cos")), data)
    truth = reference.exact_topk_int8_cosine(data, queries, K)
    return ctx, data, queries, truth


@pytest.mark.parametrize("q", [1, 8, 32, 128])
def test_served_answers_are_the_exact_integer_scan(served, q):
    ctx, data, queries, (ref_ids, ref_scores) = served
    two_stage = metrics.counter_value("flat.select_two_stage")
    ids, dists = _ask(ctx, queries[:q])
    assert metrics.counter_value("flat.select_two_stage") - two_stage \
        == (q != 32)
    assert compare.invalid_lists(ids, ROWS) == 0
    # every distance is exactly 16129 - dot of the id it came with
    exact = reference.exact_scores(data, queries[:q], ids)
    assert np.array_equal(dists.astype(np.int64), exact)
    assert np.array_equal(dists, np.trunc(dists))
    # nearest first, and rank for rank the exact scan's scores; among
    # equal scores (the twins; rank 10 and the first row left out) the
    # ids may be either's
    assert np.array_equal(exact, ref_scores[:q])
    twins = slice(0, min(q, TWINS))
    assert (ref_scores[twins, 0] == ref_scores[twins, 1]).all()
    assert (ids[:, 0] != ids[:, 1]).all()
    # the benchmark's rule says the same of these answers
    rule = load_by_name("checks", "exact_ids_int_cosine")
    got = rule.check(data, queries[:q], np.arange(q),
                     compare.answers_as_window(ids, dists), CONFIG)
    assert all(n["ok"] for n in got["numbers"]), got


def test_the_fused_route_serves_the_same_answers(served):
    """PR 37: with the Pallas kernels interpreted the 128-query rung takes
    its group minima from the scan itself (`flat.fused_minima`) - the
    served answers are the materialised route's to the last bit, the
    benchmark's rule passes them, and the counters say which route ran."""
    from sptag_tpu.ops import pallas_kernels

    ctx, data, queries, (_, ref_scores) = served
    want_ids, want_d = _ask(ctx, queries)
    assert metrics.counter_value("flat.scan_materialized") == 1
    pallas_kernels.set_interpret(True)
    try:
        ids, dists = _ask(ctx, queries)
        _ask(ctx, queries[:32])             # one stage: stays materialised
    finally:
        pallas_kernels.set_interpret(False)
    assert metrics.counter_value("flat.scan_fused_minima") == 1
    assert metrics.counter_value("flat.scan_materialized") == 2
    assert np.array_equal(ids, want_ids) and np.array_equal(dists, want_d)
    assert np.array_equal(dists.astype(np.int64), ref_scores)
    rule = load_by_name("checks", "exact_ids_int_cosine")
    got = rule.check(data, queries, np.arange(128),
                     compare.answers_as_window(ids, dists), CONFIG)
    assert all(n["ok"] for n in got["numbers"]), got


def test_a_twin_is_answered_lowest_row_first(served):
    """`exact_topk` breaks ties as `lax.top_k` over the whole row would:
    of two equal scores the lower row comes first."""
    ctx, data, queries, _ = served
    ids, dists = _ask(ctx, queries[:8])
    for row_ids, row_d in zip(ids, dists):
        for a in range(K - 1):
            if row_d[a] == row_d[a + 1]:
                assert row_ids[a] < row_ids[a + 1]


def test_the_scan_says_what_it_ran(tmp_path):
    """Counters, gauges and the `index.normalize` span on that path (an
    index of its own: the registries are emptied before every test)."""
    data, queries = clustered_int8.make(SEED + 1, 12_000, DIM, 8)
    ctx = _context(str(tmp_path), data)
    assert trace.report()["index.normalize"]["count"] == 1      # ingest
    assert metrics.gauge_value("flat.rows_resident") == 0       # lazy
    _ask(ctx, queries)
    _ask(ctx, queries[:1])
    assert metrics.counter_value("flat.dot_int8_native") == 2
    assert metrics.counter_value("flat.dot_f32") == 0
    assert metrics.counter_value("flat.dot_int16_split") == 0
    assert metrics.gauge_value("flat.rows_resident") == 12_000
    assert metrics.gauge_value("flat.row_itemsize") == 1
    # once over the corpus, once a query batch
    assert trace.report()["index.normalize"]["count"] == 3


def test_float_rows_count_the_float_contraction():
    import sptag_tpu as sp

    data = np.random.default_rng(3).standard_normal((300, 16)).astype(
        np.float32)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    index.search_batch(data[:3], 5)
    assert metrics.counter_value("flat.dot_f32") == 1
    assert metrics.counter_value("flat.dot_int8_native") == 0
    assert metrics.gauge_value("flat.row_itemsize") == 4
    assert "index.normalize" not in trace.report()              # L2


# ---- the contraction --------------------------------------------------------

#: widest rows whose dot product cannot leave int32: D * 128^2 < 2^31 for
#: int8, D * 255^2 < 2^31 for uint8
WIDEST = {np.int8: (2**31 - 1) // 128**2, np.uint8: (2**31 - 1) // 255**2}


def _extremes(dtype, d):
    info = np.iinfo(dtype)
    lo, hi = np.full(d, info.min, dtype), np.full(d, info.max, dtype)
    mixed = np.where(np.arange(d) % 2 == 0, lo, hi).astype(dtype)
    return np.stack([lo, hi, mixed])


@pytest.mark.parametrize("dtype", [np.int8, np.uint8])
@pytest.mark.parametrize("width", ["384", "widest"])
def test_one_byte_dot_is_the_int64_dot(dtype, width):
    d = DIM if width == "384" else WIDEST[dtype]
    info = np.iinfo(dtype)
    rng = np.random.default_rng(d)
    x = np.concatenate([_extremes(dtype, d), rng.integers(
        info.min, info.max + 1, (61, d)).astype(dtype)])
    q = np.concatenate([_extremes(dtype, d), rng.integers(
        info.min, info.max + 1, (5, d)).astype(dtype)])
    want = q.astype(np.int64) @ x.astype(np.int64).T
    assert np.abs(want).max() < 2**31
    assert np.abs(want).max() >= (2**31 - 1) - 255**2 or width == "384"
    got = np.asarray(jax.jit(dist_ops.pairwise_dot)(jnp.asarray(q),
                                                    jnp.asarray(x)))
    assert got.dtype == np.float32
    # the int32 sums are exact; float32 is their one rounding
    assert np.array_equal(got, want.astype(np.float32))
    assert dist_ops.dot_kind(dtype, d) == "int8_native"


def test_one_byte_operands_reach_the_contraction_as_they_are():
    """No int32 copy of the rows is asked for: the traced contraction
    takes the int8 operands themselves and accumulates in int32."""
    q = jax.ShapeDtypeStruct((8, DIM), jnp.int8)
    x = jax.ShapeDtypeStruct((1024, DIM), jnp.int8)
    text = jax.jit(dist_ops.pairwise_dot).lower(q, x).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 1
    assert "tensor<8x384xi8>, tensor<1024x384xi8>" in dots[0]
    assert "-> tensor<8x1024xi32>" in dots[0]
    # operands of two types are widened, as before
    assert dist_ops.pairwise_dot(
        jnp.ones((2, 4), jnp.int8), jnp.ones((3, 4), jnp.uint8)
    ).tolist() == [[4.0] * 3] * 2


def test_dot_kind_follows_dtype_and_width():
    assert dist_ops.dot_kind(np.int8, 384) == "int8_native"
    assert dist_ops.dot_kind(np.uint8, 128) == "int8_native"
    assert dist_ops.dot_kind(np.int16, 128) == "int16_split"
    assert dist_ops.dot_kind(np.float32, 128) == "f32"


# ---- the normalisation ------------------------------------------------------

def _one_pass(vectors, base):
    """Utils::Normalize over the whole matrix at once, as the program had
    it before it worked in blocks (PR 34): the oracle of the blocked one."""
    f = vectors.astype(np.float64)
    norms = np.sqrt(np.sum(f * f, axis=-1, keepdims=True))
    constant = (1.0 / np.sqrt(vectors.shape[-1])) * base
    scaled = np.where(norms < 1e-6, constant,
                      f / np.maximum(norms, 1e-30) * base)
    return scaled.astype(vectors.dtype)


@pytest.mark.parametrize("dtype,base", [(np.int8, 127), (np.uint8, 255),
                                        (np.int16, 32767),
                                        (np.float32, 1)])
def test_blocked_normalize_is_the_one_pass(dtype, base, monkeypatch):
    """Two workers' spans of two blocks and a part of one each, zero rows
    on both sides of a block edge and of the span edge: bit for bit what
    one pass over the whole gives."""
    monkeypatch.setattr(dist_ops, "host_cores", lambda: 2)
    block_rows = dist_ops.NORMALIZE_BLOCK_ELEMENTS // DIM
    span = 2 * block_rows + 3
    rows = 2 * span
    rng = np.random.default_rng(base)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        x = rng.integers(info.min, info.max + 1, (rows, DIM)).astype(dtype)
    else:
        x = rng.standard_normal((rows, DIM)).astype(dtype)
    for zero in (0, block_rows - 1, block_rows, span - 1, span, rows - 1):
        x[zero] = 0
    assert x.size > dist_ops.NORMALIZE_BLOCK_ELEMENTS
    got = dist_ops.normalize(x, base)
    want = _one_pass(x, base)
    assert got.dtype == want.dtype == dtype and got.shape == x.shape
    assert np.array_equal(got, want)
    # a zero row becomes the constant row base / sqrt(D), truncated
    assert np.array_equal(got[block_rows], np.full(
        DIM, base / np.sqrt(DIM)).astype(dtype))
    if dtype == np.int8:
        # and both are the plain reference's Utils::Normalize
        assert np.array_equal(got, reference.normalize_int8(x))


def test_normalize_of_one_row_of_few_and_of_none():
    x = np.arange(-64, 64, dtype=np.int8).reshape(1, 128)
    assert np.array_equal(dist_ops.normalize(x[0], 127),
                          dist_ops.normalize(x, 127)[0])
    assert np.array_equal(dist_ops.normalize(x, 127),
                          reference.normalize_int8(x))
    f = np.random.default_rng(7).standard_normal((5, 16)).astype(np.float32)
    f[2] = 0
    assert np.array_equal(dist_ops.normalize(f, 1), _one_pass(f, 1))
    assert dist_ops.normalize(x[:0], 127).shape == (0, 128)


# ---- a lower precision planted in the scan ---------------------------------

def _scan(data, queries, dot=None):
    """`scan_topk` under a jit of its own (never the cached index
    programs), its contraction replaced by `dot` -> (ids, distances)."""
    original = dist_ops.pairwise_dot
    if dot is not None:
        dist_ops.pairwise_dot = dot
    try:
        x = jnp.asarray(dist_ops.normalize(data, 127))
        q = jnp.asarray(dist_ops.normalize(queries, 127))
        n = x.shape[0]
        dists, ids = jax.jit(functools.partial(
            flat.scan_topk, k=K, metric=int(DistCalcMethod.Cosine),
            base=127))(x, dist_ops.row_sqnorms(x), jnp.zeros(n, bool), q)
    finally:
        dist_ops.pairwise_dot = original
    return np.asarray(ids), np.asarray(dists)


def _bf16_dot(q, x):
    """bfloat16 operands, a bfloat16 result.  The rounding is asked for
    by name: XLA may keep the float32 accumulator of a bfloat16 dot that
    is widened straight away (`xla_allow_excess_precision`), and int8
    values are exact in bfloat16, so without it nothing is lost."""
    dot = jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                  preferred_element_type=jnp.float32)
    return jax.lax.reduce_precision(dot, exponent_bits=8, mantissa_bits=7)


@pytest.mark.parametrize("dot,correct", [(None, True), (_bf16_dot, False)])
def test_a_lower_precision_in_the_scan_fails_the_comparison(dot, correct):
    """The benchmark's rule over `scan_topk`'s own answers: sound with
    the program's contraction, not correct by `dist_err_max` with a
    bfloat16 one in its place (16129 lies where bfloat16 steps by 64)."""
    data, queries = clustered_int8.make(SEED + 2, 6_000, DIM, 32)
    ids, dists = _scan(data, queries, dot)
    rule = load_by_name("checks", "exact_ids_int_cosine")
    got = rule.check(data, queries, np.arange(32),
                     compare.answers_as_window(ids, dists),
                     {**CONFIG, "check": {**CONFIG["check"], "queries": 32}})
    bad = {n["name"] for n in got["numbers"] if not n["ok"]}
    assert (not bad) == correct, got
    if not correct:
        assert "dist_err_max" in bad
        value = {n["name"]: n["value"] for n in got["numbers"]}
        assert 1 <= value["dist_err_max"] <= 64


def test_block_workers_follow_the_process_affinity(monkeypatch):
    """The pool of block workers is as wide as the cores this process may
    run on, not as the machine (the chip's host gives the process 13 of
    its cores, and every worker holds its own scratch: PR 34)."""
    import concurrent.futures
    import os

    from sptag_tpu import utils

    mine = os.sched_getaffinity(0)
    assert utils.host_cores() == len(mine)
    widths = []
    pool_type = concurrent.futures.ThreadPoolExecutor

    def recording(max_workers=None, *a, **kw):
        widths.append(max_workers)
        return pool_type(max_workers, *a, **kw)

    monkeypatch.setattr(concurrent.futures, "ThreadPoolExecutor", recording)
    monkeypatch.setattr(os, "cpu_count", lambda: 4096)
    x = np.ones((4 * dist_ops.NORMALIZE_BLOCK_ELEMENTS // DIM, DIM), np.int8)
    few = set(list(mine)[:2])
    os.sched_setaffinity(0, few)
    try:
        dist_ops.normalize(x, 127)
        reference.normalize_int8(x)
        clustered_int8.make(SEED, 5 * clustered_int8.BLOCK_ROWS, DIM, 4)
    finally:
        os.sched_setaffinity(0, mine)
    assert widths == [len(few)] * 3
