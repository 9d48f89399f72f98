"""The int8 FLAT scan that never writes its score matrix (PR 37).

On one-byte rows in the integer cosine `scan_topk`'s group minima come out
of the pass that computes the distances (`pallas_kernels.scan_group_minima`,
here in interpret mode on the CPU) and the chosen groups are scored again:
held, BIT FOR BIT, to the minima and to `lax.top_k` of the materialised
scores.  Which scans take the route is a pure rule (`flat.fused_minima`)
with two counters.  Nothing here says anything about speed.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.algo import flat
from sptag_tpu.core.index import MAX_DIST
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops import pallas_kernels
from sptag_tpu.utils import metrics

COS, L2 = int(DistCalcMethod.Cosine), int(DistCalcMethod.L2)
BASES = {"int8": 127, "uint8": 255}


@pytest.fixture(autouse=True)
def _interpret_mode():
    pallas_kernels.set_interpret(True)
    yield
    pallas_kernels.set_interpret(False)


def _rows(rng, dtype, shape):
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max + 1, shape).astype(dtype)


def _materialised(data, invalid, queries, base):
    """The (Q, N) masked scores as the unfused route builds them."""
    d = dist_ops.pairwise_cosine(jnp.asarray(queries), jnp.asarray(data),
                                 base)
    return jnp.where(jnp.asarray(invalid)[None, :], jnp.float32(MAX_DIST), d)


# ---- the kernel's minima ---------------------------------------------------

@pytest.mark.parametrize("dtype,dim,groups", [
    ("int8", 384, 150),     # tiles of 64 groups: two whole, one ragged
    ("uint8", 384, 150),
    ("int8", 128, 300),     # tiles of 256 groups: one whole, one ragged
    ("uint8", 128, 70),     # less than one tile
    ("int8", 256, 128),     # whole tiles only
])
def test_minima_are_the_materialised_scores_group_minima(dtype, dim, groups):
    """Masked rows, a wholly masked group, a ragged last tile, all-zero
    pad queries: `grouped.min(axis=1)` of the masked scores, bit for
    bit."""
    rng = np.random.default_rng(groups)
    n, q, base = groups * 128, 128, BASES[dtype]
    tile, _ = pallas_kernels._scan_tiling(dim, q)
    assert (groups % tile != 0) == (groups != 128)
    data, queries = _rows(rng, dtype, (n, dim)), _rows(rng, dtype, (q, dim))
    queries[-5:] = 0
    invalid = np.zeros(n, bool)
    invalid[-33:] = True                              # the pad rows
    invalid[rng.integers(0, n, 200)] = True           # deletes
    invalid[7 * 128:8 * 128] = True                   # a whole group
    got = pallas_kernels.scan_group_minima(
        jnp.asarray(data), jnp.asarray(invalid), jnp.asarray(queries),
        base=base, interpret=True)
    d = _materialised(data, invalid, queries, base)
    want = np.asarray(d.T.reshape(groups, 128, q).min(axis=1))
    assert got.dtype == jnp.float32 and got.shape == (groups, q)
    assert np.array_equal(np.asarray(got), want)
    assert (want[7] == MAX_DIST).all() and (want[6] < MAX_DIST).all()


def test_a_rounded_score_keeps_the_largest_dot_the_smallest_distance():
    """uint8 dots pass 2^24 at 384 dimensions, where float32 rounds: the
    kernel converts the group's LARGEST int32 dot, the materialised route
    every dot; both round monotonically, so the minima still agree."""
    rng = np.random.default_rng(3)
    data = rng.integers(200, 256, (128 * 8, 384)).astype(np.uint8)
    queries = rng.integers(200, 256, (128, 384)).astype(np.uint8)
    dots = queries.astype(np.int64) @ data.astype(np.int64).T
    assert dots.min() > 2 ** 24
    invalid = np.zeros(len(data), bool)
    got = pallas_kernels.scan_group_minima(
        jnp.asarray(data), jnp.asarray(invalid), jnp.asarray(queries),
        base=255, interpret=True)
    d = _materialised(data, invalid, queries, 255)
    assert np.array_equal(np.asarray(got),
                          np.asarray(d.T.reshape(8, 128, 128).min(axis=1)))


# ---- the whole scan --------------------------------------------------------

def _tied(rng, dtype, n, dim, q):
    """Rows and queries built to tie: every query's nearest row stored
    again in ANOTHER group and twice more inside its own, a block of
    identical rows across a group boundary, and all-zero pad queries
    (every row ties)."""
    data = _rows(rng, dtype, (n, dim))
    queries = _rows(rng, dtype, (q, dim))
    queries[-7:] = 0
    base = data[rng.integers(0, n, q)]
    queries[:q - 7] = base[:q - 7]
    for i in range(0, q - 7, 3):
        home = int(rng.integers(2, n // 128 - 2)) * 128
        data[home + 5] = data[home + 77] = base[i]          # inside one
        data[home + 128 * 2 + 9] = base[i]                  # across groups
    data[128 * 40 - 6:128 * 40 + 6] = data[17]              # over a boundary
    return data, queries


@pytest.mark.parametrize("dtype,dim,k,groups,q", [
    ("int8", 128, 10, 1_001, 128), ("uint8", 128, 10, 1_001, 128),
    ("int8", 384, 3, 320, 128), ("int8", 128, 1, 101, 256),
    ("uint8", 256, 2, 201, 128),
])
def test_fused_scan_is_lax_top_k_of_the_scores_bit_for_bit(dtype, dim, k,
                                                           groups, q):
    rng = np.random.default_rng(k * groups)
    n, base = groups * 128, BASES[dtype]
    data, queries = _tied(rng, dtype, n, dim, q)
    invalid = np.zeros(n, bool)
    invalid[-33:] = True
    invalid[rng.integers(0, n, n // 50)] = True
    invalid[11 * 128:12 * 128] = True
    assert flat.fused_minima(data.dtype, q, n, dim, k, COS, "interpret")
    x, dead, qs = jnp.asarray(data), jnp.asarray(invalid), jnp.asarray(queries)
    dists, ids = jax.jit(functools.partial(
        flat.scan_topk, k=k, metric=COS, base=base, fused=True,
        interpret=True))(x, jnp.zeros(n, jnp.float32), dead, qs)
    neg, cols = jax.lax.top_k(-_materialised(data, invalid, queries, base), k)
    assert np.array_equal(np.asarray(dists), np.asarray(-neg))
    assert np.array_equal(np.asarray(ids), np.asarray(cols))
    # the ties were there to be broken: equal neighbours, lowest row first
    d, i = np.asarray(dists), np.asarray(ids)
    if k > 1:
        tied = d[:, :-1] == d[:, 1:]
        assert tied.sum() >= q // 4
        assert (i[:, :-1] < i[:, 1:])[tied].all()
    assert (i[-7:] == np.arange(k)).all()            # pad queries: rows 0..


@pytest.mark.parametrize("dtype,live", [("int8", 0), ("int8", 1),
                                        ("uint8", 1), ("uint8", 3)])
def test_fused_scan_answers_minus_one_where_only_masked_rows_are_left(dtype,
                                                                      live):
    """Fewer valid rows than k: the valid ones first, then MAX_DIST with
    id -1, as the materialised route answers."""
    rng = np.random.default_rng(5 + live)
    n, dim, k, q, base = 201 * 128, 128, 2, 128, BASES[dtype]
    data, queries = _rows(rng, dtype, (n, dim)), _rows(rng, dtype, (q, dim))
    invalid = np.ones(n, bool)
    invalid[rng.choice(n, live, replace=False)] = False
    args = (jnp.asarray(data), jnp.zeros(n, jnp.float32),
            jnp.asarray(invalid), jnp.asarray(queries))
    scan = functools.partial(flat.scan_topk, k=k, metric=COS, base=base)
    dists, ids = jax.jit(functools.partial(
        scan, fused=True, interpret=True))(*args)
    want_d, want_i = jax.jit(scan)(*args)
    assert np.array_equal(np.asarray(dists), np.asarray(want_d))
    assert np.array_equal(np.asarray(ids), np.asarray(want_i))
    assert ((np.asarray(ids) == -1).sum(axis=1) == k - min(live, k)).all()


# ---- the rule and its counters ---------------------------------------------

N_CELL = 8_841_856      # flat_msmarco_i8's row slots


@pytest.mark.parametrize("dtype,q,n,dim,k,metric,platform,fused", [
    (np.int8, 128, N_CELL, 384, 10, COS, "tpu", True),      # the cell
    (np.int8, 512, N_CELL, 384, 10, COS, "tpu", True),      # the 512 rung
    (np.uint8, 128, N_CELL, 384, 10, COS, "tpu", True),
    (np.int8, 128, N_CELL, 384, 10, COS, "interpret", True),
    (np.int8, 128, N_CELL, 384, 10, COS, "cpu", False),
    (np.int8, 128, N_CELL, 384, 10, COS, "gpu", False),
    (np.int8, 128, N_CELL, 384, 10, L2, "tpu", False),      # needs the norms
    (np.int8, 32, N_CELL, 384, 10, COS, "tpu", False),      # one N-wide TopK
    (np.int8, 8, N_CELL, 384, 10, COS, "tpu", False),       # XLA fuses these
    (np.int8, 1, N_CELL, 384, 10, COS, "tpu", False),
    (np.int8, 1024, N_CELL, 384, 10, COS, "tpu", False),    # single stage
    (np.int8, 128, 100_096, 384, 10, COS, "tpu", False),    # narrow: 1 stage
    (np.int8, 128, N_CELL + 5, 384, 10, COS, "tpu", False),  # a tail
    (np.int8, 128, N_CELL, 100, 10, COS, "tpu", False),     # ragged lanes
    (np.int16, 128, N_CELL, 384, 10, COS, "tpu", False),    # the byte split
    (np.float32, 128, 1_000_064, 128, 10, L2, "tpu", False),    # flat_1m
    (np.float32, 128, 2_500_096, 96, 10, L2, "tpu", False),     # deep-10M
    (np.float32, 128, 1_000_064, 128, 10, COS, "tpu", False),
])
def test_the_route_is_a_rule_of_what_can_be_seen(dtype, q, n, dim, k, metric,
                                                 platform, fused):
    assert flat.fused_minima(np.dtype(dtype), q, n, dim, k, metric,
                             platform) == fused


def test_the_platform_is_the_device_s_or_interpret():
    assert pallas_kernels.platform() == "interpret"
    pallas_kernels.set_interpret(False)
    assert pallas_kernels.platform() == jax.devices()[0].platform == "cpu"


def test_count_route_counts_by_its_two_literal_names():
    flat.count_route(True)
    flat.count_route(False)
    flat.count_route(False)
    assert metrics.counter_value("flat.scan_fused_minima") == 1
    assert metrics.counter_value("flat.scan_materialized") == 2


@pytest.fixture(scope="module")
def int8_index():
    rng = np.random.default_rng(37)
    data = _rows(rng, np.int8, (13_000, 128))
    index = sp.create_instance("FLAT", "Int8")
    index.set_parameter("DistCalcMethod", "Cosine")
    index.build(data)
    queries = data[rng.integers(0, len(data), 128)]
    return index, queries


@pytest.mark.parametrize("q,k,interpret,fused", [
    (128, 1, True, True), (128, 1, False, False), (32, 1, True, False),
    (128, 10, True, False),
])
def test_a_dispatched_scan_is_counted_by_its_route(int8_index, q, k,
                                                   interpret, fused):
    """The index asks the rule before the call and says what it asked for;
    both routes answer alike, and the quality monitor's oracle
    (`exact_search_batch`) inherits the route without counting."""
    index, queries = int8_index
    pallas_kernels.set_interpret(False)
    want = index.search_batch(queries[:q], k)
    pallas_kernels.set_interpret(interpret)
    before = {name: metrics.counter_value(name) for name in
              ("flat.scan_fused_minima", "flat.scan_materialized")}
    got = index.search_batch(queries[:q], k)
    assert (metrics.counter_value("flat.scan_fused_minima")
            - before["flat.scan_fused_minima"]) == fused
    assert (metrics.counter_value("flat.scan_materialized")
            - before["flat.scan_materialized"]) == (not fused)
    oracle = index.exact_search_batch(queries[:q], k)
    assert metrics.counter_value("flat.scan_fused_minima") \
        - before["flat.scan_fused_minima"] == fused
    for dists, ids in (got, oracle):
        assert np.array_equal(dists, want[0])
        assert np.array_equal(ids, want[1])


def test_approximate_selections_keep_the_materialised_scores(int8_index):
    """`ApproxTopK` / `BinnedTopK` read `d`: the fused route is the exact
    select's alone, and an approximate scan counts on neither side."""
    index, queries = int8_index
    index.set_parameter("ApproxTopK", "1")
    try:
        index.search_batch(queries, 1)
    finally:
        index.set_parameter("ApproxTopK", "0")
    assert metrics.counter_value("flat.scan_fused_minima") == 0
    assert metrics.counter_value("flat.scan_materialized") == 0


# ---- float rows stay on today's program ------------------------------------

@pytest.mark.parametrize("q", [1, 128, 512])
def test_float_rows_lower_to_no_pallas_kernel_at_flat_1m_s_shapes(q):
    """`flat_1m`'s programs hold no `tpu_custom_call`, interpret mode or
    not: the rule keeps float rows off the route before anything is
    traced."""
    n, dim = 1_000_064, 128
    fused = flat.fused_minima(np.dtype(np.float32), q, n, dim, 10, L2,
                              pallas_kernels.platform())
    assert not fused
    S = jax.ShapeDtypeStruct
    text = flat._flat_search_kernel.lower(
        S((n, dim), jnp.float32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((q, dim), jnp.float32), k=10, metric=L2, base=1,
        fused=fused).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text
    assert "stablehlo.dot_general" in text


# ---- what the cost ledger bills --------------------------------------------

def test_the_ledger_bills_the_fused_scan_no_score_matrix():
    from sptag_tpu.utils import costmodel

    shape = dict(Q=128, N=N_CELL, D=384, k=10, itemsize=1)
    rows, scores = N_CELL * 384, 128 * N_CELL * 4
    before = costmodel.estimate("flat.scan", **shape)
    fused = costmodel.estimate("flat.scan", fused=True, **shape)
    kernel = costmodel.estimate("pallas.scan_group_minima", Q=128, N=N_CELL,
                                D=384)
    assert before.hbm_bytes > rows + 4 * scores
    assert rows < kernel.hbm_bytes < fused.hbm_bytes < 1.15 * rows
    assert fused.hbm_bytes < rows + scores / 8
    assert fused.flops >= kernel.flops >= 2.0 * 128 * N_CELL * 384
    sel_f, sel_b = flat._two_stage_select_cost(128, N_CELL, 10)
    fsel_f, fsel_b = flat._two_stage_select_cost(128, N_CELL, 10, fused=True,
                                                 D=384, itemsize=1)
    assert fsel_b < sel_b / 20 and fsel_f < sel_f
