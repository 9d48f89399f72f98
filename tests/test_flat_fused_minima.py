"""The FLAT scans that never write their score matrix (PRs 37 and 41).

`scan_topk`'s group minima come out of the pass that computes the
distances (`pallas_kernels.scan_group_minima`, here in interpret mode on
the CPU) and the chosen groups are scored again.  One-byte rows in the
integer cosine (PR 37): held, BIT FOR BIT, to the minima and to
`lax.top_k` of the materialised scores.  Float32 rows under L2 (PR 41):
the kernel's minima are a FILTER within a stated `eps` of the re-score's
numbers, the answer is `lax.top_k` of the re-score's own scores over all
rows bit for bit WHERE THE SELECT PROVES IT, and the materialised
program's answer where it does not.  Which scans take the route is a pure
rule (`flat.fused_minima`) with three counters.  Nothing here says
anything about speed.
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


# ---- float32 rows under L2: the kernel as a filter (PR 41) -----------------

def _float_rows(rng, n, dim, q):
    """Clustered float rows with queries near some of them."""
    data = rng.standard_normal((n, dim)).astype(np.float32)
    queries = (data[rng.integers(0, n, q)]
               + 0.1 * rng.standard_normal((q, dim))).astype(np.float32)
    return data, queries


def _mask(rng, n):
    invalid = np.zeros(n, bool)
    invalid[-33:] = True                              # the pad rows
    invalid[rng.integers(0, n, n // 50)] = True       # deletes
    invalid[7 * 128:8 * 128] = True                   # a whole group
    return invalid


def _eps(data, queries):
    return np.asarray(pallas_kernels.l2_minima_eps(
        data.shape[1], dist_ops.row_sqnorms(jnp.asarray(queries)),
        dist_ops.row_sqnorms(jnp.asarray(data))))


@pytest.mark.parametrize("dim,groups", [
    (100, 150),     # column-major block: tiles of 64 groups, one ragged
    (96, 70),       # less than one tile
    (128, 150),     # row-major block
    (128, 128),     # whole tiles only
    (256, 40),
])
def test_float_minima_are_the_materialised_group_minima_within_eps(dim,
                                                                   groups):
    """Masked rows, a wholly masked group, a ragged last tile, zero pad
    queries: `grouped.min(axis=1)` of the masked `pairwise_l2` scores to
    within `l2_minima_eps`, and MAX_DIST exactly where a group holds no
    valid row."""
    rng = np.random.default_rng(groups + dim)
    n, q = groups * 128, 128
    data, queries = _float_rows(rng, n, dim, q)
    queries[-5:] = 0
    invalid = _mask(rng, n)
    x, dead = jnp.asarray(data), jnp.asarray(invalid)
    sqnorm = dist_ops.row_sqnorms(x)
    got = np.asarray(pallas_kernels.scan_group_minima(
        x, dead, jnp.asarray(queries), base=1, interpret=True,
        sqnorm=sqnorm))
    d = jnp.where(dead[None, :], jnp.float32(MAX_DIST),
                  dist_ops.pairwise_l2(jnp.asarray(queries), x, sqnorm))
    want = np.asarray(d.T.reshape(groups, 128, q).min(axis=1))
    assert got.dtype == np.float32 and got.shape == (groups, q)
    assert (got[7] == MAX_DIST).all() and (want[7] == MAX_DIST).all()
    live = want < MAX_DIST
    assert ((got < MAX_DIST) == live).all()
    eps = _eps(data, queries)
    off = np.abs(got - want)[live] / np.broadcast_to(eps, got.shape)[live]
    assert off.max() < 0.25         # the bound is a worst case: room to spare
    assert (got >= 0).all()


@pytest.fixture
def plain_rescore(monkeypatch):
    """XLA:CPU's `qd,qcd->qc` contraction is not the same arithmetic at
    every position of a batch (a row scored at another place may differ
    in the last bit), so "the re-score's score of a row" is no single
    number here.  Under this fixture it is: the same formula with the
    dot as a multiply and a reduce a row.  The chip's re-score is a
    multiply-reduce fusion already."""
    def l2(q, cand, metric, base, cand_sqnorm=None):
        assert int(metric) == L2
        dot = jnp.sum(q[:, None, :] * cand, axis=-1)
        return jnp.maximum(jnp.sum(q * q, axis=-1)[:, None] + cand_sqnorm
                           - 2.0 * dot, 0.0)

    monkeypatch.setattr(dist_ops, "batched_gathered_distance", l2)


def _rescore_of_all_rows(data, sqnorm, invalid, queries, kc):
    """The re-score's OWN scores over every row of the block: its
    `columns` called on all groups, `kc` at a time, as the scan calls it
    on the chosen ones."""
    groups, q = data.shape[0] // 128, queries.shape[0]
    columns = jax.jit(flat._rescored_columns(
        data, sqnorm, invalid, queries, kc, L2, 1, True))
    every = np.arange(-(-groups // kc) * kc) % groups
    parts = [np.asarray(columns(jnp.broadcast_to(
        jnp.asarray(every[at:at + kc], jnp.int32), (q, kc))))
        for at in range(0, len(every), kc)]
    return np.concatenate(parts, axis=1)[:, :groups * 128]


def _float_tied(rng, n, dim, q):
    """Float rows built to tie, holding small whole numbers: every
    product and every partial sum is exact in float32 in any order, so
    each contraction here (the kernel's, the re-score's, the materialised
    scores') gives ONE number a row whatever its schedule, equal
    distances are equal to the bit, and they are many.  Every third
    query's nearest row is stored again in another group and twice more
    inside its own; a block of identical rows lies over a group boundary;
    the last queries are the zero rows a batch is padded with."""
    data = rng.integers(-60, 61, (n, dim)).astype(np.float32)
    queries = (data[rng.integers(0, n, q)]
               + rng.integers(-2, 3, (q, dim))).astype(np.float32)
    queries[-7:] = 0
    for i in range(0, q - 7, 3):
        home = int(rng.integers(2, n // 128 - 2)) * 128
        data[home + 5] = data[home + 77] = queries[i]           # inside one
        data[home + 128 * 2 + 9] = queries[i]                   # across
    data[128 * 40 - 6:128 * 40 + 6] = queries[1]                # a boundary
    return data, queries


@pytest.mark.parametrize("dim,k,groups,q", [
    (100, 1, 101, 128), (128, 1, 101, 128), (96, 3, 320, 128),
    (128, 2, 201, 256),
])
def test_float_fused_scan_is_lax_top_k_of_the_rescore_s_scores(dim, k, groups,
                                                               q):
    """Proved, the answer is `lax.top_k` of the re-score's own scores
    over ALL rows bit for bit: values, ids, lowest row first among
    equals, on inputs that tie across groups and inside one."""
    rng = np.random.default_rng(k * groups + dim)
    n = groups * 128
    data, queries = _float_tied(rng, n, dim, q)
    invalid = _mask(rng, n)
    assert flat.fused_minima(data.dtype, q, n, dim, k, L2, "interpret")
    x, dead, qs = jnp.asarray(data), jnp.asarray(invalid), jnp.asarray(queries)
    sqnorm = dist_ops.row_sqnorms(x)
    dists, ids, unproved = jax.jit(functools.partial(
        flat.scan_topk, k=k, metric=L2, base=1, fused=True,
        interpret=True))(x, sqnorm, dead, qs)
    assert not bool(unproved)
    scores = _rescore_of_all_rows(x, sqnorm, dead, qs,
                                  k + flat._SPARE_GROUPS)
    neg, cols = jax.lax.top_k(-jnp.asarray(scores), k)
    assert np.array_equal(np.asarray(ids), np.asarray(cols))
    d, i = np.asarray(dists), np.asarray(ids)
    assert np.array_equal(d, np.asarray(-neg))
    if k > 1:
        tied = d[:, :-1] == d[:, 1:]
        assert tied.sum() >= q // 8
        assert (i[:, :-1] < i[:, 1:])[tied].all()
    # the materialised program's answer too (on these rows its other
    # contraction gives the same numbers)
    want_d, want_i = jax.jit(functools.partial(
        flat.scan_topk, k=k, metric=L2, base=1))(x, sqnorm, dead, qs)
    assert np.array_equal(d, np.asarray(want_d))
    assert np.array_equal(i, np.asarray(want_i))


@pytest.mark.parametrize("live", [0, 1, 3])
def test_float_fused_scan_answers_minus_one_where_only_masked_rows_are_left(
        live):
    """Fewer valid rows than k: nothing can be proved about a k-th
    answer that does not exist, so the run answers as the materialised
    program does - the valid rows first, then MAX_DIST with id -1."""
    rng = np.random.default_rng(50 + live)
    n, dim, k, q = 201 * 128, 100, 2, 128
    data, queries = _float_rows(rng, n, dim, q)
    invalid = np.ones(n, bool)
    invalid[rng.choice(n, live, replace=False)] = False
    x = jnp.asarray(data)
    args = (x, dist_ops.row_sqnorms(x), jnp.asarray(invalid),
            jnp.asarray(queries))
    scan = functools.partial(flat.scan_topk, k=k, metric=L2, base=1)
    dists, ids, unproved = jax.jit(functools.partial(
        scan, fused=True, interpret=True))(*args)
    want_d, want_i = jax.jit(scan)(*args)
    assert bool(unproved) == (live < k)
    assert np.array_equal(np.asarray(ids), np.asarray(want_i))
    assert np.allclose(np.asarray(dists), np.asarray(want_d), rtol=1e-5)
    assert ((np.asarray(ids) == -1).sum(axis=1) == k - min(live, k)).all()


def _near_tie(rng, dim, margin_eps, groups=101, q=128):
    """k = 1: query 0's nearest row at squared distance 1 and, in
    k' = 1 + _SPARE_GROUPS OTHER groups, a row each at 1 + `margin_eps` x
    eps: the (k' + 1)-th smallest minimum clears the answer by that many
    eps.  Every other row is far."""
    n = groups * 128
    data, queries = _float_rows(rng, n, dim, q)
    queries[0] = rng.standard_normal(dim).astype(np.float32)
    eps = _eps(data, queries)[0]
    e = np.zeros(dim, np.float32)
    e[0] = 1.0
    data[3 * 128 + 5] = queries[0] + e
    for j in range(1 + flat._SPARE_GROUPS):
        data[(10 + 2 * j) * 128 + j] = queries[0] + e * np.float32(
            np.sqrt(1.0 + margin_eps * eps))
    return data, queries, float(eps)


def _scan_with_kernel_off_by(monkeypatch, data, queries, shift):
    """`scan_topk` on the proved route with a kernel whose minima are
    off by `shift(minima)`; a jit of its own, so no cached program keeps
    the altered kernel."""
    real = pallas_kernels.scan_group_minima

    def altered(*args, **kwargs):
        minima = real(*args, **kwargs)
        return jnp.where(minima < MAX_DIST, minima + shift(minima), minima)

    monkeypatch.setattr(pallas_kernels, "scan_group_minima", altered)
    x = jnp.asarray(data)
    return jax.jit(functools.partial(
        flat.scan_topk, k=1, metric=L2, base=1, fused=True,
        interpret=True))(x, dist_ops.row_sqnorms(x),
                         jnp.zeros(len(data), bool), jnp.asarray(queries))


@pytest.mark.parametrize("dim", [96, 128])
def test_a_kernel_off_by_less_than_eps_changes_no_answer(
        plain_rescore, monkeypatch, dim):
    """Whatever the kernel's minima are within `eps` of the re-score's
    numbers, the answers are the re-score's and proved: a near-tie three
    eps wide holds against minima pushed 0.9 eps either way."""
    rng = np.random.default_rng(dim)
    data, queries, eps = _near_tie(rng, dim, margin_eps=3.0)
    x = jnp.asarray(data)
    want = jax.jit(functools.partial(
        flat.scan_topk, k=1, metric=L2, base=1, fused=True,
        interpret=True))(x, dist_ops.row_sqnorms(x),
                         jnp.zeros(len(data), bool), jnp.asarray(queries))
    assert not bool(want[2]) and int(want[1][0, 0]) == 3 * 128 + 5
    sign = jnp.asarray(rng.choice([-0.9, 0.9], (len(data) // 128, 1)),
                       jnp.float32)
    got = _scan_with_kernel_off_by(monkeypatch, data, queries,
                                   lambda m: sign * jnp.float32(eps))
    assert not bool(got[2])
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("dim", [100, 128])
def test_past_the_margin_nothing_is_claimed_and_the_scores_answer(monkeypatch,
                                                                  dim):
    """The same near-tie under a kernel that reads 2.5 eps low (past its
    bound): the select cannot prove query 0, says so, and the run's
    answers are the materialised program's."""
    rng = np.random.default_rng(dim)
    data, queries, eps = _near_tie(rng, dim, margin_eps=3.0)
    x = jnp.asarray(data)
    want = jax.jit(functools.partial(flat.scan_topk, k=1, metric=L2, base=1))(
        x, dist_ops.row_sqnorms(x), jnp.zeros(len(data), bool),
        jnp.asarray(queries))
    got = _scan_with_kernel_off_by(monkeypatch, data, queries,
                                   lambda m: jnp.float32(-2.5 * eps))
    assert bool(got[2])
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


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
    (np.float32, 128, 1_000_064, 128, 10, L2, "tpu", True),     # flat_1m
    (np.float32, 128, 2_500_096, 96, 10, L2, "tpu", True),      # deep-10M
    (np.float32, 128, 5_312_640, 100, 10, L2, "tpu", True),     # the living
    (np.float32, 128, 5_312_640, 100, 32, L2, "tpu", True),     # its deletes
    (np.float32, 512, 1_000_064, 128, 10, L2, "tpu", True),
    (np.float32, 128, 1_000_064, 128, 10, L2, "interpret", True),
    (np.float32, 128, 1_000_064, 128, 10, L2, "cpu", False),
    (np.float32, 32, 5_312_640, 100, 10, L2, "tpu", False),     # the rungs
    (np.float32, 8, 5_312_640, 100, 10, L2, "tpu", False),      # below keep
    (np.float32, 1, 5_312_640, 100, 10, L2, "tpu", False),      # XLA's scan
    (np.float32, 128, 100_096, 128, 10, L2, "tpu", False),      # BKT's oracle
    (np.float32, 128, 1_000_064 + 5, 128, 10, L2, "tpu", False),    # a tail
    (np.float32, 128, 1_000_064, 200, 10, L2, "tpu", False),    # layout unseen
    (np.float32, 128, 1_000_064, 256, 10, L2, "tpu", True),
    (np.float32, 128, 1_000_064, 128, 10, COS, "tpu", False),   # no cell
    (np.float16, 128, 1_000_064, 128, 10, L2, "tpu", False),
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


@pytest.mark.parametrize("flag,counted", [(True, 1), (False, 0)])
def test_count_unproved_counts_the_program_s_flag(flag, counted):
    flat.count_unproved(jnp.asarray(flag))
    assert metrics.counter_value("flat.scan_margin_unproved") == counted


@pytest.fixture(scope="module")
def float_index():
    """13,000 x 100 float rows (column-major on a chip), query 0's
    nearest row stored in nine groups: more copies than the proved
    select has spare groups at k = 1."""
    rng = np.random.default_rng(41)
    data, queries = _float_rows(rng, 13_000, 100, 128)
    for g in range(9):
        data[(5 + 7 * g) * 128 + g] = data[40]
    queries[0] = data[40]
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    index.build(data)
    return index, queries


@pytest.mark.parametrize("first,unproved", [(1, 0), (0, 1)])
def test_an_unproved_run_is_counted_and_answers_as_the_scores_do(
        float_index, first, unproved):
    """Through the index: a 128-rung float scan takes the route and is
    counted; with query 0 in the batch (ten equal nearest rows in ten
    groups: no margin) the run is unproved, counted
    `flat.scan_margin_unproved`, and answers what the materialised
    program answers; without it, proved, the same ids."""
    index, queries = float_index
    batch = np.concatenate([queries[first:], queries[1:1 + first]])
    pallas_kernels.set_interpret(False)
    want = index.search_batch(batch, 1)
    pallas_kernels.set_interpret(True)
    names = ("flat.scan_fused_minima", "flat.scan_materialized",
             "flat.scan_margin_unproved")
    before = {name: metrics.counter_value(name) for name in names}
    got = index.search_batch(batch, 1)
    moved = {name: metrics.counter_value(name) - before[name]
             for name in names}
    assert moved == {"flat.scan_fused_minima": 1,
                     "flat.scan_materialized": 0,
                     "flat.scan_margin_unproved": unproved}
    assert np.array_equal(got[1], want[1])
    if unproved:
        assert np.array_equal(got[0], want[0])      # the scores' own
        assert got[1][0, 0] == 40                   # lowest row of the ten
    oracle = index.exact_search_batch(batch, 1)     # counts on no side
    assert np.array_equal(oracle[1], want[1])
    assert {name: metrics.counter_value(name) - before[name]
            for name in names} == moved


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


# ---- float rows: the route by rung ------------------------------------------

def _float_lowering(q, n, dim, k=10):
    fused = flat.fused_minima(np.dtype(np.float32), q, n, dim, k, L2, "tpu")
    S = jax.ShapeDtypeStruct
    # lowered FOR a TPU (this process sees a CPU, where a Pallas kernel
    # lowers in interpret mode alone)
    lowered = flat._flat_search_kernel.trace(
        S((n, dim), jnp.float32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((q, dim), jnp.float32), k=k, metric=L2, base=1, fused=fused,
        interpret=False).lower(lowering_platforms=("tpu",))
    return fused, lowered


@pytest.mark.parametrize("q", [1, 8, 32])
@pytest.mark.parametrize("n,dim", [(1_000_064, 128), (5_312_640, 100)])
def test_float_rows_below_the_route_lower_to_no_pallas_kernel(q, n, dim):
    """The 1 / 8 / 32 rungs hold no Mosaic kernel and return the pair
    they returned: the rule keeps them off the route before anything is
    traced."""
    fused, lowered = _float_lowering(q, n, dim)
    assert not fused
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text
    assert "stablehlo.dot_general" in text and "stablehlo.case" not in text
    assert len(lowered.out_info) == 2


@pytest.mark.parametrize("q", [128, 512])
@pytest.mark.parametrize("n,dim", [(1_000_064, 128), (5_312_640, 100)])
def test_float_rows_on_the_route_hold_the_scores_in_the_fallback_alone(
        q, n, dim):
    """The 128 / 512 rungs: the kernel, the flag as a third output, and
    the (N, Q) scores nowhere but inside the branch an unproved run
    takes."""
    fused, lowered = _float_lowering(q, n, dim)
    assert fused
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert len(lowered.out_info) == 3 and lowered.out_info[2].shape == ()
    assert f"tensor<{q}x{n}xf32>" in text           # the fallback's
    main = text[:text.index("stablehlo.case")]
    assert f"{q}x{n}x" not in main
    if dim != q:            # (the rows themselves are n x 128)
        assert f"{n}x{q}x" not in main
    assert f"tensor<{n // 128}x{q}xf32>" in main    # the minima
