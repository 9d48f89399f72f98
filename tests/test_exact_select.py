"""ISSUE 28 — the exact two-stage select of the FLAT scan
(`algo/flat.py::exact_topk`): group minima, a top-k over the minima, then
the exact top-k over the chosen groups' columns.

The contract is `lax.top_k(-d, k)`'s answer BIT FOR BIT — values, columns
and the lowest-column-first order among equals — on rows of every kind
the scan meets: random, heavily tied, masked to `MAX_DIST` in whole
groups / all but k-1 columns / every column, widths that leave a tail
past the last whole group, and widths each side of the engagement rule.
Then once through `FlatIndex.search_batch` against the benchmark's plain
numpy reference (the mesh path's case is in tests/test_mesh_flat.py).

Rows are small (a few queries, and for the algebra an engagement rule
brought down to a few thousand columns): what is under test is selection
algebra, not throughput.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sptag_tpu as sp
from sptag_tpu.algo import flat
from sptag_tpu.core.index import MAX_DIST
from sptag_tpu.utils import metrics

GROUP = flat._GROUP


def engage_width(k, q=4):
    """The narrowest row the two-stage select engages on."""
    n = 1
    while flat.select_stages(q, n, k) == 1:
        n *= 2
    lo, hi = n // 2, n
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (lo, mid) if flat.select_stages(q, mid, k) == 2 \
            else (mid, hi)
    return hi


@pytest.fixture
def low_rule(monkeypatch):
    """The engagement rule at 8 times k*_GROUP columns, not the chip's
    100: the same two stages on rows a CPU test can hold.  Only
    `exact_topk` under a jit of the test's own is traced under it — never
    the cached programs of the indexes."""
    monkeypatch.setattr(flat, "_TWO_STAGE_MIN_WIDTH", 8)


def assert_same_as_top_k(d, k):
    d = jnp.asarray(d)
    want_neg, want_cols = jax.lax.top_k(-d, k)
    dists, cols = jax.jit(flat.exact_topk, static_argnums=1)(d, k)
    assert np.asarray(dists).tobytes() == np.asarray(-want_neg).tobytes()
    assert np.array_equal(np.asarray(cols), np.asarray(want_cols))


def rows_of(kind, q, n, k, rng):
    """(q, n) float32 distance rows of one of the kinds the scan meets."""
    d = rng.standard_normal((q, n)).astype(np.float32) ** 2
    if kind == "random":
        return d
    if kind == "four_values":
        # heavy ties: every distance is one of four values
        return rng.integers(0, 4, (q, n)).astype(np.float32)
    if kind == "duplicated_rows":
        # a corpus that holds every row eight times over
        return np.tile(d[:, :-(-n // 8)], 8)[:, :n]
    if kind == "masked_groups":
        # whole groups masked, the global minimum's group among them
        for row in d:
            best = int(row.argmin()) // GROUP
            for g in {best, 0, n // GROUP - 1, *rng.integers(
                    0, n // GROUP, 5).tolist()}:
                row[g * GROUP:(g + 1) * GROUP] = MAX_DIST
        return d
    if kind == "all_but_k_minus_1":
        # fewer live columns than k: the rest of the answer is MAX_DIST,
        # lowest column first
        live = rng.integers(0, n, (q, max(k - 1, 0)))
        out = np.full((q, n), MAX_DIST, np.float32)
        np.put_along_axis(out, live, np.take_along_axis(d, live, 1), 1)
        return out
    if kind == "all_masked":
        return np.full((q, n), MAX_DIST, np.float32)
    raise ValueError(kind)


KINDS = ["random", "four_values", "duplicated_rows", "masked_groups",
         "all_but_k_minus_1", "all_masked"]


@pytest.mark.parametrize("k", [1, 10, 32])
@pytest.mark.parametrize("kind", KINDS)
def test_two_stage_is_top_k_bit_for_bit(low_rule, kind, k):
    """Whole groups only: a width the engagement rule takes in two
    stages, a multiple of the group."""
    n = -(-engage_width(k) // GROUP) * GROUP + 3 * GROUP
    assert flat.select_stages(5, n, k) == 2
    rng = np.random.default_rng(KINDS.index(kind) * 100 + k)
    assert_same_as_top_k(rows_of(kind, 5, n, k, rng), k)


@pytest.mark.parametrize("k", [1, 10, 32])
@pytest.mark.parametrize("tail", [1, 32, GROUP - 1])
@pytest.mark.parametrize("kind", ["random", "four_values", "all_masked"])
def test_tail_past_the_last_whole_group(low_rule, kind, tail, k):
    """A shard of 2,500,000 rows ends 32 columns past its last whole
    group: the tail is a group of its own, always among the candidates.
    The row's smallest values are planted there, and (four_values) its
    ties with the chosen groups must lose to their lower columns."""
    n = -(-engage_width(k) // GROUP) * GROUP + tail
    rng = np.random.default_rng(tail * 100 + k)
    d = rows_of(kind, 4, n, k, rng)
    if kind == "random":
        d[0, n - tail:] = 0.0            # the whole answer from the tail
        d[1, n - 1] = 0.0                # its last column alone
    assert_same_as_top_k(d, k)


@pytest.mark.parametrize("side", [-1, 0])
@pytest.mark.parametrize("q,k", [(1, 1), (1, 10), (1, 32), (5, 1), (5, 10),
                                 (5, 32), (128, 1), (128, 10)])
def test_each_side_of_the_engagement_rule(low_rule, q, k, side):
    """One narrower than the rule engages on: one `lax.top_k`.  At the
    rule: two stages.  The same answer either way, for one query, a few,
    and a block that fills the lanes."""
    n = engage_width(k, q) + side
    assert flat.select_stages(q, n, k) == (1 if side else 2)
    rng = np.random.default_rng(n + q)
    assert_same_as_top_k(rows_of("four_values", q, n, k, rng), k)
    assert_same_as_top_k(rows_of("random", q, n, k, rng), k)


def test_k_near_n_and_narrow_rows_stay_single():
    assert flat.select_stages(8, 100, 100) == 1
    assert flat.select_stages(128, 2048, 10) == 1       # a delta-row scan
    assert flat.select_stages(128, 100_096, 10) == 1    # bkt_100k's oracle
    assert flat.select_stages(128, 1_000_064, 10) == 2  # flat_1m.saturate
    assert flat.select_stages(1, 1_000_064, 10) == 2    # flat_1m.single
    assert flat.select_stages(128, 2_500_096, 10) == 2  # a deep-10M shard
    assert flat.select_stages(128, 1_000_064, 1000) == 1
    # between a sublane tile and the lane tile the N-wide top-k is the
    # cheaper one; past 512 queries the slabs outgrow the scores
    assert [flat.select_stages(q, 1_000_064, 10)
            for q in (1, 8, 9, 32, 127, 128, 512, 513, 1024)] \
        == [2, 2, 1, 1, 1, 2, 2, 1, 1]


def test_no_row_wide_sort_is_traced_for_a_wide_row(low_rule):
    """The stage count follows the traced width: the wide program holds
    no sort or top-k over its N columns, the narrow one holds the one
    `top_k` it always held."""
    n = engage_width(10, 8)

    def widths(n):
        text = jax.jit(flat.exact_topk, static_argnums=1).lower(
            jax.ShapeDtypeStruct((8, n), jnp.float32), 10).as_text()
        return [line for line in text.splitlines()
                if ("chlo.top_k" in line or "stablehlo.sort" in line)
                and f"x{n}x" in line.replace("<", "x").replace(">", "x")]
    assert not widths(n)
    assert widths(n - 1)


def test_flat_index_search_batch_takes_two_stages_and_is_exact(
        bench_reference):
    """Through `FlatIndex.search_batch`, with deleted rows, against the
    benchmark's plain numpy scan; the counter says which selection ran."""
    rng = np.random.default_rng(28)
    n, dim, k = engage_width(10, 8) + 77, 8, 10
    centers = rng.standard_normal((16, dim)).astype(np.float32)
    data = (centers[rng.integers(0, 16, n)]
            + 0.3 * rng.standard_normal((n, dim))).astype(np.float32)
    queries = data[rng.integers(0, n, 7)] + np.float32(0.01)
    index = sp.create_instance("FLAT", "Float")
    index.set_parameter("DistCalcMethod", "L2")
    assert index.build(data) == sp.ErrorCode.Success
    want_ids, _ = bench_reference.exact_topk(data, queries, k)
    gone = want_ids[:, 0]                    # every query's nearest row
    assert index.delete(data[gone]) == sp.ErrorCode.Success
    live = np.ones(n, bool)
    live[gone] = False
    kept = np.flatnonzero(live)
    want_ids, want_d = bench_reference.exact_topk(data[kept], queries, k)

    two, one = (metrics.counter_value("flat.select_two_stage"),
                metrics.counter_value("flat.select_single"))
    dists, ids = index.search_batch(queries, k)
    assert metrics.counter_value("flat.select_two_stage") == two + 1
    assert metrics.counter_value("flat.select_single") == one
    assert np.array_equal(ids, kept[want_ids])
    np.testing.assert_allclose(dists, want_d, rtol=1e-4, atol=1e-3)

    narrow = sp.create_instance("FLAT", "Float")
    narrow.set_parameter("DistCalcMethod", "L2")
    assert narrow.build(data[:500]) == sp.ErrorCode.Success
    narrow.search_batch(queries, k)
    assert metrics.counter_value("flat.select_single") == one + 1
