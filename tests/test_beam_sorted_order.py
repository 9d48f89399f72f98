"""The walk's visited / de-duplicate ensemble in sorted-id order (PRs 33, 45).

In the exact body a trip's candidates go into ascending-id order once and
stay there until the merge (`engine._sorted_fresh`): one sort and one
gather of `visited` words, where `_sorted_dedup` + `_test_bits` +
`_mark_bits_sorted` paid an argsort, two word gathers, the sorted ids'
gather, the duplicate mask's way back and the inverse permutation's
scatter.  Since PR 45 that holds for the packed-neighbour layout too: its
blocks are fetched and scored in the graph's order BEFORE the sort, and
the scores ride the sort as its payload.  Held here: the two ensembles
find the same fresh ids and leave the same bitset, a payload follows its
id; a saved index answers under the packed layout as under the row
layout; the traced body holds one `visited` gather and nothing X-wide
from a `pred` operand, whichever way it fetches; and the rule that picks
the ensemble is the one the counters follow.
"""

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.loadgen import load_by_name
from sptag_tpu.algo import engine
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.utils import metrics

K = 10


def _positional(visited, flat_safe, n):
    """The ensemble as the exact body ran it before PR 33 (the
    packed-neighbour layout until PR 45) -> (fresh, visited)."""
    seen = engine._test_bits(visited, flat_safe)
    sorted_safe, dup = engine._sorted_dedup(flat_safe)
    return ((flat_safe < n) & ~seen & ~dup,
            engine._mark_bits_sorted(visited, sorted_safe))


@pytest.mark.parametrize("seed,Q,X,N", [(0, 4, 64, 40), (1, 8, 512, 2048),
                                        (2, 3, 96, 1000), (3, 5, 32, 31)])
def test_sorted_ensemble_finds_what_the_positional_one_finds(seed, Q, X, N):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, N, (Q, X)).astype(np.int32)      # repeats
    ids[:, : X // 4] = ids[:, X // 4: X // 2]              # and more
    ids[rng.random((Q, X)) < 0.2] = -1                     # holes
    ids[0] = -1                                            # an empty row
    marked = rng.random((Q, N)) < 0.3
    words = np.zeros((Q, engine._num_words(N)), np.uint32)
    for q, i in zip(*np.nonzero(marked)):
        words[q, i >> 5] |= np.uint32(1) << np.uint32(i & 31)
    visited = jnp.asarray(words.view(np.int32))
    flat_safe = jnp.asarray(np.where(ids >= 0, ids, N).astype(np.int32))

    fresh_p, visited_p = jax.jit(_positional, static_argnums=2)(
        visited, flat_safe, N)
    # a payload that is a function of the id alone, as a candidate's score
    # is: equal among an id's copies, so it has to come out beside its id
    payload = jnp.where(flat_safe < N, flat_safe.astype(jnp.float32) * 0.5,
                        -1.0)
    ids_s, fresh_s, visited_s, none = jax.jit(
        engine._sorted_fresh, static_argnums=2)(visited, flat_safe, N)
    assert none is None
    ids_w, fresh_w, visited_w, payload_w = jax.jit(
        engine._sorted_fresh, static_argnums=2)(visited, flat_safe, N,
                                                payload)
    for with_payload, without in ((ids_w, ids_s), (fresh_w, fresh_s),
                                  (visited_w, visited_s)):
        assert np.array_equal(np.asarray(with_payload), np.asarray(without))
    assert np.array_equal(
        np.asarray(payload_w),
        np.where(np.asarray(ids_s) >= 0, np.asarray(ids_s) * 0.5, -1.0))
    ids_s, fresh_s, fresh_p = map(np.asarray, (ids_s, fresh_s, fresh_p))
    assert np.array_equal(np.asarray(visited_s), np.asarray(visited_p))
    for q in range(Q):
        valid = np.unique(ids[q][ids[q] >= 0])
        want = valid[~marked[q, valid]]
        assert np.array_equal(np.sort(ids[q][fresh_p[q]]), want)
        assert np.array_equal(ids_s[q][fresh_s[q]], want)   # ascending
        assert np.array_equal(np.sort(ids_s[q][ids_s[q] >= 0]),
                              np.sort(ids[q][ids[q] >= 0]))
        n_valid = int((ids[q] >= 0).sum())
        assert np.all(ids_s[q][n_valid:] == -1)
        # the bitset afterwards: what was marked, and every valid id
        after = np.asarray(visited_s)[q].view(np.uint32)
        bits = (after[np.arange(N) >> 5] >> (np.arange(N) & 31)) & 1
        now = marked[q].copy()
        now[valid] = True
        assert np.array_equal(bits.astype(bool), now)


def test_the_rule_that_picks_the_ensemble():
    """A rule of `merge_bins` alone: the exact body is in sorted order
    whichever way it fetches its vectors."""
    assert engine.dedup_in_sorted_order(0)
    assert not engine.dedup_in_sorted_order(512)            # binned


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    """One saved BKT index loaded twice: the row-gather layout and the
    packed-neighbour layout (both in sorted order), plus queries."""
    data, queries = load_by_name("datasets", "clustered_f32").make(
        2**31 + 33, 2000, 32, 32)
    index = sp.create_instance("BKT", "Float")
    for name, value in [("DistCalcMethod", "L2"), ("BKTNumber", "1"),
                        ("BKTKmeansK", "32"), ("TPTNumber", "4"),
                        ("TPTLeafSize", "500"), ("NeighborhoodSize", "32"),
                        ("CEF", "64"), ("MaxCheckForRefineGraph", "128"),
                        ("RefineIterations", "1"),
                        ("FinalRefineSearchMode", "same"),
                        ("SearchMode", "beam")]:
        assert index.set_parameter(name, value)
    index.build(data)
    folder = str(tmp_path_factory.mktemp("sorted_order") / "index")
    index.save_index(folder)
    index.close()
    rows, packed = sp.load_index(folder), sp.load_index(folder)
    assert rows.get_parameter("BeamPackedNeighbors") == "auto"   # CPU: rows
    assert packed.set_parameter("BeamPackedNeighbors", "1")
    assert not rows._get_engine().packed
    assert packed._get_engine().packed
    yield rows._get_engine(), packed._get_engine(), queries
    rows.close()
    packed.close()


@pytest.mark.parametrize("max_check", [64, 256, 2048])
@pytest.mark.parametrize("inject", [4, 0])
def test_a_saved_index_answers_packed_as_by_rows(engines, max_check, inject):
    rows_engine, packed_engine, queries = engines
    metrics.reset()
    d_r, ids_r = rows_engine.search(queries, K, max_check=max_check,
                                    dynamic_pivots=inject)
    assert metrics.counter_value("beam.dedup_sorted") == 1
    assert metrics.counter_value("beam.fetch_rows") == 1
    assert metrics.counter_value("beam.fetch_blocks") == 0
    rung, B = 32, rows_engine.walk_plan(K, max_check)[2]
    assert len(queries) == rung
    assert metrics.gauge_value("beam.fetches_per_trip") == rung * B * 32
    d_p, ids_p = packed_engine.search(queries, K, max_check=max_check,
                                      dynamic_pivots=inject)
    assert metrics.counter_value("beam.dedup_sorted") == 2
    assert metrics.counter_value("beam.dedup_positional") == 0
    assert metrics.counter_value("beam.fetch_rows") == 1
    assert metrics.counter_value("beam.fetch_blocks") == 1
    assert metrics.gauge_value("beam.fetches_per_trip") == rung * B
    assert packed_engine.nbr_vecs.shape == (2000, 32, 32)
    assert rows_engine.nbr_vecs is None
    assert np.array_equal(ids_r, ids_p)
    assert np.array_equal(d_r, d_p)
    assert np.all(ids_r >= 0) and np.all(np.diff(d_r, axis=1) >= 0)
    assert all(len(set(row)) == K for row in ids_r.tolist())


def _equations(jaxpr, scope=""):
    """Every equation of a jaxpr and of the jaxprs in its parameters,
    with the name stack it sits under (an inner jaxpr's is relative)."""
    for eqn in jaxpr.eqns:
        here = scope + "/" + str(eqn.source_info.name_stack)
        yield eqn, here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, here)


def body_equations(packed, merge_bins=0):
    """-> ((Q, B, m, D, N), the equations of the walk body with their
    scopes) as `_beam_segment_kernel` traces it at a small shape, with
    the packed-neighbour table or without
    (`tests/test_beam_block_fetch.py` reads them too)."""
    import jax.numpy as jnp

    Q, L, B, N, D, m, S = 8, 64, 16, 2048, 64, 32, 4
    W = engine._num_words(N)
    traced = engine._beam_segment_kernel.trace(
        jnp.zeros((N, D)), jnp.zeros((N,)), jnp.zeros((N, m), jnp.int32),
        jnp.zeros((Q, D)), jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q, L), jnp.int32), jnp.zeros((Q, L)),
        jnp.zeros((Q, L + 1), bool), jnp.zeros((Q, W), jnp.int32),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q,), jnp.int32), 10, L, B, S, int(DistCalcMethod.L2),
        1, 3, 0, None, None, None,
        jnp.zeros((N, m, D)) if packed else None, merge_bins)
    return (Q, B, m, D, N), list(_equations(traced.jaxpr.jaxpr))


@pytest.mark.parametrize("packed", [False, True])
def test_the_traced_body_holds_one_visited_gather(packed):
    """The exact body as `_beam_segment_kernel` traces it, under either
    layout: ONE gather from the `visited` table (it serves the test and
    the marker), no X-wide gather from a `pred` operand (the duplicate
    mask's way back), no X-wide scatter (the inverse permutation) and
    ONE X-wide sort: of the ids alone where the rows are gathered after
    it, of the ids with the scores as payload where the blocks were
    scored before it.  (The merge gathers nothing from a `pred` operand
    since PR 44: a column's `expanded` flag rides in its id's word.)"""
    (Q, B, m, _, N), eqns = body_equations(packed)
    X, W = B * m, engine._num_words(N)
    visited_gathers = mask_gathers = wide_scatters = sorts = 0
    for eqn, scope in eqns:
        operand = eqn.invars[0].aval if eqn.invars else None
        out = eqn.outvars[0].aval if eqn.outvars else None
        if eqn.primitive.name == "gather" and "beam.merge" in scope:
            visited_gathers += operand.shape == (Q, W)
            mask_gathers += operand.dtype == bool and out.shape == (Q, X)
        elif eqn.primitive.name == "scatter":
            wide_scatters += operand.shape == (Q, X)
        elif eqn.primitive.name == "sort" and out.shape == (Q, X):
            sorts += 1
            assert eqn.params["num_keys"] == 1
            assert [str(v.aval.dtype) for v in eqn.invars] == (
                ["int32", "float32"] if packed else ["int32"])
            assert not (packed and eqn.params["is_stable"])
    assert sorts == 1
    assert (visited_gathers, mask_gathers, wide_scatters) == (1, 0, 0)
