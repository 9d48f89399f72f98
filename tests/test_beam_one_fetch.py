"""A trip of the walk fetches by candidate id once (PR 44).

`engine._walk_machine`'s body gathers the candidates' ROWS by id and
takes their norm from the gathered block (the parent fetched
`sqnorm[id]` beside it: one float a candidate, at almost half a row's
price on the chip), and its merge gathers ONE word a pool column by
position, the id with its `expanded` flag in bit 0 (the parent: two
gathers by the same index).  Held here against the parent's body, kept
below as a reference: a saved index answers the same ids at the same
exact distances at float32 scoring; under the bfloat16 shadow recall
stays the reference's and every returned distance is the exact float32
one of its id; the word's round trip and its guard; the body lowers
with two gathers fewer and none from the `(N,)` norms; the counter.
"""

import functools

import numpy as np
import pytest

import sptag_tpu as sp
from benchmark.loadgen import load_by_name
from sptag_tpu.algo import engine
from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.utils import metrics

K = 10
MAX_DIST = engine.MAX_DIST


def parent_walk_machine(data, sqnorm, graph, queries, t_limit, k, L, B,
                        metric, base, nbp_limit, spare_ids=None,
                        spare_d=None, inject=0, data_score=None,
                        nbr_vecs=None, merge_bins=0, score_scale=0.0):
    """`_walk_machine` as PR 44's parent had it, cut to the exact body
    with the row-gather layout (what the cell runs): the candidates'
    norms are gathered from `sqnorm` by id, the merge gathers ids and
    `expanded` flags apart."""
    import jax
    import jax.numpy as jnp

    assert not merge_bins and nbr_vecs is None
    Q, N = queries.shape[0], data.shape[0]
    score_src = data_score if data_score is not None else data
    queries_s = (queries.astype(score_src.dtype)
                 if queries.dtype != score_src.dtype and
                 jnp.issubdtype(queries.dtype, jnp.floating) and
                 jnp.issubdtype(score_src.dtype, jnp.floating)
                 else queries)
    Ps = 0 if spare_ids is None else spare_ids.shape[1]
    use_spares = Ps > 0 and inject > 0
    n_spare = (jnp.sum(spare_ids >= 0, axis=1).astype(jnp.int32)
               if use_spares else None)
    k_eff = min(k, L)

    def _active(no_better, ptr):
        act = no_better < nbp_limit
        if use_spares:
            act = act | (ptr < n_spare)
        return act

    def row_alive(state):
        cand_ids, cand_d, expanded, visited, no_better, ptr, it = state
        has_work = jnp.any((~expanded[:, :L]) & (cand_ids >= 0), axis=1)
        if use_spares:
            has_work = has_work | (ptr < n_spare)
        return (it < t_limit) & _active(no_better, ptr) & has_work

    def body(state):
        cand_ids, cand_d, expanded, visited, no_better, ptr, it = state
        active = _active(no_better, ptr) & (it < t_limit)
        sel_score = jnp.where(expanded[:, :L], MAX_DIST, cand_d)
        sneg, spos = jax.lax.top_k(-sel_score, B)
        sel_ok = ((-sneg) < MAX_DIST) & active[:, None]
        sel_ids = jnp.where(
            sel_ok, jnp.take_along_axis(cand_ids, spos, axis=1), -1)
        expanded = engine._scatter_true(expanded,
                                        jnp.where(sel_ok, spos, L))
        best_pop_d = -sneg[:, 0]
        frontier_worse = best_pop_d > cand_d[:, k_eff - 1]

        nbrs = graph[jnp.maximum(sel_ids, 0)]
        nbrs = jnp.where(sel_ok[..., None], nbrs, -1)
        flat = nbrs.reshape(Q, -1)
        flat_safe = jnp.where(flat >= 0, flat, N)
        flat, fresh, visited, _ = engine._sorted_fresh(visited, flat_safe, N)

        gather_idx = jnp.where(fresh, flat, 0)
        cvecs = score_src[gather_idx]
        csq = sqnorm[gather_idx]                  # the second fetch by id
        if score_scale:
            cvecs = cvecs.astype(jnp.float32) * jnp.float32(score_scale)
        nd = dist_ops.batched_gathered_distance(
            queries_s, cvecs, DistCalcMethod(metric), base, csq)
        nd = jnp.where(fresh, nd, MAX_DIST)

        if use_spares:
            next_d = jnp.take_along_axis(
                spare_d, jnp.minimum(ptr, Ps - 1)[:, None], axis=1)[:, 0]
            stalled = no_better + 1 >= nbp_limit
            trigger = active & (ptr < n_spare) & (
                (best_pop_d > next_d) | stalled)
            idxs = ptr[:, None] + jnp.arange(inject, dtype=jnp.int32)
            ok = trigger[:, None] & (idxs < Ps)
            safe = jnp.minimum(idxs, Ps - 1)
            inj_ids = jnp.where(
                ok, jnp.take_along_axis(spare_ids, safe, axis=1), -1)
            inj_d = jnp.where(ok & (inj_ids >= 0),
                              jnp.take_along_axis(spare_d, safe, axis=1),
                              MAX_DIST)
            ptr = jnp.where(trigger, ptr + inject, ptr)
            nd = jnp.concatenate([nd, inj_d], axis=1)
            flat_m = jnp.concatenate([flat, inj_ids], axis=1)
        else:
            trigger = None
            flat_m = flat

        all_d = jnp.concatenate([cand_d, nd], axis=1)
        all_ids = jnp.concatenate([cand_ids, flat_m], axis=1)
        all_exp = jnp.concatenate(
            [expanded[:, :L],
             jnp.zeros((Q, all_d.shape[1] - L), bool)], axis=1)
        mneg, mpos = jax.lax.top_k(-all_d, L)
        cand_d = -mneg
        cand_ids = jnp.take_along_axis(all_ids, mpos, axis=1)   # two
        cand_ids = jnp.where(cand_d < MAX_DIST, cand_ids, -1)
        expanded = jnp.concatenate(                             # gathers
            [jnp.take_along_axis(all_exp, mpos, axis=1),
             jnp.zeros((Q, 1), bool)], axis=1)

        no_better = jnp.where(active,
                              jnp.where(frontier_worse, no_better + 1, 0),
                              no_better)
        if use_spares:
            no_better = jnp.where(trigger, 0, no_better)
        return cand_ids, cand_d, expanded, visited, no_better, ptr, it + 1

    return body, row_alive


def _search(eng, queries, max_check, inject, machine=None):
    """`GraphSearchEngine.search`'s monolithic program under a jit of
    its own, traced with `machine` in `_walk_machine`'s place (None: the
    library's) -> (dists, ids, live)."""
    import jax
    import jax.numpy as jnp

    k_eff, L, B, T, limit = eng.walk_plan(K, max_check)
    kernel = jax.jit(
        engine._beam_search_kernel.__wrapped__,
        static_argnames=("k", "L", "B", "metric", "base", "nbp_limit",
                         "inject", "merge_bins", "finalize_bins",
                         "seed_keep", "score_scale"))
    was = engine._walk_machine
    engine._walk_machine = machine or was
    try:
        out = kernel(
            eng.data, eng.sqnorm, eng.graph, eng.deleted, eng.pivot_ids,
            eng.pivot_vecs, eng.pivot_mask, jnp.asarray(queries),
            jnp.full((len(queries),), T, jnp.int32), k_eff, L, B,
            int(eng.metric), eng.base, limit, inject=inject,
            data_score=eng.data_score, score_scale=eng.score_scale)
    finally:
        engine._walk_machine = was
    return tuple(np.asarray(o) for o in out)


def _saved(tmp_path_factory, seed, rows=2000, dim=32, queries=32):
    data, q = load_by_name("datasets", "clustered_f32").make(
        seed, rows, dim, queries)
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
    folder = str(tmp_path_factory.mktemp("one_fetch") / f"index{seed}")
    index.save_index(folder)
    index.close()
    return folder, data, q


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    folder, data, queries = _saved(tmp_path_factory, 2**31 + 44)
    index = sp.load_index(folder)
    assert index.set_parameter("BeamScoreDtype", "f32")
    yield index, data, queries
    index.close()


def _exact(data, queries, ids):
    """float64 |q - x|^2 of the ids returned."""
    diff = data[ids].astype(np.float64) - queries[:, None, :].astype(
        np.float64)
    return np.sum(diff * diff, axis=-1)


# ---- (1) float32 scoring: the parent's answers ----------------------------

@pytest.mark.parametrize("max_check", [64, 256, 1024, 2048])
@pytest.mark.parametrize("inject", [4, 0])
def test_a_saved_index_answers_as_the_parents_body(saved, max_check,
                                                   inject):
    index, data, queries = saved
    eng = index._get_engine()
    assert eng.data_score is None and eng.nbr_vecs is None
    d_new, ids_new, live_new = _search(eng, queries, max_check, inject)
    d_old, ids_old, live_old = _search(eng, queries, max_check, inject,
                                       machine=parent_walk_machine)
    # the library's own cached program is the one traced here
    d_lib, ids_lib = eng.search(queries, K, max_check=max_check,
                                dynamic_pivots=inject)
    assert np.array_equal(ids_lib, ids_new) and np.array_equal(d_lib, d_new)
    assert np.array_equal(live_new, live_old)           # the same trips
    # at float32 scoring the two norms are one row's square sum in two
    # summation orders: an id may change places only where two in-loop
    # distances tie within that rounding
    swapped = np.nonzero(np.any(ids_new != ids_old, axis=1))[0]
    print(f"MaxCheck {max_check} inject {inject}: {len(swapped)} of "
          f"{len(queries)} lists differ from the parent's body")
    same = np.setdiff1d(np.arange(len(queries)), swapped)
    assert np.array_equal(d_new[same], d_old[same])     # bit-equal
    for q in swapped:
        np.testing.assert_allclose(d_new[q], d_old[q], rtol=4e-6)
    assert len(swapped) <= 1
    assert np.all(ids_new >= 0) and np.all(np.diff(d_new, axis=1) >= 0)
    assert all(len(set(row)) == K for row in ids_new.tolist())


# ---- (2) the bfloat16 shadow forced on the CPU ----------------------------

@pytest.mark.parametrize("seed", [2**31 + 441, 2**31 + 442, 2**31 + 443])
def test_the_shadows_own_norm_keeps_recall_and_exact_distances(
        tmp_path_factory, seed):
    folder, data, queries = _saved(tmp_path_factory, seed, rows=1500)
    index = sp.load_index(folder)
    assert index.set_parameter("BeamScoreDtype", "bf16")
    eng = index._get_engine()
    assert str(eng.data_score.dtype) == "bfloat16"
    _, truth = index.exact_search_batch(queries, K)

    def recall(ids):
        return np.mean([len(set(a) & set(b)) / K
                        for a, b in zip(ids.tolist(), truth.tolist())])

    for max_check in (256, 2048):
        d_new, ids_new, live_new = _search(eng, queries, max_check, 4)
        d_old, ids_old, live_old = _search(eng, queries, max_check, 4,
                                           machine=parent_walk_machine)
        assert abs(recall(ids_new) - recall(ids_old)) <= 0.005
        assert recall(ids_new) >= 0.9
        # the re-rank: every returned distance is the float32 distance
        # of its id to the float32 row, not the shadow's
        want = _exact(data, queries, ids_new)
        scale = (np.sum(queries.astype(np.float64) ** 2, axis=1)[:, None]
                 + np.sum(data[ids_new].astype(np.float64) ** 2, axis=-1))
        assert np.abs(d_new - want).max() <= 4 * np.max(
            scale * np.finfo(np.float32).eps)
        assert np.all(ids_new >= 0) and np.all(np.diff(d_new, axis=1) >= 0)
    index.close()


def test_an_in_loop_distance_is_the_scored_rows_own():
    """What the body hands `batched_gathered_distance`: no norm, so the
    score is |q~ - x~|^2 of the bfloat16 pair up to accumulation, never
    negative, and 0 for a row scored against itself (with the float32
    row's norm beside the shadow's dot it read up to 4e-3 |x|^2 off)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(44)
    rows = (rng.standard_normal((4, 64, 128)) * 3).astype(np.float32)
    shadow = jnp.asarray(rows).astype(jnp.bfloat16)
    q = shadow[:, 0, :]
    got = np.asarray(dist_ops.batched_gathered_distance(
        q, shadow, DistCalcMethod.L2, 1))
    s64 = np.asarray(shadow.astype(jnp.float32)).astype(np.float64)
    want = np.sum((s64 - s64[:, :1, :]) ** 2, axis=-1)
    assert got.dtype == np.float32 and np.all(got >= 0)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.sum(s64 ** 2, -1).max())
    assert np.all(got[:, 0] <= 1e-5 * np.sum(s64[:, 0] ** 2, -1))
    # the parent's mix: the float32 rows' norms beside the shadow's dot
    mixed = np.asarray(dist_ops.batched_gathered_distance(
        q, shadow, DistCalcMethod.L2, 1,
        jnp.sum(jnp.asarray(rows) ** 2, axis=-1)))
    assert np.abs(mixed - want).max() > 10 * np.abs(got - want).max()


# ---- (3) the word and its guard -------------------------------------------

def test_an_id_and_its_flag_ride_in_one_word():
    import jax.numpy as jnp

    top = engine.MAX_FLAGGED_ROWS - 1           # N at the guard's limit
    ids = np.array([-1, -1, 0, 0, 1, 7, top, top], np.int32)
    flag = np.array([0, 1, 0, 1, 1, 0, 0, 1], bool)
    key = engine._flag_ids(jnp.asarray(ids), jnp.asarray(flag))
    assert key.dtype == jnp.int32
    assert np.all((np.asarray(key) < 0) == (ids < 0))   # a void stays one
    got_ids, got_flag = engine._split_flagged(key)
    assert np.array_equal(np.asarray(got_ids), ids)
    assert np.array_equal(np.asarray(got_flag), flag)
    assert got_ids.dtype == jnp.int32 and got_flag.dtype == jnp.bool_


@pytest.mark.parametrize("rows,fits", [(engine.MAX_FLAGGED_ROWS, True),
                                       (engine.MAX_FLAGGED_ROWS + 1, False)])
def test_the_body_refuses_a_corpus_whose_ids_fill_the_word(rows, fits):
    import jax
    import jax.numpy as jnp

    def make(data, sqnorm, graph, queries, t_limit):
        engine._walk_machine(data, sqnorm, graph, queries, t_limit, K, 16,
                             4, int(DistCalcMethod.L2), 1, 3)
        return queries

    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in [
        ((rows, 8), jnp.int8), ((rows,), jnp.float32),
        ((rows, 4), jnp.int32), ((2, 8), jnp.int8), ((2,), jnp.int32)]]
    if fits:
        jax.eval_shape(make, *shapes)
    else:
        with pytest.raises(ValueError, match="one int32 word"):
            jax.eval_shape(make, *shapes)


# ---- (4) what the body lowers to ------------------------------------------

def _one_trip(machine, spares):
    """One application of `machine`'s body at a small cell-shaped plan
    (exact merge, rows gathered by id, bfloat16 shadow), lowered."""
    import jax
    import jax.numpy as jnp

    Q, L, B, N, D, m, P = 8, 64, 16, 2048, 64, 32, 24
    W = engine._num_words(N)

    def trip(data, shadow, sqnorm, graph, queries, t_limit, spare_ids,
             spare_d, state):
        body, _ = machine(
            data, sqnorm, graph, queries, t_limit, K, L, B,
            int(DistCalcMethod.L2), 1, 3,
            spare_ids=spare_ids if spares else None,
            spare_d=spare_d if spares else None,
            inject=4 if spares else 0, data_score=shadow)
        return body(state)

    state = (jnp.zeros((Q, L), jnp.int32), jnp.zeros((Q, L)),
             jnp.zeros((Q, L + 1), bool), jnp.zeros((Q, W), jnp.int32),
             jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.int32),
             jnp.zeros((Q,), jnp.int32))
    return N, jax.jit(trip).lower(
        jnp.zeros((N, D)), jnp.zeros((N, D), jnp.bfloat16), jnp.zeros((N,)),
        jnp.zeros((N, m), jnp.int32), jnp.zeros((Q, D)),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q, P), jnp.int32),
        jnp.zeros((Q, P)), state).as_text()


def _gathers(text):
    """The operand type of every gather of a lowered module."""
    import re

    return re.findall(r'"stablehlo\.gather"\(.*?:\s*\(tensor<([^>]+)>',
                      text)


@pytest.mark.parametrize("spares", [False, True])
def test_the_body_lowers_with_two_gathers_fewer(spares):
    N, new = _one_trip(engine._walk_machine, spares)
    _, old = _one_trip(parent_walk_machine, spares)
    new_ops, old_ops = _gathers(new), _gathers(old)
    assert len(old_ops) >= 6 and len(new_ops) == len(old_ops) - 2
    assert f"{N}xf32" in old_ops            # the norms, fetched by id
    assert f"{N}xf32" not in new_ops
    # the merge: ids and flags by one index, now one word
    assert sum(op.endswith("xi1") for op in old_ops) == 1
    assert not any(op.endswith("xi1") for op in new_ops)
    # what a trip still gathers: the graph rows of the pops, the shadow's
    # rows, the `visited` words, the pool by position
    for operand in (f"{N}x32xi32", f"{N}x64xbf16",
                    f"8x{engine._num_words(N)}xi32"):
        assert new_ops.count(operand) == old_ops.count(operand) == 1


# ---- the counter ----------------------------------------------------------

def test_a_dispatched_batch_counts_its_norms_from_rows(saved):
    index, _, queries = saved
    metrics.reset()
    index.search_batch(queries, K)
    index.search_batch(queries[:3], K)
    assert metrics.counter_value("beam.norm_from_rows") == 2
    assert metrics.counter_value("beam.monolithic") == 2
