"""Cost ledger (ISSUE 6): registry semantics and THE acceptance
cross-check — registered FLOPs/bytes for the flat, dense and
beam-segment kernels agree with XLA's own `Compiled.cost_analysis()`
within ±15% on the CPU backend (tools/ci_check.sh runs the crosscheck
subset standalone)."""

import jax.numpy as jnp
import numpy as np
import pytest

from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.utils import costmodel, metrics

TOL = costmodel.DEFAULT_TOLERANCE


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_has_every_serving_family():
    """Importing the kernel modules registers the families the roofline
    rows and GL605 depend on."""
    import sptag_tpu.algo.dense  # noqa: F401
    import sptag_tpu.algo.engine  # noqa: F401
    import sptag_tpu.algo.flat  # noqa: F401
    import sptag_tpu.ops.distance  # noqa: F401

    fams = set(costmodel.families())
    for want in ("flat.scan", "flat.sketch_scan", "dense.scan",
                 "dense.grouped", "beam.seed", "beam.segment",
                 "beam.finalize", "beam.walk", "distance.batch_topk",
                 "distance.row_sqnorms"):
        assert want in fams, (want, fams)
    names = set(costmodel.registered_kernel_names())
    assert "_flat_search_kernel" in names
    assert "_beam_segment_kernel" in names


def test_estimate_unknown_family_raises():
    with pytest.raises(KeyError):
        costmodel.estimate("no.such.family", Q=1)


def test_estimate_returns_positive_physics():
    import sptag_tpu.algo.flat  # noqa: F401

    est = costmodel.estimate("flat.scan", Q=32, N=1024, D=64, k=10)
    assert est.flops > 2 * 32 * 1024 * 64 * 0.9
    assert est.hbm_bytes > 1024 * 64 * 4          # at least the corpus
    assert est.intensity > 0


def test_crosscheck_mismatch_increments_counter(caplog):
    """A formula that drifts from its kernel is VISIBLE: the counter
    bumps and the delta is logged."""
    import jax

    @jax.jit
    def tiny(x):
        return x @ x

    costmodel.register("test.bad_formula", tiny,
                       lambda **s: (1.0, 1.0))   # absurdly wrong
    compiled = tiny.lower(jnp.ones((64, 64))).compile()
    before = metrics.counter_value("costmodel.xla_mismatch")
    rel = costmodel.crosscheck("test.bad_formula", compiled)
    assert metrics.counter_value("costmodel.xla_mismatch") == before + 1
    assert rel["flops_rel"] < -0.9                # ledger far below XLA


# ---------------------------------------------------------------------------
# acceptance: ledger vs cost_analysis within ±15% (CPU backend)
# ---------------------------------------------------------------------------

def _assert_close(family, compiled, **shape):
    before = metrics.counter_value("costmodel.xla_mismatch")
    rel = costmodel.crosscheck(family, compiled, **shape)
    assert abs(rel["flops_rel"]) <= TOL, (family, shape, rel)
    assert abs(rel["bytes_rel"]) <= TOL, (family, shape, rel)
    assert metrics.counter_value("costmodel.xla_mismatch") == before


@pytest.mark.parametrize("Q,N,D,k", [
    (32, 1024, 64, 10), (8, 512, 32, 5),
    # rows the exact selection takes in two stages (ISSUE 28); the last
    # leaves a tail past its last whole group
    (8, 40960, 32, 1), (128, 65536, 32, 2), (128, 40000, 64, 1)])
def test_crosscheck_flat_scan(Q, N, D, k):
    from sptag_tpu.algo.flat import _flat_search_kernel

    data = jnp.zeros((N, D))
    compiled = _flat_search_kernel.lower(
        data, jnp.zeros((N,)), jnp.zeros((N,), bool), jnp.zeros((Q, D)),
        k, int(DistCalcMethod.L2), 1, False).compile()
    _assert_close("flat.scan", compiled, Q=Q, N=N, D=D, k=k)


@pytest.mark.parametrize("Q,C,P,D,nprobe,k", [(32, 64, 128, 64, 4, 10)])
def test_crosscheck_dense_scan(Q, C, P, D, nprobe, k):
    from sptag_tpu.algo.dense import _dense_search_kernel

    compiled = _dense_search_kernel.lower(
        jnp.zeros((C, P, D)), jnp.zeros((C, P), jnp.int32),
        jnp.zeros((C, P)), jnp.zeros((C, D)), jnp.zeros((C,)),
        jnp.zeros((C * P,), bool), jnp.zeros((Q, D)),
        k, nprobe, int(DistCalcMethod.L2), 1, False, False,
        False).compile()
    _assert_close("dense.scan", compiled, Q=Q, C=C, P=P, D=D,
                  nprobe=nprobe, k=k)


@pytest.mark.parametrize("Q,C,P,D,nprobe,U,G,k",
                         [(32, 64, 128, 64, 4, 8, 8, 10),
                          (64, 32, 64, 32, 2, 4, 4, 5)])
def test_crosscheck_dense_grouped(Q, C, P, D, nprobe, U, G, k):
    """ISSUE 13 satellite: the grouped-dense family, never crosschecked
    before, holds the same ±15% bar at two shapes."""
    from sptag_tpu.algo.dense import _dense_search_grouped_kernel

    compiled = _dense_search_grouped_kernel.lower(
        jnp.zeros((C, P, D)), jnp.zeros((C, P), jnp.int32),
        jnp.zeros((C, P)), jnp.zeros((C, D)), jnp.zeros((C,)),
        jnp.zeros((C * P,), bool), jnp.zeros((Q, D)),
        jnp.int32(Q), k, nprobe, U, G, int(DistCalcMethod.L2), 1,
        False, False, False, 0).compile()
    _assert_close("dense.grouped", compiled, Q=Q, C=C, P=P, D=D,
                  nprobe=nprobe, U=U, G=G, k=k)


@pytest.mark.parametrize("Q,L,B,N,D,m,S",
                         [(8, 64, 16, 2048, 64, 32, 4),
                          (32, 128, 32, 4096, 128, 32, 8)])
def test_crosscheck_beam_segment(Q, L, B, N, D, m, S):
    """The walk body follows the count-body-once convention: the
    registered beam.segment cost is ONE iteration regardless of S (the
    two S values here compile different programs, same cost)."""
    from sptag_tpu.algo.engine import _beam_segment_kernel, _num_words

    W = _num_words(N)
    compiled = _beam_segment_kernel.lower(
        jnp.zeros((N, D)), jnp.zeros((N,)),
        jnp.zeros((N, m), jnp.int32), jnp.zeros((Q, D)),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q, L), jnp.int32),
        jnp.zeros((Q, L)), jnp.zeros((Q, L + 1), bool),
        jnp.zeros((Q, W), jnp.int32), jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.int32),
        10, L, B, S, int(DistCalcMethod.L2), 1, 3, 0,
        None, None, None, None).compile()
    _assert_close("beam.segment", compiled, Q=Q, X=B * m, D=D, W=W, N=N)


@pytest.mark.parametrize("Q,L,B,N,D,m,S",
                         [(8, 64, 16, 2048, 64, 32, 4),
                          (32, 128, 32, 4096, 128, 32, 8),
                          (16, 320, 64, 16384, 128, 32, 4)])
def test_crosscheck_beam_segment_packed(Q, L, B, N, D, m, S):
    """ISSUE 45: the exact body under the packed-neighbour layout is
    priced by the SAME formula as under the row layout (the sorted-id
    ensemble, WALK_SORTED_*; the scores ride its sort).  Cost analysis
    charges the block gather its whole operand, the (N, m, D) table,
    where the formula carries the corpus's N*D and says that it leaves
    the table out: the other (m - 1) N*D are added here."""
    from sptag_tpu.algo.engine import _beam_segment_kernel, _num_words

    W = _num_words(N)
    compiled = _beam_segment_kernel.lower(
        jnp.zeros((N, D)), jnp.zeros((N,)),
        jnp.zeros((N, m), jnp.int32), jnp.zeros((Q, D)),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q, L), jnp.int32),
        jnp.zeros((Q, L)), jnp.zeros((Q, L + 1), bool),
        jnp.zeros((Q, W), jnp.int32), jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.int32),
        10, L, B, S, int(DistCalcMethod.L2), 1, 3, 0,
        None, None, None, jnp.zeros((N, m, D))).compile()
    est = costmodel.estimate("beam.segment", Q=Q, X=B * m, D=D, W=W, N=N)
    xla_flops, xla_bytes = costmodel.xla_cost(compiled)
    table_rest = (m - 1) * N * D * 4
    assert abs(est.flops - xla_flops) <= TOL * xla_flops
    assert abs(est.hbm_bytes + table_rest - xla_bytes) \
        <= TOL * (xla_bytes - table_rest)


@pytest.mark.parametrize("N,m,D,dtype", [(2048, 32, 64, jnp.float32),
                                         (16384, 8, 128, jnp.float32),
                                         (4096, 32, 128, jnp.bfloat16)])
def test_crosscheck_beam_pack_neighbors(N, m, D, dtype):
    """PR 45: the packed-neighbour table's build is one program, priced
    as what it moves (the scoring source and the graph in, the table
    out).  The flops are held on float rows only: XLA:CPU widens
    bfloat16 on its way through and bills that."""
    from sptag_tpu.algo.engine import _pack_neighbors

    compiled = _pack_neighbors.lower(
        jnp.zeros((N, D), dtype), jnp.zeros((N, m), jnp.int32)).compile()
    est = costmodel.estimate("beam.pack_neighbors", N=N, m=m, D=D,
                             score_itemsize=jnp.dtype(dtype).itemsize)
    xla_flops, xla_bytes = costmodel.xla_cost(compiled)
    assert abs(est.hbm_bytes - xla_bytes) <= TOL * xla_bytes
    if dtype == jnp.float32:
        assert abs(est.flops - xla_flops) <= TOL * xla_flops


@pytest.mark.parametrize("Q,L,B,N,D,m,S",
                         [(8, 64, 16, 2048, 64, 32, 4),
                          (32, 128, 32, 4096, 128, 32, 8),
                          (16, 320, 64, 16384, 128, 32, 4)])
def test_crosscheck_beam_segment_binned(Q, L, B, N, D, m, S):
    """ISSUE 13: the BINNED walk body's recalibrated formula
    (WALK_BINNED_* constants + the explicit corpus gather-operand term)
    holds ±15% at three shapes, including the bench's (L=320, B=64)."""
    from sptag_tpu.algo.engine import _beam_segment_kernel, _num_words
    from sptag_tpu.ops import topk_bins

    W = _num_words(N)
    # the PRODUCTION bin rule (walk_merge_bins' pow2ceil(2L)), not an
    # arbitrary count — the crosscheck must pin the shipped configuration
    mb = topk_bins.walk_merge_bins("on", L, L + B * m)
    assert mb == topk_bins.pow2ceil(2 * L)
    compiled = _beam_segment_kernel.lower(
        jnp.zeros((N, D)), jnp.zeros((N,)),
        jnp.zeros((N, m), jnp.int32), jnp.zeros((Q, D)),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q, L), jnp.int32),
        jnp.zeros((Q, L)), jnp.zeros((Q, L + 1), bool),
        jnp.zeros((Q, W), jnp.int32), jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.int32),
        10, L, B, S, int(DistCalcMethod.L2), 1, 3, 0,
        None, None, None, None, mb).compile()
    _assert_close("beam.segment", compiled, Q=Q, X=B * m, D=D, W=W,
                  merge_bins=mb, L=L, N=N)


@pytest.mark.parametrize("Q,N,D,k,rt", [(32, 4096, 64, 10, 0.9)])
def test_crosscheck_flat_scan_binned(Q, N, D, k, rt):
    """The binned FLAT select's formula (one fewer full (Q, N) traversal
    + the shortlist select term) holds the same bar."""
    from sptag_tpu.algo.flat import _flat_search_kernel
    from sptag_tpu.ops import topk_bins

    bins = topk_bins.bins_for(k, N, rt)
    compiled = _flat_search_kernel.lower(
        jnp.zeros((N, D)), jnp.zeros((N,)), jnp.zeros((N,), bool),
        jnp.zeros((Q, D)), k, int(DistCalcMethod.L2), 1, False, rt,
        bins).compile()
    _assert_close("flat.scan", compiled, Q=Q, N=N, D=D, k=k,
                  binned_bins=bins)


def test_walk_iter_cost_matches_segment_family():
    """The engine helper the gauges and slow-query attribution consume
    is exactly the registered beam.segment formula at the engine's own
    static shapes."""
    import sptag_tpu.algo.engine as E

    rng = np.random.default_rng(0)
    data = rng.standard_normal((200, 16)).astype(np.float32)
    graph = rng.integers(0, 200, (200, 8)).astype(np.int32)
    eng = E.GraphSearchEngine(data, graph, np.arange(16, dtype=np.int32),
                              None, DistCalcMethod.L2, 1,
                              score_dtype="f32")
    est = eng.walk_iter_cost(4, 8)
    ref = costmodel.estimate("beam.segment", Q=4, X=8 * 8, D=16,
                             W=E._num_words(200), score_itemsize=4,
                             N=200)
    assert est.flops == ref.flops and est.hbm_bytes == ref.hbm_bytes


def test_xla_cost_tolerates_dict_and_list_forms():
    class FakeDict:
        def cost_analysis(self):
            return {"flops": 5.0, "bytes accessed": 7.0}

    class FakeList:
        def cost_analysis(self):
            return [{"flops": 5.0, "bytes accessed": 7.0}]

    assert costmodel.xla_cost(FakeDict()) == (5.0, 7.0)
    assert costmodel.xla_cost(FakeList()) == (5.0, 7.0)
