"""Pallas probe-scoring kernel — interpreter-mode correctness on CPU.

The real kernel runs on TPU only (ops/pallas_kernels.py gates on platform);
interpreter mode executes the same kernel logic through the Pallas
interpreter so CI validates indexing/masking without a chip.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from sptag_tpu.ops import pallas_kernels


@pytest.fixture(autouse=True)
def _interpret_mode():
    pallas_kernels.set_interpret(True)
    yield
    pallas_kernels.set_interpret(False)


def test_probe_block_dots_matches_einsum():
    rng = np.random.default_rng(0)
    C, P, D, Q, nprobe = 7, 8, 128, 4, 3
    data_perm = jnp.asarray(rng.standard_normal((C, P, D)).astype(np.float32))
    queries = jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32))
    topc = jnp.asarray(rng.integers(0, C, (Q, nprobe)).astype(np.int32))

    got = pallas_kernels.probe_block_dots(data_perm, queries, topc,
                                          interpret=True)
    want = jnp.einsum("qd,qjpd->qjp", queries, data_perm[topc])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_supported_gates():
    rng = np.random.default_rng(1)
    f32 = jnp.asarray(rng.standard_normal((4, 8, 128)).astype(np.float32))
    assert pallas_kernels.supported(f32)          # interpret mode is on
    i8 = jnp.asarray(rng.integers(-5, 5, (4, 32, 128)).astype(np.int8))
    assert pallas_kernels.supported(i8)           # int8: (32,128) tiles
    i8_bad = jnp.asarray(rng.integers(-5, 5, (4, 8, 128)).astype(np.int8))
    assert not pallas_kernels.supported(i8_bad)   # P not 32-multiple
    i16 = jnp.asarray(rng.integers(-5, 5, (4, 32, 128)).astype(np.int16))
    assert not pallas_kernels.supported(i16)      # int16 -> XLA fallback
    odd = jnp.asarray(rng.standard_normal((4, 8, 100)).astype(np.float32))
    assert not pallas_kernels.supported(odd)      # D not 128-multiple


def test_probe_block_dots_int8_exact():
    """int8 path must be the EXACT integer dot (int32 accumulation)."""
    rng = np.random.default_rng(4)
    C, P, D, Q, nprobe = 5, 32, 128, 3, 2
    data_perm = jnp.asarray(
        rng.integers(-127, 128, (C, P, D)).astype(np.int8))
    queries = jnp.asarray(rng.integers(-127, 128, (Q, D)).astype(np.int8))
    topc = jnp.asarray(rng.integers(0, C, (Q, nprobe)).astype(np.int32))

    got = pallas_kernels.probe_block_dots(data_perm, queries, topc,
                                          interpret=True)
    assert got.dtype == jnp.int32
    want = np.einsum("qd,qjpd->qjp",
                     np.asarray(queries, np.int64),
                     np.asarray(data_perm, np.int64)[np.asarray(topc)])
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_dense_kernel_pallas_vs_xla_paths():
    """The full dense kernel must produce identical ids through both the
    Pallas and the XLA scoring paths."""
    from sptag_tpu.algo.dense import _dense_search_kernel

    rng = np.random.default_rng(2)
    C, P, D, Q, nprobe = 6, 16, 128, 8, 2
    n = C * P
    data = rng.standard_normal((n, D)).astype(np.float32)
    perm = data.reshape(C, P, D)
    mids = jnp.asarray(np.arange(n, dtype=np.int32).reshape(C, P))
    sq = jnp.asarray((data ** 2).sum(1).astype(np.float32).reshape(C, P))
    cents = jnp.asarray(perm.mean(axis=1))
    cent_sq = jnp.asarray((np.asarray(cents) ** 2).sum(1))
    dead_slot = jnp.zeros((C, P), bool)
    queries = jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32))

    args = (jnp.asarray(perm), mids, sq, cents, cent_sq, dead_slot, queries,
            5, nprobe, 0, 1)
    d_x, i_x = _dense_search_kernel(*args, use_pallas=False)
    d_p, i_p = _dense_search_kernel(*args, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=1e-5, atol=1e-3)


def test_group_block_dots_matches_einsum():
    rng = np.random.default_rng(6)
    C, P, D, Q, G, U = 9, 8, 128, 16, 4, 5
    NG = Q // G
    data_perm = jnp.asarray(rng.standard_normal((C, P, D)).astype(np.float32))
    queries = jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32))
    union = jnp.asarray(rng.integers(0, C, (NG, U)).astype(np.int32))

    got = pallas_kernels.group_block_dots(data_perm, queries, union,
                                          interpret=True)
    assert got.shape == (NG, U, G, P)
    want = jnp.einsum("gqd,gupd->guqp",
                      queries.reshape(NG, G, D), data_perm[union])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_group_block_dots_int8_exact():
    rng = np.random.default_rng(7)
    C, P, D, Q, G, U = 5, 32, 128, 64, 32, 3
    NG = Q // G
    data_perm = jnp.asarray(
        rng.integers(-127, 128, (C, P, D)).astype(np.int8))
    queries = jnp.asarray(rng.integers(-127, 128, (Q, D)).astype(np.int8))
    union = jnp.asarray(rng.integers(0, C, (NG, U)).astype(np.int32))

    got = pallas_kernels.group_block_dots(data_perm, queries, union,
                                          interpret=True)
    assert got.dtype == jnp.int32
    want = np.einsum("gqd,gupd->guqp",
                     np.asarray(queries, np.int64).reshape(NG, G, D),
                     np.asarray(data_perm, np.int64)[np.asarray(union)])
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_dense_grouped_kernel_pallas_vs_xla():
    """The grouped dense kernel must produce identical ids through both
    scoring paths."""
    from sptag_tpu.algo.dense import _dense_search_grouped_kernel

    rng = np.random.default_rng(8)
    C, P, D, Q, nprobe, G = 6, 16, 128, 16, 2, 4
    n = C * P
    data = rng.standard_normal((n, D)).astype(np.float32)
    perm = data.reshape(C, P, D)
    mids = jnp.asarray(np.arange(n, dtype=np.int32).reshape(C, P))
    sq = jnp.asarray((data ** 2).sum(1).astype(np.float32).reshape(C, P))
    cents = jnp.asarray(perm.mean(axis=1))
    cent_sq = jnp.asarray((np.asarray(cents) ** 2).sum(1))
    dead_slot = jnp.zeros((C, P), bool)
    queries = jnp.asarray(rng.standard_normal((Q, D)).astype(np.float32))

    args = (jnp.asarray(perm), mids, sq, cents, cent_sq, dead_slot, queries,
            jnp.int32(Q), 5, nprobe, 4, G, 0, 1)
    d_x, i_x = _dense_search_grouped_kernel(*args, use_pallas=False)
    d_p, i_p = _dense_search_grouped_kernel(*args, use_pallas=True,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("metric,base", [(0, 127), (1, 127)])
def test_dense_kernel_int8_pallas_vs_xla(metric, base):
    """int8 metric composition (L2 qn+sq-2dot / cosine base^2-dot) must be
    identical through the Pallas int path and the XLA fallback."""
    from sptag_tpu.algo.dense import _dense_search_kernel

    rng = np.random.default_rng(5)
    C, P, D, Q, nprobe = 4, 32, 128, 8, 2
    n = C * P
    data = rng.integers(-127, 128, (n, D)).astype(np.int8)
    perm = data.reshape(C, P, D)
    mids = jnp.asarray(np.arange(n, dtype=np.int32).reshape(C, P))
    sq = jnp.asarray(
        (data.astype(np.float32) ** 2).sum(1).reshape(C, P))
    cents = jnp.asarray(perm.astype(np.float32).mean(axis=1))
    cent_sq = jnp.asarray((np.asarray(cents) ** 2).sum(1))
    dead_slot = jnp.zeros((C, P), bool)
    queries = jnp.asarray(rng.integers(-127, 128, (Q, D)).astype(np.int8))

    args = (jnp.asarray(perm), mids, sq, cents, cent_sq, dead_slot, queries,
            5, nprobe, metric, base)
    d_x, i_x = _dense_search_kernel(*args, use_pallas=False)
    d_p, i_p = _dense_search_kernel(*args, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=0, atol=0)   # both exact integer dots
