"""Batched distance kernels — the TPU-native replacement for the reference's
hand-vectorized SIMD DistanceUtils (/root/reference/AnnService/inc/Core/Common/
DistanceUtils.h:36-623).

Where the reference computes one (vector, vector) distance per call with
SSE/AVX intrinsics, the TPU framework computes whole (Q, N) distance matrices
as a single MXU matmul in the expanded form ``||q||^2 + ||x||^2 - 2 q.x``, and
gathered candidate scores as (Q, C) batched contractions.  Conventions match
the reference exactly:

* L2 distance is the **squared** euclidean distance (reference
  ComputeL2Distance accumulates squared diffs and never takes a sqrt,
  DistanceUtils.h:236-404).
* Cosine distance is ``base^2 - dot`` for integer types (int8: 16129
  :452, uint8: 65025 :492, int16: 1073676289 :533) and ``1 - dot`` for float
  (:579), with stored vectors pre-normalized to length ``base`` at build time
  (Utils::Normalize, CommonUtils.h:93-108; BKTIndex.cpp:289-296).
* All accumulation is float32, as in the reference's SIMD paths (the `_mm_*`
  kernels convert lanes to float before the horizontal add).

int8/uint8 inputs use an int32-accumulating MXU dot
(`preferred_element_type`), which is exact.  int16 uses an EXACT
high/low-byte split by default (round-4, VERDICT item 5): a = 256*hi + lo
decomposes the dot into three int32-exact MXU contractions
(hi.hi, hi.lo + lo.hi, lo.lo — every partial provably fits int32 below
_INT16_EXACT_MAX_D dims), combined with ONE float32 rounding for L2 and
with int32 wraparound (exact, since |dot| <= base^2 < 2^31 on normalized
rows) for the integer-cosine convention.  This is strictly tighter than
the reference's own `_mm_madd_epi16` path (product pairs exact in int32,
then float32 accumulation, DistanceUtils.h:536) — the measured A/B
consequence of the old per-product-f32 rounding was direction-B int16
recall 0.934 (reports/AB_REFERENCE.md).  `set_int16_exact(False)` restores
plain f32 accumulation.  Floats accumulate in float32.
"""

from __future__ import annotations

import concurrent.futures
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sptag_tpu.core.types import DistCalcMethod, VectorValueType, base_of
from sptag_tpu.utils import host_cores

# Values considered "integer typed" for the base^2 - dot convention.
_INT_DTYPES = (jnp.int8, jnp.uint8, jnp.int16)

# Matmul precision for float32 contractions.  On TPU, "highest" runs the
# fp32-accurate multi-pass bf16 algorithm (parity with the reference's f32
# SIMD accumulate); callers chasing peak MXU throughput can lower it via
# set_float_precision("default") and re-validate recall.
_FLOAT_PRECISION = "highest"


def set_float_precision(precision: str) -> None:
    global _FLOAT_PRECISION
    _FLOAT_PRECISION = precision


def float_precision() -> str:
    return _FLOAT_PRECISION


def _is_int(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.integer)


# --- exact int16 (high/low byte split) -------------------------------------

_INT16_EXACT = True
# every partial sum fits int32 below this D: the worst partial is
# sum(lo*lo) <= D * 255^2, so D <= 2^31 / 65025 ~ 33k; halved for margin
_INT16_EXACT_MAX_D = 16384


def set_int16_exact(on: bool) -> None:
    global _INT16_EXACT
    _INT16_EXACT = bool(on)


def int16_exact() -> bool:
    return _INT16_EXACT


def _use_int16_exact(dtype, d: int) -> bool:
    return (_INT16_EXACT and jnp.dtype(dtype) == jnp.int16
            and d <= _INT16_EXACT_MAX_D)


def _int16_split(a: jax.Array):
    """a = 256*hi + lo with hi in [-128, 127] (arithmetic shift) and lo in
    [0, 255] — both int32, products of any two parts fit comfortably."""
    ai = a.astype(jnp.int32)
    return ai >> 8, ai & 255


def _int16_dot_parts(q, x, contract):
    """Three int32-exact contractions whose weighted sum is the exact
    int16 dot: dot = 2^16*hh + 2^8*(hi.lo + lo.hi) + ll.  The two mixed
    terms ride ONE contraction by concatenating along the reduced axis."""
    qh, ql = _int16_split(q)
    xh, xl = _int16_split(x)
    hh = contract(qh, xh)
    mixed = contract(jnp.concatenate([qh, ql], axis=-1),
                     jnp.concatenate([xl, xh], axis=-1))
    ll = contract(ql, xl)
    return hh, mixed, ll


def _int16_parts_f32(hh, mixed, ll) -> jax.Array:
    """Float32 combine: each partial is exact IN INT32; the int32->f32
    conversion of a partial itself rounds once |partial| > 2^24 (the
    ll term exceeds that for D >~ 258), so this path carries one
    rounding per partial conversion plus the weighted sum — still far
    tighter than one rounding PER PRODUCT in the plain f32 path, but
    not exact (ADVICE r4).  Exactness needs the i32 combine below."""
    return (65536.0 * hh.astype(jnp.float32)
            + 256.0 * mixed.astype(jnp.float32)
            + ll.astype(jnp.float32))


def _int16_parts_i32(hh, mixed, ll) -> jax.Array:
    """Int32 wraparound combine: EXACT whenever the true dot fits int32
    (int32 addition is associative mod 2^32, so intermediate wraps cancel)
    — guaranteed for the cosine convention, where rows are normalized to
    length base and Cauchy-Schwarz bounds |dot| <= base^2 < 2^31."""
    return ((hh << 16) + (mixed << 8) + ll).astype(jnp.int32)


def dot_kind(dtype, d: int) -> str:
    """Which contraction `pairwise_dot` traces for operands of `dtype`
    `d` wide — what the host counters `flat.dot_<kind>` are named after:
    "int8_native" (one-byte integers on the MXU, int32 accumulation,
    exact), "int16_split" (three int32-exact contractions of the high and
    low bytes) or "f32" (floats, and int16 past the split's guard)."""
    if exact_int_dot(dtype):
        return "int8_native"
    return "int16_split" if _use_int16_exact(dtype, d) else "f32"


def exact_int_dot(dtype) -> bool:
    """True for integer dtypes whose dot products accumulate exactly in
    int32 (int8/uint8: the bound D*255^2 cannot overflow).  int16 products
    reach 2^30 and must accumulate in float32 instead — the reference's
    own int16 SIMD convention."""
    return _is_int(dtype) and jnp.dtype(dtype).itemsize < 2


def pairwise_dot(q: jax.Array, x: jax.Array) -> jax.Array:
    """(Q, D) x (N, D) -> (Q, N) dot products, float32.

    int8/uint8 contract natively with int32 accumulation (exact: the
    bound D * 255^2 stays inside int32 up to D = 33,025, D * 128^2 up to
    131,071).  int16 accumulates in float32 like the
    reference's SIMD path (DistanceUtils.h int16 kernels convert lanes to
    float before the horizontal add): an int32 accumulator overflows on
    raw int16 L2 data (a single product reaches 2^30).  Floats contract
    in float32 on the MXU.

    int16 defaults to the exact high/low split (module docstring): three
    int32-exact contractions, then one f32 rounding per partial
    conversion plus the weighted sum (see _int16_parts_f32) — strictly
    tighter than both plain-f32 accumulation AND the reference's
    pair-exact `_mm_madd_epi16` + f32 horizontal add.  Falls back to
    plain f32 when disabled or beyond _INT16_EXACT_MAX_D dims.
    """
    dn = (((1,), (1,)), ((), ()))
    if exact_int_dot(q.dtype):
        if x.dtype != q.dtype:
            q, x = q.astype(jnp.int32), x.astype(jnp.int32)
        # one-byte operands of one type go to the contraction AS THEY
        # ARE: the MXU multiplies int8 natively and accumulates in int32,
        # and nothing asks for an int32 copy of a resident corpus block
        # (13.6 GB at 8.84M x 384)
        out = jax.lax.dot_general(q, x, dn,
                                  preferred_element_type=jnp.int32)
        return out.astype(jnp.float32)
    if _use_int16_exact(q.dtype, q.shape[-1]):
        def contract(a, b):
            return jax.lax.dot_general(a, b, dn,
                                       preferred_element_type=jnp.int32)
        return _int16_parts_f32(*_int16_dot_parts(q, x, contract))
    return jax.lax.dot_general(
        q.astype(jnp.float32), x.astype(jnp.float32), dn,
        precision=_FLOAT_PRECISION,
        preferred_element_type=jnp.float32)


def row_sqnorms(x: jax.Array) -> jax.Array:
    """(N, D) -> (N,) squared norms, float32 (exact int32 path for ints)."""
    if _is_int(x.dtype):
        xi = x.astype(jnp.int32)
        # int16^2 * D can overflow int32 for D >~ 2: split the square as
        # x^2 = 2^16 h^2 + 2^9 h*l + l^2 (each partial int32-exact) and
        # combine with one f32 rounding; plain f32 otherwise
        if x.dtype == jnp.int16:
            if _use_int16_exact(x.dtype, x.shape[-1]):
                h, low = _int16_split(x)
                return (65536.0 * jnp.sum(h * h, -1).astype(jnp.float32)
                        + 512.0 * jnp.sum(h * low, -1).astype(jnp.float32)
                        + jnp.sum(low * low, -1).astype(jnp.float32))
            xf = x.astype(jnp.float32)
            return jnp.sum(xf * xf, axis=-1)
        return jnp.sum(xi * xi, axis=-1).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=-1)


def pairwise_l2(q: jax.Array, x: jax.Array,
                x_sqnorm: Optional[jax.Array] = None) -> jax.Array:
    """(Q, D) x (N, D) -> (Q, N) **squared** L2 distances, float32.

    Expanded form rides the MXU; a precomputed ``x_sqnorm`` (cached on the
    index) avoids re-reducing the corpus every batch.  Clamped at 0 to guard
    the small negative residue of the expansion under float32 rounding.
    """
    qn = row_sqnorms(q)[:, None]
    xn = (row_sqnorms(x) if x_sqnorm is None else x_sqnorm)[None, :]
    d = qn + xn - 2.0 * pairwise_dot(q, x)
    return jnp.maximum(d, 0.0)


def pairwise_cosine(q: jax.Array, x: jax.Array, base: int) -> jax.Array:
    """(Q, D) x (N, D) -> (Q, N) cosine distances per reference convention:
    ``base^2 - dot`` (int) / ``1 - dot`` (float), both reduce to
    ``base^2 - dot`` with base=1 for float.

    int16 computes ``base^2 - dot`` ENTIRELY in int32 (exact): rows are
    normalized to length base=32767 so |dot| <= base^2 < 2^31 and the
    wraparound combine is exact.  The one rounding left is the FINAL
    int32->float32 conversion (the difference can reach 2*base^2 ~ 2^31,
    beyond f32's 2^24 exact-integer range — ADVICE r4), which costs at
    most 128 ulp-of-int on the largest distances; the f32-cancellation
    near base^2 that plagued the old path never happens."""
    if _use_int16_exact(q.dtype, q.shape[-1]):
        dn = (((1,), (1,)), ((), ()))

        def contract(a, b):
            return jax.lax.dot_general(a, b, dn,
                                       preferred_element_type=jnp.int32)
        dot = _int16_parts_i32(*_int16_dot_parts(q, x, contract))
        return (jnp.int32(int(base) * int(base)) - dot).astype(jnp.float32)
    return float(base) * float(base) - pairwise_dot(q, x)


def pairwise_distance(q: jax.Array, x: jax.Array, metric: DistCalcMethod,
                      value_type: Optional[VectorValueType] = None,
                      x_sqnorm: Optional[jax.Array] = None) -> jax.Array:
    """Metric dispatch, parity with DistanceUtils::ComputeDistance
    (DistanceUtils.h:582-589)."""
    metric = DistCalcMethod(metric)
    if metric == DistCalcMethod.L2:
        return pairwise_l2(q, x, x_sqnorm)
    if value_type is None:
        value_type = VectorValueType.Float if not _is_int(q.dtype) else {
            jnp.dtype(jnp.int8): VectorValueType.Int8,
            jnp.dtype(jnp.uint8): VectorValueType.UInt8,
            jnp.dtype(jnp.int16): VectorValueType.Int16,
        }[jnp.dtype(q.dtype)]
    return pairwise_cosine(q, x, base_of(value_type))


def batched_gathered_distance(q: jax.Array, cand: jax.Array,
                              metric: DistCalcMethod, base: int,
                              cand_sqnorm: Optional[jax.Array] = None
                              ) -> jax.Array:
    """(Q, D) queries x (Q, C, D) per-query gathered candidates -> (Q, C)
    distances, float32.  The adjacency-gather scoring step of the beam-search
    engine (the reference computes these one at a time in its frontier loop,
    BKTIndex.cpp:145-152).  L2 needs the candidates' squared norms:
    without `cand_sqnorm` they are the float32 square sum of `cand`
    itself, so the result is |q - cand|^2 of the operands as given (the
    walk's body: the block is on the chip and the compiler sums it in
    the pass that contracts it, where fetching a cached norm by id is a
    second gather); with `cand_sqnorm` (Q, C) the caller states them —
    the exact re-rank passes the float32 rows' cached norms, and seeds
    scored against a quantised `cand` pass the norms of the space they
    are merged in."""
    metric = int(metric)
    if _is_int(q.dtype):
        if not exact_int_dot(q.dtype):
            if _use_int16_exact(q.dtype, q.shape[-1]):
                # exact int16 split (module docstring); cosine combines
                # fully in int32, L2 pays one f32 rounding per term
                def contract(a, b):
                    return jnp.einsum("qd,qcd->qc", a, b,
                                      preferred_element_type=jnp.int32)
                parts = _int16_dot_parts(q, cand, contract)
                if metric == int(DistCalcMethod.Cosine):
                    return (jnp.int32(int(base) * int(base))
                            - _int16_parts_i32(*parts)
                            ).astype(jnp.float32)
                dot = _int16_parts_f32(*parts)
                qn = row_sqnorms(q)[:, None]
                if cand_sqnorm is None:
                    cand_sqnorm = row_sqnorms(cand)
                return jnp.maximum(qn + cand_sqnorm - 2.0 * dot, 0.0)
            # int16 fallback: float32 accumulation (int32 overflows on
            # raw int16 data beyond the exact-path D guard)
            dot = jnp.einsum("qd,qcd->qc", q.astype(jnp.float32),
                             cand.astype(jnp.float32),
                             precision=_FLOAT_PRECISION,
                             preferred_element_type=jnp.float32)
        else:
            dot = jnp.einsum(
                "qd,qcd->qc", q.astype(jnp.int32), cand.astype(jnp.int32),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        if metric == int(DistCalcMethod.Cosine):
            return float(base) * float(base) - dot
        qf = q.astype(jnp.float32)
        qn = jnp.sum(qf * qf, axis=-1)[:, None]
        if cand_sqnorm is None:
            cf = cand.astype(jnp.float32)
            cand_sqnorm = jnp.sum(cf * cf, axis=-1)
        return jnp.maximum(qn + cand_sqnorm - 2.0 * dot, 0.0)
    if q.dtype == jnp.bfloat16 and cand.dtype == jnp.bfloat16:
        # bf16 walk-scoring path (engine BeamScoreDtype=bf16): contract the
        # native bf16 inputs on the MXU with f32 accumulation — half the
        # gather bytes of the f32 path; callers re-rank the final pool in
        # f32 so result distances stay exact
        qf, cf = q, cand
    else:
        qf = q.astype(jnp.float32)
        cf = cand.astype(jnp.float32)
    dot = jnp.einsum("qd,qcd->qc", qf, cf, precision=_FLOAT_PRECISION,
                     preferred_element_type=jnp.float32)
    if metric == int(DistCalcMethod.Cosine):
        return 1.0 - dot
    qn = jnp.sum(qf.astype(jnp.float32) ** 2, axis=-1)[:, None]
    if cand_sqnorm is None:
        cand_sqnorm = jnp.sum(cf.astype(jnp.float32) ** 2, axis=-1)
    return jnp.maximum(qn + cand_sqnorm - 2.0 * dot, 0.0)


#: elements of a `normalize` block: 8 MB of float64, so the ingest of a
#: corpus never holds a float64 copy of the whole (27 GB at 8.84M x 384)
NORMALIZE_BLOCK_ELEMENTS = 1 << 20


def _normalize_span(out: np.ndarray, vectors: np.ndarray, base: int,
                    rows: int) -> None:
    """`out` <- the rows of `vectors` (n, D) scaled to length `base` and
    C-cast to `out`'s type, `rows` rows at a time through TWO float64
    scratch blocks allocated once and written in place.  Nothing
    block-sized is allocated per block: five float64 temporaries made and
    released by every worker for every block ended the build of 8.84M x
    384 rows at the chip host's 40 GiB (its sandbox gives freed mappings
    back late); in place the whole run peaks at 17 GB there (PR 34)."""
    d = vectors.shape[1]
    f = np.empty((rows, d), np.float64)
    sq = np.empty_like(f)
    constant = (1.0 / np.sqrt(d)) * base
    for lo in range(0, vectors.shape[0], rows):
        block = vectors[lo:lo + rows]
        fb, sb = f[:len(block)], sq[:len(block)]
        np.copyto(fb, block)
        np.multiply(fb, fb, out=sb)
        norms = np.sqrt(np.sum(sb, axis=-1, keepdims=True))
        np.divide(fb, np.maximum(norms, 1e-30), out=fb)
        np.multiply(fb, base, out=fb)
        zero = norms[:, 0] < 1e-6
        if zero.any():
            fb[zero] = constant
        np.copyto(out[lo:lo + rows], fb, casting="unsafe")    # a C cast


def normalize(vectors: np.ndarray, base: int) -> np.ndarray:
    """Host-side ingest normalization, parity with Utils::Normalize
    (CommonUtils.h:93-108): scale each row to length `base`, casting back to
    the storage dtype (a C cast: integers are TRUNCATED); zero-norm rows
    become the constant vector ``base/sqrt(D)``.

    Rows are independent: a matrix of more than one block's elements is
    split into one span of rows for each core this process may run on
    (numpy releases the interpreter lock inside the operations), and a
    span goes block by block through its worker's own scratch
    (`_normalize_span`) - the same float64 operations in the same order
    whatever the split, at two blocks of float64 a core."""
    vectors = np.asarray(vectors)
    x = vectors.reshape(-1, vectors.shape[-1])
    out = np.empty_like(x)
    n, d = x.shape
    rows = max(1, NORMALIZE_BLOCK_ELEMENTS // d)
    workers = min(-(-n // rows), host_cores())
    if workers <= 1:
        _normalize_span(out, x, base, max(1, min(n, rows)))
    else:
        span = -(-n // workers)

        def work(lo: int) -> None:
            _normalize_span(out[lo:lo + span], x[lo:lo + span], base, rows)

        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            list(pool.map(work, range(0, n, span)))
    return out.reshape(vectors.shape)


def convert_cosine_similarity_to_distance(cs):
    """Parity: DistanceUtils::ConvertCosineSimilarityToDistance
    (DistanceUtils.h:591-597)."""
    return 1.0 - cs


@functools.partial(jax.jit, static_argnames=("k",))
def batch_topk(dists: jax.Array, k: int):
    """(Q, N) distances -> ((Q, k) dists ascending, (Q, k) int32 indices)."""
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, idx.astype(jnp.int32)
