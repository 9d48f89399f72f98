"""FLAT — exact brute-force index on the MXU.

No reference counterpart (SPTAG has only BKT/KDT); this is the framework's
minimum end-to-end slice (SURVEY.md §7 step 3): exact top-K as one
``(Q,D)x(N,D)`` matmul + `lax.top_k` per query batch.  It also serves as the
ground-truth oracle for recall tests and as the search path for not-yet-merged
delta rows in the mutable graph indexes.

Device layout: the corpus lives as one resident (slots, D) jax.Array block
(rows padded to a lane-friendly multiple); deletes, padding and slots not yet
taken are folded into the top-k as +inf distances (the reference filters
tombstones in its hot loop instead, BKTIndex.cpp:234-239 — on TPU a masked
dense top-k is cheaper than divergent control flow).  Mutation follows the
single-writer design (SURVEY.md §2b P7) and the device state follows the
mutations: an add writes its rows, their norms and their mask bits into
the block IN PLACE, a delete its mask bits (`_block_write_rows`,
`_block_mask_rows`: the block's arrays are donated to the write, so what
crosses to the device is what changed); slots are reserved ahead of the
rows (`reserved_slots`), so between two growths every program keeps its
shape.  A wholesale change (build, load, refine) still marks the block
dirty and the next search places a fresh one.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sptag_tpu.core.index import MAX_DIST, VectorIndex, register_algo
from sptag_tpu.core.params import FlatParams
from sptag_tpu.core.types import (
    DeviceTopK,
    DistCalcMethod,
    IndexAlgoType,
    VectorValueType,
    dtype_of,
)
from sptag_tpu.io import format as fmt
from sptag_tpu.ops import cascade
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops import pallas_kernels, topk_bins
from sptag_tpu.utils import devmem, metrics, round_up, trace

_ROW_PAD = 128      # pad corpus rows to multiples of this (TPU lane width)
_QUERY_BUCKETS = (1, 8, 32, 128, 512)


def pad_rows(n: int) -> int:
    """Row slots of the device block that holds `n` corpus rows."""
    return max(_ROW_PAD, round_up(n, _ROW_PAD))


# An in-place write carries its rows at one of these counts (pad rows are
# written masked into slots nothing occupies yet): a few programs a block,
# whatever sizes the adds come in.  More rows than the top rung go in
# pieces of it.
_WRITE_RUNGS = (8, 128, 1024)
# Slots reserved ahead of the rows when a block has to grow: a sixteenth
# of the rows (the scan reads every slot, so the reserve is what an empty
# slot costs a search: 6 %), and no fewer than this many.
_RESERVE_SHARE, _RESERVE_MIN = 16, 8192


def reserved_slots(need: int) -> int:
    """Row slots of a block grown to hold `need` rows and the adds to
    come: a rule of the rows alone, as the host buffer's doubling is
    (`FlatIndex._reserve`)."""
    return pad_rows(need + max(need // _RESERVE_SHARE, _RESERVE_MIN))


def _write_pieces(rows: int) -> list:
    """`rows` rows as the in-place writes that carry them: (first row,
    rows, rung) a write, whole top rungs and then the rest at its own."""
    top = _WRITE_RUNGS[-1]
    return [(lo, min(top, rows - lo),
             next(r for r in _WRITE_RUNGS if min(top, rows - lo) <= r))
            for lo in range(0, rows, top)]


def _query_bucket(q: int) -> int:
    for b in _QUERY_BUCKETS:
        if q <= b:
            return b
    return round_up(q, _QUERY_BUCKETS[-1])


def pad_to_bucket(queries: np.ndarray) -> np.ndarray:
    """`queries` with zero rows appended up to its rung of the query
    ladder: every served batch size runs one of a few compiled programs
    (the caller slices the answers back to its own row count)."""
    q = queries.shape[0]
    q_pad = _query_bucket(q)
    if q_pad == q:
        return queries
    return np.concatenate(
        [queries, np.zeros((q_pad - q, queries.shape[1]), queries.dtype)],
        axis=0)


# columns a group of the two-stage select holds: a lane tile
_GROUP = pallas_kernels.SCAN_GROUP
# When the two-stage select pays, as the chip showed it (PERF.md section
# 6, PR 28; v5e, whole scan programs).  Rows at least this many times
# k*_GROUP wide: below, one top-k over the row is no slower (1M x 128 at
# 128 queries, k=10: 0.71 against 0.83 ms at 131,072 columns, 2.58 against
# 6.22 at 1M).
_TWO_STAGE_MIN_WIDTH = 100
# Query blocks that fill the 128 lanes or fit one 8-row sublane tile: in
# between, the transposed scores pad every row to 128 lanes, the passes
# cost what 128 queries cost, and the N-wide top-k, which shrinks with
# the queries, stays cheaper (32 queries: 3.22 against 2.11 ms).
_SUBLANES, _LANES = 8, 128
# A chosen group is fetched for all Q queries at once, Q*Q*k*512 bytes of
# slabs: 1.3 GB at 512 queries and k=10 (13.99 against 22.78 ms), four
# times that at the next rung of the query ladder.
_TWO_STAGE_MAX_QUERIES = 512


def select_stages(q: int, n: int, k: int) -> int:
    """How many stages the exact selection of the `k` smallest of `q`
    rows `n` wide takes: 2 (`exact_topk`'s group minima) where the chip
    showed them to pay, else 1 (one `lax.top_k`: delta-row scans, small
    corpora, `k` near `n`).  A function of the traced shape alone — the
    host counters `flat.select_two_stage` / `flat.select_single` ask it
    what the program they dispatch was traced as."""
    wide = n >= _TWO_STAGE_MIN_WIDTH * k * _GROUP
    block = q <= _SUBLANES or _LANES <= q <= _TWO_STAGE_MAX_QUERIES
    return 2 if wide and block else 1


def count_select(q: int, n: int, k: int) -> None:
    """One dispatched exact scan of `q` rows `n` wide, counted by the
    stage count its program was traced with."""
    if select_stages(q, n, k) == 2:
        metrics.inc("flat.select_two_stage")
    else:
        metrics.inc("flat.select_single")


def count_dot(dtype, d: int) -> None:
    """One dispatched scan of rows of `dtype`, `d` wide, counted by the
    contraction its program was traced with (`distance.dot_kind`).  The
    names are literals: graftlint GL602 keeps metric names bounded."""
    kind = dist_ops.dot_kind(dtype, d)
    if kind == "int8_native":
        metrics.inc("flat.dot_int8_native")
    elif kind == "int16_split":
        metrics.inc("flat.dot_int16_split")
    else:
        metrics.inc("flat.dot_f32")


# Groups the proved select chooses beyond k, a rule of k alone: the
# (k + _SPARE_GROUPS + 1)-th smallest group minimum has to clear the k-th
# answer by the filter's `eps` (`_select_from_groups`), and on rows that do
# not tie it does so by orders of magnitude with one spare; the rest buy
# duplicated rows (up to _SPARE_GROUPS copies of a neighbour in groups of
# their own) at 128 re-scored rows a query each.
_SPARE_GROUPS = 6


def fused_minima(dtype, q: int, n: int, d: int, k: int, metric: int,
                 platform: str) -> bool:
    """Whether the exact scan of `q` queries over `n` rows of `dtype`,
    `d` wide, takes its group minima from the pass that computes the
    distances (`pallas_kernels.scan_group_minima`) and never holds the
    (q, n) scores: a two-stage select at a query block of whole 128-lane
    tiles (the 128 and 512 rungs), rows in whole groups of whole lane
    tiles, on a TPU, and either

    - one-byte rows in the integer cosine, `d` in whole lane tiles, where
      a re-scored row is the very number the scan saw (int32
      accumulation, `base^2 - dot` exact in float32), or
    - float32 rows under L2, a lane tile wide at most or in whole lane
      tiles (narrower than one they are resident column-major and the
      kernel reads the transpose; `d` = 96, 100, 128 in the cells), where
      the kernel's minima are a FILTER: another schedule of the float32
      contraction than the re-score's, within a stated `eps` of it, so
      the selection is proved on the device a query and a run with a
      query unproved answers from the materialised scores
      (`_select_from_groups`; `flat.scan_margin_unproved` counts them).

    L2 on one-byte rows, float cosine and int16 stay materialised (no
    cell runs them).  A function of what can be seen BEFORE the call, as
    `pallas_kernels.supported` is: no switch turns the route off after a
    failure.  The host counters `flat.scan_fused_minima` /
    `flat.scan_materialized` ask it what the program they dispatch was
    traced as."""
    staged = (select_stages(q, n, k) == 2 and q % _LANES == 0
              and n % _GROUP == 0 and platform in ("tpu", "interpret"))
    int8_cosine = (dist_ops.dot_kind(dtype, d) == "int8_native"
                   and metric == int(DistCalcMethod.Cosine)
                   and d % _LANES == 0)
    float_l2 = (np.dtype(dtype) == np.float32
                and metric == int(DistCalcMethod.L2)
                and (d % _LANES == 0 or d < _LANES))
    return staged and (int8_cosine or float_l2)


def proved_form(fused: bool, dtype) -> bool:
    """Whether a scan on the fused route (`fused_minima` passed it) is
    the float32 form: minima as a filter, the answer from the re-score,
    the selection proved, the run's flag a third output."""
    return fused and np.dtype(dtype) == np.float32


def scan_route(dtype, q: int, n: int, d: int, k: int, metric: int) -> dict:
    """`fused_minima` here and now, as the static arguments `fused` /
    `interpret` of the scan programs (`_flat_search_kernel`;
    `_sharded_search_kernel`, where `n` is one shard's block)."""
    fused = fused_minima(dtype, q, n, d, k, metric,
                         pallas_kernels.platform())
    return {"fused": fused,
            "interpret": fused and pallas_kernels.interpret()}


def count_route(fused: bool) -> None:
    """One dispatched exact scan, counted by where its group minima came
    from (`fused_minima`)."""
    if fused:
        metrics.inc("flat.scan_fused_minima")
    else:
        metrics.inc("flat.scan_materialized")


def count_unproved(unproved) -> None:
    """One fused scan whose proved select left a query unproved, so that
    the run answered from the materialised scores: the flag the program
    returned beside its answers (`ProvedTopK.unproved`), read where the
    host reads the answers."""
    if bool(np.asarray(unproved)):
        metrics.inc("flat.scan_margin_unproved")


def exact_topk(d, k: int):
    """The `k` smallest of every row of `d` (Q, N), ascending, with their
    columns: `lax.top_k(-d, k)`'s answer BIT FOR BIT (values, columns,
    lowest column first among equals), without sorting N-wide rows.

    Wide rows take two stages.  (1) The minimum of every group of
    `_GROUP` consecutive columns, one pass over `d`.  (2) `lax.top_k` over
    the (Q, N/_GROUP) minima picks the k groups with the smallest minima,
    lowest group first among equals.  (3) Those groups' columns — and the
    tail past the last whole group, a group of its own that is always
    kept — meet in ascending column order in one `lax.top_k` over
    k*_GROUP (+ tail) candidates.

    Exact by construction: a column x of an unchosen group g cannot be
    among the k smallest, because each of the k chosen groups h holds a
    column <= min(g) <= x, and where it is equal h < g, so that column
    lies left of x: k columns precede x in (value, column) order.  The
    candidates meet in column order, so the last top-k breaks ties as the
    N-wide one would.

    Everything reads `d` through its transpose, the query as the minor
    dimension: there a group is _GROUP whole rows, its minimum an
    elementwise one, and a chosen group is fetched as the (_GROUP, Q) slab
    that holds it for every query — one contiguous piece — of which the
    query's own lane is kept.  (Laid out query-major, the chip's compiler
    transposes all of `d` for the reduction and once more for the gather;
    a corpus placed in whole groups, as the snapshots and the mesh shards
    are, has no tail to slice the groups off.)

    Stages 2 and 3 are `_select_from_groups`, which `scan_topk`'s fused
    route shares: there the minima come out of the distance pass and the
    chosen groups' columns are scored again, and `d` never exists.  On
    one-byte cosine rows the minima are the re-score's own numbers and
    the proof above holds as it stands; on float32 L2 rows they are a
    FILTER within `eps` of them, k + `_SPARE_GROUPS` groups are chosen
    and the proof carries the margin (`_select_from_groups`)."""
    q, n = d.shape
    if select_stages(q, n, k) == 1:
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, idx
    groups, tail = divmod(n, _GROUP)
    dt = d.T
    grouped = (dt[:groups * _GROUP] if tail else dt).reshape(
        groups, _GROUP, q)

    def columns(chosen):
        slabs = jnp.take(grouped, chosen.reshape(-1), axis=0, mode="clip")
        own = jnp.eye(q, dtype=bool)[:, None, None, :]
        return jnp.where(own, slabs.reshape(q, k, _GROUP, q),
                         jnp.float32(np.inf)).min(axis=3).reshape(
                             q, k * _GROUP)

    return _select_from_groups(
        grouped.min(axis=1), columns, k,
        dt[groups * _GROUP:].T if tail else None)


def _select_from_groups(minima, columns, k: int, tail=None, spare: int = 0,
                        eps=None):
    """Stages 2 and 3 of `exact_topk`, whose proof this is the code of:
    `minima` (groups, Q) holds every group's smallest score a query,
    `columns(chosen)` gives the scores of the (Q, k + spare) chosen
    groups' columns as (Q, (k + spare)*_GROUP), groups ascending, `tail`
    (Q, t) the scores past the last whole group.  Where the minima and
    the columns come from is the caller's: the materialised scores
    (`exact_topk`) or the fused scan and a re-score (`scan_topk`).

    With `eps` (Q,) the minima are a FILTER and the select proves itself:
    -> (values, columns, proved (Q,) bool).  Let s(x) be the score
    `columns` computes for column x and s~(x) the one the minima were
    taken of, |s~(x) - s(x)| <= eps for every valid x of the block
    (`pallas_kernels.l2_minima_eps`: (4 d + 32) 2^-24 (|q|^2 + max
    |x|^2); a masked x reads MAX_DIST on both sides).  The k' = k + spare
    groups with the smallest minima are chosen and ALL their columns
    scored with s; v_k is the k-th smallest of those scores, m the
    (k' + 1)-th smallest minimum.  Every column x of an unchosen group g
    has s(x) >= s~(x) - eps >= min~(g) - eps >= m - eps.  So where
    m - eps > v_k no column of an unchosen group can precede the k-th
    answer in (value, column) order: the k smallest of the candidates, met
    in column order, are `lax.top_k` of s over ALL columns, bit for bit.
    Where it does not hold nothing is claimed: `proved` is False and the
    caller answers otherwise."""
    groups, kc = minima.shape[0], k + spare
    neg_min, chosen = jax.lax.top_k(-minima.T, kc)
    if eps is not None:
        # m: the smallest (minimum, group) pair past the last chosen one
        # (`top_k` takes the lowest group first among equals).  Read off
        # the minima in one elementwise pass, and from `top_k`'s outputs
        # WHOLE: a slice of them (one more rank, or `[:, k - 1]` below)
        # turns the chip's partial TopK into a sort of the whole row
        # (4.1 ms over 41,505 groups where TopK takes 0.04)
        last_min = jnp.max(-neg_min, axis=1)
        last = jnp.max(jnp.where(-neg_min == last_min[:, None], chosen, -1),
                       axis=1)
        group_of = jax.lax.broadcasted_iota(jnp.int32, minima.shape, 0)
        m = jnp.min(jnp.where(
            (minima > last_min[None, :])
            | ((minima == last_min[None, :]) & (group_of > last[None, :])),
            minima, jnp.float32(np.inf)), axis=0)
    chosen = jnp.sort(chosen, axis=1)                   # column order
    cand = columns(chosen)
    if tail is not None:
        cand = jnp.concatenate([cand, tail], axis=1)
    neg, pos = jax.lax.top_k(-cand, k)
    group = jnp.take_along_axis(chosen, jnp.minimum(pos // _GROUP, kc - 1),
                                axis=1)
    cols = jnp.where(pos < kc * _GROUP, group * _GROUP + pos % _GROUP,
                     pos + (groups - kc) * _GROUP)
    if eps is None:
        return -neg, cols
    return -neg, cols, m - eps > jnp.max(-neg, axis=1)


def _rescored_columns(data, sqnorm, invalid, queries, kc: int, metric: int,
                      base: int, interpret: bool = False):
    """`_select_from_groups`' `columns` for the fused route: the chosen
    groups' rows, `kc` contiguous slabs of _GROUP rows a query, gathered
    from the resident block and scored again under the mask: the integer
    cosine (exact, so the very numbers the scan took its minima of) or
    float32 L2 at `highest` with the cached norms (the numbers that are
    returned)."""
    q = queries.shape[0]
    groups = data.shape[0] // _GROUP
    l2 = metric == int(DistCalcMethod.L2)

    def columns(chosen):
        rows = pallas_kernels.group_rows(data, chosen, interpret)
        norms = (jnp.take(sqnorm.reshape(groups, _GROUP), chosen,
                          axis=0).reshape(q, kc * _GROUP) if l2 else None)
        d = dist_ops.batched_gathered_distance(
            queries, rows.reshape(q, kc * _GROUP, -1),
            DistCalcMethod(metric), base, norms)
        dead = jnp.take(invalid.reshape(groups, _GROUP), chosen, axis=0)
        return jnp.where(dead.reshape(q, kc * _GROUP),
                         jnp.float32(MAX_DIST), d)

    return columns


def _masked_ids(dists, idx):
    return jnp.where(dists >= jnp.float32(MAX_DIST), -1,
                     idx).astype(jnp.int32)


def scan_topk(data, sqnorm, invalid, queries, k: int, metric: int,
              base: int, approx: bool = False, recall_target: float = 0.99,
              binned_bins: int = 0, fused: bool = False,
              interpret: bool = False):
    """Distance matrix -> mask -> top-k of one resident block of rows:
    THE scan body, traced into the one-chip program
    (`_flat_search_kernel`) and, per shard, into the mesh program
    (parallel/sharded.py `_sharded_search_kernel`), so a distance or
    top-k change reaches both.  -> ((Q, k) float32 distances, (Q, k)
    int32 row ids of THIS block; -1 where only masked rows were left).

    `fused` (the caller's `fused_minima`, decided before the call): the
    exact select's group minima come out of the distance pass itself
    (`pallas_kernels.scan_group_minima`; `interpret` runs it on the CPU)
    and the chosen groups are scored again, and no (Q, N) matrix is
    written.  One-byte cosine rows: the same answers bit for bit.
    Float32 L2 rows (`_proved_scan`): one more output, the run's
    `unproved` flag."""
    if proved_form(fused, data.dtype):
        return _proved_scan(data, sqnorm, invalid, queries, k, base,
                            interpret)
    # the scope names are what a profiler trace calls the two stages
    # (benchmark kernel.topk_ms_per_batch reads `flat.topk`): kernel PRs
    # keep them
    with jax.named_scope("flat.distance"):
        if fused:
            minima = pallas_kernels.scan_group_minima(
                data, invalid, queries, base=base, interpret=interpret)
        else:
            if metric == int(DistCalcMethod.L2):
                d = dist_ops.pairwise_l2(queries, data, sqnorm)
            else:
                d = dist_ops.pairwise_cosine(queries, data, base)
            d = jnp.where(invalid[None, :], jnp.float32(MAX_DIST), d)
    with jax.named_scope("flat.topk"):
        if fused:
            dists, idx = _select_from_groups(
                minima, _rescored_columns(data, sqnorm, invalid, queries, k,
                                          metric, base, interpret), k)
        elif binned_bins:
            dists, idx = topk_bins.binned_topk(d, k, binned_bins)
        elif approx:
            neg, idx = jax.lax.approx_max_k(-d, k,
                                            recall_target=recall_target)
            dists = -neg
        else:
            dists, idx = exact_topk(d, k)
        ids = _masked_ids(dists, idx)
    return dists, ids


def _proved_scan(data, sqnorm, invalid, queries, k: int, base: int,
                 interpret: bool):
    """`scan_topk`'s fused route on float32 rows under L2 -> (distances,
    ids, unproved).  The kernel's minima FILTER the groups, k +
    _SPARE_GROUPS of them a query are scored again at `highest` from the
    cached norms - the only numbers that are returned: each the float32
    distance of the id it comes with, the list `lax.top_k` of those
    scores over the whole block - and the selection is proved a query
    (`_select_from_groups`).  What is not proved is not returned: with
    any query of the run unproved (`unproved`, a scalar the host counts:
    `count_unproved`) the SAME program answers from the materialised
    scores instead, as the unfused route does (`lax.cond`: no second
    dispatch, nothing compiled later; its (Q, N) temporary is the
    program's as it was the unfused program's)."""
    l2 = int(DistCalcMethod.L2)
    with jax.named_scope("flat.distance"):
        minima = pallas_kernels.scan_group_minima(
            data, invalid, queries, base=base, interpret=interpret,
            sqnorm=sqnorm)
    with jax.named_scope("flat.topk"):
        eps = pallas_kernels.l2_minima_eps(
            data.shape[1], dist_ops.row_sqnorms(queries), sqnorm)
        dists, idx, proved = _select_from_groups(
            minima, _rescored_columns(data, sqnorm, invalid, queries,
                                      k + _SPARE_GROUPS, l2, base,
                                      interpret), k,
            spare=_SPARE_GROUPS, eps=eps)
        ids = _masked_ids(dists, idx)
        unproved = jnp.logical_not(jnp.all(proved))
    dists, ids = jax.lax.cond(
        unproved,
        lambda: scan_topk(data, sqnorm, invalid, queries, k, l2, base),
        lambda: (dists, ids))
    return dists, ids, unproved


class ProvedTopK(NamedTuple):
    """What a scan program on the proved route returns (`_proved_scan`):
    `DeviceTopK`'s pair and the run's flag.  Every other program returns
    `DeviceTopK` as before: its StableHLO is what it was."""

    dists: jax.Array
    ids: jax.Array
    unproved: jax.Array


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "base", "approx",
                                    "recall_target", "binned_bins", "fused",
                                    "interpret"))
def _flat_search_kernel(data, sqnorm, invalid, queries, k: int,
                        metric: int, base: int, approx: bool = False,
                        recall_target: float = 0.99,
                        binned_bins: int = 0, fused: bool = False,
                        interpret: bool = False):
    """One fused program: distance matrix -> mask -> top-k (`scan_topk`).

    `approx=True` selects `lax.approx_max_k` — the TPU's hardware-
    accelerated partial-reduction top-k (the peak-FLOP/s KNN recipe of
    arXiv:2206.14286, PAPERS.md): the (Q, N) selection stops being the
    bottleneck of the exact scan at large N.  Per-op `recall_target`
    (the ApproxRecallTarget parameter — previously a hard-coded 0.99);
    the handful of true neighbors it may miss are beyond the exactness
    contract the `ApproxTopK` parameter explicitly trades away.

    `binned_bins` > 0 selects the portable bin-reduction top-k instead
    (ops/topk_bins.py, BinnedTopK): same coarse-select shape, but it
    accelerates every backend — `approx_max_k` lowers to a full sort
    off-TPU.  When both are set, binned wins (it subsumes the recipe).

    `fused` / `interpret`: the exact select's route, the caller's
    `fused_minima` (see `scan_topk`; on float32 L2 rows the answer is
    `ProvedTopK`)."""
    answer = ProvedTopK if proved_form(fused, data.dtype) else DeviceTopK
    return answer(*scan_topk(data, sqnorm, invalid, queries, k, metric, base,
                             approx, recall_target, binned_bins, fused,
                             interpret))


def exact_device_scan(data_d, sqnorm_d, invalid_d, queries: np.ndarray,
                      k: int, metric: int, base: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact masked scan at a bucketed query batch — THE ground-truth
    oracle shared by FlatIndex and the graph indexes'
    `exact_search_batch` (the quality monitor's shadow path,
    utils/qualmon.py).  Always the exact kernel: ApproxTopK and
    SketchPrefilter never apply here, whatever the index is configured
    to serve with — an oracle that inherited the approximations it is
    supposed to measure would be no oracle at all.  Rides the
    `flat.scan` kernel family (no jit site of its own)."""
    q = queries.shape[0]
    k_eff = min(k, data_d.shape[0])
    queries = pad_to_bucket(queries)
    dists, ids = _flat_search_kernel(
        data_d, sqnorm_d, invalid_d, jnp.asarray(queries), k_eff, metric,
        base, approx=False,
        **scan_route(data_d.dtype, queries.shape[0], *data_d.shape, k_eff,
                     metric))[:2]
    return np.asarray(dists)[:q], np.asarray(ids)[:q]


# canonical sketch packer now lives with the tiered cascade (ops/
# cascade.py, ISSUE 14) — the standalone SketchPrefilter and the
# cascade's sketch tier must pack identical bits
_pack_sign_bits = cascade.pack_sign_bits

_PACK_JIT = jax.jit(_pack_sign_bits)    # one wrapper -> shape-keyed cache

_INT_SQNORMS = jax.jit(dist_ops.row_sqnorms)


# ---------------------------------------------------------------------------
# the resident block's in-place writes
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _block_write_rows(data, sqnorm, invalid, rows, dead, start):
    """`rows` (a write rung, D), their norms and their mask bits `dead`
    written at slots [start, start + rung) of the donated block: the
    arrays are updated where they lie, and the host sends the rows, one
    byte a row of mask and `start`."""
    return (jax.lax.dynamic_update_slice(data, rows, (start, 0)),
            jax.lax.dynamic_update_slice(
                sqnorm, dist_ops.row_sqnorms(rows), (start,)),
            jax.lax.dynamic_update_slice(invalid, dead, (start,)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _block_mask_rows(invalid, slots):
    """Mask bits of `slots` (a write rung of int32; a slot past the end
    is dropped: the rung's padding) set in the donated mask."""
    return invalid.at[slots].set(True, mode="drop")


@functools.partial(jax.jit, static_argnames=("slots",))
def _block_grown(data, sqnorm, invalid, slots: int):
    """The block copied on the device into one of `slots` row slots, the
    new ones zero and masked.  Nothing is donated: the shapes differ."""
    extra = slots - data.shape[0]
    return (jnp.pad(data, ((0, extra), (0, 0))), jnp.pad(sqnorm, (0, extra)),
            jnp.pad(invalid, (0, extra), constant_values=True))


_CAL_SAMPLE = 64        # rows sampled as self-queries for calibration
_CAL_K = 10             # neighbor depth the shortlist is calibrated to


@functools.partial(jax.jit, static_argnames=("k", "metric", "base"))
def _sketch_cal_kernel(data, sqnorm, invalid, sketches, mean, queries,
                       k: int, metric: int, base: int):
    """Sketch-rank calibration: for each sample query, find its exact
    top-k rows, then count the corpus rows whose sketch Hamming distance
    is <= the WORST true neighbor's — the shortlist size R the prefilter
    would need to keep all k of them (<= counts ties conservatively:
    top_k's tie order is by index, which the sketch scan does not share).
    Returns (S,) int32 required-R per query."""
    if metric == int(DistCalcMethod.L2):
        d = dist_ops.pairwise_l2(queries, data, sqnorm)
    else:
        d = dist_ops.pairwise_cosine(queries, data, base)
    d = jnp.where(invalid[None, :], jnp.float32(MAX_DIST), d)
    _, topk = jax.lax.top_k(-d, k)                       # (S, k)
    qbits = _pack_sign_bits(queries.astype(jnp.float32) - mean[None, :])
    ham = jnp.zeros((queries.shape[0], sketches.shape[0]), jnp.int32)
    for w in range(sketches.shape[1]):
        ham = ham + jax.lax.population_count(
            jnp.bitwise_xor(qbits[:, w:w + 1], sketches[None, :, w]))
    ham = jnp.where(invalid[None, :], jnp.int32(1 << 30), ham)
    worst = jnp.take_along_axis(ham, topk, axis=1).max(axis=1,
                                                       keepdims=True)
    return (ham <= worst).sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "R", "metric", "base"))
def _flat_sketch_kernel(data, sqnorm, invalid, sketches, mean, queries,
                        k: int, R: int, metric: int, base: int):
    """Sketch-shortlist exact search: XOR+popcount Hamming scan over the
    packed sign sketches (1/32 of the corpus scan bytes), `lax.top_k`
    shortlist of R rows, exact distances on the gathered rows only, final
    top-k.  The Hamming accumulation unrolls over the W words so the
    (Q, N) running sum is the only large intermediate — never (Q, N, W).
    """
    Q = queries.shape[0]
    qbits = _pack_sign_bits(queries.astype(jnp.float32) - mean[None, :])
    W = sketches.shape[1]
    ham = jnp.zeros((Q, sketches.shape[0]), jnp.int32)
    for w in range(W):
        ham = ham + jax.lax.population_count(
            jnp.bitwise_xor(qbits[:, w:w + 1], sketches[None, :, w]))
    ham = jnp.where(invalid[None, :], jnp.int32(1 << 30), ham)
    _, short = jax.lax.top_k(-ham, R)                       # (Q, R)
    rows = data[short]                                      # (Q, R, D)
    if metric == int(DistCalcMethod.L2):
        d = dist_ops.batched_gathered_distance(
            queries, rows, DistCalcMethod.L2, base, sqnorm[short])
    else:
        d = dist_ops.batched_gathered_distance(
            queries, rows, DistCalcMethod.Cosine, base, sqnorm[short])
    d = jnp.where(invalid[short], jnp.float32(MAX_DIST), d)
    neg, pos = jax.lax.top_k(-d, k)
    dists = -neg
    ids = jnp.take_along_axis(short, pos, axis=1)
    ids = jnp.where(dists >= jnp.float32(MAX_DIST), -1, ids)
    return dists, ids.astype(jnp.int32)


@register_algo
class FlatIndex(VectorIndex):
    algo = IndexAlgoType.FLAT

    def __init__(self, value_type: VectorValueType):
        super().__init__(value_type)
        self._host: Optional[np.ndarray] = None   # capacity x D
        self._n = 0
        self._deleted = np.zeros(0, dtype=bool)
        self._num_deleted = 0
        self._dirty = True
        # the resident block (rows, norms, mask).  Its arrays are DONATED
        # to the next in-place write: whoever reads the tuple dispatches
        # on it under `_lock` (`_live`, `_device_append`)
        self._device: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None
        # scan programs dispatched on this block, as their static
        # arguments: a growth runs each once on the new shape, so the
        # compile is the writer's and not the next search's
        self._programs: set = set()
        self._sketch: Optional[Tuple[jax.Array, jax.Array]] = None
        # tiered cascade snapshot (ops/cascade.py, ISSUE 14); rebuilt on
        # mutation like the sketch cache
        self._cascade: Optional[cascade.CascadeState] = None
        # persisted SketchRerank calibration (save/load satellite):
        # (main_rows, num_deleted, cal_r) from sketch_cal.bin — consumed
        # by _ensure_calibrated iff the corpus is untouched since save
        self._loaded_cal: Optional[Tuple[int, int, int]] = None

    def _invalidate_derived(self) -> None:
        """Drop snapshot-derived caches on corpus mutation: the cascade
        state covers stale rows, and a persisted calibration no longer
        describes this corpus (the satellite's invalidation contract)."""
        self._cascade = None
        self._loaded_cal = None

    def _make_params(self) -> FlatParams:
        return FlatParams()

    # ---- storage ----------------------------------------------------------

    @property
    def num_samples(self) -> int:
        return self._n

    @property
    def num_deleted(self) -> int:
        return self._num_deleted

    @property
    def feature_dim(self) -> int:
        return 0 if self._host is None else self._host.shape[1]

    def contains_sample(self, vid: int) -> bool:
        return 0 <= vid < self._n and not self._deleted[vid]

    def get_sample(self, vid: int) -> np.ndarray:
        return self._host[vid]

    def _reserve(self, extra: int) -> None:
        need = self._n + extra
        if self._host is None:
            raise RuntimeError("index not built")
        cap = self._host.shape[0]
        if need > cap:
            new_cap = max(need, cap * 2, 1024)
            grown = np.empty((new_cap, self._host.shape[1]),
                             self._host.dtype)
            grown[:self._n] = self._host[:self._n]
            self._host = grown
            dels = np.zeros(new_cap, dtype=bool)
            dels[:self._n] = self._deleted[:self._n]
            self._deleted = dels

    def _build(self, data: np.ndarray, checkpoint=None) -> None:
        # exact index: single-stage build, nothing to checkpoint
        self._host = np.ascontiguousarray(data)
        self._n = data.shape[0]
        self._deleted = np.zeros(self._n, dtype=bool)
        self._num_deleted = 0
        self._dirty = True
        self._invalidate_derived()

    def _add(self, data: np.ndarray) -> int:
        begin = self._n
        self._reserve(data.shape[0])
        self._host[begin:begin + data.shape[0]] = data
        self._n += data.shape[0]
        if self._live():
            self._device_append(begin, self._host[begin:self._n])
        else:
            self._dirty = True
        self._invalidate_derived()
        return begin

    def _delete_id(self, vid: int) -> bool:
        return self._delete_ids([vid]) == 1

    def _delete_ids(self, vids) -> int:
        fresh = [v for v in dict.fromkeys(map(int, vids))
                 if not self._deleted[v]]
        if not fresh:
            return 0
        self._deleted[fresh] = True
        self._num_deleted += len(fresh)
        if self._live():
            self._device_mask(fresh)
        else:
            self._dirty = True
        self._invalidate_derived()
        return len(fresh)

    # ---- the resident block follows its mutations -------------------------

    def _live(self) -> bool:
        """Whether a mutation writes the resident block in place (lock
        held): a block is placed and current, and nothing cached was
        derived from it.  A sketch or a cascade state holds the block's
        arrays outside the lock (`_calibrate`, `CascadeState.fp_dev`)
        and is rebuilt over the whole corpus after every mutation
        anyway: with one cached the mutation marks the block dirty, as
        every mutation did before the block took writes in place."""
        return (self._device is not None and not self._dirty
                and self._sketch is None and self._cascade is None)

    def _device_append(self, begin: int, rows: np.ndarray) -> None:
        """Rows [begin, begin + len(rows)) of the host buffer written
        into the resident block (lock held), growing it first where the
        write's rung would pass its last slot.  Host -> device: the
        rungs' rows, a byte of mask a row, the start."""
        pieces = _write_pieces(len(rows))
        need = begin + pieces[-1][0] + pieces[-1][2]
        if need > self._device[0].shape[0]:
            self._grow(need)
        sent = 0
        with trace.span("flat.block_update"):
            block = self._device
            for lo, count, rung in pieces:
                padded = np.zeros((rung, rows.shape[1]), rows.dtype)
                padded[:count] = rows[lo:lo + count]
                dead = np.ones(rung, bool)
                dead[:count] = self._deleted[begin + lo:begin + lo + count]
                block = _block_write_rows(*block, padded, dead,
                                          np.int32(begin + lo))
                sent += padded.nbytes + dead.nbytes + 4
            self._publish(block)
        metrics.inc("flat.block_updates")
        trace.record_sum("flat.block_upload_bytes", sent, len(rows))

    def _device_mask(self, vids) -> None:
        """Mask bits of rows `vids` set in the resident block (lock
        held).  Host -> device: four bytes a row of its rung."""
        sent = 0
        with trace.span("flat.block_update"):
            data_d, sqnorm_d, invalid_d = self._device
            for lo, count, rung in _write_pieces(len(vids)):
                slots = np.full(rung, invalid_d.shape[0], np.int32)
                slots[:count] = vids[lo:lo + count]
                invalid_d = _block_mask_rows(invalid_d, slots)
                sent += slots.nbytes
            self._publish((data_d, sqnorm_d, invalid_d))
        metrics.inc("flat.block_updates")
        trace.record_sum("flat.block_upload_bytes", sent, len(vids))

    def _grow(self, need: int) -> None:
        """A block of `reserved_slots(need)` slots in the resident one's
        place (lock held), copied on the device, and every scan program
        that ran on the old shape run once on the new one: the writer
        that outgrew the reserve pays the compiles, not the searches
        after it."""
        with trace.span("flat.block_grow"):
            block = _block_grown(*self._device, slots=reserved_slots(need))
            zeros = {}
            for q, k, statics in self._programs:
                if q not in zeros:
                    zeros[q] = jnp.zeros((q, block[0].shape[1]),
                                         block[0].dtype)
                statics = dict(statics)
                if "fused" in statics:
                    # the exact select's route is a rule of the shape:
                    # what the searches after the growth will ask for
                    statics.update(scan_route(
                        block[0].dtype, q, *block[0].shape, k,
                        statics["metric"]))
                _flat_search_kernel(*block, zeros[q], k, **statics)
            self._publish(block)
        metrics.inc("flat.block_grows")

    def _publish(self, block) -> None:
        """`block` as the resident one (lock held)."""
        self._device = block
        metrics.set_gauge("flat.rows_resident", self._n)
        metrics.set_gauge("flat.slots_reserved", block[0].shape[0])
        devmem.track("corpus", block[0],
                     block[0].nbytes + block[1].nbytes + block[2].nbytes)

    # ---- device snapshot --------------------------------------------------

    def _retrack_devmem(self) -> None:
        # DeviceBytesLedger re-enabled on a warm index: re-register the
        # live snapshot/sketch (disable dropped their entries)
        with self._lock:
            if self._device is not None:
                data_d, sqnorm_d, invalid_d = self._device
                devmem.track("corpus", data_d,
                             data_d.nbytes + sqnorm_d.nbytes
                             + invalid_d.nbytes)
            if self._sketch is not None:
                packed, mean = self._sketch[1], self._sketch[2]
                devmem.track("sketch", packed,
                             packed.nbytes + mean.nbytes)
            if self._cascade is not None:
                self._cascade.register_devmem()

    def _snapshot(self):
        """The resident block, placed anew where a wholesale change left
        it dirty.  The tuple is good for a dispatch made under `_lock`:
        the next in-place write donates its arrays."""
        if not self._dirty and self._device is not None:
            return self._device
        # Rebuild under the index's single-writer lock so a mutation landing
        # mid-copy can't be lost behind a cleared dirty flag (P7 design).
        with self._lock:
            if not self._dirty and self._device is not None:
                return self._device
            n = self._n
            n_pad = pad_rows(n)
            dt = dtype_of(self.value_type)
            data = np.zeros((n_pad, self.feature_dim), dtype=dt)
            data[:n] = self._host[:n]
            invalid = np.ones(n_pad, dtype=bool)
            invalid[:n] = self._deleted[:n]
            data_d = jnp.asarray(data)
            del data
            # integer rows: ONE program, so that the int32 widening fuses
            # into the reduction (op by op it is an int32 copy of the
            # block, 13.6 GB at 8.84M x 384 int8)
            sqnorm_d = (_INT_SQNORMS(data_d) if np.issubdtype(dt, np.integer)
                        else dist_ops.row_sqnorms(data_d))
            invalid_d = jnp.asarray(invalid)
            metrics.set_gauge("flat.row_itemsize", data_d.dtype.itemsize)
            # device-memory ledger (in `_publish`): the block's resident
            # bytes, owned by the data array itself — a rebuild, a growth
            # or an in-place write drops the old entry when the old array
            # is collected
            self._publish((data_d, sqnorm_d, invalid_d))
            self._programs = set()
            self._sketch = None          # derived; rebuilt on demand
            self._dirty = False
            return self._device

    def _sketch_snapshot(self):
        """(device tuple, packed (Npad, W) int32 sketches, (D,) f32 mean)
        as ONE atomic read — the sketch cache is keyed to the exact device
        snapshot it was derived from, so a concurrent mutation rebuilding
        the snapshot can never pair v1 data with v2 sketches (or cache
        stale sketches after its own rebuild).  +N*ceil(D/32)*4 bytes of
        HBM, derived lazily."""
        with self._lock:
            device = self._snapshot()
            if self._sketch is not None and self._sketch[0] is device:
                return device, self._sketch[1], self._sketch[2], \
                    self._sketch[3]
            data_d, sqnorm_d, invalid_d = device
            f = data_d.astype(jnp.float32)
            live = (~invalid_d).astype(jnp.float32)
            mean = ((f * live[:, None]).sum(0)
                    / jnp.maximum(live.sum(), 1.0))
            packed = _PACK_JIT(f - mean[None, :])
            devmem.track("sketch", packed, packed.nbytes + mean.nbytes)
            # cal_r starts None: the auto-shortlist path calibrates it
            # OUTSIDE this lock via _ensure_calibrated (the O(64*N)
            # exact scan + compiles must not stall concurrent searches);
            # explicit-SketchRerank deployments never pay for it at all
            self._sketch = (device, packed, mean, None)
            return device, packed, mean, None

    def _calibrate(self, data_d, sqnorm_d, invalid_d, packed, mean):
        """Measured AUTO shortlist: sample live rows as self-queries,
        measure the sketch rank their true top-_CAL_K neighbors actually
        land at, and take a high percentile as the R the auto path uses.
        A fixed N-fraction heuristic has no single good value — clustered
        corpora keep true neighbors in the sketch's top ~N/48 while
        UNIFORM data scatters them across a quarter of the corpus
        (ADVICE r3: d=24 uniform measured recall@10 0.53 under the old
        N/32 heuristic) — so the index measures its own corpus instead
        of guessing.  Returns None on any failure (calibration must
        never fail search)."""
        try:
            live_idx = np.flatnonzero(~np.asarray(invalid_d, dtype=bool))
            if len(live_idx) < 8:
                return None
            rs = np.random.default_rng(0xC0FFEE)
            sample = live_idx[rs.integers(0, len(live_idx), _CAL_SAMPLE)]
            ranks = np.asarray(_sketch_cal_kernel(
                data_d, sqnorm_d, invalid_d, packed, mean,
                data_d[jnp.asarray(sample)], _CAL_K,
                int(self.dist_calc_method), self.base))
            r = int(np.percentile(ranks, 95))
            # quantize UP to a power of two: R is a static kernel-shape
            # parameter, and an unquantized calibration would mint a
            # fresh XLA compile after nearly every mutation (the same
            # bounded-compile-cache rationale as the server's $maxcheck
            # sanitizer); rounding up never shrinks the shortlist
            return 1 << (max(r, 1) - 1).bit_length()
        except Exception:                              # noqa: BLE001
            return None

    def _ensure_calibrated(self):
        """(device, packed, mean, cal_r) with calibration present if it
        can be computed.  The O(64*N) calibration scan runs OUTSIDE the
        index lock — a mutation-heavy workload must not stall every
        concurrent search behind it — and the result is stored only if
        the snapshot it was derived from is still current (a concurrent
        mutation simply triggers a fresh calibration next search).
        A FAILED calibration (<8 live rows, kernel error) is cached as a
        -1 sentinel so it is attempted at most once per snapshot — the
        consumer's cal_r<=0 test falls back to the N/32 heuristic without
        re-paying the exact scan on every search (ADVICE r4).

        A calibration PERSISTED with the index blobs (sketch_cal.bin,
        manifest-checksummed) short-circuits the whole scan on a warm
        start — valid only while the corpus is untouched since save
        (`_invalidate_derived` drops it on any mutation, and the
        (rows, deletes) fingerprint double-checks)."""
        device, packed, mean, cal_r = self._sketch_snapshot()
        if cal_r is not None:
            return device, packed, mean, cal_r
        loaded = self._loaded_cal
        if loaded is not None and loaded[0] == self._n \
                and loaded[1] == self._num_deleted and loaded[2] > 0:
            cal_r = int(loaded[2])
        else:
            data_d, sqnorm_d, invalid_d = device
            cal_r = self._calibrate(data_d, sqnorm_d, invalid_d, packed,
                                    mean)
        with self._lock:
            if self._sketch is not None and self._sketch[0] is device:
                self._sketch = (device, packed, mean,
                                cal_r if cal_r is not None else -1)
        return device, packed, mean, cal_r

    # ---- search -----------------------------------------------------------

    def _search_batch(self, queries: np.ndarray, k: int,
                      max_check: Optional[int] = None,
                      search_mode: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        if self._n == 0:
            raise RuntimeError("index is empty")
        del max_check, search_mode      # exact scan: no budget, no modes
        q = queries.shape[0]
        queries = pad_to_bucket(queries)
        if self._cascade_active():
            # tiered cascade (ops/cascade.py, ISSUE 14): sketch Hamming
            # scan -> int8 re-rank -> fp exact re-rank, per-tier
            # budgeted.  Routed BEFORE the snapshot read: with
            # CorpusTier=host/host_all the fp corpus must never become
            # device-resident on the serve path
            st = self._cascade_state()
            k_eff = min(k, st.n_pad)
            dists, ids = st.search(
                np.asarray(queries, np.float32), k_eff,
                int(getattr(self.params, "tier_budget_sketch", 0)),
                int(getattr(self.params, "tier_budget_int8", 0)))
            return self._pad_k(dists[:q], ids[:q], q, k, k_eff)
        flag = ()       # the proved route's `unproved`, where it ran
        if getattr(self.params, "sketch_prefilter", False) \
                and pad_rows(self._n) > 256:
            # the sketches are read atomically WITH the block they were
            # derived from; while they are cached no write donates it
            # (`_live`), so the dispatch needs no lock
            explicit_r = getattr(self.params, "sketch_rerank", 0)
            if explicit_r:
                (data_d, sqnorm_d, invalid_d), sketches, mean, cal_r = \
                    self._sketch_snapshot()
            else:
                (data_d, sqnorm_d, invalid_d), sketches, mean, cal_r = \
                    self._ensure_calibrated()
            k_eff = min(k, data_d.shape[0])
            # auto shortlist: CALIBRATED per snapshot (_sketch_snapshot
            # measures the sketch rank of sampled rows' true neighbors —
            # clustered corpora calibrate to ~N/48 while uniform/low-D
            # data needs far more; ADVICE r3 measured recall@10 0.53 at
            # d=24 uniform under the old fixed N/32 heuristic).  The 16k
            # floor covers k beyond the calibration depth; the 8192 cap
            # bounds the (Q, R, D) re-rank gather — a corpus whose
            # calibration EXCEEDS the cap gets the cap and the documented
            # advice is an explicit SketchRerank (or no prefilter)
            auto = max(128, 16 * k_eff,
                       cal_r if (cal_r and cal_r > 0)
                       else data_d.shape[0] // 32)
            R = explicit_r or min(auto, 8192)
            R = min(max(R, k_eff), data_d.shape[0])
            dists, ids = _flat_sketch_kernel(
                data_d, sqnorm_d, invalid_d, sketches, mean,
                jnp.asarray(queries), k_eff, R,
                int(self.dist_calc_method), self.base)
        else:
            rt = topk_bins.validate_recall_target(
                getattr(self.params, "approx_recall_target", 0.99))
            approx = bool(getattr(self.params, "approx_topk", False))
            queries_d = jnp.asarray(queries)
            # read the block and enqueue the program on it under the
            # writer's lock: the next in-place write donates these arrays,
            # and the device runs it after what is already enqueued.  A
            # search sees the block before a mutation or after it
            with self._lock:
                data_d, sqnorm_d, invalid_d = self._snapshot()
                k_eff = min(k, data_d.shape[0])
                statics = {"metric": int(self.dist_calc_method),
                           "base": self.base, "approx": approx,
                           "recall_target": rt,
                           "binned_bins": topk_bins.resolve_bins(
                               str(getattr(self.params, "binned_topk",
                                           "off")),
                               k_eff, data_d.shape[0], rt)}
                if not (approx or statics["binned_bins"]):
                    count_select(queries.shape[0], data_d.shape[0], k_eff)
                    statics.update(scan_route(
                        data_d.dtype, queries.shape[0], *data_d.shape,
                        k_eff, int(self.dist_calc_method)))
                    count_route(statics["fused"])
                count_dot(data_d.dtype, data_d.shape[1])
                self._programs.add((queries.shape[0], k_eff,
                                    tuple(statics.items())))
                dists, ids, *flag = _flat_search_kernel(
                    data_d, sqnorm_d, invalid_d, queries_d, k_eff,
                    **statics)
        with trace.span("index.readback"):
            # the host blocks here until the program has run
            dists = np.asarray(dists)[:q]
            ids = np.asarray(ids)[:q]
            if flag:
                count_unproved(flag[0])
        return self._pad_k(dists, ids, q, k, k_eff)

    @staticmethod
    def _pad_k(dists, ids, q: int, k: int, k_eff: int):
        if k_eff < k:
            pad_d = np.full((q, k - k_eff), MAX_DIST, np.float32)
            pad_i = np.full((q, k - k_eff), -1, np.int32)
            dists = np.concatenate([dists, pad_d], axis=1)
            ids = np.concatenate([ids, pad_i], axis=1)
        return dists, ids

    # ---- tiered cascade (ops/cascade.py, ISSUE 14) ------------------------

    def _cascade_active(self) -> bool:
        """CascadeSearch applies to FLOAT value types only — integer
        corpora are already quantized and keep their documented exact
        integer distance paths (int16 byte-split exactness included);
        the knob is an ignored no-op there, same as the graph engines'
        guard."""
        return (int(getattr(self.params, "cascade_search", 0)) != 0
                and np.issubdtype(dtype_of(self.value_type),
                                  np.floating))

    def _cascade_state(self) -> cascade.CascadeState:
        """Pinned cascade snapshot, rebuilt on mutation (same epoch
        semantics as _sketch_snapshot).  Device tier reuses the fp
        snapshot the oracle already holds (zero extra fp HBM); host
        tiers build WITHOUT ever calling _snapshot — the fp corpus
        stays host-side."""
        tier = cascade.normalize_tier(
            getattr(self.params, "corpus_tier", "device"))
        with self._lock:
            st = self._cascade
            if st is not None and st.tier == tier:
                return st
            n = self._n
            st = cascade.CascadeState(
                np.asarray(self._host[:n], np.float32),
                self._deleted[:n], tier, int(self.dist_calc_method),
                self.base,
                fp_dev=(self._snapshot()[0] if tier == "device"
                        else None))
            st.register_devmem()
            self._cascade = st
            return st

    def cascade_triage(self, query: np.ndarray, truth_ids,
                       k: int = 10) -> Optional[dict]:
        """Quality-monitor triage hook (utils/qualmon.py
        classify_low_recall): which cascade tier dropped the true
        neighbors of one sampled low-recall query?  None when the
        cascade is off — the caller falls back to the legacy verdicts."""
        if not self._cascade_active():
            return None
        st = self._cascade_state()
        return st.tier_membership(
            query, truth_ids, k,
            int(getattr(self.params, "tier_budget_sketch", 0)),
            int(getattr(self.params, "tier_budget_int8", 0)))

    def _exact_scan(self, queries: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Quality-monitor oracle (core/index.py exact_search_batch):
        the cached device snapshot + the exact kernel, bypassing the
        ApproxTopK / SketchPrefilter / CascadeSearch serving
        configuration.  Host-tier cascade indexes stream the scan
        through fixed fp blocks instead (cascade.host_exact_scan) — an
        oracle that re-uploaded the full corpus would break the
        zero-residency contract the tier exists for."""
        if self._cascade_active():
            st = self._cascade_state()
            if st.fp_host is not None:
                q = queries.shape[0]
                d, ids = cascade.host_exact_scan(
                    st.fp_host, st.invalid_host, pad_to_bucket(queries),
                    min(k, st.n_pad), int(self.dist_calc_method),
                    self.base)
                return d[:q], ids[:q]
        with self._lock:     # the block's arrays are the next write's
            data_d, sqnorm_d, invalid_d = self._snapshot()
            return exact_device_scan(data_d, sqnorm_d, invalid_d, queries,
                                     k, int(self.dist_calc_method),
                                     self.base)

    # ---- refine / persistence ---------------------------------------------

    def _refine_impl(self) -> None:
        keep = np.flatnonzero(~self._deleted[:self._n])
        self._host = np.ascontiguousarray(self._host[keep])
        self._n = len(keep)
        self._deleted = np.zeros(self._n, dtype=bool)
        self._num_deleted = 0
        if self.metadata is not None:
            self.metadata = self.metadata.refine(keep.tolist())
        if self._meta_to_vec is not None:
            self.build_meta_mapping()
        self._dirty = True
        self._invalidate_derived()

    def _blob_writers(self):
        return [
            (self.params.vector_file,
             lambda f: fmt.write_matrix(f, self._host[:self._n])),
            (self.params.delete_file,
             lambda f: fmt.write_deletes(f, self._deleted[:self._n])),
        ]

    def _load_vectors_stream(self, f) -> None:
        self._build(fmt.read_matrix(f, dtype_of(self.value_type)))

    def _load_deletes_stream(self, f) -> None:
        mask = fmt.read_deletes(f)
        self._deleted[:len(mask)] = mask
        self._num_deleted = int(mask.sum())

    def _blob_loaders(self):
        return [
            (self.params.vector_file, self._load_vectors_stream, False),
            (self.params.delete_file, self._load_deletes_stream, True),
        ]

    # SketchRerank calibration persistence (ISSUE 14 satellite).  A
    # folder-only side blob — NOT part of _blob_writers: the wrapper
    # blob surface pairs blobs to loaders positionally, and a
    # conditionally-present blob would shift the metadata blobs.  The
    # save_index manifest checksums every folder file, this one
    # included, so a corrupt calibration fails the load like any blob.
    _CAL_FILE = "sketch_cal.bin"
    _CAL_MAGIC = b"SPTSCAL1"

    def _cal_payload(self) -> Optional[bytes]:
        """(rows, deletes, cal_r) of the CURRENT corpus, or None when no
        valid calibration exists (nothing is written then — default-off
        saves stay byte-identical file sets)."""
        import struct

        n, ndel = self._n, self._num_deleted
        cal_r = 0
        with self._lock:
            if not self._dirty and self._sketch is not None \
                    and self._sketch[3] and self._sketch[3] > 0:
                cal_r = int(self._sketch[3])
        if cal_r <= 0 and self._loaded_cal is not None \
                and self._loaded_cal[0] == n \
                and self._loaded_cal[1] == ndel:
            cal_r = int(self._loaded_cal[2])
        if cal_r <= 0:
            return None
        return struct.pack("<8sqqi", self._CAL_MAGIC, n, ndel, cal_r)

    def _save_index_data(self, folder: str) -> None:
        from sptag_tpu.io import atomic

        for name, writer in self._blob_writers():
            with atomic.checked_open(os.path.join(folder, name),
                                     "wb") as f:
                writer(f)
        payload = self._cal_payload()
        if payload is not None:
            with atomic.checked_open(
                    os.path.join(folder, self._CAL_FILE), "wb") as f:
                f.write(payload)

    def _load_index_data(self, folder: str) -> None:
        import struct

        for name, loader, optional in self._blob_loaders():
            path = os.path.join(folder, name)
            if not os.path.exists(path):
                if optional:
                    continue
                raise FileNotFoundError(path)
            with open(path, "rb") as f:
                loader(f)
        cal_path = os.path.join(folder, self._CAL_FILE)
        if os.path.exists(cal_path):
            try:
                with open(cal_path, "rb") as f:
                    magic, n, ndel, cal_r = struct.unpack(
                        "<8sqqi", f.read(struct.calcsize("<8sqqi")))
                if magic == self._CAL_MAGIC and cal_r > 0:
                    # validated again at consume time against the LIVE
                    # (rows, deletes) fingerprint (_ensure_calibrated)
                    self._loaded_cal = (int(n), int(ndel), int(cal_r))
            except Exception:                          # noqa: BLE001
                self._loaded_cal = None    # corrupt cal -> recalibrate
