"""Batched beam-search engine — the TPU reshape of SPTAG's serving hot path.

The reference search (/root/reference/AnnService/src/Core/BKT/
BKTIndex.cpp:105-157) pops ONE frontier node at a time from a priority queue,
scores its <=32 graph neighbors with scalar SIMD calls, and stops when the
`MaxCheck` budget is spent or `ThresholdOfNumberOfContinuousNoBetterPropagation`
consecutive pops fail to improve the top-K.  That data-dependent serial walk
would leave the MXU idle; here it becomes a fixed-shape device loop
(SURVEY.md §7):

* a query BATCH (Q, D) runs as one compiled program — the batch dimension
  replaces the reference's OpenMP-over-queries (VectorIndex.cpp:212-220);
* tree seeding is one dense (Q, P) distance matrix against a pivot set
  collected from the trees (replacing InitSearchTrees/SearchTrees,
  BKTree.h:279-320) — the top-L pivots initialize the beam;
* each iteration pops the best `B` unexpanded beam entries AT ONCE, gathers
  their B*32 neighbors, dedupes against a per-query visited table, scores all
  candidates as one batched contraction, and merges beam+candidates with
  `lax.top_k` — `ceil(max_check / B)` iterations under `lax.while_loop`
  preserve the MaxCheck budget semantics (each iteration expands B nodes, the
  reference expands 1 per pop);
* the no-better-propagation early exit carries over per query: a query whose
  top-k worst distance fails to improve for `nbp_limit` consecutive
  iterations stops expanding (each iteration aggregates B pops, so the limit
  bites at comparable budget);
* tombstoned rows (Labelset, reference Labelset.h) are traversed but filtered
  from the final top-k (the reference filters in-loop, BKTIndex.cpp:234-239;
  a masked dense top-k is the cheaper TPU equivalent).

Why the walk's scattered-row gather stays XLA (round-3 design decision,
investigated for the verdict's "Pallas DMA kernel for the walk" ask): the
dense path's Pallas kernels (ops/pallas_kernels.py) win because their
gathers are BLOCK-granular — one scalar-prefetched index DMAs a whole
(P, D) tile.  The walk gathers Q*B*32 SINGLE rows at uniformly scattered
ids; every Pallas formulation is worse than XLA's gather here: per-row
async DMAs cost ~0.5-1 us of issue overhead x 500k rows/iteration, and
the 8-row-tile trick reads 8x the bytes (vs XLA's 2x materialize+reread).
The measured roofline agrees the gather is not the limit — the walk runs
at ~3 GB/s against an 819 GB/s chip, i.e. it is bound by the SERIAL
iteration count and per-iteration fixed costs, not bandwidth.  The
round-3 attack is therefore: budget-scaled beam width (fewer, fatter
iterations — beam_width_for), a bf16 shadow corpus for in-loop scoring
(half the gather bytes, exact f32 re-rank at the end), and the int8 path
(quarter the bytes) — not a row-gather kernel.

The visited structure is a per-query PACKED BITSET (Q, ceil((N+1)/32))
int32 — the TPU replacement for the reference's OptHashPosVector
open-addressing hash (WorkSpace.h:33-134).  Packing matters: a loop-carried
array that is read and scatter-written every iteration gets double-buffered
by XLA, so its size is pure copy cost per iteration — a boolean (Q, N) table
at N=200k costs ~4ms/iter in copies; the packed table is 32x smaller.
Setting bits without a scatter-OR primitive uses a sort + segmented
associative OR-scan: candidate ids are sorted (the same sort also yields the
intra-batch duplicate mask), runs of ids in the same word OR their bits
together, and each run's last element scatter-writes `existing | run_or`.
"""

from __future__ import annotations

import functools
import time
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops import topk_bins
from sptag_tpu.utils import (devmem, flightrec, locksan, metrics,
                             query_bucket, recompile_guard, trace)

MAX_DIST = np.float32(3.4e38)   # plain scalar: module import must NOT init a backend

# visited-table memory budget per search call (bytes)
_VISITED_BUDGET = 1 << 29


class BeamWalk(NamedTuple):
    """What the jitted walk programs return: the (Q, k) answers, and what
    the walk did to get them — `live`, per row the trips (body
    applications of the while loop) in which the row was still alive;
    each one pops up to B nodes and scores their neighbours.  A loop runs
    while any row is alive and dead is absorbing, so its trips are
    `live.max()`.  The count rides the answers' readback; the chunked
    forms carry a leading chunk axis on all three.  The field names reach
    the program's StableHLO (`jax.result_info`): with them each beam
    program has a compile-cache key of its own (core/types.py
    `DeviceTopK`)."""

    dists: Any
    ids: Any
    live: Any


def _scatter_true(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """arr (Q, W) bool; idx (Q, X) int in [0, W) -> set True, batched.
    Only used for the small (Q, L+1) expanded flags — the big visited
    structure is the packed bitset below."""
    return jax.vmap(lambda a, i: a.at[i].set(True))(arr, idx)


def _num_words(n: int) -> int:
    """Packed-bitset word count covering ids [0, n] (id n is the dump id for
    masked candidates: its bit lands in a real word but no real id owns it)."""
    return (n + 1 + 31) // 32


def _test_bits(words: jax.Array, ids: jax.Array) -> jax.Array:
    """words (Q, W) int32 bitset; ids (Q, X) in [0, 32W) -> (Q, X) bool."""
    w = jnp.right_shift(ids, 5)
    got = jnp.take_along_axis(words, w, axis=1)
    return (jnp.right_shift(got, ids & 31) & 1).astype(bool)


def _seg_or(bits: jax.Array, first: jax.Array) -> jax.Array:
    """Segmented inclusive OR-scan along axis 1: `first` marks run starts."""
    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av | bv), af | bf
    orv, _ = jax.lax.associative_scan(op, (bits, first), axis=1)
    return orv


def _mark_bits(words: jax.Array, ids: jax.Array) -> jax.Array:
    """Set bits `ids` (Q, X) in the packed bitset (Q, W) without a
    scatter-OR primitive: sort ids, OR together the bits of each same-word
    run with a segmented scan, and let only each run's LAST element write
    ``existing | run_or`` (distinct words per row -> no scatter conflicts).
    """
    return _mark_bits_sorted(words, jnp.sort(ids, axis=1))


def _mark_bits_sorted(words: jax.Array, s: jax.Array,
                      existing: Optional[jax.Array] = None) -> jax.Array:
    """_mark_bits for ids already sorted ascending along axis 1 — the walk
    shares one sort between duplicate detection and bit marking
    (marking is an OR, so re-marking already-visited ids is a no-op and
    the caller can pass ALL valid candidates, not just fresh ones).
    `existing` (Q, X): the words of `s` (`words` at `s >> 5`) where the
    caller has gathered them already (`_walk_machine`'s visited test)."""
    Q, X = s.shape
    W = words.shape[1]
    w = jnp.right_shift(s, 5)
    b = jnp.left_shift(jnp.int32(1), s & 31)
    first = jnp.concatenate(
        [jnp.ones((Q, 1), bool), w[:, 1:] != w[:, :-1]], axis=1)
    run_or = _seg_or(b, first)
    last = jnp.concatenate(
        [w[:, 1:] != w[:, :-1], jnp.ones((Q, 1), bool)], axis=1)
    if existing is None:
        existing = jnp.take_along_axis(words, w, axis=1)
    val = existing | run_or
    target = jnp.where(last, w, W)          # W = out of bounds -> dropped
    return jax.vmap(
        lambda row, t, v: row.at[t].set(v, mode="drop"))(words, target, val)


def beam_width_for(beam_width: int, max_check: int, L: int) -> int:
    """Budget-scaled beam width, shared by the single-chip and sharded
    walks.  At high budgets wider pops cut the SERIAL iteration count
    T = ceil(max_check/B) — the walk's real cost on TPU (roofline shows it
    overhead-bound at ~3 GB/s, not bandwidth-bound) — with measured
    recall-safe width: B 16 -> 64 at MaxCheck 2048 was flat (0.8977 ->
    0.8992, round 3) and the round-4 ladder measured recall RISING to
    B=256 (200k corpus, MaxCheck 2048: 0.9267 @ B32 -> 0.9285 @ B128 ->
    0.9339 @ B256), so the auto scale is max_check/32 capped at 128
    (2048 -> 64 pops/iter, 8192 -> 128).  `beam_width` is a FLOOR, never
    reduced: an explicitly tuned BeamWidth above the cap (e.g. 256) is
    honored as-is."""
    return max(1, min(max(beam_width, min(max_check // 32, 128)), L))


def beam_pool_size(k: int, max_check: int, n: int,
                   pool_size: Optional[int] = None) -> int:
    """Budget-scaled beam (frontier) capacity, shared by the single-chip and
    sharded search paths.  A fixed frontier saturates and flattens the
    recall/MaxCheck curve (the reference's NG queue holds maxCheck*30 cells,
    /root/reference/AnnService/inc/Core/Common/WorkSpace.h:182-208; measured
    here: recall stuck at 0.82 from MaxCheck 512 to 8192 with L=64)."""
    L = pool_size or max(2 * k, min(64 + max_check // 8, 1024))
    return min(max(L, k), n)


def _sorted_dedup(ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(Q, X) int -> (sorted ids (Q, X), dup mask (Q, X)).

    One argsort serves both outputs: `dup` is True on every occurrence of
    an id after the first (in original positions — the inverse permutation
    comes from a SCATTER, not a second sort), and the sorted array feeds
    `_mark_bits_sorted` directly.  Called by the seeded kernel's seed
    dedupe, the dense epilogue's replica dedupe (`algo/dense.py`) and
    the binned walk body's L-wide pool dedupe.  The walk's exact body
    no longer calls it, whichever way it fetches its vectors: a trip's
    candidates stay in sorted order there (`_sorted_fresh`; the
    packed-neighbour layout's scores ride the sort as its payload),
    and the two gathers and the scatter that carry the mask back fall
    away."""
    Q = ids.shape[0]
    order = jnp.argsort(ids, axis=1, stable=True)
    sorted_ids = jnp.take_along_axis(ids, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((Q, 1), bool),
         sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=1)
    inv = jax.vmap(lambda o: jnp.zeros_like(o).at[o].set(
        jnp.arange(o.shape[0], dtype=o.dtype)))(order)
    return sorted_ids, jnp.take_along_axis(dup_sorted, inv, axis=1)


def _sorted_dup_mask(ids: jax.Array):
    """(Q, X) int -> (Q, X) bool duplicate mask (see _sorted_dedup)."""
    return _sorted_dedup(ids)[1]


def dedup_in_sorted_order(merge_bins: int) -> bool:
    """Whether a walk body keeps a trip's candidates in ascending-id
    order from the de-duplication to the merge (`_sorted_fresh`): the
    exact body (`merge_bins == 0`), whether it gathers its rows by id
    after the sort or scores the packed-neighbour blocks before it and
    carries the scores through the sort.  The binned body has no X-wide
    sort.  `_walk_machine` traces by this rule and
    `GraphSearchEngine._publish_walk` counts by it."""
    return not merge_bins


def packed_layout_fits(n: int, m: int, dim: int, itemsize: int,
                       device_bytes: Optional[int],
                       in_use: int = 0) -> bool:
    """`BeamPackedNeighbors=auto`: whether an engine of `n` rows with `m`
    neighbours a row lays every node's neighbour vectors side by side
    (`(n, m, dim)` in the scoring dtype) so that a trip fetches B blocks
    a query instead of B x m rows.  It does where the table is at most
    an eighth of the device's memory (`device_bytes`: a TPU's
    `memory_stats()["bytes_limit"]`; the two engines of a snapshot swap
    hold a quarter between them) AND at most half of what is free when
    the first walk asks (`device_bytes - in_use`: the other half is the
    walk's own and whatever loads next; dense blocks, other indexes and
    a superseded engine's table are all `in_use`).  It keeps the row
    layout where the device states no limit (off a TPU: `None`).  100k x
    32 x 128 bf16 is 0.82 GB of a v5e's 16: packed; 1M rows, 8.2 GB:
    rows."""
    if not device_bytes:
        return False
    return n * m * dim * itemsize <= min(device_bytes // 8,
                                         (device_bytes - in_use) // 2)


def packed_param(value) -> Union[str, bool]:
    """What a `BeamPackedNeighbors` line asks of `GraphSearchEngine`:
    `auto` leaves it to `packed_layout_fits`, `1` insists on the table
    (the only way to it off a TPU).  `0` was the default until PR 45 and
    `save_config` writes every value, so every folder saved before says
    `0` without anybody having chosen it: read as `auto`.  Raises on
    anything else."""
    v = str(value).strip().lower()
    if v in ("auto", "0"):
        return "auto"
    if v == "1":
        return True
    raise ValueError(f"BeamPackedNeighbors must be auto or 1, got {value!r}")


def _device_memory() -> Tuple[Optional[int], int]:
    """The default device's memory as `packed_layout_fits` takes it: a
    TPU's `bytes_limit` and `bytes_in_use`, (None, 0) on any other
    platform."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None, 0
    stats = dev.memory_stats() or {}
    return stats.get("bytes_limit"), int(stats.get("bytes_in_use", 0))


@jax.jit
def _pack_neighbors(src: jax.Array, graph: jax.Array) -> jax.Array:
    """`src[graph]` as ONE program: the gather writes the (N, m, D)
    table and nothing beside it (dispatched op by op the same
    expression held a second copy while it reshaped: 0.82 GB more at
    the beam cell's size, PERF.md PR 45).  -1 slots point at row 0."""
    return src[jnp.maximum(graph, 0)]


#: the largest corpus whose ids leave a bit free in an int32 word
#: (`_flag_ids`); `_walk_machine` refuses a larger one
MAX_FLAGGED_ROWS = 1 << 30


def _flag_ids(ids: jax.Array, flag: jax.Array) -> jax.Array:
    """int32 ids (-1 or < `MAX_FLAGGED_ROWS`) and a bool per id -> ONE
    int32 word per id, the flag in bit 0 (a void stays negative: -2 / -1),
    so a gather by position fetches both at once; `_split_flagged`
    undoes it."""
    return jnp.left_shift(ids, 1) | flag.astype(jnp.int32)


def _split_flagged(key: jax.Array):
    """`_flag_ids`' word -> (ids, flag); the arithmetic shift brings a
    void back as -1 whatever its flag."""
    return jnp.right_shift(key, 1), (key & 1).astype(bool)


def walk_takes_norm(metric) -> bool:
    """Whether a walk body's score has a candidate norm in it (L2; cosine
    has none): `_publish_walk`'s `beam.norm_from_rows` and the engines'
    `walk_iter_cost` follow it."""
    return int(metric) == int(DistCalcMethod.L2)


def _sorted_fresh(visited: jax.Array, flat_safe: jax.Array, n: int,
                  payload: Optional[jax.Array] = None):
    """The walk's visited / de-duplicate ensemble in sorted-id order.
    `flat_safe` (Q, X): a trip's candidate ids, `n` in the holes.  ->
    (ids (Q, X) ascending with -1 after them, fresh (Q, X) bool: first
    occurrence of an id not in `visited`, visited with every valid id
    marked, `payload` in the ids' order).  One sort, ONE gather of
    `visited` words: it serves the test and the marker's `existing`, and
    nothing is carried back to the order the candidates came in.
    `payload` (Q, X), what the caller holds per candidate in the order
    it came (the packed-neighbour layout's scores), rides the sort
    beside the ids.  It has to be equal among the copies of one id: the
    sort is not asked to keep them in order (a stable sort of two
    operands carries a third, the positions)."""
    Q = flat_safe.shape[0]
    if payload is None:
        s = jnp.sort(flat_safe, axis=1)
    else:
        s, payload = jax.lax.sort((flat_safe, payload), dimension=1,
                                  is_stable=False, num_keys=1)
    got = jnp.take_along_axis(visited, jnp.right_shift(s, 5), axis=1)
    seen = (jnp.right_shift(got, s & 31) & 1).astype(bool)
    dup = jnp.concatenate(
        [jnp.zeros((Q, 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
    valid = s < n
    return (jnp.where(valid, s, -1), valid & ~seen & ~dup,
            _mark_bits_sorted(visited, s, existing=got), payload)


@jax.named_scope("beam.seed")
def _seed_from_pivots(pivot_ids, pivot_vecs, pivot_mask, queries, L: int,
                      metric: int, seed_keep: int = 0):
    """Shared-pivot seeding (BKT): one dense (Q, P) matmul scores the whole
    pivot set; the top-L pivots initialize every query's beam.  `pivot_mask`
    (W,) int32 is the precomputed packed bitset of the pivot ids.

    Pivots beyond the top L form a per-query sorted SPARE queue — the walk
    injects the next `inject` of them whenever the frontier falls behind
    the best unvisited pivot, mirroring the reference's mid-walk
    `SearchTrees` refill (`NGQueue.top > SPTQueue.top`, BKTIndex.cpp:153-155;
    `NumberOfOtherDynamicPivots` is the refill size).

    `seed_keep` > 0 (BinnedTopK; topk_bins.seed_spare_keep) replaces the
    (Q, P)-wide argsort — the single biggest sort left in the binned
    walk — with a bin reduction + exact top-(L + seed_keep): the beam
    gets its top-L (approximately; bin collisions can swap tail
    entries) and the spare queue is TRUNCATED to `seed_keep` sorted
    pivots, far beyond any real injection budget.

    Returns (cand_ids, cand_d, visited, spare_ids, spare_d)."""
    Q = queries.shape[0]
    P = pivot_ids.shape[0]

    d0 = dist_ops.pairwise_distance(queries, pivot_vecs,
                                    DistCalcMethod(metric))      # (Q, P)
    if P < L:
        d0 = jnp.concatenate(
            [d0, jnp.full((Q, L - P), MAX_DIST, jnp.float32)], axis=1)
        seed_ids = jnp.concatenate(
            [pivot_ids, jnp.full((L - P,), -1, jnp.int32)])
    else:
        seed_ids = pivot_ids
    if seed_keep > 0:
        K = min(L + seed_keep, d0.shape[1])
        sorted_d, sorted_cols = topk_bins.binned_topk(
            d0, K, topk_bins.pow2ceil(K))
        sorted_ids = jnp.where(sorted_d < MAX_DIST,
                               seed_ids[sorted_cols], -1)
    else:
        order = jnp.argsort(d0, axis=1)                         # ascending
        sorted_d = jnp.take_along_axis(d0, order, axis=1)
        sorted_ids = jnp.where(sorted_d < MAX_DIST, seed_ids[order], -1)
    cand_d = sorted_d[:, :L]
    cand_ids = sorted_ids[:, :L]
    spare_ids = sorted_ids[:, L:]
    spare_d = sorted_d[:, L:]

    # every pivot was scored: mark visited so the walk never re-scores one
    visited = jnp.broadcast_to(pivot_mask[None, :],
                               (Q, pivot_mask.shape[0])).astype(jnp.int32)
    return cand_ids, cand_d, visited, spare_ids, spare_d


@jax.named_scope("beam.seed")
def _seed_from_seeds(data, sqnorm, seed_ids, queries, L: int, metric: int,
                     base: int, score_scale: float = 0.0):
    """Per-query seeding (KDT): `seed_ids` (Q, S) come from a host-side tree
    descent per query (the reference's KDTSearch leaf seeding,
    KDTree.h:178-215); they are gathered and scored as one batched
    contraction.  Returns (cand_ids, cand_d, visited).

    `score_scale` > 0 AND an integer `data` (host-tier cascade: `data`
    IS the int8 quantization): dequantize the gathered seed rows so
    seed distances live in the same space as the walk's dequantized
    scoring and the rescaled `sqnorm` — raw int8 rows against
    dequantized norms would seed the beam with garbage distances.  The
    dtype guard matters: on the DEVICE tier `data` stays fp (only the
    walk's data_score shadow is int8) and scaling fp seed rows would
    corrupt them instead."""
    Q = queries.shape[0]
    N = data.shape[0]
    S = seed_ids.shape[1]

    svecs = data[jnp.maximum(seed_ids, 0)]                       # (Q, S, D)
    if score_scale and jnp.issubdtype(svecs.dtype, jnp.integer):
        svecs = svecs.astype(jnp.float32) * jnp.float32(score_scale)
    ssq = sqnorm[jnp.maximum(seed_ids, 0)]
    d0 = dist_ops.batched_gathered_distance(
        queries, svecs, DistCalcMethod(metric), base, ssq)
    # duplicate seeds (same leaf reached twice) must not double-occupy the
    # beam: keep the first occurrence only
    seeds_safe = jnp.where(seed_ids >= 0, seed_ids, N)
    sorted_seeds, seed_dup = _sorted_dedup(seeds_safe)
    d0 = jnp.where((seed_ids < 0) | seed_dup, MAX_DIST, d0)
    visited = jnp.zeros((Q, _num_words(N)), jnp.int32)
    visited = _mark_bits_sorted(visited, sorted_seeds)
    if S < L:
        d0 = jnp.concatenate(
            [d0, jnp.full((Q, L - S), MAX_DIST, jnp.float32)], axis=1)
        seed_ids = jnp.concatenate(
            [seed_ids, jnp.full((Q, L - S), -1, jnp.int32)], axis=1)
    neg, pos = jax.lax.top_k(-d0, L)
    cand_d = -neg
    cand_ids = jnp.where(cand_d < MAX_DIST,
                         jnp.take_along_axis(seed_ids, pos, axis=1), -1)
    return cand_ids, cand_d, visited


@functools.partial(jax.jit, static_argnames=("L", "metric", "seed_keep"))
def _beam_seed_kernel(pivot_ids, pivot_vecs, pivot_mask, queries, L: int,
                      metric: int, seed_keep: int = 0):
    """Standalone jit of the pivot seeding — the scheduler seeds refill
    buckets with it, then walks them under `_beam_segment_kernel`."""
    return _seed_from_pivots(pivot_ids, pivot_vecs, pivot_mask, queries, L,
                             metric, seed_keep=seed_keep)


@functools.partial(jax.jit, static_argnames=("L", "metric", "base",
                                             "score_scale"))
def _beam_seed_seeded_kernel(data, sqnorm, seed_ids, queries, L: int,
                             metric: int, base: int,
                             score_scale: float = 0.0):
    return _seed_from_seeds(data, sqnorm, seed_ids, queries, L, metric,
                            base, score_scale=score_scale)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "B", "metric", "base", "nbp_limit",
                     "inject", "merge_bins", "finalize_bins", "seed_keep",
                     "score_scale"))
def _beam_search_kernel(data, sqnorm, graph, deleted, pivot_ids, pivot_vecs,
                        pivot_mask, queries, t_limit, k: int, L: int,
                        B: int, metric: int, base: int, nbp_limit: int,
                        inject: int = 4, data_score=None, nbr_vecs=None,
                        merge_bins: int = 0, finalize_bins: int = 0,
                        seed_keep: int = 0, score_scale: float = 0.0):
    """Pivot-seeded monolithic walk: seed + walk + finalize fused in one
    program.  `t_limit` (Q,) carries the per-row iteration budget as a
    TRACED array, so distinct MaxCheck values that map to the same (L, B)
    reuse one compiled program."""
    cand_ids, cand_d, visited, spare_ids, spare_d = _seed_from_pivots(
        pivot_ids, pivot_vecs, pivot_mask, queries, L, metric,
        seed_keep=seed_keep)
    return _walk(data, sqnorm, graph, deleted, queries, cand_ids, cand_d,
                 visited, k, L, B, t_limit, metric, base, nbp_limit,
                 spare_ids=spare_ids, spare_d=spare_d, inject=inject,
                 data_score=data_score, nbr_vecs=nbr_vecs,
                 merge_bins=merge_bins, finalize_bins=finalize_bins,
                 score_scale=score_scale)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "B", "metric", "base", "nbp_limit",
                     "merge_bins", "finalize_bins", "score_scale"))
def _beam_search_seeded_kernel(data, sqnorm, graph, deleted, seed_ids,
                               queries, t_limit, k: int, L: int, B: int,
                               metric: int, base: int, nbp_limit: int,
                               data_score=None, nbr_vecs=None,
                               merge_bins: int = 0, finalize_bins: int = 0,
                               score_scale: float = 0.0):
    cand_ids, cand_d, visited = _seed_from_seeds(data, sqnorm, seed_ids,
                                                 queries, L, metric, base,
                                                 score_scale=score_scale)
    return _walk(data, sqnorm, graph, deleted, queries, cand_ids, cand_d,
                 visited, k, L, B, t_limit, metric, base, nbp_limit,
                 data_score=data_score, nbr_vecs=nbr_vecs,
                 merge_bins=merge_bins, finalize_bins=finalize_bins,
                 score_scale=score_scale)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "B", "metric", "base", "nbp_limit",
                     "inject", "merge_bins", "finalize_bins", "seed_keep",
                     "score_scale"))
def _beam_search_chunked(data, sqnorm, graph, deleted, pivot_ids, pivot_vecs,
                         pivot_mask, queries3, t_limit, k: int, L: int,
                         B: int, metric: int, base: int, nbp_limit: int,
                         inject: int = 4, data_score=None, nbr_vecs=None,
                         merge_bins: int = 0, finalize_bins: int = 0,
                         seed_keep: int = 0, score_scale: float = 0.0):
    """(M, chunk, D) query chunks under one `lax.map` — a single device
    program for any batch size (one upload, one dispatch, one read;
    every synced host round trip has a fixed cost).  The per-chunk
    visited bitset is reused across sequential chunks instead of scaling
    with the total batch.  `t_limit` is (chunk,) and shared by all chunks
    (one search call = one budget)."""
    def body(q):
        return _beam_search_kernel(data, sqnorm, graph, deleted, pivot_ids,
                                   pivot_vecs, pivot_mask, q, t_limit, k,
                                   L, B, metric, base, nbp_limit, inject,
                                   data_score=data_score,
                                   nbr_vecs=nbr_vecs,
                                   merge_bins=merge_bins,
                                   finalize_bins=finalize_bins,
                                   seed_keep=seed_keep,
                                   score_scale=score_scale)
    return jax.lax.map(body, queries3)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "B", "metric", "base", "nbp_limit",
                     "merge_bins", "finalize_bins", "score_scale"))
def _beam_search_seeded_chunked(data, sqnorm, graph, deleted, seeds3,
                                queries3, t_limit, k: int, L: int, B: int,
                                metric: int, base: int, nbp_limit: int,
                                data_score=None, nbr_vecs=None,
                                merge_bins: int = 0, finalize_bins: int = 0,
                                score_scale: float = 0.0):
    def body(args):
        s, q = args
        return _beam_search_seeded_kernel(data, sqnorm, graph, deleted, s,
                                          q, t_limit, k, L, B, metric,
                                          base, nbp_limit,
                                          data_score=data_score,
                                          nbr_vecs=nbr_vecs,
                                          merge_bins=merge_bins,
                                          finalize_bins=finalize_bins,
                                          score_scale=score_scale)
    return jax.lax.map(body, (seeds3, queries3))


def _init_walk_state(cand_ids, cand_d, visited):
    """Fresh loop-carried state over a seeded beam: the 7-tuple
    `(cand_ids, cand_d, expanded, visited, no_better, ptr, it)` that the
    monolithic walk, the segmented kernel, and the slot scheduler all
    carry (the state-checkpointing contract — DESIGN.md §10).  `it` is a
    PER-QUERY iteration counter (Q,) so rows with different budgets can
    share one compiled program via the traced `t_limit` vector."""
    Q, L = cand_ids.shape
    # expanded has a dump slot at column L; visited a dump slot at row N
    expanded = jnp.concatenate(
        [cand_ids < 0, jnp.zeros((Q, 1), bool)], axis=1)        # (Q, L+1)
    no_better = jnp.zeros((Q,), jnp.int32)
    ptr = jnp.zeros((Q,), jnp.int32)      # next un-injected spare pivot
    it = jnp.zeros((Q,), jnp.int32)
    return cand_ids, cand_d, expanded, visited, no_better, ptr, it


def _walk_machine(data, sqnorm, graph, queries, t_limit, k: int, L: int,
                  B: int, metric: int, base: int, nbp_limit: int,
                  spare_ids=None, spare_d=None, inject: int = 0,
                  data_score=None, nbr_vecs=None,
                  merge_bins: int = 0, score_scale: float = 0.0):
    """One beam iteration as a reusable (body, row_alive) pair over the
    walk's constants — shared verbatim by the monolithic `lax.while_loop`
    walk and the segmented kernel, so the two execute IDENTICAL per-row
    trajectories (the bit-parity contract the scheduler's retire decision
    rests on).

    `merge_bins` > 0 switches the body to the BIN-REDUCTION frontier
    maintenance (ops/topk_bins.py, the TPU-KNN recipe; BinnedTopK
    param).  Three sort-ensemble replacements, exploiting the pool's
    sortedness invariant (every merge ends in an exact top-L, so
    `cand_d` is always ascending with MAX_DIST voids):

    * **pop** — the best-B unexpanded select becomes an exact
      rank-select (cumsum + one scatter) over the sorted pool instead of
      an L-wide `lax.top_k`;
    * **merge** — beam + candidates are strided-binned into
      `merge_bins` bins (>= L, so the sorted beam prefix maps onto
      distinct bins and can never self-collide), each bin keeps its
      best element, and the exact top-L runs over the bins-wide winner
      row instead of the (L + B*m)-wide concat.  A candidate is lost
      only when a better element shares its bin — and because marking
      is lazy (below), a lost candidate stays rediscoverable;
    * **lazy visited marking** — only ids that ENTER the beam are
      marked (one L-wide mark instead of the X-wide
      argsort+scan+scatter ensemble).  Same-iteration multi-parent
      copies carry bit-identical distances, land adjacent after the
      exact top-L, and collapse there; cross-iteration duplicates are
      excluded by the `seen` test because beam membership is always a
      subset of `visited` (seeds are pre-marked, every entrant is
      marked on entry).

    Per-row termination (t_limit / nbp / spare injection) is untouched,
    so the absorbing-state contract — and with it segmented/scheduler
    bit-parity AGAINST THE SAME merge_bins — holds exactly as in the
    exact body.  merge_bins=0 is the byte-identical legacy path.

    `row_alive(state)` is the per-row continuation predicate: True while
    the next body application could still change the row's pool.  A row
    for which it is False is in an ABSORBING no-op state — the body
    freezes its beam, counters and spare pointer — so retiring it early
    (scheduler) and keeping it resident (monolithic batch) yield the same
    final (dists, ids).  That absorption is why `no_better` is FROZEN for
    non-live rows rather than reset on a non-worse frontier: the old
    reset let a tripped row re-activate one iteration later, making its
    result depend on whether OTHER queries kept the batch loop running —
    batch-composition-dependent results that no compacting scheduler
    could reproduce.  (The reference never un-trips either: below budget
    it re-enters the trees — the spare-injection path here — rather than
    observing frontier improvement without expanding.)

    `data_score`: optional low-precision (bf16) shadow of `data` used for
    the in-loop candidate scoring — halves the dominant gather's HBM bytes
    and doubles the MXU rate on TPU.  The loop's distances only ORDER the
    beam; the final pool is re-ranked against the exact f32 rows before the
    top-k (_finalize), so returned distances (and the included/excluded
    boundary at k) are computed at full precision.

    `nbr_vecs` (N, m, D): optional packed per-node neighbor vectors
    (BeamPackedNeighbors) in the scoring dtype, `src[graph]` — the
    in-loop fetch becomes B block reads per query instead of B*m
    scattered row reads.

    WHAT A TRIP FETCHES (PRs 44, 45).  The vectors it scores, once, one
    of two ways.  Row-gather layout: by candidate id, the Q*B*m rows of
    the FRESH candidates, after the sort.  Packed-neighbour layout: by
    popped node, Q*B blocks of (m, D) — every neighbour's vector, fresh
    or not — before the sort, scored in the graph's order, the scores
    then riding the ONE sort by id as its payload (`_sorted_fresh`), so
    that nothing is carried back to the graph's order.  The same rows
    are scored against the same queries either way (the table holds the
    scoring source's very values), so the two layouts walk the same
    trajectories.  A candidate's norm is the float32 square sum of the
    row the fetch just brought — the shadow's, the dequantised tier's,
    the packed block's — so an in-loop L2 distance is |q~ - x~|^2 of
    the pair that was contracted, up to accumulation, whatever the
    scoring source; `sqnorm` (the float32 rows' norms) is read by the
    seeding from seeds and by `_finalize` only.  By pool position,
    once: the merge's `_flag_ids` word, a column's id with its
    `expanded` flag in bit 0.  Beside them: the pops' graph rows and the
    `visited` words (`_sorted_fresh`: one X-wide element gather).  An
    element gather is priced on the chip by the element fetched and by
    the distinct rows, not by the bytes (a float a candidate cost almost
    half of what its 256-byte row costs; an 8 KB block costs far less
    than its 32 rows), so what a fetch already carries is computed, and
    the same bytes are asked for in fewer, larger pieces where the
    device has room for the table (`packed_layout_fits`)."""
    if merge_bins:
        # the strided binning maps the sorted beam prefix (cols 0..L-1)
        # onto distinct bins ONLY when bins >= L — a narrower reduction
        # would self-collide the beam; engines size bins via
        # merge_bins_for, this guards direct kernel callers
        assert merge_bins >= L, (merge_bins, L)
    Q = queries.shape[0]
    N = data.shape[0]
    if N > MAX_FLAGGED_ROWS:
        raise ValueError(
            "the walk's merge keeps an id and its expanded flag in one "
            "int32 word: more than MAX_FLAGGED_ROWS (2^30) rows", N)
    sorted_order = dedup_in_sorted_order(merge_bins)
    score_src = data_score if data_score is not None else data
    # the bf16-shadow cast only applies between FLOAT dtypes: an int8
    # scoring corpus (score_scale below) keeps f32 queries — the
    # gathered rows are dequantized back to f32 before the contraction
    queries_s = (queries.astype(score_src.dtype)
                 if queries.dtype != score_src.dtype and
                 jnp.issubdtype(queries.dtype, jnp.floating) and
                 jnp.issubdtype(score_src.dtype, jnp.floating)
                 else queries)
    Ps = 0 if spare_ids is None else spare_ids.shape[1]
    use_spares = Ps > 0 and inject > 0
    # only REAL spare entries count as remaining work — the spare queue is
    # -1/MAX_DIST padded (fewer pivots than slots), and treating pads as
    # pending injections would keep converged queries spinning through
    # no-op inject/reset cycles until the full budget
    n_spare = (jnp.sum(spare_ids >= 0, axis=1).astype(jnp.int32)
               if use_spares else None)
    k_eff = min(k, L)

    def _active(no_better, ptr):
        # the reference only STOPS on continuous no-better-propagation when
        # the budget is also spent — below budget it re-enters the trees
        # for fresh pivots and keeps walking (BKTIndex.cpp:139-144, the
        # `m_iNumberOfCheckedLeaves > m_iMaxCheck` guard before the break).
        # Here: a query whose nbp counter trips stays active while real
        # spare pivots remain (the injection below resets the counter).
        act = no_better < nbp_limit
        if use_spares:
            act = act | (ptr < n_spare)
        return act

    def row_alive(state):
        cand_ids, cand_d, expanded, visited, no_better, ptr, it = state
        active = _active(no_better, ptr)
        has_work = jnp.any((~expanded[:, :L]) & (cand_ids >= 0), axis=1)
        if use_spares:
            # a fully-expanded beam with pending spares still has work —
            # the next injection may open an unreached graph component
            has_work = has_work | (ptr < n_spare)
        return (it < t_limit) & active & has_work

    @jax.named_scope("beam.score")
    def score(cvecs):
        # ---- score a trip's candidates (one batched contraction)
        if score_scale:
            # int8 cascade tier (CascadeSearch, ops/cascade.py): the
            # gathered rows are the int8 quantization of the corpus —
            # dequantize so in-loop distances stay in (approximately)
            # the true-distance space the f32-scored seeds live in; the
            # finalize re-rank restores exact fp distances
            cvecs = cvecs.astype(jnp.float32) * jnp.float32(score_scale)
        # no norm operand: a candidate's norm is the square sum of
        # the row just gathered, not a float fetched again by id
        return dist_ops.batched_gathered_distance(
            queries_s, cvecs, DistCalcMethod(metric), base)

    def body(state):
        cand_ids, cand_d, expanded, visited, no_better, ptr, it = state
        # a row past its own budget is frozen exactly like an nbp-tripped
        # one — this is what lets rows with DIFFERENT t_limit values share
        # one compiled program (mixed-MaxCheck slot pools)
        active = _active(no_better, ptr) & (it < t_limit)        # (Q,)

        with jax.named_scope("beam.merge"):
            if merge_bins:
                # ---- pop best B unexpanded entries: exact RANK-SELECT over
                # the sorted pool (eligible entries stay ascending around the
                # MAX_DIST voids, so the first B eligible positions ARE the
                # best B — same selection, same tie order as the top_k below,
                # without the L-wide sort)
                elig = (~expanded[:, :L]) & (cand_d < MAX_DIST)
                rank = jnp.where(
                    elig, jnp.cumsum(elig.astype(jnp.int32), axis=1) - 1,
                    B)                                           # B = drop
                spos = jax.vmap(
                    lambda r: jnp.full((B,), L, jnp.int32).at[r].set(
                        jnp.arange(L, dtype=jnp.int32), mode="drop"))(rank)
                sel_ok = (spos < L) & active[:, None]
                spos_safe = jnp.minimum(spos, L - 1)
                sel_d = jnp.where(
                    sel_ok, jnp.take_along_axis(cand_d, spos_safe, axis=1),
                    MAX_DIST)
                sel_ids = jnp.where(
                    sel_ok, jnp.take_along_axis(cand_ids, spos_safe, axis=1),
                    -1)
                expanded = _scatter_true(expanded,
                                         jnp.where(sel_ok, spos_safe, L))
                best_pop_d = sel_d[:, 0]
                frontier_worse = best_pop_d > cand_d[:, k_eff - 1]
            else:
                # ---- pop best B unexpanded entries
                sel_score = jnp.where(expanded[:, :L], MAX_DIST, cand_d)
                sneg, spos = jax.lax.top_k(-sel_score, B)            # (Q, B)
                sel_ok = ((-sneg) < MAX_DIST) & active[:, None]
                sel_ids = jnp.where(
                    sel_ok, jnp.take_along_axis(cand_ids, spos, axis=1), -1)
                expanded = _scatter_true(expanded, jnp.where(sel_ok, spos, L))
                # "no better propagation": the best popped frontier node is
                # already farther than the current worst result (reference
                # increments per such pop, BKTIndex.cpp:139-144; an iteration
                # here aggregates B pops, so the caller scales the limit by
                # 1/B)
                best_pop_d = -sneg[:, 0]
                frontier_worse = best_pop_d > cand_d[:, k_eff - 1]

        with jax.named_scope("beam.gather"):
            # ---- gather neighbors, dedupe against visited
            nbrs = graph[jnp.maximum(sel_ids, 0)]  # (Q, B, m)
            nbrs = jnp.where(sel_ok[..., None], nbrs, -1)
            flat = nbrs.reshape(Q, -1)                               # (Q, B*m)
            flat_safe = jnp.where(flat >= 0, flat, N)
        nd = None
        if nbr_vecs is not None:
            # packed-neighbour layout: the trip's vectors are fetched by
            # POPPED NODE, Q*B blocks of (m, D) instead of Q*B*m
            # scattered rows, and scored in the graph's order, which is
            # `flat`'s; the fetch waits for no `fresh` mask (a masked
            # pop's block is node 0's: scored and discarded)
            with jax.named_scope("beam.gather"):
                cvecs = nbr_vecs[jnp.maximum(sel_ids, 0)].reshape(
                    Q, flat.shape[1], -1)
            nd = score(cvecs)
        with jax.named_scope("beam.merge"):
            if sorted_order:
                # the candidates go into ascending-id order ONCE and stay
                # there: nothing downstream needs the graph's order (the
                # merge is a top_k over distances, the row gather takes
                # any order, the spares are appended after).  Scores the
                # blocks brought ride the sort as its payload: copies of
                # one id carry bit-identical scores (the same row against
                # the same query), so which copy the mask keeps is all one
                flat, fresh, visited, nd = _sorted_fresh(
                    visited, flat_safe, N, payload=nd)
            else:
                seen = _test_bits(visited, flat_safe)
                # binned body: NO X-wide sort.  Same-iteration duplicates are
                # collapsed after the merge's exact top-L (identical ids carry
                # bit-identical distances and land adjacent there), and the
                # visited marking is LAZY — only beam entrants are marked, in
                # the merge below.  `seen` still excludes everything already
                # in the beam or ever admitted to it (beam ⊆ visited).
                fresh = (flat >= 0) & ~seen
        if nd is None:
            # row-gather layout: the fresh candidates' rows by id, once
            with jax.named_scope("beam.gather"):
                cvecs = score_src[jnp.where(fresh, flat, 0)]  # (Q, C, D)
            nd = score(cvecs)
        with jax.named_scope("beam.score"):
            nd = jnp.where(fresh, nd, MAX_DIST)

        with jax.named_scope("beam.merge"):
            # ---- mid-walk re-seed: inject spare pivots when the frontier
            # falls behind the next unvisited pivot OR the nbp counter trips
            # with budget remaining (SearchTrees-on-demand,
            # BKTIndex.cpp:139-155)
            if use_spares:
                next_d = jnp.take_along_axis(
                    spare_d, jnp.minimum(ptr, Ps - 1)[:, None], axis=1)[:, 0]
                stalled = no_better + 1 >= nbp_limit     # would trip this iter
                trigger = active & (ptr < n_spare) & (
                    (best_pop_d > next_d) | stalled)
                idxs = ptr[:, None] + jnp.arange(inject, dtype=jnp.int32)
                ok = trigger[:, None] & (idxs < Ps)
                safe = jnp.minimum(idxs, Ps - 1)
                inj_ids = jnp.where(ok, jnp.take_along_axis(spare_ids, safe,
                                                            axis=1), -1)
                inj_d = jnp.where(ok & (inj_ids >= 0),
                                  jnp.take_along_axis(spare_d, safe, axis=1),
                                  MAX_DIST)
                ptr = jnp.where(trigger, ptr + inject, ptr)
                nd = jnp.concatenate([nd, inj_d], axis=1)
                flat_m = jnp.concatenate([flat, inj_ids], axis=1)
            else:
                trigger = None
                flat_m = flat

            # ---- merge beam + candidates, keep top-L
            # ONE word a merged column, the id with its `expanded` flag
            # in bit 0 (a fresh candidate's is clear): what is gathered by
            # position below is gathered once
            all_d = jnp.concatenate([cand_d, nd], axis=1)
            all_key = jnp.concatenate(
                [_flag_ids(cand_ids, expanded[:, :L]),
                 jnp.left_shift(flat_m, 1)], axis=1)
            if merge_bins:
                # bin-reduction merge: strided binning keeps the sorted beam
                # prefix collision-free (cols 0..L-1 -> distinct bins because
                # merge_bins >= L); each bin's best survives, then the exact
                # top-L runs over the bins-wide winner row
                vals, cols = topk_bins.bin_shortlist(all_d, merge_bins)
                sh_key = jnp.take_along_axis(all_key, cols, axis=1)
                mneg, mpos = jax.lax.top_k(-vals, L)
                cand_d = -mneg
                cand_ids, new_exp = _split_flagged(
                    jnp.take_along_axis(sh_key, mpos, axis=1))
                cand_ids = jnp.where(cand_d < MAX_DIST, cand_ids, -1)
                # same-iteration multi-parent copies: collapse duplicates
                # with the exact body's L-wide _sorted_dedup (an
                # adjacency-only mask would miss copies separated by an
                # unrelated bit-identical tie — common for integer
                # distances).  The kept copy is the lowest original
                # position = the better-ranked one, and the voids (-1 /
                # MAX_DIST / expanded) keep the pool's eligible subsequence
                # sorted, which the rank-select pop depends on.  ONE
                # argsort serves both the dup mask and the lazy visited
                # marking below.
                safe_ids = jnp.where(cand_ids >= 0, cand_ids, N)
                sorted_beam, dup = _sorted_dedup(safe_ids)
                dup = dup & (cand_ids >= 0)
                cand_ids = jnp.where(dup, -1, cand_ids)
                cand_d = jnp.where(dup, MAX_DIST, cand_d)
                expanded = jnp.concatenate(
                    [new_exp | dup, jnp.zeros((Q, 1), bool)], axis=1)
                # lazy visited marking: beam ENTRANTS only (an L-wide mark
                # instead of the exact body's X-wide ensemble; re-marking
                # resident ids is an idempotent OR, so marking the voided
                # dup copies too is harmless).  Shortlist-dropped
                # candidates stay unmarked — rediscoverable via another
                # parent, which is what keeps the binned walk's recall close
                # to exact.
                visited = _mark_bits_sorted(visited, sorted_beam)
            else:
                mneg, mpos = jax.lax.top_k(-all_d, L)
                cand_d = -mneg
                cand_ids, new_exp = _split_flagged(
                    jnp.take_along_axis(all_key, mpos, axis=1))
                cand_ids = jnp.where(cand_d < MAX_DIST, cand_ids, -1)
                expanded = jnp.concatenate(
                    [new_exp, jnp.zeros((Q, 1), bool)], axis=1)

        # non-live rows FREEZE their counter (see _walk_machine docstring:
        # resetting it on a non-worse frontier made a tripped row's fate
        # depend on the rest of the batch)
        no_better = jnp.where(active,
                              jnp.where(frontier_worse, no_better + 1, 0),
                              no_better)
        if use_spares:
            # a fresh tree re-seed resets the stall counter (the reference
            # continues its loop after SearchTrees rather than breaking)
            no_better = jnp.where(trigger, 0, no_better)
        return cand_ids, cand_d, expanded, visited, no_better, ptr, it + 1

    return body, row_alive


def _walk(data, sqnorm, graph, deleted, queries, cand_ids, cand_d, visited,
          k: int, L: int, B: int, t_limit, metric: int, base: int,
          nbp_limit: int, spare_ids=None, spare_d=None, inject: int = 0,
          data_score=None, nbr_vecs=None, merge_bins: int = 0,
          finalize_bins: int = 0, score_scale: float = 0.0):
    """Monolithic walk: run the shared body under one `lax.while_loop`
    until no row is alive, then finalize.  `t_limit` is a (Q,) traced
    budget vector (iterations per row) — budgets no longer mint compiles,
    only (L, B, k) do.  -> `BeamWalk`."""
    body, row_alive = _walk_machine(
        data, sqnorm, graph, queries, t_limit, k, L, B, metric, base,
        nbp_limit, spare_ids=spare_ids, spare_d=spare_d, inject=inject,
        data_score=data_score, nbr_vecs=nbr_vecs,
        merge_bins=merge_bins, score_scale=score_scale)

    def cond(carry):
        return jnp.any(row_alive(carry[1]))

    def counted(carry):
        # the count rides BESIDE the state: the body, and with it every
        # row's trajectory, is the segmented kernel's
        live, state = carry
        return live + row_alive(state).astype(jnp.int32), body(state)

    state = _init_walk_state(cand_ids, cand_d, visited)
    live, (cand_ids, cand_d, *_) = jax.lax.while_loop(
        cond, counted,
        (jnp.zeros(cand_ids.shape[:1], jnp.int32), state))
    rerank = data_score is not None and data_score.dtype != data.dtype
    final_d, final_ids = _finalize(
        data, sqnorm, deleted, queries, cand_ids, cand_d, min(k, L),
        metric, base, rerank, binned_bins=finalize_bins)
    return BeamWalk(final_d, final_ids, live)


@jax.named_scope("beam.finalize")
def _finalize(data, sqnorm, deleted, queries, cand_ids, cand_d, k_eff: int,
              metric: int, base: int, rerank: bool, binned_bins: int = 0):
    """Walk epilogue shared by the monolithic kernels and the scheduler's
    retire path: optional exact f32 re-rank of the L-pool, tombstone
    filter, final top-k.  `binned_bins` > 0 routes the final selection
    through the bin reduction (ops/topk_bins.py) — worthwhile only for
    wide pools (engines gate it on the recall-target bin math)."""
    if rerank:
        # exact f32 re-rank of the final L-pool: one (Q, L, D) gather —
        # about the cost of a single loop iteration's candidate gather
        safe = jnp.maximum(cand_ids, 0)
        exact = dist_ops.batched_gathered_distance(
            queries, data[safe], DistCalcMethod(metric), base, sqnorm[safe])
        cand_d = jnp.where(cand_ids >= 0, exact, MAX_DIST)

    # ---- final top-k with tombstones filtered -----------------------------
    dead = deleted[jnp.maximum(cand_ids, 0)] | (cand_ids < 0)
    out_d = jnp.where(dead, MAX_DIST, cand_d)
    if binned_bins:
        final_d, fpos = topk_bins.binned_topk(out_d, k_eff, binned_bins)
    else:
        fneg, fpos = jax.lax.top_k(-out_d, k_eff)
        final_d = -fneg
    final_ids = jnp.take_along_axis(cand_ids, fpos, axis=1)
    final_ids = jnp.where(final_d < MAX_DIST, final_ids, -1)
    return final_d, final_ids.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "B", "S", "metric", "base", "nbp_limit",
                     "inject", "merge_bins", "score_scale"))
def _beam_segment_kernel(data, sqnorm, graph, queries, t_limit, cand_ids,
                         cand_d, expanded, visited, no_better, ptr, it,
                         k: int, L: int, B: int, S: int, metric: int,
                         base: int, nbp_limit: int, inject: int = 0,
                         spare_ids=None, spare_d=None, data_score=None,
                         nbr_vecs=None, merge_bins: int = 0,
                         score_scale: float = 0.0):
    """Segmented walk: at most S iterations of the SAME body the
    monolithic walk runs, over loop-carried state passed in and returned
    intact — the device half of the continuous-batching walk
    (algo/scheduler.py).  Returns the updated 7-tuple plus the per-row
    `alive` flag and `live`, per row the iterations of THIS segment it
    was alive in; a row with alive=False is in the absorbing done state
    (retire it — its pool is final).  Empty slots are encoded as rows
    with t_limit=0 (never alive, body is a no-op on them)."""
    body, row_alive = _walk_machine(
        data, sqnorm, graph, queries, t_limit, k, L, B, metric, base,
        nbp_limit, spare_ids=spare_ids, spare_d=spare_d, inject=inject,
        data_score=data_score, nbr_vecs=nbr_vecs,
        merge_bins=merge_bins, score_scale=score_scale)

    def cond(carry):
        seg, _, state = carry
        return (seg < S) & jnp.any(row_alive(state))

    def sbody(carry):
        seg, live, state = carry
        return (seg + 1, live + row_alive(state).astype(jnp.int32),
                body(state))

    state = (cand_ids, cand_d, expanded, visited, no_better, ptr, it)
    _, live, state = jax.lax.while_loop(
        cond, sbody, (jnp.int32(0), jnp.zeros_like(it), state))
    return state + (row_alive(state), live)


@functools.partial(
    jax.jit, static_argnames=("k_eff", "metric", "base", "rerank",
                              "binned_bins"))
def _beam_finalize_kernel(data, sqnorm, deleted, queries, cand_ids, cand_d,
                          k_eff: int, metric: int, base: int, rerank: bool,
                          binned_bins: int = 0):
    return _finalize(data, sqnorm, deleted, queries, cand_ids, cand_d,
                     k_eff, metric, base, rerank, binned_bins=binned_bins)


@functools.partial(jax.jit, static_argnames=("k_eff", "metric", "base"))
def _beam_finalize_gathered_kernel(rows, dead, queries, cand_ids,
                                   k_eff: int, metric: int, base: int):
    """Host-tier finalize (CorpusTier=host, ops/cascade.py ISSUE 14):
    exact fp re-rank of the final L-pool over rows FETCHED FROM HOST
    RAM — the walk itself scored the int8 quantization, and the fp
    corpus never becomes device-resident.  `rows` is the (Q, L, D) f32
    host gather of `cand_ids` (row 0 for voids); `dead` the matching
    tombstone gather.  Tombstones fold into the ids and the epilogue IS
    cascade.rerank_gathered — the one traced function every fp re-rank
    tier shares (its bit-parity contract)."""
    from sptag_tpu.ops import cascade as cascade_ops

    ids = jnp.where(dead, -1, cand_ids)
    return cascade_ops.rerank_gathered(queries, rows, ids, k_eff, metric,
                                       base)


class GraphSearchEngine:
    """Immutable device snapshot of {vectors, graph, tombstones, pivots}
    plus the compiled beam-search program (the single-writer snapshot design
    of SURVEY.md §2b P7 — mutation builds a NEW engine, searches never lock).
    """

    def __init__(self, data: np.ndarray, graph: np.ndarray,
                 pivot_ids: np.ndarray, deleted: Optional[np.ndarray],
                 metric: DistCalcMethod, base: int,
                 score_dtype: str = "auto",
                 packed_neighbors="auto",
                 device_sample_rate: float = 0.0,
                 binned_topk: str = "off",
                 recall_target: float = topk_bins.DEFAULT_RECALL_TARGET,
                 cascade_search: bool = False,
                 corpus_tier: str = "device"):
        from sptag_tpu.ops import cascade as cascade_ops

        n = data.shape[0]
        assert graph.shape[0] == n, (graph.shape, n)
        self.n = n
        self.metric = DistCalcMethod(metric)
        self.base = base
        # tiered cascade (CascadeSearch, ops/cascade.py ISSUE 14): the
        # walk scores the int8 quantization of a float corpus (quarter
        # the gather bytes of f32, half of the bf16 shadow) and the
        # finalize re-ranks the final pool in exact fp.  CorpusTier=host
        # additionally moves the fp corpus to HOST RAM: the int8 blocks
        # ARE the device corpus, and the finalize fetches only the final
        # L-pool rows host->device (zero full-corpus device residency).
        # Integer corpora ignore the cascade (already quantized).
        self.cascade = bool(cascade_search) and \
            np.issubdtype(np.asarray(data).dtype, np.floating)
        self.corpus_tier = (cascade_ops.normalize_tier(corpus_tier)
                            if self.cascade else "device")
        if self.corpus_tier == "host_all":
            self.corpus_tier = "host"   # graph engines have no sketch tier
        self.score_scale = 0.0
        self.fp_host: Optional[np.ndarray] = None
        self._deleted_np: Optional[np.ndarray] = None
        self._cascade_int8 = None
        if self.cascade:
            int8_np, scale = cascade_ops.quantize_int8(
                np.asarray(data, np.float32))
            self._cascade_int8 = int8_np
            self.score_scale = cascade_ops.walk_score_scale(
                True, np.int8, scale)
            # the packed-neighbor layout materializes SCORE-dtype rows;
            # with the int8 tier active it would duplicate the corpus at
            # the wrong dtype — the cascade supersedes it
            packed_neighbors = False
        # bin-reduction top-k (BinnedTopK param, ops/topk_bins.py):
        # "off" keeps every selection exact (bit-parity path), "on"
        # forces the binned frontier merge + finalize, "auto" engages
        # them only at shapes where the reduction actually shrinks the
        # sorted width.  Baked into the snapshot like score_dtype — a
        # param flip invalidates the engine, never a live program.
        self.binned_mode = topk_bins.normalize_mode(binned_topk)
        self.recall_target = topk_bins.validate_recall_target(recall_target)
        if self.cascade and self.corpus_tier == "host":
            # host tier: the int8 quantization IS the device corpus; the
            # fp rows live host-side for the finalize fetch
            self.data = jnp.asarray(self._cascade_int8)
            self.fp_host = np.ascontiguousarray(
                np.asarray(data, np.float32))
        else:
            self.data = jnp.asarray(data)
        # bf16 shadow corpus for in-loop scoring (BeamScoreDtype param):
        # halves the walk's dominant gather bytes and doubles the MXU rate
        # at +50% corpus HBM.  "auto" = bf16 on TPU only — CPU's bf16
        # matmuls are emulated (slower) and the tests assert exact-f32
        # distances there.  The final pool is re-ranked in f32 (_walk), so
        # returned distances are exact either way; int corpora ignore this
        # (int8 gathers are already 4x smaller than f32).
        if score_dtype == "auto":
            score_dtype = ("bf16" if jax.devices()[0].platform == "tpu"
                           else "f32")
        if self.cascade and self.corpus_tier == "device":
            # device-tier cascade: the int8 quantization replaces the
            # bf16 shadow as the in-loop scoring corpus (half its bytes
            # again); the finalize re-rank against the resident fp
            # corpus restores exact distances, same as the bf16 path
            self.data_score = jnp.asarray(self._cascade_int8)
        else:
            self.data_score = (self.data.astype(jnp.bfloat16)
                               if score_dtype == "bf16"
                               and self.data.dtype == jnp.float32
                               else None)
        self._cascade_int8 = None        # host copy served its purpose
        self.sqnorm = jax.jit(dist_ops.row_sqnorms)(self.data)
        if self.fp_host is not None:
            # host tier: `data` is int8, so its norms are in quantized
            # units — rescale into the dequantized space the walk's
            # scoring (and the f32-scored pivot seeds) live in
            self.sqnorm = self.sqnorm * jnp.float32(self.score_scale
                                                    * self.score_scale)
        self.graph = jnp.asarray(graph.astype(np.int32, copy=False))
        if deleted is None:
            deleted = np.zeros(n, bool)
        self.deleted = jnp.asarray(deleted[:n])
        if self.fp_host is not None:
            # host finalize gathers tombstones host-side alongside rows
            self._deleted_np = np.ascontiguousarray(deleted[:n])
        pivot_ids = np.asarray(pivot_ids, np.int32)
        if len(pivot_ids) == 0:
            pivot_ids = np.zeros(1, np.int32)
        self.pivot_ids = jnp.asarray(pivot_ids)
        if self.fp_host is not None:
            # dequantized f32 pivots: seed distances must live in the
            # same (approximate) space the walk's dequantized scoring
            # does — the beam pool merges both
            self.pivot_vecs = (self.data[self.pivot_ids]
                               .astype(jnp.float32)
                               * jnp.float32(self.score_scale))
        else:
            self.pivot_vecs = self.data[self.pivot_ids]
        mask = np.zeros(_num_words(n), np.uint32)
        np.bitwise_or.at(mask, pivot_ids >> 5,
                         np.uint32(1) << (pivot_ids.astype(np.uint32) & 31))
        self.pivot_mask = jnp.asarray(mask.view(np.int32))
        # packed-neighbor layout (BeamPackedNeighbors): each node's m
        # neighbor VECTORS side by side, so the walk's in-loop fetch is B
        # block reads per query instead of B*m scattered rows, at m x
        # the scoring corpus in HBM.  True / False are obeyed; "auto" is
        # None here and settled, like the table is built, by the engine's
        # first walk (`walk_table`: `packed_layout_fits` over what the
        # device holds THEN; never off a TPU), so an engine that serves
        # `exact_scan`, a dense-mode index's refresh or a refine pass
        # through the dense searcher never pays for it.
        if packed_neighbors not in ("auto", True, False):
            raise ValueError(
                f"packed_neighbors is auto / True / False, got "
                f"{packed_neighbors!r}")
        self.packed: Optional[bool] = (
            None if packed_neighbors == "auto" else bool(packed_neighbors))
        self.nbr_vecs = None
        self._table_lock = locksan.make_lock(
            "GraphSearchEngine._table_lock")
        # device-time attribution (FlightDeviceSampleRate): every Nth
        # segment dispatch is timed to completion (block_until_ready) and
        # fed to the flight recorder + the engine.segment_device_ns
        # histogram, separating device time from host overhead.  The
        # sample gate is a deterministic counter (no RNG on the hot path,
        # reproducible traces); 0 disables.
        self.device_sample_rate = max(0.0, float(device_sample_rate))
        self._seg_dispatches = 0
        # device-memory ledger: every resident array of this snapshot,
        # owned by the engine (a snapshot swap retires the entry when
        # the superseded engine is collected)
        self.register_devmem()

    def register_devmem(self) -> None:
        """(Re-)register this snapshot's resident bytes with the memory
        ledger — called at build, and again when DeviceBytesLedger is
        re-enabled on a warm index (the disable dropped the entries).
        A host-tier cascade engine splits the accounting: the int8
        device corpus under ``int8_blocks`` and the host-RAM fp rows
        under ``host_corpus`` (host=True — on /debug/memory, excluded
        from the HBM total the capacity bench reads)."""
        if self.fp_host is not None:
            devmem.track("int8_blocks", self,
                         self.data.nbytes + self.sqnorm.nbytes
                         + self.deleted.nbytes)
            devmem.track("host_corpus", self, self.fp_host.nbytes,
                         host=True)
        else:
            devmem.track("corpus", self,
                         self.data.nbytes + self.sqnorm.nbytes
                         + (self.data_score.nbytes
                            if self.data_score is not None else 0)
                         + self.deleted.nbytes)
        devmem.track("graph", self, self.graph.nbytes)
        devmem.track("tree", self,
                     self.pivot_ids.nbytes + self.pivot_vecs.nbytes
                     + self.pivot_mask.nbytes)
        if self.nbr_vecs is not None:
            devmem.track("packed_neighbors", self,
                         self.nbr_vecs.nbytes)

    def walk_table(self) -> Optional[jax.Array]:
        """The packed-neighbour table a walk of this engine fetches its
        blocks from, None under the row-gather layout.  The first walk
        that asks settles an `auto` layout (`packed_layout_fits` over
        the device's memory as it stands now) and builds the table (one
        gather of N x m rows of the scoring source; -1 graph slots point
        at row 0, whose scores the walk's `fresh` mask discards like the
        row-gather path's placeholders), resident from then on."""
        if self.nbr_vecs is None and self.packed is not False:
            with self._table_lock:
                if self.packed is None:
                    self.packed = packed_layout_fits(
                        self.n, int(self.graph.shape[1]),
                        int(self.data.shape[1]), self.score_itemsize(),
                        *_device_memory())
                if self.packed and self.nbr_vecs is None:
                    src = (self.data_score if self.data_score is not None
                           else self.data)
                    self.nbr_vecs = _pack_neighbors(src, self.graph)
                    devmem.track("packed_neighbors", self,
                                 self.nbr_vecs.nbytes)
        return self.nbr_vecs

    def set_deleted(self, deleted: np.ndarray) -> None:
        """Swap only the tombstone mask — mutation path for delete-only
        changes, which must not pay a full snapshot rebuild."""
        self.deleted = jnp.asarray(deleted[:self.n])
        if self.fp_host is not None:
            self._deleted_np = np.ascontiguousarray(deleted[:self.n])

    def exact_scan(self, queries: np.ndarray, k: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact FLAT/MXU top-k over THIS snapshot's corpus — the
        quality monitor's ground-truth oracle for graph indexes
        (utils/qualmon.py shadow path, via VectorIndex
        .exact_search_batch).  Reuses the engine's already-resident
        data/sqnorm/deleted arrays, so the shadow path costs zero extra
        HBM, and rides the `flat.scan` kernel family.
        A host-tier cascade engine has no resident fp corpus: the
        oracle streams the scan through fixed fp blocks instead
        (cascade.host_exact_scan — re-uploading the corpus would break
        the zero-residency contract it is supposed to measure)."""
        if self.fp_host is not None:
            from sptag_tpu.ops import cascade as cascade_ops

            return cascade_ops.host_exact_scan(
                self.fp_host, self._deleted_np, queries,
                min(k, self.n), int(self.metric), self.base)
        from sptag_tpu.algo.flat import exact_device_scan

        return exact_device_scan(self.data, self.sqnorm, self.deleted,
                                 queries, k, int(self.metric), self.base)

    # ---- walk configuration / scheduler surface ---------------------------

    def walk_plan(self, k: int, max_check: int, beam_width: int = 16,
                  pool_size: Optional[int] = None, nbp_limit: int = 3
                  ) -> Tuple[int, int, int, int, int]:
        """(k_eff, L, B, T, limit): the static walk configuration for a
        budget — THE single formula shared by search() and the slot
        scheduler (algo/scheduler.py keys its pools on (k_eff, L, B,
        limit); T rides per-row as `t_limit`, so budgets that agree on
        the rest share a pool AND a compiled program)."""
        k_eff = min(k, self.n)
        L = beam_pool_size(k_eff, max_check, self.n, pool_size)
        B = beam_width_for(beam_width, max_check, L)
        T = max(1, -(-max_check // B))
        # continuous no-better-propagation limit: maxCheck/64 pops in the
        # reference (WorkSpace.h:191), aggregated B pops per iteration here
        limit = max(nbp_limit, (max_check // 64) // B, 1)
        return k_eff, L, B, T, limit

    def chunk_size(self) -> int:
        """Largest per-program query batch the visited-bitset budget
        allows (packed bitset: 4 bytes per 32 ids -> N/8 bytes/query)."""
        return max(1, min(_VISITED_BUDGET // max(self.n // 8, 1), 1024))

    def merge_bins_for(self, L: int, B: int) -> int:
        """Bin count of the walk's binned frontier merge at pool size L
        (0 = exact merge) — delegates to THE shared rule
        (topk_bins.walk_merge_bins; the sharded/mesh kernels use the
        same one, which is what keeps their id-parity contract intact
        with BinnedTopK on)."""
        return topk_bins.walk_merge_bins(
            self.binned_mode, L, L + B * int(self.graph.shape[1]))

    def seed_keep_for(self, L: int) -> int:
        """Spare-queue depth of the binned pivot seeding (0 = exact
        argsort seeding) — the shared topk_bins.seed_spare_keep rule at
        this engine's pivot-pool width."""
        return topk_bins.seed_spare_keep(
            self.binned_mode, L, max(int(self.pivot_ids.shape[0]), L))

    def finalize_bins_for(self, k_eff: int, L: int) -> int:
        """Bin count of the finalize top-k over the L-wide pool (0 =
        exact); sized by the recall-target formula, so it only engages
        for pools much wider than k_eff."""
        if self.binned_mode == "off":
            return 0
        return topk_bins.resolve_bins(self.binned_mode, k_eff, L,
                                      self.recall_target)

    def score_itemsize(self) -> int:
        """Bytes per element of the in-loop scoring corpus (bf16 shadow
        halves the walk's gather bytes) — gauge `beam.score_itemsize`,
        the byte scale of the benchmark's kernel.beam_walk_roofline."""
        src = self.data_score if self.data_score is not None else self.data
        return int(jnp.dtype(src.dtype).itemsize)

    def seed_state(self, queries: jax.Array, L: int,
                   seeds: Optional[jax.Array] = None) -> dict:
        """Seed a fresh walk state for `queries` (already device-shaped
        (Q, D)): the dict of loop-carried arrays plus the per-row spare
        queues and the queries themselves — everything a segment needs
        besides the engine snapshot.  The scheduler compacts/refills these
        arrays between segments; `run_segment` consumes them verbatim."""
        if seeds is None:
            cand_ids, cand_d, visited, spare_ids, spare_d = \
                _beam_seed_kernel(self.pivot_ids, self.pivot_vecs,
                                  self.pivot_mask, queries, L,
                                  int(self.metric),
                                  seed_keep=self.seed_keep_for(L))
        else:
            cand_ids, cand_d, visited = _beam_seed_seeded_kernel(
                self.data, self.sqnorm, seeds, queries, L,
                int(self.metric), self.base,
                score_scale=self.score_scale)
            spare_ids = spare_d = None
        cand_ids, cand_d, expanded, visited, no_better, ptr, it = \
            _init_walk_state(cand_ids, cand_d, visited)
        return {"queries": queries, "cand_ids": cand_ids, "cand_d": cand_d,
                "expanded": expanded, "visited": visited,
                "no_better": no_better, "ptr": ptr, "it": it,
                "spare_ids": spare_ids, "spare_d": spare_d}

    def run_segment(self, state: dict, t_limit: jax.Array, k_eff: int,
                    L: int, B: int, nbp_limit: int, S: int,
                    inject: int = 0) -> Tuple[dict, jax.Array]:
        """Advance every row of `state` by at most S walk iterations;
        returns (new state, (Q,) alive).  Rows with alive=False are done
        (absorbing) — their pool is final and `finalize` may retire them.
        `new["live"]` (Q,) counts, per row, the iterations of THIS
        segment it was alive in (what `_publish_walk` is fed from)."""
        spare_ids = state["spare_ids"]
        sample = False
        if self.device_sample_rate > 0:
            self._seg_dispatches += 1
            every = (1 if self.device_sample_rate >= 1.0
                     else max(1, int(round(1.0 / self.device_sample_rate))))
            sample = (self._seg_dispatches % every) == 0
        t0 = time.monotonic_ns() if sample else 0
        out = _beam_segment_kernel(
            self.data, self.sqnorm, self.graph, state["queries"], t_limit,
            state["cand_ids"], state["cand_d"], state["expanded"],
            state["visited"], state["no_better"], state["ptr"], state["it"],
            k_eff, L, B, S, int(self.metric), self.base, nbp_limit,
            inject=inject if spare_ids is not None else 0,
            spare_ids=spare_ids, spare_d=state["spare_d"],
            data_score=self.data_score, nbr_vecs=self.walk_table(),
            merge_bins=self.merge_bins_for(L, B),
            score_scale=self.score_scale)
        if sample:
            # dispatch-to-completion wall time: the kernel call returns as
            # soon as XLA enqueues, so only a sampled block_until_ready
            # observes the DEVICE time of a segment.  Values are
            # nanoseconds (the _ns suffix contract; consume mean via
            # _sum/_count — the log buckets are second-scaled).
            jax.block_until_ready(out)
            dev_ns = time.monotonic_ns() - t0
            metrics.observe("engine.segment_device_ns", dev_ns)
            rows = int(state["queries"].shape[0])
            flightrec.record("engine", "segment_device", dur_ns=dev_ns,
                             payload={"rows": rows, "iters": S})
        new = dict(state)
        (new["cand_ids"], new["cand_d"], new["expanded"], new["visited"],
         new["no_better"], new["ptr"], new["it"], alive,
         new["live"]) = out
        return new, alive

    def finalize(self, state: dict, k_eff: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Rerank + tombstone-filter + top-k over the state's pools;
        identical epilogue to the monolithic kernels.  A host-tier
        cascade engine fetches ONLY the final L-pool's fp rows from
        host RAM for the exact re-rank (the beyond-HBM contract:
        the fp corpus never rides the device)."""
        if self.fp_host is not None:
            # device_get: the ONE sanctioned mid-walk readback — the
            # host-tier gather needs the pool ids on the host by design
            # (the trace sentinel blesses it; np.asarray here would trip
            # GL902 and, on real accelerators, the transfer guard)
            with trace.span("index.readback"):
                ids_np = recompile_guard.device_get(state["cand_ids"])
            safe = np.clip(ids_np, 0, self.fp_host.shape[0] - 1)
            rows = self.fp_host[safe]
            dead = self._deleted_np[safe]
            d, ids = _beam_finalize_gathered_kernel(
                jnp.asarray(rows), jnp.asarray(dead), state["queries"],
                state["cand_ids"], k_eff, int(self.metric), self.base)
            with trace.span("index.readback"):
                return recompile_guard.device_get((d, ids))
        rerank = (self.data_score is not None
                  and self.data_score.dtype != self.data.dtype)
        d, ids = _beam_finalize_kernel(
            self.data, self.sqnorm, self.deleted, state["queries"],
            state["cand_ids"], state["cand_d"], k_eff, int(self.metric),
            self.base, rerank,
            binned_bins=self.finalize_bins_for(
                k_eff, int(state["cand_ids"].shape[1])))
        with trace.span("index.readback"):
            # the host blocks here until the walk and the re-rank have run
            return recompile_guard.device_get((d, ids))

    def _search_segmented(self, queries: np.ndarray,
                          seeds: Optional[np.ndarray], k_eff: int, L: int,
                          B: int, T: int, limit: int, inject: int,
                          chunk: int, S: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """search() via repeated fixed-S segments (BeamSegmentIters) —
        the checkpoint/resume execution of the same walk, bit-identical
        to the monolithic kernels (tests/test_beam_segmented.py pins it).
        No refill here; the slot scheduler adds that on top."""
        nq, D = queries.shape
        out_d = np.zeros((nq, k_eff), np.float32)
        out_i = np.zeros((nq, k_eff), np.int32)
        trips, live = 0, np.zeros((nq,), np.int64)
        for start in range(0, nq, chunk):
            q = queries[start:start + chunk]
            nqc = q.shape[0]
            q_pad = query_bucket(nqc, chunk)
            if q_pad != nqc:
                q = np.concatenate([q, np.zeros((q_pad - nqc, D), q.dtype)])
            s = None
            if seeds is not None:
                s = seeds[start:start + nqc].astype(np.int32, copy=False)
                if q_pad != nqc:
                    s = np.concatenate(
                        [s, np.full((q_pad - nqc, s.shape[1]), -1,
                                    np.int32)])
                s = jnp.asarray(s)
            state = self.seed_state(jnp.asarray(q), L, seeds=s)
            # pad rows get t_limit 0: never alive, bit-frozen no-ops
            t_limit = np.zeros((q_pad,), np.int32)
            t_limit[:nqc] = T
            t_limit = jnp.asarray(t_limit)
            while True:
                state, alive = self.run_segment(state, t_limit, k_eff, L,
                                                B, limit, S, inject=inject)
                # explicit readback: the segment loop's continue-flag is
                # the intended per-segment sync point; the segment's live
                # counts ride it
                with trace.span("index.readback"):
                    going, seg_live = recompile_guard.device_get(
                        (jnp.any(alive), state["live"]))
                # a row alive in a segment's last iteration was alive in
                # all of them (dead is absorbing; pad rows never live)
                trips += int(seg_live.max())
                live[start:start + nqc] += seg_live[:nqc]
                if not bool(going):
                    break
            d, ids = self.finalize(state, k_eff)
            out_d[start:start + nqc] = d[:nqc]
            out_i[start:start + nqc] = ids[:nqc]
        metrics.inc("beam.segmented")
        self._publish_walk(trips, live, nq, B, L,
                           query_bucket(min(nq, chunk), chunk))
        return out_d, out_i

    def _publish_walk(self, trips: int, live, nq: int, B: int,
                      L: int, rung: int) -> None:
        """What the batch just read back walked, from the count its
        program returned with the answers (the caller has counted which
        driver ran it: `beam.monolithic` / `.chunked` / `.segmented`;
        counted here: which visited / de-duplicate ensemble its program
        was traced with, `beam.dedup_sorted` / `.dedup_positional`,
        which way it fetched its vectors, `beam.fetch_blocks` /
        `.fetch_rows` with the gauge `beam.fetches_per_trip` — a
        program run of `rung` query rows asks for rung x B blocks or
        rung x B x m rows a trip —, and `beam.norm_from_rows` where it
        scored L2):
        its trips (`beam.trips`, `beam.trips_total`: the `live.max()` of
        each while loop, so a chunked or segmented batch reads the sum
        over the chunks it walked one after another), its pool, pivot
        table and scoring itemsize, and the rows a real query had scored
        — B pops x the neighbourhood for every trip the query was alive
        in, over the `nq` real rows (pad rows walk in the monolithic
        programs and are not counted): the batch's mean as a gauge, and
        `beam.rows_scored_total` over `beam.queries_total` for a ratio
        of totals.  docs/TELEMETRY.md; the benchmark's kernel.beam_*
        read them."""
        m = int(self.graph.shape[1])
        scored = int(np.sum(np.reshape(live, -1)[:nq])) * B * m
        if dedup_in_sorted_order(self.merge_bins_for(L, B)):
            metrics.inc("beam.dedup_sorted")
        else:
            metrics.inc("beam.dedup_positional")
        if self.packed:
            metrics.inc("beam.fetch_blocks")
        else:
            metrics.inc("beam.fetch_rows")
        metrics.set_gauge("beam.fetches_per_trip",
                          rung * B * (1 if self.packed else m))
        if walk_takes_norm(self.metric):
            # the body scored against the square sum of the rows its
            # gather brought, not a norm fetched by id (cosine has none)
            metrics.inc("beam.norm_from_rows")
        metrics.inc("beam.trips_total", trips)
        metrics.inc("beam.rows_scored_total", scored)
        metrics.inc("beam.queries_total", nq)
        metrics.set_gauge("beam.trips", trips)
        metrics.set_gauge("beam.pool", L)
        metrics.set_gauge("beam.pivots", int(self.pivot_ids.shape[0]))
        metrics.set_gauge("beam.score_itemsize", self.score_itemsize())
        metrics.set_gauge("beam.rows_scored_per_query", scored / nq)

    def search(self, queries: np.ndarray, k: int, max_check: int = 2048,
               beam_width: int = 16, pool_size: Optional[int] = None,
               nbp_limit: int = 3, seeds: Optional[np.ndarray] = None,
               dynamic_pivots: int = 4,
               segment_iters: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched search; returns ((Q, k) dists, (Q, k) int32 ids),
        ascending, -1 / MAX_DIST padded.

        `seeds` (Q, S) int32 overrides the engine's shared pivot seeding
        with per-query seed ids (KDT tree-descent seeding), -1 padded.
        `dynamic_pivots` = spare pivots injected per mid-walk re-seed
        (reference NumberOfOtherDynamicPivots); 0 disables re-seeding.
        `segment_iters` > 0 runs the walk as fixed-size compiled segments
        of that many iterations (state checkpointed between segments)
        instead of one monolithic while-loop — same results bit for bit.
        """
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        k_eff, L, B, T, limit = self.walk_plan(k, max_check, beam_width,
                                               pool_size, nbp_limit)
        mb = self.merge_bins_for(L, B)
        fb = self.finalize_bins_for(k_eff, L)
        sk = self.seed_keep_for(L)
        chunk = self.chunk_size()
        table = self.walk_table()
        out_d = np.full((nq, k), np.float32(MAX_DIST), np.float32)
        out_i = np.full((nq, k), -1, np.int32)
        D = queries.shape[1]
        if self.fp_host is not None and not segment_iters:
            # host-tier cascade: the finalize's fp rows come from HOST
            # RAM, which the monolithic fused kernels cannot express —
            # run the walk as one full-budget segment and finalize
            # through the host-gather epilogue (bit-identical walk
            # trajectories either way; DESIGN.md §10's parity contract)
            segment_iters = T
        if segment_iters:
            d, ids = self._search_segmented(
                queries, seeds, k_eff, L, B, T, limit, dynamic_pivots,
                chunk, int(segment_iters))
            out_d[:, :k_eff] = d
            out_i[:, :k_eff] = ids
            return out_d, out_i
        if nq <= chunk:
            q_pad = query_bucket(nq, chunk)
            q = queries
            if q_pad != nq:
                q = np.concatenate(
                    [q, np.zeros((q_pad - nq, D), q.dtype)])
            t_limit = jnp.full((q_pad,), T, jnp.int32)
            if seeds is None:
                out = _beam_search_kernel(
                    self.data, self.sqnorm, self.graph, self.deleted,
                    self.pivot_ids, self.pivot_vecs, self.pivot_mask,
                    jnp.asarray(q), t_limit,
                    k_eff, L, B, int(self.metric), self.base, limit,
                    inject=dynamic_pivots, data_score=self.data_score,
                    nbr_vecs=table,
                    merge_bins=mb, finalize_bins=fb, seed_keep=sk,
                    score_scale=self.score_scale)
            else:
                s = seeds.astype(np.int32, copy=False)
                if q_pad != nq:
                    s = np.concatenate(
                        [s, np.full((q_pad - nq, s.shape[1]), -1,
                                    np.int32)])
                out = _beam_search_seeded_kernel(
                    self.data, self.sqnorm, self.graph, self.deleted,
                    jnp.asarray(s), jnp.asarray(q), t_limit,
                    k_eff, L, B, int(self.metric), self.base, limit,
                    data_score=self.data_score,
                    nbr_vecs=table,
                    merge_bins=mb, finalize_bins=fb,
                    score_scale=self.score_scale)
            with trace.span("index.readback"):
                # the host blocks here until the program has run; the
                # walk's count comes back with the answers
                d, ids, live = recompile_guard.device_get(out)
            out_d[:, :k_eff] = d[:nq]
            out_i[:, :k_eff] = ids[:nq]
            metrics.inc("beam.monolithic")
            self._publish_walk(int(live.max()), live, nq, B, L, q_pad)
            return out_d, out_i
        # multi-chunk: one lax.map device program (one upload / dispatch /
        # read — a Python chunk loop pays a synced host round trip once
        # PER chunk)
        m = -(-nq // chunk)
        q = queries
        if m * chunk != nq:
            q = np.concatenate(
                [q, np.zeros((m * chunk - nq, D), q.dtype)])
        t_limit = jnp.full((chunk,), T, jnp.int32)
        if seeds is None:
            out = _beam_search_chunked(
                self.data, self.sqnorm, self.graph, self.deleted,
                self.pivot_ids, self.pivot_vecs, self.pivot_mask,
                jnp.asarray(q.reshape(m, chunk, D)), t_limit,
                k_eff, L, B, int(self.metric), self.base, limit,
                inject=dynamic_pivots, data_score=self.data_score,
                nbr_vecs=table,
                merge_bins=mb, finalize_bins=fb, seed_keep=sk,
                score_scale=self.score_scale)
        else:
            s = seeds.astype(np.int32, copy=False)
            if m * chunk != nq:
                s = np.concatenate(
                    [s, np.full((m * chunk - nq, s.shape[1]), -1,
                                np.int32)])
            out = _beam_search_seeded_chunked(
                self.data, self.sqnorm, self.graph, self.deleted,
                jnp.asarray(s.reshape(m, chunk, -1)),
                jnp.asarray(q.reshape(m, chunk, D)), t_limit,
                k_eff, L, B, int(self.metric), self.base, limit,
                data_score=self.data_score,
                nbr_vecs=table,
                merge_bins=mb, finalize_bins=fb,
                score_scale=self.score_scale)
        with trace.span("index.readback"):
            d, ids, live = recompile_guard.device_get(out)
        out_d[:, :k_eff] = d.reshape(m * chunk, -1)[:nq]
        out_i[:, :k_eff] = ids.reshape(m * chunk, -1)[:nq]
        metrics.inc("beam.chunked")
        # (m, chunk): lax.map walks the chunks one after another
        self._publish_walk(int(live.max(axis=1).sum()), live, nq, B, L,
                           chunk)
        return out_d, out_i


