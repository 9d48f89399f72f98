"""Bin-reduction approximate top-k — the peak-FLOP/s selection primitive.

`lax.top_k` over an N-wide score row is a full sort under XLA:CPU and a
multi-pass O(N log N) selection on TPU — at serving shapes it is the part
of every scan/merge kernel that is NOT a matmul, and a CPU run (record
removed in PR 29) read it, with the argsort ensembles around it,
dominating the beam path.
"TPU-KNN: K Nearest Neighbor Search at Peak FLOP/s" (arXiv:2206.14286)
replaces it with a **partial bin reduction**: scatter the N scores into
``bins`` bins with a cheap strided rule, keep each bin's best element
(min + argmin — one O(N) pass, no data movement beyond a reshape), and
run the exact top-k only over the ``bins``-wide winner row.  The result
is exact whenever no two of the true top-k collide in a bin; the expected
recall over uniformly scattered winners is

    E[recall@k] = prod_{i<k} (1 - i/bins)  ~=  exp(-k(k-1) / (2*bins))

which `bins_for` inverts to size the reduction for a recall target
("Fast top-K Cosine Similarity Search through XOR-Friendly Binary
Quantization", arXiv:2008.02002, validates the same coarse-select ->
exact-re-rank shape end to end).  Distances of returned ids are always
exact — only membership of the selected set is approximate.

Binning is **strided** (column ``j`` lands in bin ``j % bins``): the beam
walk's merge concatenates an already-sorted beam prefix ahead of the
unsorted candidate block, and a strided rule maps any ``bins``-long
sorted prefix onto distinct bins (contiguous binning would pile the
whole prefix into bin 0 and truncate the beam to one entry).  Ties
within a bin resolve to the lowest stride (= lowest original column),
matching `lax.top_k`'s lowest-index tie rule.

All helpers here are plain traceable functions composed INSIDE the
registered scan/walk kernels; the standalone jitted `binned_topk_kernel`
(registered as the ``ops.binned_topk`` cost family) exists for direct
callers and the property tests.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


MAX_DIST = np.float32(3.4e38)   # plain scalar: module import must NOT init a backend

#: default recall target of the `auto` engagement rule (overridable via
#: the ApproxRecallTarget parameter on every index family)
DEFAULT_RECALL_TARGET = 0.99

#: `auto` engages the reduction only when the row is at least this many
#: times wider than the bin count — below that the exact top-k is the
#: same work and strictly better
AUTO_WIDTH_FACTOR = 2


def pow2ceil(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


def validate_recall_target(rt: float) -> float:
    """Recall targets live in (0, 1]; 1.0 means exact selection."""
    rt = float(rt)
    if not (0.0 < rt <= 1.0):
        raise ValueError(
            f"recall target must be in (0, 1], got {rt!r} "
            "(ApproxRecallTarget / BinnedTopK contract)")
    return rt


def bins_for(k: int, width: int,
             recall_target: float = DEFAULT_RECALL_TARGET) -> int:
    """Power-of-two bin count meeting `recall_target` for a top-`k`
    selection over a `width`-wide row of uniformly scattered winners:
    inverting E[recall] ~= exp(-k(k-1)/(2*bins)) gives
    bins >= k(k-1) / (2 ln(1/recall)).  Floored at 2k (the reduction
    must leave headroom over the selection width) and capped at the row
    width (more bins than columns is the identity)."""
    recall_target = validate_recall_target(recall_target)
    if recall_target >= 1.0:
        need = width                      # exact: every column its own bin
    elif k <= 1:
        need = 1
    else:
        need = k * (k - 1) / (2.0 * math.log(1.0 / recall_target))
    bins = pow2ceil(max(int(math.ceil(need)), 2 * k, 1))
    return min(bins, pow2ceil(width))


def auto_bins(k: int, width: int,
              recall_target: float = DEFAULT_RECALL_TARGET) -> int:
    """The `BinnedTopK=auto` engagement rule: the bin count from
    `bins_for`, or 0 (stay exact) when the row is not at least
    AUTO_WIDTH_FACTOR times wider than it — the reduction only pays for
    itself when it actually shrinks the sorted width."""
    bins = bins_for(k, width, recall_target)
    return bins if width >= AUTO_WIDTH_FACTOR * bins else 0


def normalize_mode(mode) -> str:
    """Canonical BinnedTopK value: off / on / auto (raises otherwise)."""
    m = (str(mode) if mode is not None else "off").strip().lower()
    if m in ("off", "0", ""):
        return "off"
    if m in ("on", "1"):
        return "on"
    if m == "auto":
        return "auto"
    raise ValueError(f"BinnedTopK must be off/on/auto, got {mode!r}")


def walk_merge_bins(mode: str, L: int, width: int) -> int:
    """THE bin-count rule of the beam walk's frontier merge, shared by
    the single-chip engine, the monolithic sharded kernel and the mesh
    segment engine (one formula or their bit-parity contract would hinge
    on three copies agreeing).  Structural, not recall-target math:
    bins = pow2ceil(2L) >= 2L keeps the sorted beam prefix
    collision-free under the strided binning AND leaves every beam slot
    a collision-free partner bin for incoming candidates (measured on
    the 200k bench graph: bins = pow2ceil(L+1) lost 0.9pt recall@10 vs
    the exact merge, pow2ceil(2L) closed it to inside the Wilson CI for
    ~2% iteration cost); `width` is the merged row (L + B*m,
    spare-injection columns excluded).  0 = exact merge."""
    mode = normalize_mode(mode)
    if mode == "off":
        return 0
    bins = pow2ceil(2 * L)
    if mode == "on":
        return bins if width > bins else 0
    return bins if width >= AUTO_WIDTH_FACTOR * bins else 0


def seed_spare_keep(mode: str, L: int, width: int) -> int:
    """Binned SEEDING rule (shared like `walk_merge_bins`): how many
    sorted spare pivots beyond the top-L the bin-reduced seed select
    keeps (0 = exact full-argsort seeding).  The walk can consume at
    most `inject` spares per iteration, so 3L spares (~hundreds of
    injections at bench shapes) is far past any real budget — while the
    seed's (Q, P)-wide argsort, the single most expensive sort left in
    the binned walk, shrinks to a bin reduction + top-(L + keep)."""
    if normalize_mode(mode) == "off":
        return 0
    keep = max(min(width - L, 3 * L), 0)
    kbins = pow2ceil(L + keep)
    if width < AUTO_WIDTH_FACTOR * kbins:
        return 0              # row too narrow: exact seeding is cheaper
    return keep


def resolve_bins(mode: str, k: int, width: int,
                 recall_target: float = DEFAULT_RECALL_TARGET) -> int:
    """Map a BinnedTopK parameter value to a bin count (0 = exact).

    "off"/"0"/"" never bins; "on"/"1" always bins at the recall-target
    size (still 0 when the row is no wider than the bins — binning
    would be the identity); "auto" applies the width-factor rule."""
    mode = normalize_mode(mode)
    if mode == "off":
        return 0
    if mode == "on":
        bins = bins_for(k, width, recall_target)
        return bins if width > bins else 0
    return auto_bins(k, width, recall_target)


def bin_shortlist(d: jax.Array, bins: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """(Q, W) distances -> ((Q, bins) per-bin minima, (Q, bins) source
    columns).  Column ``j`` belongs to bin ``j % bins``; the row is
    MAX_DIST-padded up to a stride multiple, so empty bins surface as
    MAX_DIST winners (callers already treat MAX_DIST as padding)."""
    q, w = d.shape
    strides = -(-w // bins)
    pad = strides * bins - w
    if pad:
        d = jnp.concatenate(
            [d, jnp.full((q, pad), MAX_DIST, d.dtype)], axis=1)
    r = d.reshape(q, strides, bins)
    amin = jnp.argmin(r, axis=1)                           # (Q, bins)
    vals = jnp.min(r, axis=1)
    cols = (amin.astype(jnp.int32) * jnp.int32(bins)
            + jnp.arange(bins, dtype=jnp.int32)[None, :])
    return vals, cols


def binned_topk(d: jax.Array, k: int, bins: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Approximate ``(-lax.top_k(-d, k))``: per-bin reduction, then the
    exact top-k over the ``bins``-wide winner row.  Returns
    ((Q, k) distances ascending, (Q, k) int32 column indices into the
    original row).  ``k`` is clamped to ``bins`` (a wider ask cannot be
    served by a ``bins``-wide shortlist — callers size bins via
    `bins_for`, which floors at 2k)."""
    vals, cols = bin_shortlist(d, bins)
    neg, pos = jax.lax.top_k(-vals, min(k, bins))
    return -neg, jnp.take_along_axis(cols, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "bins"))
def binned_topk_kernel(d: jax.Array, k: int, bins: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """Standalone jitted `binned_topk` for direct callers (tests, the
    perf probe); the scan/walk kernels compose the traceable helpers
    inline instead."""
    return binned_topk(d, k, bins)
