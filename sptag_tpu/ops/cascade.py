"""Tiered corpus cascade — sketch Hamming scan → int8 re-rank → fp exact.

The 1-bit SketchPrefilter (algo/flat.py) and the exact s8×s8→s32 MXU
path (ops/distance.py) existed as separate opt-in modes; a device still
had to hold the full f32 corpus to serve exact results.  This module
promotes the production pattern of KBest (arXiv:2508.03016) — quantized
coarse scan over *everything*, exact re-rank on a per-tier-budgeted
shortlist — to a first-class pipeline, and adds the next tier SPTAG
itself grew into ("Exploiting Modern Hardware for High-Dimensional NN
Search", arXiv:1712.02912): full-precision vectors resident in HOST
memory, fetched asynchronously for the exact re-rank only.

Tier contract (DESIGN.md §20):

* **sketch tier** — XOR+popcount Hamming scan over packed 1-bit sign
  sketches (1/32 of the f32 corpus bytes); keeps the best
  ``TierBudgetSketch`` rows per query.  A budget covering the whole
  corpus disables the tier's filtering and the program composes without
  it (the int8 tier then scans everything).
* **int8 tier** — exact s8×s8→s32 MXU contraction of per-query-quantized
  queries against the symmetric per-corpus int8 quantization of the
  shortlist rows (1/4 of the f32 bytes); keeps ``TierBudgetInt8`` rows.
  Distances here only ORDER candidates — they are dequantized estimates.
* **fp tier** — exact f32 re-rank of the surviving shortlist; returned
  distances are always exact, whatever the upstream tiers did.

``CorpusTier`` decides residency: ``device`` keeps all three tiers in
HBM (one fused program, a pure speed play); ``host`` keeps only
sketches + int8 blocks in HBM and the fp corpus in host RAM — the exact
re-rank gathers just the shortlist rows host→device, double-buffered so
the next chunk's device scan overlaps the current chunk's host fetch;
``host_all`` additionally hosts the int8 blocks (the sketch scan is the
only per-corpus HBM cost — maximum vectors per HBM byte, two host
fetches per chunk).  The shortlist/re-rank split uses the SAME traced
re-rank function for every tier, so a host-fetched re-rank is
bit-identical to the device-resident one (tests/test_cascade.py pins
it).

All knobs default off; with CascadeSearch=0 no kernel here is ever
built and serve bytes are byte-identical (the off-parity contract every
subsystem in this repo carries).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sptag_tpu.core.types import DistCalcMethod
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.ops.topk_bins import pow2ceil
from sptag_tpu.utils import devmem, metrics

MAX_DIST = np.float32(3.4e38)   # plain scalar: import must NOT init a backend

#: corpus rows are padded to multiples of this (TPU lane width), same
#: layout rule as algo/flat.py's snapshot
ROW_PAD = 128

#: host-tier pipeline chunk: queries per shortlist dispatch (the unit of
#: the double buffer — chunk i+1's device scan is enqueued before chunk
#: i's host fetch begins)
HOST_CHUNK = 256

#: row block of the streaming host exact scan (the oracle of host-tier
#: indexes): bounds transient HBM at block_rows * D * 4 bytes
HOST_SCAN_BLOCK = 65536

CORPUS_TIERS = ("device", "host", "host_all")


def normalize_tier(tier: str) -> str:
    """Validate a CorpusTier value (the parameter is INI-settable and a
    typo'd tier silently serving fp-resident would defeat the point)."""
    t = str(tier or "device").strip().lower()
    if t not in CORPUS_TIERS:
        raise ValueError(
            f"CorpusTier must be one of {CORPUS_TIERS}, got {tier!r}")
    return t


def resolve_budgets(b1: int, b2: int, k: int, n: int) -> Tuple[int, int]:
    """Static per-tier candidate budgets for a corpus of `n` live-padded
    rows: (sketch shortlist, int8 shortlist).

    0 = auto (the SketchRerank-style heuristic: generous enough that the
    fp tier sees every plausible neighbor on clustered corpora).
    Negative budgets are a configuration error.  Budgets are quantized
    UP to powers of two — they are static kernel-shape parameters, and
    unquantized values would mint a fresh XLA compile per distinct
    setting (the same bounded-compile-cache rationale as SketchRerank's
    calibration quantization).  Invariant: k <= B2 <= B1 <= n; a budget
    quantizing to >= n disables that tier's filtering entirely (the
    composed program skips the stage — see `build_state`/kernels)."""
    b1, b2, k, n = int(b1), int(b2), int(k), int(n)
    if b1 < 0 or b2 < 0:
        raise ValueError(
            f"tier budgets must be >= 0 (0 = auto): "
            f"TierBudgetSketch={b1} TierBudgetInt8={b2}")
    if b1 == 0:
        b1 = min(max(128, 16 * k, n // 16), 8192)
    if b2 == 0:
        b2 = min(max(4 * k, 64), 1024)
    b1 = min(max(pow2ceil(max(b1, k)), 1), n)
    b2 = min(max(pow2ceil(max(b2, k)), 1), b1, n)
    return b1, b2


def quantize_int8(data: np.ndarray) -> Tuple[np.ndarray, float]:
    """Symmetric per-corpus int8 quantization of an f32 corpus:
    ``x ~= scale * q`` with q in [-127, 127].  One global scale (not
    per-row) keeps the int8 distances comparable ACROSS rows, which is
    all the tier needs — its distances only order candidates."""
    data = np.asarray(data)
    if not np.issubdtype(data.dtype, np.floating):
        raise ValueError(
            "the int8 cascade tier quantizes FLOAT corpora; value type "
            f"{data.dtype} is already integer — the cascade would be an "
            "identity there (serve it directly)")
    m = float(np.max(np.abs(data))) if data.size else 0.0
    scale = (m / 127.0) if m > 0 else 1.0
    q = np.clip(np.rint(data / scale), -127, 127).astype(np.int8)
    return q, scale


def pack_sign_bits(centered: jax.Array) -> jax.Array:
    """(R, D) centered values -> (R, W) int32 packed sign bits, W =
    ceil(D/32).  Bit i of word w = sign(x[32w + i]) > 0; D is zero-padded
    so query and corpus pads contribute identical bits (XOR = 0).
    (Canonical home of the sketch packer; algo/flat.py re-exports it.)"""
    r, d = centered.shape
    w = (d + 31) // 32
    pad = w * 32 - d
    bits = (centered > 0)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((r, pad), bool)], axis=1)
    bits = bits.reshape(r, w, 32).astype(jnp.int32)
    powers = jnp.left_shift(jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
    return (bits * powers[None, None, :]).sum(axis=2).astype(jnp.int32)


# ---------------------------------------------------------------------------
# traced tier stages (composed inside the registered kernels)
# ---------------------------------------------------------------------------

def _hamming(sketches, qbits, invalid):
    """(Q, W) query bits vs (N, W) corpus sketches -> (Q, N) int32
    Hamming distances, invalid rows pushed to a sentinel.  Unrolled over
    the W words so the (Q, N) running sum is the only large
    intermediate — never (Q, N, W)."""
    ham = jnp.zeros((qbits.shape[0], sketches.shape[0]), jnp.int32)
    for w in range(sketches.shape[1]):
        ham = ham + jax.lax.population_count(
            jnp.bitwise_xor(qbits[:, w:w + 1], sketches[None, :, w]))
    return jnp.where(invalid[None, :], jnp.int32(1 << 30), ham)


def _quantize_queries(queries):
    """Per-query symmetric int8 quantization: (Q, D) f32 -> ((Q, D) int8,
    (Q, 1) f32 scales).  Per-QUERY scales are free here (ordering is per
    query) and track each query's dynamic range."""
    qf = queries.astype(jnp.float32)
    qmax = jnp.max(jnp.abs(qf), axis=-1, keepdims=True)
    qs = jnp.maximum(qmax / 127.0, jnp.float32(1e-30))
    qq = jnp.clip(jnp.round(qf / qs), -127, 127).astype(jnp.int8)
    return qq, qs


def _int8_full_scores(queries, int8_data, scale, metric: int, base: int):
    """(Q, D) f32 queries vs the whole (N, D) int8 corpus -> (Q, N)
    dequantized distance estimates via ONE exact s8×s8→s32 contraction."""
    qq, qs = _quantize_queries(queries)
    dn = (((1,), (1,)), ((), ()))
    idot = jax.lax.dot_general(qq.astype(jnp.int32),
                               int8_data.astype(jnp.int32), dn,
                               preferred_element_type=jnp.int32)
    dot = qs * scale * idot.astype(jnp.float32)
    if metric == int(DistCalcMethod.Cosine):
        return float(base) * float(base) - dot
    qf = queries.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    x2 = jnp.sum(jnp.square(int8_data.astype(jnp.int32)),
                 axis=-1).astype(jnp.float32) * (scale * scale)
    return jnp.maximum(qn + x2[None, :] - 2.0 * dot, 0.0)


def _int8_gathered_scores(queries, rows8, scale, metric: int, base: int):
    """(Q, D) f32 queries vs per-query gathered (Q, C, D) int8 rows ->
    (Q, C) dequantized distance estimates (exact s8×s8→s32 dot)."""
    qq, qs = _quantize_queries(queries)
    idot = jnp.einsum("qd,qcd->qc", qq.astype(jnp.int32),
                      rows8.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
    dot = qs * scale * idot.astype(jnp.float32)
    if metric == int(DistCalcMethod.Cosine):
        return float(base) * float(base) - dot
    qf = queries.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    x2 = jnp.sum(jnp.square(rows8.astype(jnp.int32)),
                 axis=-1).astype(jnp.float32) * (scale * scale)
    return jnp.maximum(qn + x2 - 2.0 * dot, 0.0)


def _shortlist_sketch(sketches, mean, invalid, queries, b1: int):
    """Sketch tier: (Q, b1) shortlist ids, dropped/invalid rows -> -1."""
    qbits = pack_sign_bits(queries.astype(jnp.float32) - mean[None, :])
    ham = _hamming(sketches, qbits, invalid)
    hneg, short1 = jax.lax.top_k(-ham, b1)
    return jnp.where(-hneg >= (1 << 30), -1, short1).astype(jnp.int32)


def _shortlist_int8_from(queries, int8_data, scale, invalid, short1,
                         b2: int, metric: int, base: int):
    """int8 tier over a prior shortlist: gather + score + keep b2.
    -1 inputs and tombstoned rows carry MAX_DIST and stay -1."""
    rows8 = int8_data[jnp.maximum(short1, 0)]
    d8 = _int8_gathered_scores(queries, rows8, scale, metric, base)
    d8 = jnp.where(invalid[jnp.maximum(short1, 0)] | (short1 < 0),
                   jnp.float32(MAX_DIST), d8)
    neg, pos = jax.lax.top_k(-d8, b2)
    short2 = jnp.take_along_axis(short1, pos, axis=1)
    return jnp.where(-neg >= jnp.float32(MAX_DIST), -1, short2)


def _shortlist_int8_full(queries, int8_data, scale, invalid, b2: int,
                         metric: int, base: int):
    """int8 tier over the whole corpus (sketch tier disabled)."""
    d8 = _int8_full_scores(queries, int8_data, scale, metric, base)
    d8 = jnp.where(invalid[None, :], jnp.float32(MAX_DIST), d8)
    neg, short2 = jax.lax.top_k(-d8, b2)
    return jnp.where(-neg >= jnp.float32(MAX_DIST), -1,
                     short2).astype(jnp.int32)


def rerank_gathered(queries, rows, ids, k: int, metric: int, base: int):
    """THE fp tier: exact f32 re-rank of per-query gathered rows.

    Shared verbatim by the fused device-tier kernel (rows gathered
    in-program) and the host-tier re-rank kernel (rows fetched from
    host RAM) — one traced function is what makes the host-fetched
    re-rank bit-identical to the device-resident one.  Candidate
    sqnorms are computed from the gathered rows INSIDE this function
    (never from a corpus-wide precomputed array) for the same reason.
    -1 ids (tier drops, tombstones) carry MAX_DIST and return -1."""
    d = dist_ops.batched_gathered_distance(
        queries.astype(jnp.float32), rows.astype(jnp.float32),
        DistCalcMethod(metric), base)
    d = jnp.where(ids < 0, jnp.float32(MAX_DIST), d)
    neg, pos = jax.lax.top_k(-d, k)
    dists = -neg
    out = jnp.take_along_axis(ids, pos, axis=1)
    out = jnp.where(dists >= jnp.float32(MAX_DIST), -1, out)
    return dists, out.astype(jnp.int32)


# ---------------------------------------------------------------------------
# jitted kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "b1", "b2", "metric",
                                             "base", "use_sketch",
                                             "use_int8"))
def _cascade_search_kernel(fp_data, int8_data, sketches, mean, invalid,
                           scale, queries, k: int, b1: int, b2: int,
                           metric: int, base: int, use_sketch: bool,
                           use_int8: bool):
    """Device-tier cascade: ONE composed program, sketch Hamming scan ->
    int8 re-rank -> fp exact re-rank, with the per-tier budgets as
    static shape parameters.  Disabled tiers (budget >= corpus) are
    composed out at trace time, so `use_sketch=use_int8=False`
    degenerates to the exact masked scan."""
    if use_sketch:
        short1 = _shortlist_sketch(sketches, mean, invalid, queries, b1)
        if use_int8:
            short2 = _shortlist_int8_from(queries, int8_data, scale,
                                          invalid, short1, b2, metric,
                                          base)
        else:
            short2 = short1
    elif use_int8:
        short2 = _shortlist_int8_full(queries, int8_data, scale, invalid,
                                      b2, metric, base)
    else:
        # both tiers composed out: the exact masked scan — one (Q, N)
        # score matrix, never a (Q, N, D) gather (which would be ~N/k
        # times the legacy scan's HBM for nothing)
        qf = queries.astype(jnp.float32)
        if metric == int(DistCalcMethod.L2):
            d = dist_ops.pairwise_l2(qf, fp_data)
        else:
            d = dist_ops.pairwise_cosine(qf, fp_data, base)
        d = jnp.where(invalid[None, :], jnp.float32(MAX_DIST), d)
        neg, idx = jax.lax.top_k(-d, k)
        dists = -neg
        ids = jnp.where(dists >= jnp.float32(MAX_DIST), -1,
                        idx).astype(jnp.int32)
        return dists, ids
    rows = fp_data[jnp.maximum(short2, 0)]
    return rerank_gathered(queries, rows, short2, k, metric, base)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "metric", "base",
                                             "use_sketch"))
def _cascade_shortlist_kernel(int8_data, sketches, mean, invalid, scale,
                              queries, b1: int, b2: int, metric: int,
                              base: int, use_sketch: bool):
    """Host-tier stage A (CorpusTier=host): sketch + int8 tiers fused on
    device, returning the (Q, b2) global-id shortlist the host fp fetch
    re-ranks.  -1 marks tier drops/tombstones."""
    if use_sketch:
        short1 = _shortlist_sketch(sketches, mean, invalid, queries, b1)
        return _shortlist_int8_from(queries, int8_data, scale, invalid,
                                    short1, b2, metric, base)
    return _shortlist_int8_full(queries, int8_data, scale, invalid, b2,
                                metric, base)


@functools.partial(jax.jit, static_argnames=("b1",))
def _sketch_shortlist_kernel(sketches, mean, invalid, queries, b1: int):
    """Host-all stage A1: sketch tier only (the int8 blocks live host-
    side too and are fetched like the fp rows)."""
    return _shortlist_sketch(sketches, mean, invalid, queries, b1)


@functools.partial(jax.jit, static_argnames=("b2", "metric", "base"))
def _int8_rerank_kernel(queries, rows8, short1, scale, b2: int,
                        metric: int, base: int):
    """Host-all stage A2: int8 re-rank of host-fetched rows.  Tombstones
    were already folded into `short1` as -1 by stage A1."""
    d8 = _int8_gathered_scores(queries, rows8, scale, metric, base)
    d8 = jnp.where(short1 < 0, jnp.float32(MAX_DIST), d8)
    neg, pos = jax.lax.top_k(-d8, b2)
    short2 = jnp.take_along_axis(short1, pos, axis=1)
    return jnp.where(-neg >= jnp.float32(MAX_DIST), -1, short2)


@functools.partial(jax.jit, static_argnames=("k", "metric", "base"))
def _fp_rerank_kernel(queries, rows, ids, k: int, metric: int, base: int):
    """Host-tier stage B: the SAME rerank_gathered the fused device
    kernel traces — host-fetch bit-parity rests on this being one
    function."""
    return rerank_gathered(queries, rows, ids, k, metric, base)


@functools.partial(jax.jit, static_argnames=("k", "metric", "base"))
def _fp_rerank_resident_kernel(fp_data, queries, ids, k: int, metric: int,
                               base: int):
    """Device-resident fp re-rank: in-program gather + the shared
    rerank_gathered — the dense engine's fp tier when CorpusTier=device
    (algo/dense.py DenseTreeSearcher cascade path)."""
    rows = fp_data[jnp.maximum(ids, 0)]
    return rerank_gathered(queries, rows, ids, k, metric, base)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "metric", "base",
                                             "use_sketch", "use_int8"))
def _cascade_tiers_kernel(int8_data, sketches, mean, invalid, scale,
                          queries, b1: int, b2: int, metric: int,
                          base: int, use_sketch: bool, use_int8: bool):
    """Triage variant: BOTH tier shortlists for one sampled query, so
    qualmon's classifier can name the tier that dropped a true neighbor
    (utils/qualmon.py classify_low_recall).  Never on the serve path —
    only the quality monitor's sampled shadow jobs run it."""
    if use_sketch:
        short1 = _shortlist_sketch(sketches, mean, invalid, queries, b1)
    else:
        short1 = jnp.broadcast_to(
            jnp.arange(int8_data.shape[0], dtype=jnp.int32)[None, :],
            (queries.shape[0], int8_data.shape[0]))
        short1 = jnp.where(invalid[None, :], -1, short1)
    if use_int8:
        if use_sketch:
            short2 = _shortlist_int8_from(queries, int8_data, scale,
                                          invalid, short1, b2, metric,
                                          base)
        else:
            short2 = _shortlist_int8_full(queries, int8_data, scale,
                                          invalid, b2, metric, base)
    else:
        short2 = short1
    return short1, short2


@functools.partial(jax.jit, static_argnames=("k", "metric", "base"))
def _host_scan_block_kernel(rows, dead, queries, k: int, metric: int,
                            base: int):
    """One block of the STREAMING host exact scan: exact distances of a
    host-fetched (R, D) fp block against the whole query batch, local
    top-k.  The host merges block results — an exact oracle for
    host-tier indexes that never materializes the fp corpus in HBM."""
    if metric == int(DistCalcMethod.L2):
        d = dist_ops.pairwise_l2(queries, rows)
    else:
        d = dist_ops.pairwise_cosine(queries, rows, base)
    d = jnp.where(dead[None, :], jnp.float32(MAX_DIST), d)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def gather_host_rows(fp_host: np.ndarray, ids: np.ndarray):
    """Host-RAM gather of per-query shortlist rows, with out-of-range
    ACCOUNTING (DESIGN.md §20: fetch failures are never silent) — shared
    by CascadeState's pipeline and the dense engine's fp tier.  -1 ids
    (tier drops, tombstones) fetch row 0 and stay masked downstream; ids
    beyond the host array (impossible within one snapshot — defense in
    depth against a mid-swap misuse) are dropped to -1 and counted.
    Returns (rows, ids, drops)."""
    bad = ids >= fp_host.shape[0]
    drops = int(bad.sum())
    if drops:
        metrics.inc("cascade.host_fetch_dropped", drops)
        ids = np.where(bad, -1, ids)
    rows = fp_host[np.clip(ids, 0, fp_host.shape[0] - 1)]
    return rows, ids, drops


# ---------------------------------------------------------------------------
# corpus state
# ---------------------------------------------------------------------------

class CascadeState:
    """Immutable tiered snapshot of one corpus (single-writer snapshot
    design, SURVEY.md §2b P7): packed sketches + mean, int8 quantization
    + scale, tombstone mask, and the fp corpus — device-resident or
    host-resident per the tier.  Owners (FlatIndex, DenseTreeSearcher)
    rebuild a fresh state on mutation; searches pin one reference."""

    def __init__(self, data: np.ndarray, deleted: Optional[np.ndarray],
                 tier: str, metric: int, base: int,
                 fp_dev: Optional[jax.Array] = None):
        """`fp_dev` (device tier only): an already-resident padded
        (n_pad, D) f32 snapshot to reuse as the fp tier — the owner
        keeps accounting for it (FlatIndex's oracle snapshot), so the
        cascade never doubles the fp HBM footprint."""
        self.tier = normalize_tier(tier)
        self.metric = int(metric)
        self.base = int(base)
        n, dim = data.shape
        self.n = n
        self.dim = dim
        n_pad = max(ROW_PAD, ((n + ROW_PAD - 1) // ROW_PAD) * ROW_PAD)
        self.n_pad = n_pad
        fp = np.zeros((n_pad, dim), np.float32)
        fp[:n] = data
        invalid = np.ones(n_pad, bool)
        invalid[:n] = (deleted[:n] if deleted is not None
                       else np.zeros(n, bool))
        int8_host, self.scale = quantize_int8(fp)
        live = ~invalid
        denom = max(int(live.sum()), 1)
        mean = (fp[:n][live[:n]].sum(axis=0) / denom
                if n else np.zeros(dim, np.float32))
        self.mean_d = jnp.asarray(mean.astype(np.float32))
        #: host mirror of the tombstone/pad mask — the streamed host
        #: oracle reads it every call; re-downloading the device copy
        #: per shadow replay would be a pure D2H waste
        self.invalid_host = invalid
        self.invalid_d = jnp.asarray(invalid)
        # sketches are always HBM-resident (the tier that scans
        # everything); packed on device from the dequantized view so the
        # sketch of a row never disagrees with what the int8 tier scores
        self.sketches_d = _pack_sketches_jit(
            jnp.asarray(int8_host), jnp.float32(self.scale), self.mean_d)
        self.scale_d = jnp.float32(self.scale)
        if self.tier == "host_all":
            self.int8_d = None
            self.int8_host = np.ascontiguousarray(int8_host)
        else:
            self.int8_d = jnp.asarray(int8_host)
            self.int8_host = None
        self._fp_dev_shared = False
        if self.tier == "device":
            if fp_dev is not None and tuple(fp_dev.shape) == fp.shape \
                    and fp_dev.dtype == jnp.float32:
                self.fp_d = fp_dev
                self._fp_dev_shared = True
            else:
                self.fp_d = jnp.asarray(fp)
            self.fp_host = None
        else:
            self.fp_d = None
            # the host-RAM fp tier: page-aligned C-contiguous so the
            # h2d copies stream (true pinned registration is a backend
            # service; np contiguity is what XLA's copy path wants)
            self.fp_host = np.ascontiguousarray(fp)
        self.host_fetch_drops = 0
        from sptag_tpu.utils import locksan

        self._lock = locksan.make_lock("CascadeState._lock")

    # ---- residency accounting --------------------------------------------

    def device_bytes(self) -> int:
        total = (self.sketches_d.nbytes + self.mean_d.nbytes
                 + self.invalid_d.nbytes)
        if self.int8_d is not None:
            total += self.int8_d.nbytes
        if self.fp_d is not None:
            total += self.fp_d.nbytes
        return int(total)

    def host_bytes(self) -> int:
        total = 0
        if self.fp_host is not None:
            total += self.fp_host.nbytes
        if self.int8_host is not None:
            total += self.int8_host.nbytes
        return int(total)

    def register_devmem(self) -> None:
        """Component-split ledger entries, owned by this state (a
        snapshot swap retires them when the old state is collected).
        Host-resident fp/int8 bytes are `host=True` — visible on
        /debug/memory, excluded from the device total the HBM budget is
        judged by (the acceptance proof that the host tier serves with
        zero full-corpus device residency)."""
        devmem.track("sketch", self,
                     self.sketches_d.nbytes + self.mean_d.nbytes
                     + self.invalid_d.nbytes)
        if self.int8_d is not None:
            devmem.track("int8_blocks", self, self.int8_d.nbytes)
        if self.fp_d is not None and not self._fp_dev_shared:
            # a SHARED fp snapshot is accounted by its owner (FLAT's
            # oracle snapshot entry) — double-tracking would inflate the
            # capacity numbers bench reads off the ledger
            devmem.track("corpus", self, self.fp_d.nbytes)
        if self.host_bytes():
            devmem.track("host_corpus", self, self.host_bytes(),
                         host=True)

    # ---- search ----------------------------------------------------------

    def _budget_flags(self, k: int, b1: int, b2: int):
        b1, b2 = resolve_budgets(b1, b2, k, self.n_pad)
        use_sketch = b1 < self.n_pad
        use_int8 = b2 < (b1 if use_sketch else self.n_pad)
        return b1, b2, use_sketch, use_int8

    def search(self, queries: np.ndarray, k: int, b1: int, b2: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched cascade search; (Q, k) ascending dists / int32 ids,
        MAX_DIST / -1 padded.  Queries must already be query-bucketed by
        the caller (algo/flat.py owns that layout rule)."""
        k = min(int(k), self.n_pad)
        b1, b2, use_sketch, use_int8 = self._budget_flags(k, b1, b2)
        if self.tier == "device":
            d, ids = _cascade_search_kernel(
                self.fp_d, self.int8_d, self.sketches_d, self.mean_d,
                self.invalid_d, self.scale_d, jnp.asarray(queries), k,
                b1, b2, self.metric, self.base, use_sketch, use_int8)
            return np.asarray(d), np.asarray(ids)
        return self._search_host(queries, k, b1, b2, use_sketch,
                                 use_int8)

    def _fetch_fp(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Host-RAM gather of the fp shortlist rows via the shared
        accounted gather (`gather_host_rows`); drops additionally land
        in this state's counter for the triage path."""
        rows, ids, drops = gather_host_rows(self.fp_host, ids)
        if drops:
            with self._lock:
                self.host_fetch_drops += drops
        return rows, ids

    def _search_host(self, queries: np.ndarray, k: int, b1: int, b2: int,
                     use_sketch: bool, use_int8: bool
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-tier pipeline, double-buffered: chunk i+1's device
        shortlist program is ENQUEUED before chunk i's host fetch blocks
        on its ids — jax dispatch is asynchronous, so the device scans
        ahead while the host gathers fp (and, for host_all, int8) rows.
        The overlap model and its failure accounting are DESIGN.md §20.
        """
        if not use_sketch and not use_int8:
            # both tiers composed out: stream the exact scan — the
            # shortlist machinery has nothing to shortlist
            return host_exact_scan(self.fp_host, self.invalid_host,
                                   queries, k, self.metric, self.base)
        if self.tier == "host_all" and not use_sketch:
            raise ValueError(
                "CorpusTier=host_all needs an active sketch tier "
                "(TierBudgetSketch below the corpus size): with it "
                "composed out, the int8 tier would host-fetch the whole "
                "corpus per query")
        nq, dim = queries.shape
        out_d = np.full((nq, k), MAX_DIST, np.float32)
        out_i = np.full((nq, k), -1, np.int32)
        chunks = []
        for start in range(0, nq, HOST_CHUNK):
            q = jnp.asarray(queries[start:start + HOST_CHUNK])
            if self.tier == "host_all":
                short = _sketch_shortlist_kernel(
                    self.sketches_d, self.mean_d, self.invalid_d, q,
                    b1 if use_sketch else self.n_pad)
            else:
                short = _cascade_shortlist_kernel(
                    self.int8_d, self.sketches_d, self.mean_d,
                    self.invalid_d, self.scale_d, q, b1, b2, self.metric,
                    self.base, use_sketch)
            chunks.append((start, q, short))

        def complete(start, q, short):
            ids = np.asarray(short)               # sync point, chunk i
            if self.tier == "host_all" and use_int8:
                rows8 = self.int8_host[np.clip(ids, 0,
                                               self.int8_host.shape[0] - 1)]
                short2 = _int8_rerank_kernel(
                    q, jnp.asarray(rows8), jnp.asarray(ids),
                    self.scale_d, b2, self.metric, self.base)
                ids = np.asarray(short2)
            rows, ids = self._fetch_fp(ids)
            d, out = _fp_rerank_kernel(q, jnp.asarray(rows),
                                       jnp.asarray(ids), k, self.metric,
                                       self.base)
            stop = min(start + HOST_CHUNK, nq) - start
            out_d[start:start + stop] = np.asarray(d)[:stop]
            out_i[start:start + stop] = np.asarray(out)[:stop]

        # two-deep pipeline: dispatching every shortlist above already
        # enqueued the device work; completing in order lets chunk i's
        # host fetch overlap chunk i+1..n's device scans
        for start, q, short in chunks:
            complete(start, q, short)
        return out_d, out_i

    # ---- triage ----------------------------------------------------------

    def tier_membership(self, query: np.ndarray, truth_ids, k: int,
                        b1: int, b2: int) -> dict:
        """Which tier dropped each true neighbor?  Re-runs the shortlist
        stages for ONE query (the quality monitor's sampled triage path,
        never the serve path) and counts the truth ids missing from each
        tier's shortlist."""
        k = min(int(k), self.n_pad)
        b1, b2, use_sketch, use_int8 = self._budget_flags(k, b1, b2)
        q = np.asarray(query, np.float32).reshape(1, -1)
        int8_ref = (self.int8_d if self.int8_d is not None
                    else jnp.asarray(self.int8_host))
        s1, s2 = _cascade_tiers_kernel(
            int8_ref, self.sketches_d, self.mean_d, self.invalid_d,
            self.scale_d, jnp.asarray(q), b1, b2, self.metric, self.base,
            use_sketch, use_int8)
        s1 = np.asarray(s1)[0]
        s2 = np.asarray(s2)[0]
        truth = np.asarray([t for t in np.asarray(truth_ids).ravel()
                            if t >= 0], np.int32)
        in1 = np.isin(truth, s1)
        in2 = np.isin(truth, s2)
        with self._lock:
            drops = self.host_fetch_drops
        return {
            "sketch_dropped": int((~in1).sum()) if use_sketch else 0,
            "int8_dropped": int((in1 & ~in2).sum()) if use_int8 else 0,
            # LIFETIME drop counter of this snapshot (a triage re-run
            # cannot observe a past query's fetch): qualmon treats it as
            # the fallback verdict when both shortlists kept every true
            # neighbor, never as overriding a measured budget starvation
            "host_dropped": int(drops),
        }


@functools.partial(jax.jit)
def _pack_sketches_jit(int8_data, scale, mean):
    """Packed sign sketches of the DEQUANTIZED corpus view — one device
    program at build; the fp corpus itself never has to be resident."""
    return pack_sign_bits(int8_data.astype(jnp.float32) * scale
                          - mean[None, :])


# ---------------------------------------------------------------------------
# streaming host exact scan (the host-tier oracle)
# ---------------------------------------------------------------------------

def host_exact_scan(fp_host: np.ndarray, deleted: Optional[np.ndarray],
                    queries: np.ndarray, k: int, metric: int, base: int,
                    block_rows: int = HOST_SCAN_BLOCK
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact masked top-k over a HOST-resident fp corpus, streamed
    through the device in fixed row blocks: at no point is more than one
    (block_rows, D) fp slab resident in HBM.  This is the ground-truth
    oracle for host-tier indexes (qualmon's shadow path) — an oracle
    that re-uploaded the full corpus would break the zero-residency
    contract the tier exists for."""
    queries = np.asarray(queries, np.float32)
    nq = queries.shape[0]
    n = fp_host.shape[0]
    k_eff = min(int(k), n)
    block_rows = max(int(block_rows), k_eff)
    q_dev = jnp.asarray(queries)
    best_d = np.full((nq, k_eff), MAX_DIST, np.float32)
    best_i = np.full((nq, k_eff), -1, np.int64)
    for start in range(0, n, block_rows):
        stop = min(start + block_rows, n)
        rows = fp_host[start:stop]
        dead = (deleted[start:stop] if deleted is not None
                else np.zeros(stop - start, bool))
        d, idx = _host_scan_block_kernel(
            jnp.asarray(rows), jnp.asarray(dead), q_dev,
            min(k_eff, stop - start), int(metric), int(base))
        d = np.asarray(d)
        gids = np.asarray(idx).astype(np.int64) + start
        gids[d >= MAX_DIST] = -1
        # host merge of the running top-k with this block's local top-k
        cat_d = np.concatenate([best_d, d], axis=1)
        cat_i = np.concatenate([best_i, gids], axis=1)
        order = np.argsort(cat_d, axis=1, kind="stable")[:, :k_eff]
        best_d = np.take_along_axis(cat_d, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
    return best_d, best_i.astype(np.int32)


# ---------------------------------------------------------------------------
# graph-engine tier rules (shared by algo/engine.py, parallel/sharded.py
# and parallel/mesh_engine.py — ONE rule per site is what keeps the
# scheduler-vs-monolithic id-parity contract intact with the cascade on)
# ---------------------------------------------------------------------------

def walk_score_scale(cascade_on: bool, data_dtype, scale: float) -> float:
    """Static dequantization scale of the walk's in-loop int8 scoring:
    0.0 (off — the byte-identical legacy body) unless the cascade is on
    AND the scoring corpus is the int8 quantization of a float corpus."""
    if not cascade_on:
        return 0.0
    if jnp.dtype(data_dtype) != jnp.dtype(jnp.int8):
        return 0.0
    return float(scale)
