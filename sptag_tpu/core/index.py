"""VectorIndex — THE public API of the framework, plus the algo factory.

Parity: the reference abstract base `VectorIndex` (/root/reference/AnnService/
inc/Core/VectorIndex.h:18-130) and its shared logic (src/Core/
VectorIndex.cpp): BuildIndex / AddIndex / DeleteIndex / SearchIndex /
RefineIndex / SaveIndex / LoadIndex / MergeIndex, the static factory
`CreateInstance(algo, valuetype)` (:286-320), folder save/load around
`indexloader.ini` (:92-109, :324-360), and the metadata→vector mapping
(:113-122, :235-242).

TPU-first departures: search is batch-native (a (Q, D) query block is one
compiled XLA program — the reference's OpenMP-over-queries loop,
VectorIndex.cpp:212-220, becomes the batch dimension), and mutation follows a
single-writer immutable-device-snapshot design (SURVEY.md §2b P7) instead of
mutexes around shared rows.
"""

from __future__ import annotations

import abc
import errno
import logging
import os
import shutil
import threading
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Type, Union

import numpy as np

from sptag_tpu.core.params import ParamSet
from sptag_tpu.core.types import (
    DistCalcMethod,
    ErrorCode,
    IndexAlgoType,
    VectorValueType,
    base_of,
    convert_to_string,
    dtype_of,
    enum_from_string,
)
from sptag_tpu.core.vectorset import MetadataSet, VectorSet, metas_for
from sptag_tpu.io import atomic, wal
from sptag_tpu.ops import distance as dist_ops
from sptag_tpu.utils import faultinject, locksan, metrics, trace
from sptag_tpu.utils.ini import IniReader

log = logging.getLogger(__name__)

# THE sentinel distance for empty/filtered result slots, shared with every
# kernel module (ops/*, algo/*, graph/rng, parallel/*).  Must stay 3.4e38,
# not finfo-max: kernels pad with exactly np.float32(3.4e38), and a larger
# core constant would let kernel sentinels pass `dist < MAX_DIST` client
# filters as "real" results.
MAX_DIST = np.float32(3.4e38)

# Distance at-or-below which a searched vector counts as "the same vector"
# for DeleteIndex(vector) (reference BKTIndex.cpp:439-453 uses 1e-6).
DELETE_EPS = 1e-6
# pre-filter width for the exact-recheck in delete(): wide enough to admit
# any true duplicate's expanded-form f32 residue at realistic norms
_NEAR_EPS = 1e-2


@dataclass
class SearchResult:
    """One query's results; parity with QueryResult/BasicResult
    (reference inc/Core/SearchQuery.h:15-190, SearchResult.h:12-23)."""

    ids: np.ndarray                  # (K,) int32, -1 padded
    dists: np.ndarray                # (K,) float32, MAX_DIST padded
    metas: Optional[List[bytes]] = None

    def __len__(self) -> int:
        return len(self.ids)


def resolved_futures(search_batch, nrows: int) -> List["Future"]:
    """THE pre-resolved-futures fallback shared by every submit_batch
    surface (base VectorIndex, the mesh ServingAdapter/ShardedBKTIndex):
    run `search_batch()` once for the whole block and hand back one
    already-resolved future per row — a failure resolves EVERY row's
    future with the exception, so streaming callers see the same error
    contract as scheduler-backed paths."""
    futs: List[Future] = []
    try:
        dists, ids = search_batch()
    except Exception as e:                               # noqa: BLE001
        for _ in range(nrows):
            f: Future = Future()
            f.set_exception(e)
            futs.append(f)
        return futs
    for row in range(ids.shape[0]):
        f = Future()
        f.set_result((dists[row], ids[row]))
        futs.append(f)
    return futs


_REGISTRY: Dict[IndexAlgoType, Type["VectorIndex"]] = {}


def register_algo(cls: Type["VectorIndex"]) -> Type["VectorIndex"]:
    _REGISTRY[cls.algo] = cls
    return cls


def create_instance(algo: Union[IndexAlgoType, str],
                    value_type: Union[VectorValueType, str]) -> "VectorIndex":
    """Parity: VectorIndex::CreateInstance (reference VectorIndex.cpp:286-320)."""
    if isinstance(algo, str):
        algo = enum_from_string(IndexAlgoType, algo)
    if isinstance(value_type, str):
        value_type = enum_from_string(VectorValueType, value_type)
    cls = _REGISTRY.get(IndexAlgoType(algo))
    if cls is None:
        raise ValueError(f"no index algorithm registered for {algo}")
    return cls(value_type)


@locksan.race_track
class VectorIndex(abc.ABC):
    algo: IndexAlgoType = IndexAlgoType.Undefined

    def __init__(self, value_type: VectorValueType):
        from sptag_tpu.utils import enable_compile_cache

        # every index path (build, load+search) wants the persistent XLA
        # compile cache; idempotent and backend-free, so ctor is the one
        # place that covers them all
        enable_compile_cache()
        self.value_type = VectorValueType(value_type)
        self.params: ParamSet = self._make_params()
        self.metadata: Optional[MetadataSet] = None
        self._meta_to_vec: Optional[Dict[bytes, int]] = None
        # single-writer mutation lock (P7); sanitized under SPTAG_LOCKSAN
        # (utils/locksan.py) — plain RLock otherwise
        self._lock = locksan.make_rlock("VectorIndex._lock")
        self._meta_file = "metadata.bin"
        self._meta_index_file = "metadataIndex.bin"
        # mutation-under-load state (ISSUE 9).  The WAL writer is armed
        # by load_index / a successful save_index when WalEnabled=1;
        # _wal_replaying suppresses re-logging while records re-apply.
        self._wal: Optional[wal.WalWriter] = None
        self._wal_folder: Optional[str] = None
        self._wal_replaying = False
        self._acked_writes = 0
        # bounded FLAT-scanned side index for fresh rows (core/delta.py);
        # None until DeltaShardCapacity routes an add into it
        self._delta = None
        # epoch-based snapshot handoff: readers pin a snapshot by local
        # reference, writers bump the epoch at every publish — the
        # number a /healthz probe watches to see swaps land
        self._snapshot_epoch = 0
        self._swap_count = 0
        self._refine_in_flight = False
        # (start_ms, end_ms) monotonic wall windows of recent swaps —
        # the bench's swap-window p99 partitioning reads these.
        # COPY-ON-WRITE tuple, never mutated in place: mutation_state()
        # iterates it lock-free from /healthz scrapes, and an in-place
        # append racing that iteration would raise (review fix)
        self._swap_windows: tuple = ()

    # ---- subclass surface -------------------------------------------------

    @abc.abstractmethod
    def _make_params(self) -> ParamSet: ...

    @abc.abstractmethod
    def _build(self, data: np.ndarray, checkpoint=None) -> None:
        """Build index structures over `data` (already normalized if cosine).

        `checkpoint` (utils/build_ckpt.BuildCheckpoint or None): stage
        store for resumable builds — implementations that run multi-stage
        pipelines load completed stages from it and save each stage as it
        finishes; exact (single-stage) indexes ignore it."""

    @abc.abstractmethod
    def _search_batch(self, queries: np.ndarray, k: int,
                      max_check: Optional[int] = None,
                      search_mode: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(Q, D) queries (already normalized if cosine) -> ((Q, K) dists,
        (Q, K) int32 ids), ascending, -1/MAX_DIST padded, excluding deleted.
        `max_check` overrides the MaxCheck parameter for this call (budgeted
        indexes only; exact indexes ignore it).  `search_mode` overrides
        the SearchMode parameter ("beam"/"dense") for this call (graph
        indexes only)."""

    @abc.abstractmethod
    def _add(self, data: np.ndarray) -> int:
        """Append rows (already normalized if cosine); returns first new id."""

    def _delete_ids(self, vids) -> int:
        """Tombstone rows `vids` (lock held) -> how many were live.  One
        call a delete, so that a family whose device state follows its
        mutations sends the mask bits once (algo/flat.py)."""
        return sum(1 for v in vids if self._delete_id(int(v)))

    @abc.abstractmethod
    def _delete_id(self, vid: int) -> bool:
        """Tombstone one id; returns False if already deleted."""

    @abc.abstractmethod
    def _save_index_data(self, folder: str) -> None: ...

    @abc.abstractmethod
    def _load_index_data(self, folder: str) -> None: ...

    @property
    @abc.abstractmethod
    def num_samples(self) -> int: ...

    @property
    @abc.abstractmethod
    def num_deleted(self) -> int: ...

    @property
    @abc.abstractmethod
    def feature_dim(self) -> int: ...

    @abc.abstractmethod
    def contains_sample(self, vid: int) -> bool: ...

    @abc.abstractmethod
    def get_sample(self, vid: int) -> np.ndarray: ...

    def _refine_impl(self) -> None:
        """Compact deleted rows; subclasses with graphs/trees override."""
        raise NotImplementedError

    # ---- common parameter / metric helpers --------------------------------

    @property
    def dist_calc_method(self) -> DistCalcMethod:
        return DistCalcMethod(getattr(self.params, "dist_calc_method",
                                      DistCalcMethod.L2))

    @property
    def base(self) -> int:
        return base_of(self.value_type)

    # quality-monitor knobs (utils/qualmon.py, ISSUE 7): process-wide,
    # live-applied at set_parameter time for EVERY index family — the
    # flight-recorder pattern; each maps to its own configure field so
    # setting one never clobbers the others
    _QUALITY_PARAMS = frozenset({"qualitysamplerate", "qualityrecallfloor",
                                 "qualityshadowbudget", "qualitywindow"})

    def set_parameter(self, name: str, value: str) -> bool:
        ok = self.params.set_param(name, value)
        low = name.lower()
        if ok and low == "devicebytesledger":
            # process-wide device-memory ledger flag (utils/devmem.py):
            # applied directly, for EVERY index family — a registry-only
            # write would be a silent no-op on a warm index
            from sptag_tpu.utils import devmem

            enabled = bool(int(getattr(self.params,
                                       "device_bytes_ledger", 1)))
            devmem.configure(enabled=enabled)
            if enabled:
                # RE-enable on a warm index: disabling dropped every
                # entry, and snapshots only track at build time — re-
                # register the live ones so gauges come back without a
                # rebuild (slot pools re-track on their next resize)
                self._retrack_devmem()
        if ok and low in ("timelineintervalms", "timelineevents"):
            # serving timeline (utils/timeline.py, ISSUE 15): process-
            # wide, live-applied like the quality knobs — interval > 0
            # arms + starts the sampler, 0 stops it; the events knob
            # resizes the per-series rings
            from sptag_tpu.utils import timeline

            if low == "timelineintervalms":
                interval = float(getattr(self.params,
                                         "timeline_interval_ms", 0.0))
                if interval > 0:
                    timeline.configure(enabled=True, interval_ms=interval)
                    timeline.start()
                else:
                    timeline.configure(enabled=False)
                    timeline.stop()
            else:
                timeline.configure(
                    capacity=int(getattr(self.params, "timeline_events",
                                         0)) or None)
        if ok and low in self._QUALITY_PARAMS:
            from sptag_tpu.utils import qualmon

            p = self.params
            qualmon.configure(
                sample_rate=(float(getattr(p, "quality_sample_rate", 0.0))
                             if low == "qualitysamplerate" else None),
                recall_floor=(float(getattr(p, "quality_recall_floor", 0.0))
                              if low == "qualityrecallfloor" else None),
                shadow_budget_gflops=(
                    float(getattr(p, "quality_shadow_budget", 0.0))
                    if low == "qualityshadowbudget" else None),
                window=(int(getattr(p, "quality_window", 0))
                        if low == "qualitywindow" else None))
        return ok

    def _retrack_devmem(self) -> None:
        """Re-register this index's live device allocations with the
        memory ledger (subclass hook; called when DeviceBytesLedger is
        re-enabled on a warm index).  Default: nothing tracked."""

    def get_parameter(self, name: str) -> Optional[str]:
        return self.params.get_param(name)

    def _prepare_vectors(self, vectors, normalize: bool = True) -> np.ndarray:
        if isinstance(vectors, VectorSet):
            if vectors.value_type != self.value_type:
                raise ValueError("VectorSet value type mismatch")
            data = vectors.data
        else:
            data = np.asarray(vectors)
            if data.ndim == 1:
                data = data[None, :]
            data = data.astype(dtype_of(self.value_type), copy=False)
        if normalize and self.dist_calc_method == DistCalcMethod.Cosine:
            # Build-time corpus normalization, parity with the reference
            # (BKTIndex.cpp:289-296 + Utils::Normalize CommonUtils.h:93-108).
            with trace.span("index.normalize"):
                data = dist_ops.normalize(data, self.base)
        return np.ascontiguousarray(data)

    # ---- build / search ---------------------------------------------------

    def build(self, vectors, metadata: Optional[MetadataSet] = None,
              with_meta_index: bool = False,
              checkpoint_dir: Optional[str] = None,
              keep_checkpoint: bool = False) -> ErrorCode:
        """Parity: VectorIndex::BuildIndex (reference VectorIndex.cpp:192-208).

        `checkpoint_dir` (or env SPTAG_TPU_BUILD_CKPT) enables RESUMABLE
        builds — a framework extension with no reference counterpart: each
        completed build stage (tree, per-TPT-tree candidate merge, refine
        pass) is checkpointed there, and a re-run over the same data +
        params resumes at the first incomplete stage instead of restarting
        a possibly hour-long build after a backend death.  The checkpoint
        is fingerprint-bound (utils/build_ckpt.py) and removed on success.
        """
        data = self._prepare_vectors(vectors)
        if data.size == 0:
            return ErrorCode.EmptyData
        if checkpoint_dir is None:
            checkpoint_dir = os.environ.get("SPTAG_TPU_BUILD_CKPT") or None
        ck = None
        if checkpoint_dir:
            from sptag_tpu.utils.build_ckpt import (BuildCheckpoint,
                                                    build_fingerprint)
            config = (f"{type(self).__name__}:{int(self.value_type)}:"
                      f"{sorted(self.params.__dict__.items())!r}")
            ck = BuildCheckpoint(checkpoint_dir,
                                 build_fingerprint(data, config))
        with self._lock:
            self._build(data, checkpoint=ck)
            self._reset_delta()
            self.metadata = metadata
            if with_meta_index and metadata is not None:
                self.build_meta_mapping()
            # flag + checkpoint cleanup stay INSIDE the lock: with two
            # concurrent build() calls, doing these after release let one
            # build's clear() interleave with the other's stage writes
            # (ADVICE r3).  `keep_checkpoint=True` defers the clear to the
            # caller — a MULTI-shard build must keep every finished
            # shard's stages until ALL shards succeed, or a death in
            # shard s forces shards [0, s) to rebuild from scratch on
            # resume; the caller clears via the handle stashed on
            # `last_checkpoint`.
            self.build_resumed = ck is not None and ck.resumed
            self.last_checkpoint = ck
            if ck is not None and not keep_checkpoint:
                ck.clear()
                self.last_checkpoint = None
        # index-health metrics at every structural mutation (ISSUE 7):
        # one flag test when off; the O(n) sweep runs on the shadow
        # worker, never inline on the mutation path
        self.publish_quality_health(background=True)
        return ErrorCode.Success

    def build_meta_mapping(self) -> None:
        """Parity: VectorIndex::BuildMetaMapping (VectorIndex.cpp:113-122)."""
        assert self.metadata is not None
        mapping: Dict[bytes, int] = {}
        for i in range(self.metadata.count):
            if self.contains_sample(i):
                mapping[self.metadata.get_metadata(i)] = i
        self._meta_to_vec = mapping

    def search(self, query, k: int = 10, with_metadata: bool = False,
               max_check: Optional[int] = None,
               search_mode: Optional[str] = None) -> SearchResult:
        dists, ids = self.search_batch(np.asarray(query)[None, :], k,
                                       max_check=max_check,
                                       search_mode=search_mode)
        metas = (metas_for(self.metadata, ids[0])
                 if with_metadata else None)
        return SearchResult(ids[0], dists[0], metas)

    def search_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Batch search: the whole (Q, D) block is one device program —
        replaces the reference's OpenMP parallel-for over queries
        (VectorIndex.cpp:212-220).  `max_check` and `search_mode` override
        the MaxCheck / SearchMode parameters for this call only (stateless
        — safe under concurrent searches, unlike set_parameter)."""
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.feature_dim:
            raise ValueError(
                f"query dim {queries.shape[1]} != index dim {self.feature_dim}")
        # the seam every synchronous search of FLAT, BKT and KDT crosses:
        # query preparation, padding, dispatch, device wait, readback
        # (`index.readback` inside it) and the delta merge
        with trace.span("index.search"):
            queries = self._prepare_query(queries)
            # delta/main union (ISSUE 9): the main tier covers its frozen
            # snapshot; fresh rows ride the FLAT-scanned delta shard and
            # the two top-k lists merge here — one flag test when no delta
            return self._merge_delta(
                queries, k, self._search_batch(queries, k, max_check,
                                               search_mode))

    def submit_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None,
                     rids: Optional[List[str]] = None) -> List["Future"]:
        """Per-query futures over a (Q, D) block — the streaming-capable
        serve surface (serve/service.py execute_batch's on_ready path).
        Each future resolves to `(dists (k,), ids (k,))` with search_batch's
        padding contract.  `rids` (one request id per query, optional) is
        attribution-only: scheduler-backed overrides tag their flight
        events with it; the synchronous base path ignores it.

        The base implementation executes the whole batch synchronously and
        returns already-resolved futures, so every index is submittable;
        graph indexes with ContinuousBatching=1 override it to resolve
        futures AS QUERIES RETIRE from the slot scheduler
        (algo/scheduler.py) — that is what lets a server stream responses
        at per-query rather than whole-batch granularity."""
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        return resolved_futures(
            lambda: self.search_batch(queries, k, max_check=max_check,
                                      search_mode=search_mode),
            queries.shape[0])

    def _exact_scan(self, queries: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact FLAT/MXU scan over this index's corpus (queries already
        prepared) — subclass hook behind `exact_search_batch`.  FLAT
        runs its cached snapshot; the graph indexes run their engine
        snapshot's resident arrays (algo/engine.py exact_scan)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no exact-scan oracle")

    def exact_search_batch(self, queries: np.ndarray, k: int = 10
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Ground-truth exact top-k over this index's live corpus —
        search_batch's contract ((Q, k) dists/ids, MAX_DIST / -1
        padded, deleted rows excluded), but ALWAYS the exact masked
        FLAT/MXU scan regardless of the configured search mode or any
        approximation knobs.  This is the oracle the quality monitor's
        shadow path replays sampled queries through (utils/qualmon.py),
        and the in-process truth source for recall tests."""
        if self.num_samples == 0:
            raise RuntimeError("index is empty")
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.feature_dim:
            raise ValueError(
                f"query dim {queries.shape[1]} != index dim "
                f"{self.feature_dim}")
        queries = self._prepare_query(queries)
        k_eff = min(k, self.num_samples)
        # the oracle unions the delta scan too: both tiers are exact, and
        # an oracle blind to just-acked rows would score the serving path
        # against a stale truth (utils/qualmon.py)
        dists, ids = self._merge_delta(queries, k_eff,
                                       self._exact_scan(queries, k_eff))
        if dists.shape[1] < k:
            q = dists.shape[0]
            dists = np.concatenate(
                [dists, np.full((q, k - dists.shape[1]), MAX_DIST,
                                np.float32)], axis=1)
            ids = np.concatenate(
                [ids, np.full((q, k - ids.shape[1]), -1, np.int32)],
                axis=1)
        return dists, ids

    # ---- quality health (utils/qualmon.py, ISSUE 7) -----------------------

    def publish_quality_health(self, shard: Optional[str] = None,
                               background: bool = False) -> None:
        """Publish this index's health metrics to the quality monitor
        (deleted fraction, sample count; graph indexes add degree /
        reciprocity / reachability via `_health_payload`).  `shard`
        names the series (a serving tier passes its index name and the
        label sticks for later mutation-path republishes).  No-op with
        the monitor off; never raises — health must not break serving
        or mutation paths.

        `background=True` (the mutation-path hooks) runs the sweep on
        the quality monitor's shadow worker instead of the caller's
        thread: `_health_payload` is O(n) host numpy (reciprocity
        gather + reachability BFS over the whole graph) and must not be
        paid inline per add/delete.  A pending-flag debounce coalesces
        mutation storms into one sweep per queue drain — the job reads
        CURRENT index state at run time, so the final state is always
        the one published."""
        from sptag_tpu.utils import qualmon

        if shard is not None:
            self._quality_shard = str(shard)
        if not qualmon.enabled():
            return
        label = getattr(self, "_quality_shard",
                        type(self).__name__.lower())
        if background:
            if getattr(self, "_health_job_pending", False):
                return
            self._health_job_pending = True

            def job():
                # label resolved at RUN time, like the index state: a
                # debounced storm publishes the final label, not the
                # one current when the pending job was queued
                try:
                    self._publish_health_now(
                        getattr(self, "_quality_shard",
                                type(self).__name__.lower()))
                finally:
                    self._health_job_pending = False
            if not qualmon.submit(job):
                self._health_job_pending = False
            return
        self._publish_health_now(label)

    def _publish_health_now(self, label: str) -> None:
        from sptag_tpu.utils import qualmon

        try:
            n = self.num_samples
            payload = {"samples": int(n), "deleted": int(self.num_deleted)}
            qualmon.gauge("index.samples", n, shard=label)
            qualmon.gauge("index.deleted_fraction",
                          (self.num_deleted / n) if n else 0.0,
                          shard=label)
            extra = self._health_payload()
            if extra:
                payload.update(extra)
            qualmon.note_health(label, **payload)
        except Exception:                                # noqa: BLE001
            qualmon.inc("health_errors")
            log.exception("quality health publish failed")

    def _health_payload(self) -> Optional[dict]:
        """Index-family health extras for /debug/quality (graph indexes
        override with graph/reachability metrics).  Scalars worth a
        time series should additionally ride `qualmon.gauge`."""
        return None

    def _prepare_query(self, queries: np.ndarray) -> np.ndarray:
        """Queries are normalized for cosine, like the reference harness does
        at load (Utils::PrepareQuerys, CommonUtils.h:110-143)."""
        queries = queries.astype(dtype_of(self.value_type), copy=False)
        if self.dist_calc_method == DistCalcMethod.Cosine:
            with trace.span("index.normalize"):
                queries = dist_ops.normalize(queries, self.base)
        return np.ascontiguousarray(queries)

    # ---- mutation ---------------------------------------------------------

    def add(self, vectors, metadata: Optional[MetadataSet] = None,
            with_meta_index: bool = False) -> ErrorCode:
        """Parity: VectorIndex::AddIndex + BKT dedupe-by-metadata semantics
        (reference VectorIndex.cpp:224-231, BKTIndex.cpp:462-529).

        Durability (ISSUE 9): with the WAL armed, the add's record is
        appended + fsync'd BEFORE this returns — an acked add survives
        process death (load_index replays it).  With DeltaShardCapacity
        set, the rows land in the FLAT-scanned delta shard and are
        searchable immediately, without re-linking the graph or
        invalidating the engine snapshot."""
        with trace.span("index.add"):
            data = self._prepare_vectors(vectors)
            if data.size == 0:
                return ErrorCode.EmptyData
            metas = ([metadata.get_metadata(i) for i in range(data.shape[0])]
                     if metadata is not None else None)
            with self._lock:
                # log BEFORE apply (standard WAL ordering, review fix): a
                # failed append leaves the in-memory index untouched, so an
                # un-acked add is never resident (and never folded into a
                # later save); a torn record truncates at replay.  `begin`
                # is the tail by construction — every add path appends.
                # Redo semantics for the inverse failure (append succeeded,
                # apply raised): the caller sees an exception and the
                # write's outcome is INDETERMINATE — a restart may replay
                # the durable record.  That is the standard WAL contract;
                # what is guaranteed is never a HALF-applied state.
                begin = self.num_samples
                self._wal_log(wal.pack_add(begin, data, metas))
                applied = self._apply_add(data, metas, with_meta_index)
                assert applied == begin, (applied, begin)
            self.publish_quality_health(background=True)
            self._maybe_auto_refine()
            return ErrorCode.Success

    def _apply_add(self, data: np.ndarray, metas: Optional[List[bytes]],
                   with_meta_index: bool) -> int:
        """THE add effect, shared verbatim by the live path and WAL
        replay (caller holds the lock; `data` already prepared).
        Returns the global id the first row landed at."""
        if self.num_samples == 0:
            # data is already normalized; bypass build()'s re-preparation
            self._build(data)
            self._reset_delta()
            self.metadata = (MetadataSet(metas) if metas is not None
                             else None)
            if with_meta_index and self.metadata is not None:
                self.build_meta_mapping()
            return 0
        begin = self._route_add(data)
        if metas is not None:
            if self.metadata is None:
                self.metadata = MetadataSet([b""] * begin)
            for i in range(data.shape[0]):
                meta = metas[i]
                self.metadata.add(meta)
                if self._meta_to_vec is not None and meta:
                    old = self._meta_to_vec.get(meta)
                    if old is not None:
                        self._delete_id(old)
                    self._meta_to_vec[meta] = begin + i
        elif self.metadata is not None:
            for _ in range(data.shape[0]):
                self.metadata.add(b"")
        if with_meta_index and self.metadata is not None \
                and self._meta_to_vec is None:
            # honor with_meta_index on an ALREADY-BUILT index too (it
            # previously only applied to the first-add-as-build path,
            # leaving delete_by_metadata dead after admin adds)
            self.build_meta_mapping()
        return begin

    def _route_add(self, data: np.ndarray) -> int:
        """Storage routing for appended rows (lock held): the delta
        shard when enabled and the batch fits, the subclass's linked
        `_add` otherwise.  The delta is always the TAIL of the id space
        — a fallback to `_add` absorbs it first so ids stay ordered
        main-then-delta."""
        cap = int(getattr(self.params, "delta_shard_capacity", 0) or 0)
        if cap > 0:
            if data.shape[0] > cap:
                # bulk load: the shard can never hold it — fold any
                # pending delta, then take the linked path
                self._absorb_delta_locked()
            else:
                if self._delta is not None and \
                        self._delta.count + data.shape[0] > self._delta.capacity:
                    self._absorb_delta_locked()
                begin = self._delta_append(data, cap)
                if begin is not None:
                    return begin
        elif self._delta is not None:
            # knob turned off with rows still resident: fold them back
            self._absorb_delta_locked()
        return self._add(data)

    def _delta_append(self, data: np.ndarray, cap: int) -> Optional[int]:
        """Append `data` to the delta shard (creating it at the current
        tail when absent); None when the subclass has no unlinked-append
        support — the caller falls back to `_add`."""
        from sptag_tpu.core.delta import DeltaShard

        begin = self._append_rows_unlinked(data)
        if begin is None:
            return None
        if self._delta is None:
            self._delta = DeltaShard(begin, data.shape[1], data.dtype,
                                     cap, int(self.dist_calc_method),
                                     self.base)
        self._delta.append(data, begin)
        metrics.set_gauge("mutation.delta_rows", self._delta.count)
        return begin

    # ---- delta-shard surface (subclass hooks + shared plumbing) -----------

    def _append_rows_unlinked(self, data: np.ndarray) -> Optional[int]:
        """Append rows to the subclass's storage WITHOUT linking them
        into search structures or invalidating the engine snapshot —
        the delta shard serves them until a refine absorbs them.
        Returns the first new global id, or None when the index family
        has no such fast path (the caller then uses `_add`)."""
        return None

    def _tombstone_mask(self) -> Optional[np.ndarray]:
        """The full (num_samples,) tombstone mask, for masking delta
        rows at query time; None when the family keeps none."""
        return None

    def _absorb_delta_impl(self, begin: int, count: int) -> None:
        """Fold rows [begin, begin+count) — currently served by the
        delta shard — into the subclass's main structures (lock held).
        Families that support `_append_rows_unlinked` must override."""
        raise NotImplementedError

    def _absorb_delta_locked(self) -> None:
        """Absorb + drop the delta shard (lock held); no-op when empty.
        Every path that appends via `_add`, remaps ids, or persists the
        index calls this first — the invariant is that the delta is
        always the unlinked TAIL [base_id, num_samples)."""
        d = self._delta
        if d is None:
            return
        self._delta = None
        if d.count:
            self._absorb_delta_impl(d.base_id, d.count)
        from sptag_tpu.utils import devmem

        devmem.untrack(d)
        metrics.set_gauge("mutation.delta_rows", 0)

    def _reset_delta(self) -> None:
        """Discard the delta wholesale (build/load replaced the corpus;
        there is no tail to fold)."""
        if self._delta is not None:
            from sptag_tpu.utils import devmem

            devmem.untrack(self._delta)
            self._delta = None
            metrics.set_gauge("mutation.delta_rows", 0)

    def _main_rows(self) -> int:
        """Rows covered by the MAIN search structures: everything below
        the delta shard's base (== num_samples when no delta is live).
        Engine/dense snapshot builds size themselves with this, so the
        two tiers never overlap."""
        d = self._delta
        return d.base_id if (d is not None and d.count) else \
            self.num_samples

    def _merge_delta(self, queries: np.ndarray, k: int,
                     main: Tuple[np.ndarray, np.ndarray]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Union the main tier's top-k with the delta scan's (queries
        already prepared).  Reads the shard via ONE local reference —
        a concurrent swap retires it harmlessly (merge_topk dedupes the
        brief double-coverage window)."""
        d = self._delta
        if d is None or not d.count:
            return main
        from sptag_tpu.core.delta import merge_topk

        dd, di = d.search(queries, min(k, d.count),
                          self._tombstone_mask())
        return merge_topk(main[0], main[1], dd, di, k)

    def _maybe_auto_refine(self) -> None:
        """Schedule a background absorb+swap once the delta crosses
        AutoRefineThreshold (subclass hook decides how; the base folds
        inline — correct for families whose absorb is cheap)."""
        thr = int(getattr(self.params, "auto_refine_threshold", 0) or 0)
        d = self._delta
        if thr <= 0 or d is None or d.count < thr:
            return
        self._schedule_auto_refine()

    def _schedule_auto_refine(self) -> None:
        with self._lock:
            self._absorb_delta_locked()

    def mutation_state(self) -> Dict[str, object]:
        """Swap/durability state for /healthz and /debug/mutation: the
        epoch a reader pins, WAL accounting, delta occupancy, and the
        recent swap windows the bench partitions latencies by."""
        d = self._delta
        return {
            "epoch": self._snapshot_epoch,
            "wal": self._wal is not None,
            "wal_folder": self._wal_folder or "",
            "acked_writes": self._acked_writes,
            "delta_rows": int(d.count) if d is not None else 0,
            "delta_capacity": int(getattr(self.params,
                                          "delta_shard_capacity", 0) or 0),
            "swap_count": self._swap_count,
            "refine_in_flight": self._refine_in_flight,
            "swap_windows_ms": [list(w) for w in self._swap_windows],
        }

    # ---- write-ahead log plumbing -----------------------------------------

    def _wal_log(self, payload: bytes) -> None:
        """Append one mutation record (lock held).  Raising here means
        the mutation was NOT acked — by the crash-consistency contract
        the caller's exception propagates and the client must retry."""
        if self._wal is None or self._wal_replaying:
            return
        with trace.span("index.wal_append"):    # the write and its fsync
            self._wal.append(payload)
        self._acked_writes += 1
        metrics.inc("mutation.wal_appends")

    def _arm_wal(self, folder: str) -> None:
        """(Re)open the WAL writer at `folder` — called after load and
        after every successful save (the publish moved the log)."""
        if self._wal is not None:
            self._wal.close()
        self._wal = wal.WalWriter(
            os.path.join(folder, wal.WAL_NAME),
            sync=bool(int(getattr(self.params, "wal_fsync", 1) or 0)))
        self._wal_folder = folder

    def _replay_wal(self, folder: str) -> None:
        """Re-apply the folder's log over the loaded snapshot: torn
        tails truncate, records already inside the snapshot (the
        published-but-log-not-yet-reset window) are skipped by their
        `begin`, deletes are idempotent."""
        path = os.path.join(folder, wal.WAL_NAME)
        records, torn = wal.replay(path)
        if torn:
            metrics.inc("mutation.wal_torn_tails")
        if not records:
            return
        applied = 0
        with self._lock:
            self._wal_replaying = True
            try:
                for rec in records:
                    try:
                        if isinstance(rec, wal.WalAdd):
                            n = self.num_samples
                            if rec.begin + rec.rows.shape[0] <= n:
                                continue      # folded into the snapshot
                            skip = max(0, n - rec.begin)
                            rows = rec.rows[skip:]
                            metas = (rec.metas[skip:]
                                     if rec.metas is not None else None)
                            self._apply_add(np.ascontiguousarray(rows),
                                            metas, False)
                        else:
                            self._delete_ids(
                                [int(v) for v in rec.vids
                                 if 0 <= v < self.num_samples])
                        applied += 1
                    except Exception:                    # noqa: BLE001
                        # a record that fails to APPLY (resource
                        # exhaustion, a bug) must not make a folder
                        # with a perfectly valid snapshot unloadable —
                        # stop at the failed record (later ones may
                        # depend on it) and serve the durable prefix;
                        # the failure is loud, never silent
                        metrics.inc("mutation.wal_replay_errors")
                        log.exception(
                            "WAL replay: record %d failed to apply; "
                            "serving the snapshot + %d replayed "
                            "record(s)", applied, applied)
                        break
            finally:
                self._wal_replaying = False
        if applied:
            log.info("WAL replay: %d record(s) re-applied from %s",
                     applied, path)
            metrics.inc("mutation.wal_replayed", applied)

    def delete(self, vectors) -> ErrorCode:
        """Delete-by-content: search each vector, tombstone exact matches
        (dist <= eps), parity with BKT::DeleteIndex (BKTIndex.cpp:439-453)."""
        return self.delete_rows(vectors)[0]

    def delete_rows(self, vectors) -> Tuple[ErrorCode, int]:
        """`delete`, and how many rows it tombstoned (what `$admin:delete`
        replies): -> (code, rows tombstoned by this call)."""
        with trace.span("index.delete"):
            if self.num_samples == 0:
                return ErrorCode.VectorNotFound, 0
            data = self._prepare_vectors(vectors, normalize=True)
            if data.shape[1] != self.feature_dim:
                return ErrorCode.DimensionSizeMismatch, 0
            found_any = False
            # data is already normalized — call the subclass engine directly
            # rather than search_batch, which would normalize a second time.
            # The reference searches with k=CEF for deletes (BKTIndex.cpp:441).
            # The delta merge rides along: a row acked into the delta shard
            # moments ago is deletable-by-content like any other.
            k = int(getattr(self.params, "cef", 32))
            k_eff = min(k, self.num_samples)
            dists, ids = self._merge_delta(
                data, k_eff, self._search_batch(data, k_eff))
            tombstoned: List[int] = []
            seen = set()
            with self._lock:
                # collect the matches first, LOG, then apply (the add
                # path's log-before-apply ordering, review fix)
                for q, row_d, row_i in zip(data, dists, ids):
                    for d, v in zip(row_d, row_i):
                        if v >= 0 and d <= max(DELETE_EPS, _NEAR_EPS) and \
                                self._exact_distance(q, int(v)) <= DELETE_EPS:
                            found_any = True
                            if int(v) not in seen and \
                                    self.contains_sample(int(v)):
                                seen.add(int(v))
                                tombstoned.append(int(v))
                if tombstoned:
                    self._wal_log(wal.pack_delete(tombstoned))
                    self._delete_ids(tombstoned)
            if found_any:
                self.publish_quality_health(background=True)
            return (ErrorCode.Success if found_any
                    else ErrorCode.VectorNotFound), len(tombstoned)

    def _exact_distance(self, q: np.ndarray, vid: int) -> float:
        """Host recheck of one candidate at float64, by DIRECT subtraction/
        dot — the reference compares its (exactly-zero-on-identical) scalar
        L2 against 1e-6 (BKTIndex.cpp:439-453), while the MXU expanded form
        ||q||^2+||x||^2-2qx leaves an O(||x||^2 * eps_f32) residue on
        identical rows that would fail that test on large-norm data."""
        x = self.get_sample(vid).astype(np.float64)
        qf = q.astype(np.float64)
        if self.dist_calc_method == DistCalcMethod.L2:
            diff = qf - x
            return float((diff * diff).sum())
        return float(self.base) ** 2 - float(qf @ x)

    def delete_by_metadata(self, meta: bytes) -> ErrorCode:
        """Parity: VectorIndex::DeleteIndex(ByteArray) (VectorIndex.cpp:235-242)."""
        if self._meta_to_vec is None:
            return ErrorCode.VectorNotFound
        vid = self._meta_to_vec.get(bytes(meta))
        if vid is None:
            return ErrorCode.VectorNotFound
        with self._lock:
            if self.contains_sample(vid):
                self._wal_log(wal.pack_delete([vid]))     # log first
                self._delete_id(vid)
        return ErrorCode.Success

    # ---- refine / merge ---------------------------------------------------

    @property
    def need_refine(self) -> bool:
        """Parity: deleted fraction > DeletePercentageForRefine (reference
        BKT/Index.h:122)."""
        n = self.num_samples
        if n == 0:
            return False
        limit = getattr(self.params, "delete_percentage_for_refine", 0.4)
        return self.num_deleted >= limit * n

    def refine_index(self) -> ErrorCode:
        with self._lock:
            # compaction remaps ids: the delta's global-id tail must be
            # folded into the main structures first
            self._absorb_delta_locked()
            self._refine_impl()
        self.publish_quality_health(background=True)
        return ErrorCode.Success

    def merge_index(self, other: "VectorIndex") -> ErrorCode:
        """Parity: VectorIndex::MergeIndex re-add loop (VectorIndex.cpp:246-268)."""
        if other.value_type != self.value_type:
            return ErrorCode.Fail
        if other.dist_calc_method != self.dist_calc_method:
            # rows below are taken as-is from the source index; they are only
            # valid under the same metric (cosine rows are pre-normalized)
            return ErrorCode.Fail
        if self.num_samples > 0 and other.feature_dim != self.feature_dim:
            return ErrorCode.Fail
        keep = [i for i in range(other.num_samples) if other.contains_sample(i)]
        if not keep:
            return ErrorCode.Success
        rows = np.stack([other.get_sample(i) for i in keep])
        metas = None
        if other.metadata is not None:
            metas = MetadataSet(other.metadata.get_metadata(i) for i in keep)
        # rows are already normalized by the source index for cosine
        with self._lock:
            if self.num_samples == 0:
                self._build(rows)
                self._reset_delta()
                self.metadata = metas
            else:
                self._absorb_delta_locked()   # _add appends at the tail
                self._wal_log(wal.pack_add(   # log first (add() ordering)
                    self.num_samples, rows,
                    [metas.get_metadata(i) for i in range(len(keep))]
                    if metas is not None else None))
                begin = self._add(rows)
                if metas is not None:
                    if self.metadata is None:
                        self.metadata = MetadataSet([b""] * begin)
                    self.metadata.add_batch(metas)
                elif self.metadata is not None:
                    for _ in keep:
                        self.metadata.add(b"")
        if self._meta_to_vec is not None:
            self.build_meta_mapping()
        return ErrorCode.Success

    # ---- persistence ------------------------------------------------------

    def save_index_config(self) -> str:
        """Parity: VectorIndex::SaveIndexConfig (VectorIndex.cpp:92-109)."""
        out = []
        if self.metadata is not None:
            out.append("[MetaData]")
            out.append(f"MetaDataFilePath={self._meta_file}")
            out.append(f"MetaDataIndexPath={self._meta_index_file}")
            if self._meta_to_vec is not None:
                out.append("MetaDataToVectorIndex=true")
            out.append("")
        out.append("[Index]")
        out.append(f"IndexAlgoType={convert_to_string(self.algo)}")
        out.append(f"ValueType={convert_to_string(self.value_type)}")
        out.append("")
        out.append(self.params.save_config())
        return "\n".join(out)

    def save_index(self, folder: str) -> ErrorCode:
        """Parity: VectorIndex::SaveIndex(folder) (VectorIndex.cpp:162-190),
        including the transparent compaction of a >40%-deleted index.

        Crash-safe improvement over the reference (which writes in place,
        corrupting the previous checkpoint on a mid-save crash): when
        `folder` already holds an index, the save lands in a sibling
        temporary directory that atomically replaces the target only after
        every file is written."""
        if self.num_samples - self.num_deleted == 0:
            return ErrorCode.EmptyIndex
        with self._lock:
            # the existing-check and staging setup sit INSIDE the lock so
            # two threads saving to the same folder can't delete each
            # other's staging directory mid-write
            existing = os.path.exists(
                os.path.join(folder, "indexloader.ini"))
            # ALWAYS stage (round 5): a fresh save used to write straight
            # into `folder`, indexloader.ini first — a crash mid-save left
            # a folder that passes the "indexloader.ini exists"
            # completeness check with truncated data files.  Staging +
            # rename makes indexloader.ini a true completeness sentinel
            # for fresh and overwrite saves alike.
            # unique staging/backup names: a predictable ".saving"
            # could collide with (and rmtree) unrelated user data
            token = f"{os.getpid()}-{threading.get_ident()}"
            target = folder.rstrip("/\\") + f".saving-{token}"
            os.makedirs(target, exist_ok=True)
            # saved snapshots are always fully linked: the delta tail
            # folds into the main structures before a byte is staged
            self._absorb_delta_locked()
            if self.need_refine:
                self._refine_impl()
            wal_on = bool(int(getattr(self.params, "wal_enabled", 0)
                              or 0))
            with atomic.checked_open(
                    os.path.join(target, "indexloader.ini"), "w") as f:
                f.write(self.save_index_config())
            if self.metadata is not None:
                self.metadata.save(os.path.join(target, self._meta_file),
                                   os.path.join(target,
                                                self._meta_index_file))
            self._save_index_data(target)
            if wal_on:
                # the published snapshot ships an EMPTY log: every acked
                # record is folded into the blobs beside it, and the
                # directory swap retires the old log atomically with the
                # old blobs — there is no post-publish truncate to crash
                # between
                wal.create_empty(os.path.join(target, wal.WAL_NAME))
            # manifest LAST: its presence vouches for the checksums of
            # everything staged before it.  Excluded: the WAL (it
            # legitimately grows after the publish) and indexloader.ini
            # (a TEXT config operators legitimately hand-edit between
            # save and load — checksums protect the binary blobs, the
            # ini's completeness-sentinel role is structural)
            atomic.write_manifest(
                target, exclude=(wal.WAL_NAME, "indexloader.ini"))
            faultinject.crash_point("save.pre_rename")
            if existing:
                backup = folder.rstrip("/\\") + f".old-{token}"
                try:
                    os.rename(folder, backup)  # previous checkpoint intact
                except OSError as e:
                    if e.errno not in (errno.EXDEV, errno.EBUSY):
                        raise
                    # `folder` is a mountpoint (container volume): it can
                    # be neither renamed (EBUSY) nor atomically swapped
                    # from the staging sibling's filesystem (EXDEV) —
                    # degrade to the per-file move with indexloader.ini
                    # LAST, the same ordering the pre-created-folder
                    # branch uses (ADVICE r5).  The OLD sentinel must go
                    # FIRST: with it in place, a crash mid-loop would
                    # leave mixed old/new data files behind a valid-
                    # looking indexloader.ini (silent corruption); with
                    # it gone, the window reads as incomplete and load
                    # fails loudly instead
                    os.unlink(os.path.join(folder, "indexloader.ini"))
                    names = [nm for nm in os.listdir(target)
                             if nm != "indexloader.ini"]
                    for nm in names + ["indexloader.ini"]:
                        _replace_file(os.path.join(target, nm),
                                      os.path.join(folder, nm))
                    shutil.rmtree(target, ignore_errors=True)
                    faultinject.crash_point("save.post_rename")
                    if wal_on:
                        self._arm_wal(folder)
                    return ErrorCode.Success
                os.rename(target, folder)     # the swap
                # best-effort: the save has SUCCEEDED once the swap lands;
                # a cleanup failure (symlinked folder, open handles) must
                # not turn success into an exception
                try:
                    shutil.rmtree(backup)
                except OSError:
                    pass
            elif not os.path.exists(folder):
                try:
                    os.rename(target, folder)
                except OSError:
                    # a concurrent saver won the fresh-create race (the
                    # rename target now exists): their complete index is
                    # in place — discard our staging and report success
                    if not os.path.exists(
                            os.path.join(folder, "indexloader.ini")):
                        raise
                    try:
                        shutil.rmtree(target)
                    except OSError:
                        pass
            else:
                # pre-created non-index folder (may hold unrelated user
                # files — reference semantics write into it, never wipe
                # it): move the staged files in one by one with
                # indexloader.ini LAST, so the sentinel never exists
                # before the data it vouches for
                names = [nm for nm in os.listdir(target)
                         if nm != "indexloader.ini"]
                for nm in names + ["indexloader.ini"]:
                    _replace_file(os.path.join(target, nm),
                                  os.path.join(folder, nm))
                shutil.rmtree(target, ignore_errors=True)
            faultinject.crash_point("save.post_rename")
            if wal_on:
                # the acked log now lives (empty) inside the published
                # folder; future acks append there
                self._arm_wal(folder)
        return ErrorCode.Success

    # ---- in-memory blob persistence (embedding-host path) -----------------

    def _blob_writers(self):
        """Ordered (name, write(stream)) pairs for the index's binary blobs.
        Subclasses override; shared by folder save and blob save."""
        raise NotImplementedError

    def _blob_loaders(self):
        """Ordered (name, load(stream), optional) triples mirroring
        `_blob_writers`."""
        raise NotImplementedError

    def save_index_blobs(self) -> Tuple[str, List[bytes]]:
        """Serialize the whole index into caller-held memory buffers — the
        reference's embedding-host path, SaveIndex(config, blobs)
        (VectorIndex.cpp:126-158).  Returns (config_str, blobs) with blobs
        ordered [vectors, <index structures...>, deletes][, metadata,
        metadataIndex]; each blob is byte-identical to its folder file."""
        import io as _io

        with self._lock:
            self._absorb_delta_locked()
            if self.need_refine:
                self._refine_impl()
            config = self.save_index_config()
            blobs: List[bytes] = []
            for _name, writer in self._blob_writers():
                buf = _io.BytesIO()
                writer(buf)
                blobs.append(buf.getvalue())
            if self.metadata is not None:
                mb, ib = _io.BytesIO(), _io.BytesIO()
                self.metadata.save(mb, ib)
                blobs.extend([mb.getvalue(), ib.getvalue()])
        return config, blobs

    def load_index_blobs_data(self, config: str,
                              blobs: Sequence[bytes]) -> None:
        """Counterpart of `save_index_blobs` for an existing instance;
        module-level `load_index_blobs` is the factory entry point
        (reference LoadIndex from blobs, VectorIndex.cpp:364-400)."""
        import io as _io

        reader = IniReader.loads(config)
        # the whole swap runs under the writer lock (GL801): both load
        # surfaces are public and callable on a LIVE index, and the blob
        # loaders replace corpus/tree/graph/delta state that concurrent
        # searches and the background rebuild otherwise read mid-swap
        with self._lock:
            self.params.load_config(reader.section_items("Index"))
            pos = 0
            for _name, loader, optional in self._blob_loaders():
                if pos >= len(blobs):
                    if optional:
                        continue
                    raise ValueError(
                        f"missing index blob #{pos} ({_name})")
                loader(_io.BytesIO(blobs[pos]))
                pos += 1
            if reader.does_section_exist("MetaData") and \
                    pos + 1 < len(blobs):
                self.metadata = MetadataSet.load(
                    _io.BytesIO(blobs[pos]), _io.BytesIO(blobs[pos + 1]))
                if reader.get_parameter(
                        "MetaData", "MetaDataToVectorIndex",
                        "") == "true":
                    self.build_meta_mapping()

    def load_index_data(self, folder: str, reader: IniReader,
                        lazy_metadata: bool = False) -> None:
        with self._lock:                       # see load_index_blobs_data
            self.params.load_config(reader.section_items("Index"))
            self._load_index_data(folder)
            self._reset_delta()
            if reader.does_section_exist("MetaData"):
                self._meta_file = reader.get_parameter(
                    "MetaData", "MetaDataFilePath", self._meta_file)
                self._meta_index_file = reader.get_parameter(
                    "MetaData", "MetaDataIndexPath", self._meta_index_file)
                meta_path = os.path.join(folder, self._meta_file)
                index_path = os.path.join(folder, self._meta_index_file)
                if lazy_metadata:
                    # FileMetadataSet: offsets resident, payload read on
                    # demand (reference inc/Core/MetadataSet.h:46)
                    from sptag_tpu.core.vectorset import FileMetadataSet
                    self.metadata = FileMetadataSet(meta_path, index_path)
                else:
                    self.metadata = MetadataSet.load(meta_path, index_path)
                if reader.get_parameter(
                        "MetaData", "MetaDataToVectorIndex",
                        "") == "true":
                    self.build_meta_mapping()


#: kept as a module name for callers/tests; the implementation moved to
#: io/atomic.py (the GL411 write-path funnel) unchanged
_replace_file = atomic.replace_file


def _recover_interrupted_save(folder: str) -> None:
    """Heal the non-atomic window of save_index's directory swap: a crash
    between its two renames leaves `folder` absent with the complete new
    index at `folder.saving-*` (preferred — it was fully written before
    the swap began) or the previous one at `folder.old-*`."""
    if os.path.exists(os.path.join(folder, "indexloader.ini")):
        return
    base = folder.rstrip("/\\")
    parent = os.path.dirname(base) or "."
    name = os.path.basename(base)
    if not os.path.isdir(parent):
        return
    for prefix in (name + ".saving-", name + ".old-"):
        candidates = sorted(
            e for e in os.listdir(parent)
            if e.startswith(prefix) and os.path.exists(
                os.path.join(parent, e, "indexloader.ini")))
        if candidates:
            os.rename(os.path.join(parent, candidates[-1]), folder)
            return


def load_index(folder: str, lazy_metadata: bool = False) -> VectorIndex:
    """Parity: VectorIndex::LoadIndex(folder) (VectorIndex.cpp:324-360).
    `lazy_metadata=True` loads metadata as a FileMetadataSet (offsets only
    resident; payload read per lookup).

    Crash-consistency (ISSUE 9): interrupted-save recovery first, then
    manifest checksum verification (a corrupt blob fails the load, never
    deserializes), then — for a WalEnabled index — WAL replay over the
    loaded snapshot and re-arming of the log, so every acked mutation is
    present and future acks keep appending.

    Mesh folders (ISSUE 11): a folder carrying a ``sharded.json``
    manifest is a persisted mesh index (one reference-format sub-folder
    per shard; the builder CLI with ``Index.MeshShardAxis=N`` or
    ShardedBKTIndex.build(save_to=...) writes one).  The manifest's
    ``algo`` names the shards' family (FLAT -> `ShardedFlatIndex`,
    BKT / KDT or absent -> `ShardedBKTIndex`); either loads as a
    `ServingAdapter` over the reassembled mesh placement, so a
    ``[Index_<name>] IndexFolder=<mesh folder>`` ini line deploys
    in-mesh serving through the same config surface as any index."""
    if os.path.exists(os.path.join(folder, "sharded.json")):
        from sptag_tpu.parallel.sharded import ServingAdapter, \
            load_mesh_index

        sharded = load_mesh_index(folder)
        return ServingAdapter(
            sharded, feature_dim=int(sharded.data.shape[1]))
    _recover_interrupted_save(folder)
    atomic.verify_manifest(folder)
    reader = IniReader.load(os.path.join(folder, "indexloader.ini"))
    algo = reader.get_parameter("Index", "IndexAlgoType")
    value_type = reader.get_parameter("Index", "ValueType")
    if algo is None or value_type is None:
        raise ValueError("indexloader.ini missing IndexAlgoType/ValueType")
    index = create_instance(algo, value_type)
    index.load_index_data(folder, reader, lazy_metadata=lazy_metadata)
    if int(getattr(index.params, "wal_enabled", 0) or 0):
        index._replay_wal(folder)
        index._arm_wal(folder)
    return index


def load_index_blobs(config: str, blobs: Sequence[bytes]) -> VectorIndex:
    """Load an index entirely from memory buffers produced by
    `save_index_blobs` — zero filesystem use (reference LoadIndex from
    blobs, VectorIndex.cpp:364-400)."""
    reader = IniReader.loads(config)
    algo = reader.get_parameter("Index", "IndexAlgoType")
    value_type = reader.get_parameter("Index", "ValueType")
    if algo is None or value_type is None:
        raise ValueError("config missing IndexAlgoType/ValueType")
    index = create_instance(algo, value_type)
    index.load_index_blobs_data(config, blobs)
    return index


# ---- capacity planning (parity: VectorIndex.cpp:403-437) -------------------

def _tree_node_size(algo) -> int:
    """Bytes per tree node: BKT stores {centerid, childStart, childEnd}
    int32s; KDT stores {left, right} int32 + split_dim int32 + split_value
    float (reference EstimatedVectorCount, VectorIndex.cpp:403-417)."""
    if isinstance(algo, str):
        algo = enum_from_string(IndexAlgoType, algo)
    algo = IndexAlgoType(algo)
    if algo == IndexAlgoType.BKT:
        return 4 * 3
    if algo == IndexAlgoType.KDT:
        return 4 * 2 + 4 + 4
    return 0


def estimated_memory_usage(vector_count: int, dimension: int,
                           algo, value_type,
                           tree_number: int = 1,
                           neighborhood_size: int = 32) -> int:
    """Host bytes to hold an index of `vector_count` rows — the reference
    capacity-planning formula (VectorIndex::EstimatedMemoryUsage,
    VectorIndex.cpp:421-437): vectors + metadata offsets + graph rows +
    tombstone byte + tree nodes.  Returns 0 for algorithms outside
    BKT/KDT, exactly as the reference does (:430-432)."""
    tree_node = _tree_node_size(algo)
    if tree_node == 0:
        return 0
    if isinstance(value_type, str):
        value_type = enum_from_string(VectorValueType, value_type)
    unit = (np.dtype(dtype_of(VectorValueType(value_type))).itemsize
            * dimension)
    total = unit * vector_count                    # vectors
    total += 8 * vector_count                      # metadata offset table
    total += 4 * neighborhood_size * vector_count  # graph rows
    total += vector_count                          # tombstone flags
    total += tree_node * tree_number * vector_count
    return total


def estimated_vector_count(memory_bytes: int, dimension: int,
                           algo, value_type,
                           tree_number: int = 1,
                           neighborhood_size: int = 32) -> int:
    """Rows that fit in `memory_bytes` (inverse of estimated_memory_usage;
    reference VectorIndex.cpp:403-419)."""
    per_row = estimated_memory_usage(1, dimension, algo, value_type,
                                     tree_number, neighborhood_size)
    return 0 if per_row == 0 else memory_bytes // per_row


def estimated_hbm_usage(vector_count: int, dimension: int, value_type,
                        neighborhood_size: int = 32,
                        dense_mode: bool = True,
                        dense_cluster_size: int = 256,
                        dense_replicas: int = 1) -> int:
    """Device-HBM bytes for the search snapshots — the TPU-specific
    counterpart the reference doesn't need.

    Beam engine (algo/engine.py): vectors + float32 sqnorms + int32 graph
    rows + a bool tombstone mask (1 byte/row — the packed bitset there is
    the per-query visited table, not the tombstones).  Dense mode
    (algo/dense.py) additionally holds the packed cluster-contiguous
    vector copy (~1.15x at measured ~87% block fill), int32 member ids and
    float32 member sqnorms for every padded slot, the float32 block-mean
    centroids, and its own tombstone mask copy."""
    if isinstance(value_type, str):
        value_type = enum_from_string(VectorValueType, value_type)
    unit = (np.dtype(dtype_of(VectorValueType(value_type))).itemsize
            * dimension)
    # measured ~1.15x padding at 87% block fill; DenseReplicas multiplies
    # the packed copy (closure assignment duplicates boundary rows)
    pad = 1.15 * max(1, dense_replicas)
    total = unit * vector_count                    # engine vector snapshot
    total += 4 * vector_count                      # sqnorms
    total += 4 * neighborhood_size * vector_count  # graph
    total += vector_count                          # bool tombstones
    if dense_mode:
        slots = int(vector_count * pad)
        n_blocks = max(1, slots // max(dense_cluster_size, 1))
        total += unit * slots                      # packed blocks
        total += 4 * slots                         # member ids (int32)
        total += 4 * slots                         # member sqnorms
        total += 4 * dimension * n_blocks          # block-mean centroids
        total += vector_count                      # tombstone mask copy
    return total
