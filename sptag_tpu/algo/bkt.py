"""BKT index — balanced k-means tree forest + RNG graph + beam search.

Parity: BKT::Index<T> (/root/reference/AnnService/inc/Core/BKT/Index.h:37-161,
src/Core/BKT/BKTIndex.cpp): composition of {Dataset, BKTree, RNG graph,
Labelset, WorkSpacePool} with

* BuildIndex (BKTIndex.cpp:279-306): normalize (cosine), build tree forest,
  build + refine graph;
* SearchIndex (:216-264): tree-seeded budgeted best-first walk — here the
  batched beam engine (algo/engine.py);
* AddIndex (:462-529): append rows, link each new node into the graph via an
  AddCEF-budget search + RNG prune, insert reverse edges, and rebuild the
  tree forest after `AddCountForRebuild` appends (the reference queues an
  async RebuildJob on a thread pool, BKTIndex.cpp:39-49; here the rebuild is
  a synchronous snapshot swap under the writer lock — single-writer design,
  SURVEY.md §2b P4/P7);
* DeleteIndex / RefineIndex (:308-453): tombstones + compaction that remaps
  the graph and rebuilds tree + refine pass.

Duplicate-center semantics: the reference excludes duplicate points from the
graph and chases them through the tree's sample-center map at search time
(BKTree.h:184-205, BKTIndex.cpp:120-138).  Here every row — duplicates
included — is a TPT-leaf member and therefore a graph node, so duplicates are
reachable through the graph itself and no chase is needed; the map is still
built and persisted for tree-format compatibility.
"""

from __future__ import annotations

import io
import logging
import os
import threading
import time
from typing import Optional, Tuple

import numpy as np

from sptag_tpu.algo.dense import (DenseTreeSearcher, partition_from_tree,
                                  place_rows)
from sptag_tpu.algo.engine import GraphSearchEngine, packed_param
from sptag_tpu.core.index import MAX_DIST, VectorIndex, register_algo
from sptag_tpu.core.params import BKTParams
from sptag_tpu.core.types import (DistCalcMethod, IndexAlgoType,
                                  VectorValueType, dtype_of)
from sptag_tpu.graph.rng import RelativeNeighborhoodGraph
from sptag_tpu.utils import trace
from sptag_tpu.io import format as fmt
from sptag_tpu.trees.bktree import BKTree

log = logging.getLogger(__name__)


def pivot_budget(params, n: int = 0) -> int:
    """Shared-pivot set size budget (before the corpus-size clamp).

    THE single source of truth: the sharded/multihost builds pad their
    per-shard pivot arrays to exactly this value and would silently
    truncate pivots if a private copy of the formula diverged.

    Scales with corpus size (round 5, measured at 250k/10M): the beam
    walk's recall ceiling is SEED COVERAGE, not budget — a fixed
    1,600-pivot pool over a corpus with more natural clusters than that
    leaves whole clusters unreachable (250k x 2048-cluster corpus:
    recall flat at 0.45 from MaxCheck 8192 to 32768 with nbp/injection
    knobs irrelevant; 8x the pivots took it to 0.80 at identical graph).
    The reference sidesteps this by descending the tree PER QUERY
    (InitSearchTrees seeds NumberOfInitialDynamicPivots leaves wherever
    the query lands, BKTree.h:279-320); the shared-pool design must make
    the pool dense enough to land near every query instead.  n/24 keeps
    the (Q, P) seed matmul trivial on the MXU (P <= 16,384 at d=128 is
    ~8 MB of pivot vectors); the cap bounds the device-side sort."""
    base = max(64, params.initial_dynamic_pivots * 32)
    div = int(getattr(params, "seed_pivot_auto_scale", 24))
    if n and div > 0:
        base = max(base, min(n // div, 16384))
    return base


@register_algo
class BKTIndex(VectorIndex):
    algo = IndexAlgoType.BKT

    def __init__(self, value_type: VectorValueType):
        super().__init__(value_type)
        self._host: Optional[np.ndarray] = None
        self._n = 0
        self._deleted = np.zeros(0, bool)
        self._num_deleted = 0
        self._tree: Optional[BKTree] = None
        self._graph: Optional[RelativeNeighborhoodGraph] = None
        self._engine: Optional[GraphSearchEngine] = None
        self._dense: Optional[DenseTreeSearcher] = None
        self._dirty = True
        self._tombstones_dirty = False
        self._adds_since_rebuild = 0
        self._rebuild_pool = None         # lazy 1-worker ThreadPool
        self._rebuild_done = threading.Event()
        self._rebuild_done.set()          # no rebuild in flight
        self._rebuild_pending = False
        self._refine_dense_cache = None   # (key, DenseTreeSearcher)
        # continuous-batching slot scheduler (algo/scheduler.py), bound to
        # ONE engine snapshot; rebuilt lazily when the engine is replaced
        self._scheduler = None
        # bumped whenever row ids are remapped (build / compaction) so an
        # in-flight background rebuild can detect its snapshot went stale
        self._structure_gen = 0
        # bumped when an engine-baked parameter changes (set_parameter's
        # _ENGINE_PARAMS invalidation): a background refine that built
        # its engine under the OLD values must discard, not publish a
        # snapshot that silently reverts the operator's change
        self._engine_param_gen = 0

    def _make_params(self) -> BKTParams:
        return BKTParams()

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
        cap = self._host.shape[0]
        if need > cap:
            new_cap = max(need, cap * 2, 1024)
            grown = np.empty((new_cap, self._host.shape[1]), self._host.dtype)
            grown[:self._n] = self._host[:self._n]
            self._host = grown
            dels = np.zeros(new_cap, bool)
            dels[:self._n] = self._deleted[:self._n]
            self._deleted = dels

    # ---- component factories ----------------------------------------------

    def _new_tree(self) -> BKTree:
        p = self.params
        return BKTree(tree_number=p.tree_number, kmeans_k=p.kmeans_k,
                      leaf_size=p.leaf_size, samples=p.samples,
                      metric=int(self.dist_calc_method), base=self.base)

    def _load_tree(self, path: str) -> BKTree:
        p = self.params
        return BKTree.load(path, tree_number=p.tree_number,
                           kmeans_k=p.kmeans_k, leaf_size=p.leaf_size,
                           samples=p.samples,
                           metric=int(self.dist_calc_method), base=self.base)

    def _new_graph(self) -> RelativeNeighborhoodGraph:
        p = self.params
        return RelativeNeighborhoodGraph(
            neighborhood_size=p.neighborhood_size, tpt_number=p.tpt_number,
            tpt_leaf_size=p.tpt_leaf_size,
            neighborhood_scale=p.neighborhood_scale, cef_scale=p.cef_scale,
            refine_iterations=p.refine_iterations, cef=p.cef,
            tpt_top_dims=p.tpt_top_dims, tpt_samples=p.samples,
            refine_accuracy_guard=bool(p.refine_accuracy_guard),
            refine_accuracy_floor=float(p.refine_accuracy_floor))

    def _pivot_ids(self, rows: Optional[int] = None) -> np.ndarray:
        """Seed-pivot ids valid for an engine over `rows` corpus rows
        (default: the main-tier coverage).  The tree may postdate a
        delta absorb and reference ids past a smaller engine's corpus —
        those are clamped out (the delta scan covers their rows)."""
        rows = self._main_rows() if rows is None else rows
        max_pivots = min(rows, pivot_budget(self.params, rows))
        pivots = self._tree.collect_pivots(max_pivots)
        return pivots[pivots < rows]

    # parameters whose value is BAKED into a materialized engine snapshot:
    # changing one must invalidate the engine or the setting is a silent
    # no-op until the next unrelated mutation
    _ENGINE_PARAMS = frozenset({"beampackedneighbors", "beamscoredtype",
                                # the sample rate is baked into the
                                # engine at _make_engine time: without
                                # invalidation a set_parameter on a warm
                                # index would be a silent no-op
                                "flightdevicesamplerate",
                                # bin-reduction top-k mode + its recall
                                # target are baked into the engine's
                                # compiled walk programs (ISSUE 13)
                                "binnedtopk", "approxrecalltarget",
                                # tiered cascade (ISSUE 14): the int8
                                # scoring corpus, its residency tier and
                                # the fp re-rank budget are snapshot
                                # state — a flip must rebuild, never
                                # patch a live program
                                "cascadesearch", "corpustier",
                                "tierbudgetint8", "tierbudgetsketch"})
    # process-wide recorder knobs: applied DIRECTLY to flightrec at
    # set_parameter time (each maps to its own configure field, so
    # setting one never clobbers the others) — they are not baked into
    # the engine snapshot, and invalidating the engine for a dump-dir
    # string would force XLA recompiles for nothing
    _FLIGHT_PARAMS = frozenset({"flightrecorder", "flightrecorderevents",
                                "flightdumponslowquery"})
    # baked into the materialized DENSE snapshot (replication layout and
    # cluster partition); DenseQueryGroup/DenseUnionFactor are read live
    # at each search and need no invalidation.  The cascade knobs bake
    # the int8 block layout + fp re-rank tier into the dense snapshot
    # exactly like the engine (ISSUE 14)
    _DENSE_PARAMS = frozenset({"densereplicas", "denseclustersize",
                               "cascadesearch", "corpustier",
                               "tierbudgetint8", "tierbudgetsketch"})

    def set_parameter(self, name: str, value: str) -> bool:
        ok = super().set_parameter(name, value)
        low = name.lower()
        if ok and low in self._ENGINE_PARAMS:
            with self._lock:
                self._engine = None
                self._engine_param_gen += 1
        if ok and low in self._DENSE_PARAMS:
            with self._lock:
                self._dense = None
        if ok and low in self._FLIGHT_PARAMS:
            from sptag_tpu.utils import flightrec

            p = self.params
            flightrec.configure(
                enabled=(bool(int(getattr(p, "flight_recorder", 0)))
                         if low == "flightrecorder" else None),
                max_events=(int(getattr(p, "flight_recorder_events", 0))
                            or None
                            if low == "flightrecorderevents" else None),
                dump_dir=(getattr(p, "flight_dump_on_slow_query", "")
                          if low == "flightdumponslowquery" else None))
        return ok

    def _retrack_devmem(self) -> None:
        # DeviceBytesLedger re-enabled on a warm index: re-register the
        # materialized snapshots (disable dropped their entries); slot
        # pools re-track on their next resize
        with self._lock:
            if self._engine is not None:
                self._engine.register_devmem()
            if self._dense is not None:
                self._dense.register_devmem()

    def _make_engine(self, graph: np.ndarray, rows: Optional[int] = None,
                     serving: bool = True) -> GraphSearchEngine:
        """Materialize an engine snapshot over `rows` corpus rows
        (default: the main-tier coverage — rows in the delta shard are
        served by the delta scan, never by the engine).  `serving=False`
        is an engine made to link or refine a few rows and be dropped:
        it walks the row layout whatever `BeamPackedNeighbors` says (an
        N x m x D table gathered for one search is all cost)."""
        p = self.params
        rows = self._main_rows() if rows is None else rows
        if int(getattr(p, "flight_recorder", 0)):
            # index-level FlightRecorder=1 is the OFFLINE-run surface
            # (builder/searcher/bench CLIs with Index.Param passthrough):
            # enable the process ring when the engine materializes, so a
            # run with no [Service] config still records
            from sptag_tpu.utils import flightrec

            flightrec.configure(
                enabled=True,
                max_events=int(getattr(p, "flight_recorder_events", 0))
                or None,
                dump_dir=getattr(p, "flight_dump_on_slow_query", "")
                or None)
        return GraphSearchEngine(self._host[:rows], graph[:rows],
                                 self._pivot_ids(rows),
                                 self._deleted[:rows],
                                 self.dist_calc_method, self.base,
                                 score_dtype=getattr(
                                     self.params, "beam_score_dtype", "auto"),
                                 packed_neighbors=packed_param(getattr(
                                     self.params, "beam_packed_neighbors",
                                     "auto")) if serving else False,
                                 device_sample_rate=float(getattr(
                                     self.params,
                                     "flight_device_sample_rate", 0.0)),
                                 binned_topk=str(getattr(
                                     self.params, "binned_topk", "off")),
                                 recall_target=float(getattr(
                                     self.params, "approx_recall_target",
                                     0.99)),
                                 cascade_search=bool(int(getattr(
                                     self.params, "cascade_search", 0))),
                                 corpus_tier=str(getattr(
                                     self.params, "corpus_tier",
                                     "device")))

    def _get_engine(self) -> GraphSearchEngine:
        """Pin the current engine snapshot (epoch-based handoff,
        ISSUE 9): readers take ONE unlocked reference of an IMMUTABLE
        snapshot and keep using it even if a writer publishes a newer
        one mid-search — monotone, never torn.  The old code's fast
        path re-read `self._engine` after its flag checks, so a
        concurrent `set_parameter` nulling the attribute could hand a
        reader None (or mutate a mask on an engine the writer was
        discarding); now the pinned local is what's returned, and every
        publish happens under the lock with an epoch bump."""
        eng = self._engine
        if eng is not None and not self._dirty \
                and not self._tombstones_dirty:
            return eng
        with self._lock:
            if self._dirty or self._engine is None:
                self._engine = self._make_engine(self._graph.graph)
                self._dense = None
                self._dirty = False
                self._tombstones_dirty = False
                self._snapshot_epoch += 1
            elif self._tombstones_dirty:
                # delete-only change: swap the mask, keep the snapshots
                self._engine.set_deleted(self._deleted)
                if self._dense is not None:
                    self._dense.set_deleted(self._deleted)
                self._tombstones_dirty = False
            return self._engine

    def _build_dense_searcher(self,
                              replicas: Optional[int] = None,
                              cascade_ok: bool = True
                              ) -> DenseTreeSearcher:
        """Cluster-contiguous snapshot from the current tree.

        Rows appended after the last tree rebuild are not under any tree
        node yet; they are assigned to their nearest cut-center cluster so
        the partition always covers the whole corpus.  `replicas` defaults
        to the DenseReplicas search knob; build-time callers (the refine
        searcher) pass 1 — replication is a SEARCH-time recall/memory
        tradeoff and would halve the refine pass's distinct-row coverage.
        """
        if replicas is None:
            replicas = getattr(self.params, "dense_replicas", 1)
        with trace.span("build.dense_pack"):
            n = self._main_rows()
            data = self._host[:n]
            centers, clusters = self._dense_clusters()
            cascade_cfg = None
            if cascade_ok \
                    and int(getattr(self.params, "cascade_search", 0)) \
                    and np.issubdtype(data.dtype, np.floating):
                # tiered cascade (ISSUE 14): int8-quantized dense blocks
                # with a TierBudgetInt8-budgeted exact fp re-rank; the
                # dense partition's nprobe prefilter plays the coarse-tier
                # role the sketch scan plays on FLAT
                cascade_cfg = {
                    "tier": str(getattr(self.params, "corpus_tier",
                                        "device")),
                    "rerank_budget": int(getattr(self.params,
                                                 "tier_budget_int8", 0)),
                }
            return DenseTreeSearcher(
                data, centers, clusters, self._deleted[:n],
                self.dist_calc_method, self.base,
                replicas=replicas, cascade_cfg=cascade_cfg)

    def _dense_clusters(self):
        """Tree partition plus nearest-center assignment of the rows it
        leaves out: those appended after the last rebuild, and the center
        samples of the tree's nodes above the cut (host numpy throughout —
        the mesh packer calls this without touching the device).  Coverage
        stops at the delta base like every main-tier snapshot."""
        n = self._main_rows()
        centers, clusters = self._partition_tree(n)
        covered = np.zeros(n, bool)
        if clusters:
            covered[np.concatenate(clusters)] = True
        missing = np.flatnonzero(~covered)
        if len(missing):
            clusters = place_rows(self._host[:n], centers, clusters, missing,
                                  self.params.dense_cluster_size,
                                  self.dist_calc_method)
        return centers, clusters

    def _partition_tree(self, rows: Optional[int] = None):
        """Cut the current tree into a corpus partition for the dense
        layout; subclasses override per tree type (KDT cuts kd cells).
        `rows` bounds the partition to the main-tier coverage."""
        return partition_from_tree(self._tree,
                                   self._main_rows() if rows is None
                                   else rows,
                                   self.params.dense_cluster_size,
                                   place_loose=False)

    def _get_dense(self) -> DenseTreeSearcher:
        """Lazy dense snapshot for the dense search mode (pinned by
        local reference, like _get_engine — readers must never observe
        a concurrent invalidation as None)."""
        if not getattr(self.params, "build_graph", 1):
            # dense-only index: refresh state WITHOUT materializing the
            # beam engine — its device copies of data + graph would
            # double HBM use for a mode that never reads them
            with self._lock:
                if self._dirty:
                    self._engine = None
                    self._dense = None
                    self._dirty = False
                    self._tombstones_dirty = False
                    self._snapshot_epoch += 1
                elif self._tombstones_dirty:
                    if self._dense is not None:
                        self._dense.set_deleted(
                            self._deleted[:self._main_rows()])
                    self._tombstones_dirty = False
                if self._dense is None:
                    self._dense = self._build_dense_searcher()
                return self._dense
        self._get_engine()          # refresh dirty state under one lock
        dense = self._dense
        if dense is not None:
            return dense
        with self._lock:
            if self._dense is None:
                self._dense = self._build_dense_searcher()
            return self._dense

    # ---- build ------------------------------------------------------------

    def _build(self, data: np.ndarray, checkpoint=None) -> None:
        self._host = np.ascontiguousarray(data)
        self._n = data.shape[0]
        self._deleted = np.zeros(self._n, bool)
        self._num_deleted = 0
        self._adds_since_rebuild = 0
        self._structure_gen += 1

        # resumable build (utils/build_ckpt.py): the tree stage is loaded
        # from the checkpoint when a prior run already finished it
        self._tree = None
        if checkpoint is not None:
            raw = checkpoint.get_bytes("tree")
            if raw is not None:
                try:
                    self._tree = self._load_tree(io.BytesIO(raw))
                    log.info("build resume: tree stage from checkpoint")
                except Exception:                      # noqa: BLE001
                    self._tree = None                  # corrupt -> rebuild
        if self._tree is None:
            self._tree = self._new_tree()
            with trace.span("build.bkt_tree"):
                self._tree.build(self._host[:self._n])
            if checkpoint is not None:
                buf = io.BytesIO()
                self._tree.save(buf)
                checkpoint.put_bytes("tree", buf.getvalue())
        log.info("BKT forest built: %d nodes", self._tree.num_nodes)

        self._graph = self._new_graph()
        if not getattr(self.params, "build_graph", 1):
            # dense-only build (BuildGraph=0, a framework extension with
            # no reference counterpart): the RNG graph's TPT partition +
            # refine passes are the dominant build cost, and the MXU
            # dense scan never reads the graph — skip it.  The graph
            # array stays shape-correct (all -1) so save/load and the
            # mutation bookkeeping are unchanged; beam search refuses
            # with a clear error (_search_batch).
            self._graph.graph = np.full(
                (self._n, self._graph.neighborhood_size), -1, np.int32)
            self._dirty = True
            return
        try:
            with trace.span("build.rng_graph"):
                p = self.params
                fmode = getattr(p, "final_refine_search_mode", "beam")
                # the final pass may run a DIFFERENT engine to optimize
                # walk navigability (FinalRefineSearchMode guardrail) —
                # sampled precision@m cannot judge that pass, so the
                # accuracy guard must not roll it back
                same_engine = fmode == "same" or \
                    fmode == getattr(p, "refine_search_mode", "beam")
                self._graph.build(self._host[:self._n],
                                  int(self.dist_calc_method), self.base,
                                  self._refine_search_factory,
                                  checkpoint=checkpoint,
                                  guard_final=same_engine)
        finally:
            # free the mid-build device snapshot even when the build dies
            self._refine_dense_cache = None
        self._dirty = True

    def _refine_search_factory(self, graph: np.ndarray,
                               final: bool = False):
        """SearchFn over a mid-build graph snapshot, at the refine budget
        (MaxCheckForRefineGraph — reference RefineSearchIndex,
        BKTIndex.cpp:266-276).

        RefineSearchMode=dense (default) routes the per-node refine
        searches through the MXU cluster scan instead of the beam walk —
        graph build becomes matmul-bound (the beam-refine pass measured
        ~20x the rest of the build combined off-TPU).  The FINAL pass
        honors FinalRefineSearchMode (default "beam"): dense-refined
        graphs score 0.937-0.940 under the reference's walk vs
        0.990-1.000 beam-refined (reports/AB_REFERENCE.md), so the pass
        that defines the saved edges walks by default while the wide
        early passes stay matmul-bound."""
        p = self.params
        budget = p.max_check_for_refine_graph
        mode = getattr(p, "refine_search_mode", "beam")
        if final:
            fmode = getattr(p, "final_refine_search_mode", "beam")
            if fmode != "same":
                mode = fmode
        # dense refine cuts the current tree into a partition via
        # _partition_tree — KDT shares this path through its kd-cell cut
        if mode == "dense" and \
                self._tree is not None:
            # the dense searcher depends on the TREE, not the graph snapshot
            # this factory receives — cache it across the refine passes of
            # one build (each pass re-invokes the factory)
            key = (id(self._tree), self._structure_gen)
            cached = self._refine_dense_cache
            if cached is not None and cached[0] == key:
                searcher = cached[1]
            else:
                # refine searches stay full-precision: the cascade is a
                # SERVING residency/speed trade, and a quantized refine
                # would bake its noise into the saved graph edges
                searcher = self._build_dense_searcher(replicas=1,
                                                      cascade_ok=False)
                self._refine_dense_cache = (key, searcher)
                # starvation check at the SOURCE (round 5, measured at
                # 10M: budget 256 over ~5,700 clusters probes nprobe=1 —
                # one cluster — and the refine pass replaced TPT edges
                # with near-random results, recall 0.589 -> 0.469).
                # Warn when the refine budget covers
                # fewer than two probes of the partition it searches.
                # the search closure below runs max_check=max(budget, 2k)
                # with k=cef+1, so judge the EFFECTIVE budget (the final
                # pass's cef — non-final passes run wider still)
                eff = max(budget, 2 * (p.cef + 1))
                nprobe_est = max(1, -(-eff // searcher.cluster_size))
                if searcher.num_clusters >= 8 and nprobe_est < 2:
                    log.warning(
                        "dense refine budget MaxCheckForRefineGraph=%d "
                        "(effective %d) probes only %d of %d clusters "
                        "(cluster size %d) — refine at this coverage can "
                        "DEGRADE the graph (measured at 10M, round 5); "
                        "raise the budget or set RefineIterations=0",
                        budget, eff, nprobe_est, searcher.num_clusters,
                        searcher.cluster_size)

            # grouped probing helps refine especially — its queries ARE
            # corpus rows, maximally probe-local after the partition sort.
            # RefineQueryGroup selects the refine knob PAIR; a config that
            # only set the search-time DenseQueryGroup falls back to BOTH
            # dense knobs (group and union factor together — mixing the
            # pairs would silently change tuned builds)
            rg = getattr(p, "refine_query_group", 0)
            if rg:
                group = rg
                union = getattr(p, "refine_union_factor", 4)
            else:
                group = getattr(p, "dense_query_group", 0)
                union = getattr(p, "dense_union_factor", 2)

            def search(queries: np.ndarray, k: int):
                # a candidate pool at least as big as k keeps the RNG prune
                # supplied even when the budget knob is set below CEF
                return searcher.search(
                    queries, k, max_check=max(budget, 2 * k),
                    group=group, union_factor=union)
            return search

        engine = self._make_engine(graph, serving=False)

        def search(queries: np.ndarray, k: int):
            return engine.search(
                queries, k, max_check=budget,
                beam_width=getattr(p, "beam_width", 16),
                pool_size=max(2 * k, 64),
                nbp_limit=p.no_better_propagation_limit)
        return search

    # ---- search -----------------------------------------------------------

    def resolve_search_mode(self, mode: str, max_check: int) -> str:
        """Resolve "auto" to a concrete engine: beam below the
        AutoModeThreshold budget, dense at or above it — the measured
        crossover (beam holds recall at small MaxCheck where the dense
        scan collapses, dense wins both QPS and recall at large budgets;
        round-3 chip sessions, not measured on this code).  A dense-only index (BuildGraph=0) has
        no walk to fall back to, so auto always resolves to dense there."""
        if mode != "auto":
            return mode
        if not getattr(self.params, "build_graph", 1):
            return "dense"
        thr = int(getattr(self.params, "auto_mode_threshold", 1024))
        return "beam" if max_check < thr else "dense"

    def search_mode_ready(self, mode: str, max_check: int = 0) -> bool:
        """True when serving `mode` needs no NEW device materialization —
        the guard a server uses before honoring a wire-level $searchmode
        override (a lazily built dense pack is roughly a second corpus
        copy in HBM; a remote client must not be able to force that on an
        operator who configured beam-only).  The index's own configured
        mode always reports ready: its engine would be built by the first
        ordinary search anyway."""
        default_mc = int(getattr(self.params, "max_check", 8192))
        mode = self.resolve_search_mode(mode, max_check or default_mc)
        configured = self.resolve_search_mode(
            getattr(self.params, "search_mode", "beam"), default_mc)
        if mode == configured:
            return True
        if mode == "beam" and not getattr(self.params, "build_graph", 1):
            # no graph to walk: the search raises immediately WITHOUT
            # allocating — honoring the override preserves the documented
            # failure semantics and costs nothing
            return True
        if self._dirty:
            # a pending mutation invalidates the materialized engines; the
            # next search REBUILDS whichever engine it needs, so a stale
            # non-None handle is not "ready" — honoring the override here
            # would let a wire client trigger exactly the rebuild the
            # guard exists to prevent
            return False
        return (self._dense if mode == "dense" else self._engine) is not None

    def _search_batch(self, queries: np.ndarray, k: int,
                      max_check: Optional[int] = None,
                      search_mode: Optional[str] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        if self._n == 0:
            raise RuntimeError("index is empty")
        p = self.params
        mc = max_check if max_check is not None else p.max_check
        mode = search_mode or getattr(p, "search_mode", "beam")
        if mode not in ("beam", "dense", "auto"):
            raise ValueError(f"unknown search mode {mode!r}")
        mode = self.resolve_search_mode(mode, mc)
        if mode == "dense":
            d, ids = self._get_dense().search(
                queries, min(k, self._n), max_check=mc,
                group=getattr(p, "dense_query_group", 0),
                union_factor=getattr(p, "dense_union_factor", 2),
                binned=str(getattr(p, "binned_topk", "off")),
                recall_target=float(
                    getattr(p, "approx_recall_target", 0.99)))
        else:
            if not getattr(p, "build_graph", 1):
                raise RuntimeError(
                    "beam search needs the RNG graph, but this index was "
                    "built with BuildGraph=0 (dense-only); use "
                    "SearchMode=dense or rebuild with BuildGraph=1")
            d, ids = self._engine_search(queries, min(k, self._n), mc)
        return self._pad_results(d, ids, k)

    def _get_scheduler(self):
        """Slot scheduler over the CURRENT engine snapshot (created
        lazily).  A snapshot swap RETIRES the old scheduler: it stops
        accepting new queries but finishes everything already submitted
        against its (immutable) old snapshot — the same semantics as
        monolithic searches that were mid-flight when the swap landed —
        and its worker exits on its own once drained."""
        from sptag_tpu.algo.scheduler import BeamSlotScheduler

        engine = self._get_engine()
        old = None
        with self._lock:
            sched = self._scheduler
            if (sched is not None and sched._engine is engine
                    and not sched._stopped and not sched._draining):
                return sched
            old = sched
            p = self.params
            sched = BeamSlotScheduler(
                engine, slots=int(getattr(p, "beam_slots", 1024)),
                segment_iters=int(getattr(p, "beam_segment_iters", 0)),
                name="beam-sched")
            self._scheduler = sched
        if old is not None:
            old.retire()      # non-blocking; in-flight queries complete
        return sched

    def _scheduler_submit(self, queries: np.ndarray, k: int,
                          max_check: int,
                          rids: Optional[list] = None) -> list:
        """Submit prepared queries to the slot scheduler; KDT overrides to
        attach its per-query kd-tree seeds.  `rids` (one per query) tag
        the scheduler's flight-recorder events and per-rid stats."""
        p = self.params
        sched = self._get_scheduler()
        return [sched.submit(queries[i], k, max_check,
                             beam_width=getattr(p, "beam_width", 16),
                             nbp_limit=p.no_better_propagation_limit,
                             dynamic_pivots=p.other_dynamic_pivots,
                             rid=rids[i] if rids else "")
                for i in range(queries.shape[0])]

    def _engine_search(self, queries: np.ndarray, k: int, max_check: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Beam-walk branch of _search_batch; KDT overrides to seed from
        its kd-tree descent instead of the shared pivots."""
        p = self.params
        if int(getattr(p, "continuous_batching", 0)):
            # same results, continuously batched: the sync batch rides the
            # slot scheduler so it shares device time with concurrent
            # submitters instead of convoying them
            from sptag_tpu.algo.scheduler import gather_futures

            return gather_futures(
                self._scheduler_submit(queries, k, max_check), k)
        seg = int(getattr(p, "beam_segment_iters", 0))
        return self._get_engine().search(
            queries, k, max_check=max_check,
            beam_width=getattr(p, "beam_width", 16),
            nbp_limit=p.no_better_propagation_limit,
            dynamic_pivots=p.other_dynamic_pivots,
            segment_iters=seg or None)

    def _exact_scan(self, queries: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Quality-monitor oracle (core/index.py exact_search_batch):
        the exact FLAT/MXU scan over the engine snapshot's resident
        corpus — zero extra HBM, and the graph/tree structures play no
        part (this measures what the walk MISSED, so it must not share
        the walk's blind spots)."""
        return self._get_engine().exact_scan(queries, k)

    def _health_payload(self) -> Optional[dict]:
        """Graph navigability health (utils/qualmon.py graph_health):
        degree histogram, sampled reciprocal-edge fraction, and the
        fraction of live rows reachable from the tree seeds — the
        numbers a budget-starved refine degrades first.  Scalars also
        ride qualmon gauges so /metrics carries the time series."""
        from sptag_tpu.utils import qualmon

        if self._graph is None or self._graph.graph is None:
            return None
        # main-tier rows only: while a delta is live the graph holds
        # exactly _main_rows() rows (the tail is unlinked by design and
        # would read as unreachable)
        n = min(self._main_rows(), len(self._graph.graph))
        health = qualmon.graph_health(self._graph.graph[:n],
                                      self._deleted[:n], self._pivot_ids())
        shard = getattr(self, "_quality_shard",
                        type(self).__name__.lower())
        qualmon.gauge("graph.mean_degree",
                      health.get("degree_mean", 0.0), shard=shard)
        qualmon.gauge("graph.reciprocal_fraction",
                      health.get("reciprocal_fraction", 0.0), shard=shard)
        qualmon.gauge("graph.reachable_fraction",
                      health.get("reachable_fraction", 0.0), shard=shard)
        return health

    def submit_batch(self, queries: np.ndarray, k: int = 10,
                     max_check: Optional[int] = None,
                     search_mode: Optional[str] = None,
                     rids: Optional[list] = None) -> list:
        """Streaming submit (core/index.py contract): with
        ContinuousBatching=1 and a beam-resolved mode, futures resolve AS
        QUERIES RETIRE from the slot scheduler; otherwise falls back to
        the synchronous base implementation.  `rids` (one per query)
        flow into the scheduler for flight-recorder attribution."""
        p = self.params
        mc = max_check if max_check is not None else p.max_check
        mode = search_mode or getattr(p, "search_mode", "beam")
        if (self._n == 0 or not int(getattr(p, "continuous_batching", 0))
                or mode not in ("beam", "auto")
                or self.resolve_search_mode(mode, mc) != "beam"
                or not getattr(p, "build_graph", 1)):
            return super().submit_batch(queries, k, max_check=max_check,
                                        search_mode=search_mode)
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.feature_dim:
            raise ValueError(
                f"query dim {queries.shape[1]} != index dim "
                f"{self.feature_dim}")
        from concurrent.futures import Future

        from sptag_tpu.algo.scheduler import pad_result_row

        # this path bypasses search_batch: the span holds preparation, the
        # delta scan and the hand-over to the scheduler; the walk itself
        # runs on the scheduler's thread and is waited for by the caller
        with trace.span("index.search"):
            queries = self._prepare_query(queries)
            # delta union for the streaming path: the shard is scanned
            # ONCE for the whole batch up front (fresh rows must be
            # visible to streamed results exactly like whole-batch ones),
            # and each retiring query merges its row in its resolve
            # callback.  The scheduler walks the engine snapshot pinned at
            # submit, so the two tiers stay disjoint even if a swap lands
            # mid-flight.
            delta = self._delta
            delta_res = None
            if delta is not None and delta.count:
                from sptag_tpu.core.delta import merge_topk

                delta_res = delta.search(queries, min(k, delta.count),
                                         self._tombstone_mask())
            inners = self._scheduler_submit(queries, min(k, self._n), mc,
                                            rids=rids)
        out = []
        for row, inner in enumerate(inners):
            outer: Future = Future()

            def _pad(f, outer=outer, row=row):
                e = f.exception()
                if e is not None:
                    outer.set_exception(e)
                    return
                d, ids = f.result()
                d, ids = pad_result_row(d, ids, k)
                if delta_res is not None:
                    md, mi = merge_topk(d[None, :], ids[None, :],
                                        delta_res[0][row:row + 1],
                                        delta_res[1][row:row + 1], k)
                    d, ids = md[0], mi[0]
                outer.set_result((d, ids))
            inner.add_done_callback(_pad)
            out.append(outer)
        return out

    @staticmethod
    def _pad_results(d: np.ndarray, ids: np.ndarray, k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Pad result columns out to k with MAX_DIST / -1 sentinels."""
        if ids.shape[1] < k:
            q = ids.shape[0]
            d = np.concatenate(
                [d, np.full((q, k - d.shape[1]), MAX_DIST, np.float32)], 1)
            ids = np.concatenate(
                [ids, np.full((q, k - ids.shape[1]), -1, np.int32)], 1)
        return d, ids

    # ---- mutation ---------------------------------------------------------

    def _add(self, data: np.ndarray) -> int:
        begin = self._n
        count = data.shape[0]
        link = bool(getattr(self.params, "build_graph", 1))
        # snapshot BEFORE the rows land; dense-only indexes have no graph
        # to link into (appended rows reach searches via the partition's
        # nearest-center assignment until the next rebuild)
        engine = self._get_engine() if link else None
        self._reserve(count)
        self._host[begin:begin + count] = data
        self._n += count

        if link:
            self._link_new_rows(engine, begin, count)
        else:
            self._graph.graph = np.concatenate(
                [self._graph.graph,
                 np.full((count, self._graph.graph.shape[1]), -1,
                         np.int32)], axis=0)
        self._adds_since_rebuild += count
        if self._adds_since_rebuild >= self.params.add_count_for_rebuild:
            self._adds_since_rebuild = 0
            self._schedule_rebuild()
        self._dirty = True
        return begin

    # ---- background tree rebuild (P4) --------------------------------------

    def _schedule_rebuild(self) -> None:
        """Queue a tree-forest rebuild on the index's background pool —
        searches keep serving on the current immutable snapshot while it runs
        (reference RebuildJob on Helper::ThreadPool, BKTIndex.cpp:39-49,
        ThreadPool.h:18).  Called under the writer lock.  At most one rebuild
        runs; a request arriving mid-rebuild coalesces into one follow-up
        pass."""
        # re-entrant re-acquire (the callers already hold the RLock):
        # makes the lock invariant LOCAL — the background-refine chain
        # (ISSUE 9) reaches here through several frames and the
        # protection must not depend on reading every caller
        with self._lock:
            # the worker sets _rebuild_done under this same lock before
            # it exits, so "job in flight" and "worker will still see
            # the pending flag" are one atomic condition (no lost-
            # request TOCTOU)
            if not self._rebuild_done.is_set():
                self._rebuild_pending = True
                return
            if self._rebuild_pool is None:
                from sptag_tpu.utils.threadpool import ThreadPool

                # named pool: a leaked-worker warning (threadpool.py
                # stop()) must say WHICH pool wedged, and the lock
                # sanitizer's watchdog dumps read better with the owner
                # spelled out
                self._rebuild_pool = ThreadPool(name="bkt-rebuild")
                self._rebuild_pool.init(1)  # one worker = ref cadence
            self._rebuild_pending = False
            # enqueue BEFORE clearing the event: if add() raises (pool
            # stopped by a concurrent close()), _rebuild_done must stay
            # set or no rebuild would ever be schedulable again
            self._rebuild_pool.add(self._rebuild_job)
            self._rebuild_done.clear()

    def _rebuild_job(self) -> None:
        try:
            while True:
                with self._lock:
                    gen = self._structure_gen
                    # main-tier rows only: delta rows are unlinked and
                    # would put out-of-engine ids into the pivot set
                    n = self._main_rows()
                    snapshot = self._host[:n].copy()
                tree = self._new_tree()
                tree.build(snapshot)      # the long pass — no lock held
                with self._lock:
                    # a compaction/rebuild remaps ids; drop a stale result
                    # (BKTree::Rebuild swaps under a unique_lock,
                    # BKTree.h:132-141)
                    if self._structure_gen == gen:
                        self._tree = tree
                        self._dirty = True    # pivot set changed
                    if not self._rebuild_pending:
                        self._rebuild_done.set()  # exit decided under lock
                        return
                    self._rebuild_pending = False
        except BaseException:
            # a failed rebuild (XLA OOM, MemoryError) must not wedge the
            # machinery: leave the old tree serving, unblock waiters, let
            # the next add schedule a fresh attempt
            with self._lock:
                self._rebuild_pending = False
                self._rebuild_done.set()
            raise

    def wait_for_rebuild(self, timeout: Optional[float] = None) -> None:
        """Block until any in-flight background rebuild completes (the
        reference test waits with a sleep, AlgoTest.cpp:95; this is
        deterministic)."""
        self._rebuild_done.wait(timeout)

    def close(self) -> None:
        """Stop the background rebuild worker (idempotent).  A discarded
        index otherwise leaks one idle daemon thread per ThreadPool.
        The pool swap happens under the writer lock (so _schedule_rebuild
        can't enqueue onto a stopping pool); the join happens outside it
        (a running rebuild job needs the lock to finish)."""
        with self._lock:
            pool, self._rebuild_pool = self._rebuild_pool, None
            sched, self._scheduler = self._scheduler, None
        if pool is not None:
            pool.stop()
        if sched is not None:
            sched.stop()

    def __del__(self):                    # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:                              # noqa: BLE001
            pass

    def _link_new_rows(self, engine: GraphSearchEngine, begin: int,
                       count: int) -> None:
        """Wire `count` appended rows into the RNG graph (writer-lock
        path: `self._graph.graph` holds `begin` linked rows)."""
        self._graph.graph = self._linked_graph(
            engine, self._graph.graph[:begin], begin, count, self._host)

    def _linked_graph(self, engine: GraphSearchEngine,
                      graph_base: np.ndarray, begin: int, count: int,
                      host: np.ndarray) -> np.ndarray:
        """Pure linking pass: returns a (begin+count, m') graph whose
        first `begin` rows extend `graph_base` with reverse edges and
        whose tail rows are freshly RNG-pruned — shared by the inline
        `_add` path and the BACKGROUND delta absorb (which runs it
        off-lock over pinned array references; rows [0, begin+count)
        are append-only stable, so no copies are needed).

        Parity: the AddIndex tail (BKTIndex.cpp:523-526): per new node, an
        AddCEF-budget search + RebuildNeighbors for its own row, then
        InsertNeighbors for the reverse edges.  The searches for a whole
        added batch run as ONE device batch against the pre-add snapshot.
        """
        p = self.params
        m = p.neighborhood_size
        new_rows = np.full((count, graph_base.shape[1]), -1, np.int32)
        grown = np.concatenate([graph_base, new_rows], axis=0)

        add_k = min(p.add_cef + 1, max(begin, 1))
        queries = host[begin:begin + count]
        d, ids = engine.search(
            queries, add_k, max_check=p.max_check_for_refine_graph,
            nbp_limit=p.no_better_propagation_limit)

        from sptag_tpu.ops import graph as graph_ops
        import jax.numpy as jnp
        vecs = host[np.maximum(ids, 0)].astype(np.float32)
        keep = np.asarray(graph_ops.rng_select(
            jnp.asarray(queries.astype(np.float32)), jnp.asarray(vecs),
            jnp.asarray(d), jnp.asarray(ids >= 0), m,
            int(self.dist_calc_method), self.base))
        sel = np.where(keep >= 0,
                       np.take_along_axis(ids, np.maximum(keep, 0), axis=1),
                       -1)
        grown[begin:begin + count, :m] = sel

        # Reverse edges: batched RNG re-prune of every touched row, in ONE
        # device pass.  Deliberate reshape of the reference's per-pair
        # InsertNeighbors insertion sort under a per-row lock
        # (RelativeNeighborhoodGraph.h:37-71): each target row's existing
        # neighbors plus all its inserts are re-sorted by distance and
        # re-pruned with the same RNG occlusion rule (RebuildNeighbors,
        # :18-35) — applied uniformly, including to rows with empty slots,
        # which the per-slot variant skipped.
        pairs = sel >= 0                                    # (count, m)
        if pairs.any():
            tgt = sel[pairs].astype(np.int64)               # (P,) old nodes
            vid = np.broadcast_to(
                np.arange(begin, begin + count)[:, None], sel.shape)[pairs]
            uniq, inv = np.unique(tgt, return_inverse=True)
            U = len(uniq)
            # pack each target's inserted ids into a (U, max_ins) pad table
            order = np.argsort(inv, kind="stable")
            sorted_inv = inv[order]
            group_start = np.searchsorted(sorted_inv, np.arange(U))
            pos = np.arange(len(tgt)) - group_start[sorted_inv]
            max_ins = int(pos.max()) + 1
            ins = np.full((U, max_ins), -1, np.int64)
            ins[sorted_inv, pos] = vid[order]

            cand = np.concatenate([grown[uniq].astype(np.int64), ins], axis=1)
            valid = cand >= 0
            cvecs = host[np.maximum(cand, 0)].astype(np.float32)
            tvecs = host[uniq].astype(np.float32)
            cd = np.asarray(graph_ops.node_candidate_dists(
                jnp.asarray(tvecs), jnp.asarray(cvecs),
                int(self.dist_calc_method), self.base))
            cd = np.where(valid, cd, np.float32(MAX_DIST))
            ordc = np.argsort(cd, axis=1, kind="stable")
            cand_s = np.take_along_axis(cand, ordc, axis=1)
            cd_s = np.take_along_axis(cd, ordc, axis=1)
            valid_s = np.take_along_axis(valid, ordc, axis=1)
            keep_r = np.asarray(graph_ops.rng_select(
                jnp.asarray(tvecs),
                jnp.asarray(np.take_along_axis(
                    cvecs, ordc[:, :, None], axis=1)),
                jnp.asarray(cd_s), jnp.asarray(valid_s), grown.shape[1],
                int(self.dist_calc_method), self.base))
            new_rows = np.where(
                keep_r >= 0,
                np.take_along_axis(cand_s, np.maximum(keep_r, 0), axis=1),
                -1).astype(np.int32)
            grown[uniq] = new_rows
        return grown

    def _delete_id(self, vid: int) -> bool:
        if self._deleted[vid]:
            return False
        self._deleted[vid] = True
        self._num_deleted += 1
        # tombstones ride a cheap mask swap, not a snapshot rebuild
        self._tombstones_dirty = True
        return True

    # ---- delta shard + background refine/swap (ISSUE 9) -------------------

    def _append_rows_unlinked(self, data: np.ndarray) -> Optional[int]:
        """Delta-shard fast path: rows land in host storage but are NOT
        linked (no AddCEF search) and do NOT invalidate the engine
        snapshot — the FLAT delta scan serves them until a refine
        absorbs the tail.  The GRAPH is deliberately untouched: while a
        delta is live the graph holds exactly `_main_rows()` rows, and
        the absorb's `_linked_graph` pass appends the tail rows then —
        growing it here with -1 rows cost an O(n*m) full-graph copy per
        acked add batch (review fix), for rows nothing reads."""
        begin = self._n
        count = data.shape[0]
        self._reserve(count)
        self._host[begin:begin + count] = data
        self._n += count
        return begin

    def _tombstone_mask(self) -> Optional[np.ndarray]:
        return self._deleted[:self._n]

    def _absorb_delta_impl(self, begin: int, count: int) -> None:
        """Synchronous absorb (lock held): link the delta tail into the
        graph against an engine covering [0, begin), then invalidate so
        the next snapshot covers everything.  Used at overflow, save,
        and explicit refine; the BACKGROUND path (_auto_refine_job)
        does the same work off-thread and swaps atomically."""
        if getattr(self.params, "build_graph", 1):
            engine = self._engine
            if engine is None or engine.n != begin:
                engine = self._make_engine(self._graph.graph, rows=begin,
                                           serving=False)
            # the graph holds exactly `begin` rows while the delta is
            # live (_append_rows_unlinked defers growth); linking
            # appends the tail and refreshes the prefix reverse edges
            self._graph.graph = self._linked_graph(
                engine, self._graph.graph[:begin], begin, count,
                self._host)
            self._adds_since_rebuild += count
            if self._adds_since_rebuild >= \
                    self.params.add_count_for_rebuild:
                self._adds_since_rebuild = 0
                self._schedule_rebuild()
        self._dirty = True

    def _schedule_auto_refine(self) -> None:
        """Queue the background absorb+swap on the index's worker pool
        (shared with the tree rebuild — background work serializes).
        At most one refine is in flight; the job re-checks the
        threshold when it finishes, so a delta that refilled during the
        build gets the next round without a new trigger."""
        with self._lock:
            if self._refine_in_flight:
                return
            d = self._delta
            if d is None or not d.count:
                return
            if not getattr(self.params, "build_graph", 1):
                # dense-only: absorbing is a partition reassignment at
                # the next snapshot — cheap enough inline
                self._absorb_delta_locked()
                return
            if self._rebuild_pool is None:
                from sptag_tpu.utils.threadpool import ThreadPool

                self._rebuild_pool = ThreadPool(name="bkt-rebuild")
                self._rebuild_pool.init(1)
            self._refine_in_flight = True
            try:
                self._rebuild_pool.add(self._auto_refine_job)
            except BaseException:
                self._refine_in_flight = False
                raise

    def _auto_refine_job(self) -> None:
        """Background refine + snapshot swap WITHOUT drain: link the
        delta tail into a graph copy and build a fresh engine OFF the
        writer lock (searches and acks continue throughout), then
        publish under the lock and retire the superseded scheduler —
        its resident queries finish on the old immutable snapshot while
        the replacement accepts refills (BeamSlotScheduler.retire(),
        THE snapshot-swap path).  Zero queries dropped; staleness is
        bounded by this job's wall time."""
        from sptag_tpu.utils import flightrec, metrics

        t0 = time.monotonic()
        old_sched = None
        try:
            with self._lock:
                d = self._delta
                if d is None or not d.count:
                    return
                gen = self._structure_gen
                pgen = self._engine_param_gen
                b0 = d.base_id
                n0 = b0 + d.count
                host = self._host          # pinned; rows [0, n0) stable
                graph_base = self._graph.graph[:b0].copy()
                engine = self._engine
                if engine is None or engine.n != b0 or self._dirty:
                    engine = None
            if flightrec.enabled():
                flightrec.record("index", "swap_begin",
                                 payload={"rows": n0 - b0, "base": b0})
            if engine is None:
                # off-lock materialization over the stable prefix
                engine = self._make_engine(self._graph.graph, rows=b0,
                                           serving=False)
            new_graph = self._linked_graph(engine, graph_base, b0,
                                           n0 - b0, host)
            new_engine = self._make_engine(new_graph, rows=n0)
            with self._lock:
                d = self._delta
                if self._structure_gen != gen or d is None \
                        or d.base_id != b0 \
                        or self._engine_param_gen != pgen:
                    # a compaction / synchronous absorb / engine-baked
                    # set_parameter raced the build; its result
                    # supersedes ours (publishing would silently revert
                    # the operator's change — review fix)
                    metrics.inc("mutation.swap_stale_discards")
                    return
                # install the WHOLE linked graph, not just the tail
                # rows: _linked_graph also re-pruned prefix rows with
                # reverse edges INTO the absorbed tail, and dropping
                # those left the host graph unable to reach the new
                # rows after the next engine rebuild (review fix).  The
                # prefix is stable under us: any writer that could have
                # changed rows [0, b0) also bumped _structure_gen or
                # replaced the delta, both caught above.
                self._graph.graph = new_graph
                # fold tombstones that landed during the build, then
                # publish: one attribute write, readers pin by reference
                new_engine.set_deleted(self._deleted[:n0])
                self._engine = new_engine
                self._dense = None
                self._dirty = False
                self._tombstones_dirty = False
                self._snapshot_epoch += 1
                self._swap_count += 1
                tail = (self._host[n0:self._n].copy()
                        if self._n > n0 else None)
                self._delta = d.rebased(n0, tail)
                metrics.set_gauge(
                    "mutation.delta_rows",
                    self._delta.count if self._delta is not None else 0)
                self._adds_since_rebuild += n0 - b0
                if self._adds_since_rebuild >= \
                        self.params.add_count_for_rebuild:
                    self._adds_since_rebuild = 0
                    self._schedule_rebuild()
                old_sched = self._scheduler
                self._scheduler = None
            if old_sched is not None:
                old_sched.retire()    # non-blocking; residents finish
            t1 = time.monotonic()
            with self._lock:      # GL802: the append is a read-modify-
                # write racing a concurrent swap/reset; the tuple copy
                # is tiny, so the lock hold is trivial
                self._swap_windows = tuple(self._swap_windows[-15:]) + (
                    (t0 * 1000.0, t1 * 1000.0),)
            metrics.inc("mutation.swaps")
            metrics.observe("mutation.swap_s", t1 - t0)
            if flightrec.enabled():
                flightrec.record("index", "swap_publish",
                                 dur_ns=int((t1 - t0) * 1e9),
                                 payload={"rows": n0 - b0,
                                          "epoch": self._snapshot_epoch})
            self.publish_quality_health(background=True)
        except BaseException:
            # a failed refine must not wedge mutation: the delta keeps
            # serving, the next trigger retries
            metrics.inc("mutation.refine_errors")
            log.exception("background delta refine failed")
        finally:
            with self._lock:
                self._refine_in_flight = False
            self._maybe_auto_refine()

    # ---- refine (compaction) ----------------------------------------------

    def _refine_impl(self) -> None:
        """Parity: BKT::RefineIndex (BKTIndex.cpp:308-398): drop tombstoned
        rows, remap ids, rebuild the tree forest, re-run one graph refine
        pass over the compacted corpus."""
        self._structure_gen += 1     # invalidate in-flight background rebuild
        keep = np.flatnonzero(~self._deleted[:self._n])
        remap = np.full(self._n, -1, np.int64)
        remap[keep] = np.arange(len(keep))

        self._host = np.ascontiguousarray(self._host[keep])
        old_graph = self._graph.graph
        g = old_graph[keep]
        g = np.where(g >= 0, remap[np.maximum(g, 0)], -1).astype(np.int32)
        # compact each row's surviving neighbors to the front
        order = np.argsort(g < 0, axis=1, kind="stable")
        g = np.take_along_axis(g, order, axis=1)
        self._graph.graph = g

        self._n = len(keep)
        self._deleted = np.zeros(self._n, bool)
        self._num_deleted = 0
        if self.metadata is not None:
            self.metadata = self.metadata.refine(keep.tolist())
        if self._meta_to_vec is not None:
            self.build_meta_mapping()

        self._tree = self._new_tree()
        self._tree.build(self._host[:self._n])
        if getattr(self.params, "build_graph", 1):
            try:
                self._graph.refine_once(
                    self._host[:self._n],
                    # compaction refine IS the final pass of its rebuild:
                    # the FinalRefineSearchMode guardrail applies
                    self._refine_search_factory(self._graph.graph,
                                                final=True),
                    self._graph.neighborhood_size,
                    int(self.dist_calc_method), self.base)
            finally:
                # free the refine-time device snapshot (as _build's clear)
                self._refine_dense_cache = None
            self._graph.repair_connectivity()
        self._adds_since_rebuild = 0
        self._dirty = True

    # ---- persistence ------------------------------------------------------

    def _blob_writers(self):
        """Blob order parity: vectors, tree, graph, deletes
        (SaveIndexDataFromMemory, reference BKTIndex.cpp:64-77)."""
        p = self.params
        return [
            (p.vector_file,
             lambda f: fmt.write_matrix(f, self._host[:self._n])),
            (p.tree_file, lambda f: self._tree.save(f)),
            (p.graph_file, lambda f: fmt.write_graph(f, self._graph.graph)),
            (p.delete_file,
             lambda f: fmt.write_deletes(f, self._deleted[:self._n])),
        ]

    def _load_vectors_stream(self, f) -> None:
        data = fmt.read_matrix(f, dtype_of(self.value_type))
        self._host = np.ascontiguousarray(data)
        self._n = data.shape[0]
        self._deleted = np.zeros(self._n, bool)
        self._num_deleted = 0
        self._adds_since_rebuild = 0
        self._structure_gen += 1     # invalidate in-flight background rebuild

    def _load_tree_stream(self, f) -> None:
        self._tree = self._load_tree(f)

    def _load_graph_stream(self, f) -> None:
        self._graph = self._new_graph()
        self._graph.graph = fmt.read_graph(f)
        self._graph.neighborhood_size = self._graph.graph.shape[1]
        self._dirty = True

    def _load_deletes_stream(self, f) -> None:
        mask = fmt.read_deletes(f)
        self._deleted[:len(mask)] = mask[:self._n]
        self._num_deleted = int(self._deleted.sum())

    def _blob_loaders(self):
        p = self.params
        return [
            (p.vector_file, self._load_vectors_stream, False),
            (p.tree_file, self._load_tree_stream, False),
            (p.graph_file, self._load_graph_stream, False),
            (p.delete_file, self._load_deletes_stream, True),
        ]

    def _save_index_data(self, folder: str) -> None:
        from sptag_tpu.io import atomic

        for name, writer in self._blob_writers():
            with atomic.checked_open(os.path.join(folder, name),
                                     "wb") as f:
                writer(f)

    def _load_index_data(self, folder: str) -> None:
        for name, loader, optional in self._blob_loaders():
            path = os.path.join(folder, name)
            if not os.path.exists(path):
                if optional:
                    continue
                raise FileNotFoundError(path)
            with open(path, "rb") as f:
                loader(f)
