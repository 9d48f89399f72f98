"""Typed parameter registry with string get/set parity.

Parity: the reference's X-macro parameter system — `DefineBKTParameter(var,
type, default, "Name")` (/root/reference/AnnService/inc/Core/BKT/
ParameterDefinitionList.h:7-38, KDT :7-36) expands into member init,
SetParameter/GetParameter string dispatch (src/Core/BKT/BKTIndex.cpp:537-573)
and config save/load (:18-27, :64-73).  Here the registry is a plain dict of
ParamSpec; each index class owns a Params instance.  `set_param`/`get_param`
accept the same case-insensitive RepresentStr names the wrappers use
(CoreInterface.h SetBuildParam/SetSearchParam).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from sptag_tpu.core.types import (
    DistCalcMethod,
    convert_string_to,
    convert_to_string,
)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    attr: str           # python attribute name
    py_type: type       # int / float / str / enum
    default: Any
    name: str           # RepresentStr (external, case-insensitive)


class ParamSet:
    """A bag of typed parameters addressable by external string name."""

    SPECS: List[ParamSpec] = []

    def __init__(self, **overrides):
        self._by_name: Dict[str, ParamSpec] = {
            s.name.lower(): s for s in self.SPECS
        }
        for spec in self.SPECS:
            setattr(self, spec.attr, spec.default)
        for attr, value in overrides.items():
            if not any(s.attr == attr for s in self.SPECS):
                raise AttributeError(f"unknown parameter attribute {attr!r}")
            setattr(self, attr, value)

    def set_param(self, name: str, value: str) -> bool:
        """String-typed set; returns False for unknown names (the reference
        returns ErrorCode::Fail, BKTIndex.cpp:546)."""
        spec = self._by_name.get(name.lower())
        if spec is None:
            return False
        setattr(self, spec.attr, convert_string_to(str(value), spec.py_type))
        return True

    def get_param(self, name: str) -> Optional[str]:
        spec = self._by_name.get(name.lower())
        if spec is None:
            return None
        return convert_to_string(getattr(self, spec.attr))

    def items(self):
        for spec in self.SPECS:
            yield spec.name, convert_to_string(getattr(self, spec.attr))

    def non_default_items(self):
        """(name, value) for every parameter whose current value differs
        from its registered default — the compact config view the serving
        /healthz endpoint publishes, so an operator can read what a live
        index was actually built/tuned with without diffing ini files."""
        for spec in self.SPECS:
            current = getattr(self, spec.attr)
            if current != spec.default:
                yield spec.name, convert_to_string(current)

    def save_config(self) -> str:
        """One `Name=Value` line per registered param, in registry order —
        same shape the reference writes into indexloader.ini [Index]
        (BKTIndex.cpp:64-73)."""
        return "".join(f"{k}={v}\n" for k, v in self.items())

    def load_config(self, section: Dict[str, str]) -> None:
        for key, value in section.items():
            self.set_param(key, value)


def _spec(attr, py_type, default, name):
    return ParamSpec(attr, py_type, default, name)


# Shared graph params appear in both BKT and KDT registries, matching the two
# reference ParameterDefinitionList.h files line for line.
_GRAPH_SPECS = [
    _spec("tpt_number", int, 32, "TPTNumber"),
    _spec("tpt_leaf_size", int, 2000, "TPTLeafSize"),
    _spec("neighborhood_size", int, 32, "NeighborhoodSize"),
    _spec("neighborhood_scale", int, 2, "GraphNeighborhoodScale"),
    _spec("cef_scale", int, 2, "GraphCEFScale"),
    _spec("refine_iterations", int, 2, "RefineIterations"),
    _spec("cef", int, 1000, "CEF"),
    _spec("add_cef", int, 500, "AddCEF"),
    _spec("max_check_for_refine_graph", int, 8192, "MaxCheckForRefineGraph"),
    # TPU-side addition (no reference counterpart): roll back a refine
    # pass that lowers sampled graph accuracy by > 0.02 — measured at 10M
    # (round 5): a budget-starved refine pass replaces
    # TPT candidate edges with near-random search results
    _spec("refine_accuracy_guard", int, 1, "RefineAccuracyGuard"),
    # catastrophic absolute floor for the guard's rollback: a pass must
    # BOTH drop the paired estimate by > 0.02 AND land below this to roll
    # back (graph/rng.py).  0.35 separates every observed healthy refine
    # (>= 0.5) from the budget-starved 10M failure mode (0.22-0.24);
    # datasets whose legitimate post-refine precision@m sits lower tune
    # this down instead of disabling the guard outright (ADVICE r5)
    _spec("refine_accuracy_floor", float, 0.35, "RefineAccuracyFloor"),
    # TPU-side addition: the shared seed-pivot pool scales as n/THIS
    # (capped 16,384) — seed coverage, not search budget, is the beam
    # walk's recall ceiling at scale (measured 250k: 0.45 -> 0.78 recall
    # from this alone, round 5).  0 disables the
    # auto-scale and restores the NumberOfInitialDynamicPivots*32 pool
    # for operators trading recall for seed-matmul cost.
    _spec("seed_pivot_auto_scale", int, 24, "SeedPivotAutoScale"),
]

_COMMON_TAIL_SPECS = [
    _spec("number_of_threads", int, 1, "NumberOfThreads"),
    _spec("dist_calc_method", DistCalcMethod, DistCalcMethod.Cosine,
          "DistCalcMethod"),
    _spec("delete_percentage_for_refine", float, 0.4,
          "DeletePercentageForRefine"),
    _spec("add_count_for_rebuild", int, 1000, "AddCountForRebuild"),
    _spec("max_check", int, 8192, "MaxCheck"),
    _spec("no_better_propagation_limit", int, 3,
          "ThresholdOfNumberOfContinuousNoBetterPropagation"),
    _spec("initial_dynamic_pivots", int, 50, "NumberOfInitialDynamicPivots"),
    _spec("other_dynamic_pivots", int, 4, "NumberOfOtherDynamicPivots"),
    # TPU-only: frontier entries expanded per beam-walk iteration (the
    # reference pops one node per loop step; the batched walk pops B at
    # once and runs ceil(MaxCheck/B) iterations).  Larger B = fewer,
    # fatter device steps (throughput) but coarser budget granularity
    _spec("beam_width", int, 16, "BeamWidth"),
    # TPU-only: dtype of the walk's in-loop candidate scoring.  "auto" =
    # bf16 shadow corpus on TPU (half the gather bytes, 2x MXU rate; the
    # final pool is re-ranked in exact f32), "f32" elsewhere.  Explicit
    # "bf16"/"f32" forces either.
    _spec("beam_score_dtype", str, "auto", "BeamScoreDtype"),
    # TPU-only: run the beam walk as fixed-size compiled SEGMENTS of this
    # many iterations with the loop-carried state checkpointed between
    # them (algo/engine.py), instead of one monolithic while-loop.
    # Results are bit-identical either way; segmenting is what lets the
    # slot scheduler retire converged queries early.  0 = monolithic for
    # direct searches; the scheduler then picks ~T/4 per pool itself.
    _spec("beam_segment_iters", int, 0, "BeamSegmentIters"),
    # TPU-only, opt-in: route beam searches through the continuous-
    # batching slot scheduler (algo/scheduler.py) — converged queries
    # retire between segments and freed slots refill from a pending
    # queue, so device time tracks the MEAN per-query iteration count
    # instead of the max (a MaxCheck straggler no longer convoys the
    # batch) and the serve tier streams per-query results as they finish
    _spec("continuous_batching", int, 0, "ContinuousBatching"),
    # TPU-only: slot capacity per scheduler pool (clamped to the engine's
    # visited-bitset chunk budget); quantized to the QUERY_BUCKETS ladder
    _spec("beam_slots", int, 1024, "BeamSlots"),
    # flight recorder (utils/flightrec.py, ISSUE 5).  The recorder is
    # PROCESS-wide; these index-level registrations are the offline-run
    # surface (index_builder / index_searcher / bench pass them through
    # like any Index.Param) and the INI-parity mirror of the [Service]
    # settings the serve tiers read.  FlightRecorder=1 enables the ring
    # when the index materializes its engine; FlightRecorderEvents sizes
    # it (0 = module default); FlightDumpOnSlowQuery names the ringed
    # auto-dump directory the serve tier writes on slow/error requests.
    _spec("flight_recorder", int, 0, "FlightRecorder"),
    _spec("flight_recorder_events", int, 0, "FlightRecorderEvents"),
    # fraction of engine segment dispatches timed to completion
    # (block_until_ready) for device-time attribution: events land in the
    # flight ring and the engine.segment_device_ns histogram, separating
    # device time from host overhead.  0 disables; 1 times every segment
    # (sampling is a deterministic 1-in-round(1/rate) counter, so traces
    # are reproducible).
    _spec("flight_device_sample_rate", float, 0.0, "FlightDeviceSampleRate"),
    _spec("flight_dump_on_slow_query", str, "", "FlightDumpOnSlowQuery"),
    # device-memory ledger (utils/devmem.py): 0 disables the resident-
    # bytes accounting behind memory.device_bytes / GET /debug/memory.
    # Process-wide, applied at set_parameter time; the ledger never
    # touches the request path, so serve bytes are identical either way
    _spec("device_bytes_ledger", int, 1, "DeviceBytesLedger"),
    # search-quality monitor (utils/qualmon.py, ISSUE 7).  Process-wide
    # like the flight-recorder knobs; live-applied via set_parameter on
    # every index family, and mirrored as [Service] ini settings on the
    # serve tiers.  QualitySampleRate: fraction of served queries
    # shadow-replayed through the exact scan for online recall (0 = off
    # — one flag test per query, serve bytes byte-identical);
    # QualityRecallFloor: a sampled recall below this triggers triage
    # (verdict in the slow-query stats + flight dump);
    # QualityShadowBudget: GFLOP/s ceiling on shadow-scan device work
    # (2 x rows x dim flops a replay; 0 = unbudgeted); QualityWindow: sliding-
    # window length in samples for the recall gauges (0 = default 256)
    _spec("quality_sample_rate", float, 0.0, "QualitySampleRate"),
    _spec("quality_recall_floor", float, 0.0, "QualityRecallFloor"),
    _spec("quality_shadow_budget", float, 0.0, "QualityShadowBudget"),
    _spec("quality_window", int, 0, "QualityWindow"),
    # serving timeline (utils/timeline.py, ISSUE 15).  Process-wide
    # like the flight-recorder knobs; live-applied via set_parameter on
    # every index family (offline runs: bench / index_builder /
    # index_searcher arm the sampler through them) and mirrored as
    # [Service] ini settings on both serve tiers.  TimelineIntervalMs>0
    # starts the sampler at that cadence (0 stops it — one flag test on
    # every other path); TimelineEvents sizes the per-series fine ring
    # (0 = module default 512).
    _spec("timeline_interval_ms", float, 0.0, "TimelineIntervalMs"),
    _spec("timeline_events", int, 0, "TimelineEvents"),
    # in-mesh sharded serving (parallel/sharded.py, ISSUE 11).  All off
    # by default — single-chip indexes ignore them; the mesh build/serve
    # paths read them off the shard params.  MeshServe=1 is the offline
    # mirror of the [Service] setting (bench / index_searcher arm the
    # mesh scheduler through it); MeshShardAxis sizes the shard axis to
    # the first N local devices at build when no explicit mesh is given
    # (0 = all devices); MeshKLocal caps each shard's contribution to
    # the ICI top-k merge (0 = exact min(k, n_local) — lowering it
    # trades all-gather traffic for merge completeness on wide meshes).
    _spec("mesh_serve", int, 0, "MeshServe"),
    _spec("mesh_shard_axis", int, 0, "MeshShardAxis"),
    _spec("mesh_k_local", int, 0, "MeshKLocal"),
    # bin-reduction top-k (ops/topk_bins.py, ISSUE 13 — the TPU-KNN
    # peak-FLOP/s recipe, arXiv:2206.14286).  "off" (default) keeps
    # every selection exact and serve bytes byte-identical; "on" forces
    # the binned beam-walk frontier merge + finalize and the binned
    # dense/flat final select; "auto" engages each site only when the
    # scored row is wide enough that the reduction beats the exact
    # top-k (at least 2x the bin count).  Engine-baked: a flip on a
    # warm index invalidates the snapshot, never patches a live program
    _spec("binned_topk", str, "off", "BinnedTopK"),
    # recall target of the approximate selections: sizes the bin count
    # of BinnedTopK's recall-target sites (dense/flat final select,
    # walk finalize) AND replaces the previously hard-coded 0.99 of the
    # FLAT ApproxTopK path.  (0, 1]; 1.0 = exact.  The beam MERGE's bin
    # count is structural (>= pool size), not recall-target-sized —
    # see DESIGN.md §19
    _spec("approx_recall_target", float, 0.99, "ApproxRecallTarget"),
    # tiered corpus cascade (ops/cascade.py, ISSUE 14; DESIGN.md §20).
    # CascadeSearch=1 arms the sketch -> int8 -> fp pipeline: the dense
    # engine serves int8-quantized blocks with a budgeted fp exact
    # re-rank, and the beam walk scores candidates against the int8
    # quantization (exact fp re-rank at finalize).  Off (default) keeps
    # every engine byte-identical to the pre-cascade programs.
    _spec("cascade_search", int, 0, "CascadeSearch"),
    # per-tier candidate budgets (static kernel-shape parameters,
    # validated and power-of-two quantized by cascade.resolve_budgets;
    # 0 = auto).  A budget covering the whole corpus composes that
    # tier's filtering out of the program entirely.
    _spec("tier_budget_sketch", int, 0, "TierBudgetSketch"),
    _spec("tier_budget_int8", int, 0, "TierBudgetInt8"),
    # fp-corpus residency: "device" keeps all tiers in HBM (speed play);
    # "host" keeps only sketches + int8 blocks in HBM with the fp corpus
    # in host RAM, fetched per-shortlist for the exact re-rank;
    # "host_all" additionally hosts the int8 blocks (FLAT only —
    # maximum vectors per HBM byte)
    _spec("corpus_tier", str, "device", "CorpusTier"),
] + [
    # live-mutation durability + delta-shard knobs (ISSUE 9).  All
    # default OFF: serve bytes and on-disk layout are unchanged until an
    # operator opts in.  WalEnabled=1 arms a checksummed write-ahead log
    # (io/wal.py) at the index's home folder — every acked add/delete
    # survives process death and is replayed by load_index; WalFsync=0
    # trades that durability for append throughput (still crash-
    # CONSISTENT: torn tails truncate, never corrupt).
    _spec("wal_enabled", int, 0, "WalEnabled"),
    _spec("wal_fsync", int, 1, "WalFsync"),
    # >0: adds land in a bounded FLAT/MXU-scanned side index merged into
    # every query (core/delta.py) instead of re-linking the graph / re-
    # materializing the engine snapshot inline — fresh rows are
    # searchable in O(ms).  The capacity bounds the shard's host+HBM
    # footprint AND its per-query scan cost.
    _spec("delta_shard_capacity", int, 0, "DeltaShardCapacity"),
    # >0: once the delta holds this many rows, a BACKGROUND refine links
    # them into the main structure and atomically swaps a new engine
    # snapshot in (algo/bkt.py, riding BeamSlotScheduler.retire() — zero
    # dropped queries, staleness bounded by the build time).  0 = absorb
    # only at overflow / save / explicit refine.
    _spec("auto_refine_threshold", int, 0, "AutoRefineThreshold"),
]

_FILE_SPECS = [
    _spec("tree_file", str, "tree.bin", "TreeFilePath"),
    _spec("graph_file", str, "graph.bin", "GraphFilePath"),
    _spec("vector_file", str, "vectors.bin", "VectorFilePath"),
    _spec("delete_file", str, "deletes.bin", "DeleteVectorFilePath"),
]


class BKTParams(ParamSet):
    """Parity: inc/Core/BKT/ParameterDefinitionList.h:7-38."""

    SPECS = (
        _FILE_SPECS
        + [
            _spec("tree_number", int, 1, "BKTNumber"),
            _spec("kmeans_k", int, 32, "BKTKmeansK"),
            _spec("leaf_size", int, 8, "BKTLeafSize"),
            _spec("samples", int, 1000, "Samples"),
            # TPU-only knobs (no reference counterpart): search strategy
            # ("dense" = MXU tree-partition scan, "beam" = batched graph
            # walk with reference walk semantics) and the dense partition's
            # target cluster size
            _spec("search_mode", str, "dense", "SearchMode"),
            # packed-neighbor layout for the beam walk: each node's m
            # neighbor VECTORS are materialized contiguously (in the
            # BeamScoreDtype shadow when active), so the in-loop fetch
            # is B block reads per query instead of B*m scattered rows —
            # block-granular DMA at m x the scoring corpus in HBM (0.82
            # GB for 100k x m32 x d128 bf16).  "auto" takes it where
            # that table is at most an eighth of a TPU's memory and half
            # of what is free (algo/engine.py packed_layout_fits; never
            # off a TPU); 1 insists; 0, the default every folder saved
            # before PR 45 carries, is read as auto (packed_param)
            _spec("beam_packed_neighbors", str, "auto",
                  "BeamPackedNeighbors"),
            # SearchMode=auto: per-request engine pick by budget — beam
            # below this MaxCheck threshold, dense at or above it (the
            # crossover measured on the 200k corpus in round 3 was
            # ~1024 — beam wins recall at small budgets, dense wins
            # QPS+recall at large ones; not measured on this code)
            _spec("auto_mode_threshold", int, 1024, "AutoModeThreshold"),
            _spec("dense_cluster_size", int, 256, "DenseClusterSize"),
            # 0 = dense-only build (framework extension): skip the RNG
            # graph entirely — the index serves the MXU partition scan
            # only, beam search raises.  Build cost drops to the k-means
            # forest + layout (the graph's TPT + refine passes are the
            # dominant build cost), which is what makes 10M-row
            # single-chip corpora buildable in minutes.  Pair with a
            # coarse BKTLeafSize (~DenseClusterSize/2): the partition cut
            # never descends below the cluster size, so deep leaves buy
            # nothing a shallow forest doesn't
            _spec("build_graph", int, 1, "BuildGraph"),
            # closure assignment: each row is also packed into its
            # (replicas-1) nearest other blocks — boundary-row recall at
            # ~replicas x block memory and the same per-query score count
            # (P doubles, nprobe halves).  Helps when neighbors concentrate
            # in few partitions (+2.7pt recall@10 at MaxCheck 1024 on a 30k
            # clustered corpus), hurts when they spread across many blocks
            # (fewer DISTINCT blocks probed) — hence opt-in; 1 disables
            _spec("dense_replicas", int, 1, "DenseReplicas"),
            # query-grouped probing: sort the batch by nearest centroid,
            # split into groups of this many queries (power of two; 0
            # disables), and probe each group's top-U block UNION
            # (U = DenseUnionFactor * nprobe) with real (G, D) x (D, P) MXU
            # contractions — (Q/G)*U grid steps instead of Q*nprobe
            # matvecs.  Each query keeps its top-1 block (G is clamped to
            # <= U) and is scored against the whole union; with tight
            # groups that covers MORE of its own probes than nprobe, with
            # loose groups fewer — the engine auto-shrinks G on sparse
            # batches and disables grouping below the dtype tile floor
            # (8 queries f32, 32 int8), so small/sparse batches silently
            # run the per-query kernel.  Opt-in (0 disables, like
            # DenseReplicas): grouping scores each query against the union
            # rather than exactly its own nprobe probes, so the strict
            # "MaxCheck = candidates scored per query" reference semantics
            # only hold with it off
            _spec("dense_query_group", int, 0, "DenseQueryGroup"),
            _spec("dense_union_factor", int, 2, "DenseUnionFactor"),
            # which engine runs the per-node refine searches during graph
            # build: "dense" (MXU cluster scan — build time is matmuls) or
            # "beam" (reference RefineGraph semantics, NeighborhoodGraph.h:
            # 113-143, far slower off-TPU)
            _spec("refine_search_mode", str, "dense", "RefineSearchMode"),
            # engine for the FINAL refine pass specifically (graph-quality
            # guardrail, VERDICT r3 item 10): dense-refined graphs score
            # 0.937-0.940 under the REFERENCE's walk vs 0.990-1.000 for
            # beam-refined (reports/AB_REFERENCE.md) — our own walk doesn't
            # care, but indexes saved for reference consumers silently got
            # the lower-navigability graph.  Default "beam" makes the last
            # pass (the one that defines the saved edges) walk-refined at
            # the cost of one beam pass; "same" restores the single-knob
            # behavior, "dense"/"beam" force an engine
            _spec("final_refine_search_mode", str, "beam",
                  "FinalRefineSearchMode"),
            # query-grouped probing for the REFINE searches specifically
            # (queries are corpus rows, maximally probe-local after the
            # partition sort — measured round 2: grouped refine at budget
            # 2048 lifted 100k beam recall 0.855 -> 0.992 at a fraction of
            # beam-refine's cost).  0 = ungrouped
            _spec("refine_query_group", int, 0, "RefineQueryGroup"),
            _spec("refine_union_factor", int, 4, "RefineUnionFactor"),
        ]
        + _GRAPH_SPECS[:2]
        + [_spec("tpt_top_dims", int, 5, "NumTopDimensionTpTreeSplit")]
        + _GRAPH_SPECS[2:]
        + _COMMON_TAIL_SPECS
    )


class KDTParams(ParamSet):
    """Parity: inc/Core/KDT/ParameterDefinitionList.h:7-36."""

    SPECS = (
        _FILE_SPECS
        + [
            _spec("tree_number", int, 1, "KDTNumber"),
            _spec("kdt_top_dims", int, 5, "NumTopDimensionKDTSplit"),
            _spec("samples", int, 100, "Samples"),
            # TPU-only dense-mode knobs (same semantics as the BKT specs
            # above; the partition comes from a kd-tree cut —
            # algo/dense.py::partition_from_kdtree).  SearchMode defaults
            # to "beam" for KDT: the kd-seeded walk IS the reference's
            # KDT search; the MXU dense scan is the opt-in fast path
            _spec("search_mode", str, "beam", "SearchMode"),
            # packed-neighbor walk layout; see the BKT spec of this name
            _spec("beam_packed_neighbors", str, "auto",
                  "BeamPackedNeighbors"),
            # SearchMode=auto crossover threshold; see the BKT spec
            _spec("auto_mode_threshold", int, 1024, "AutoModeThreshold"),
            _spec("dense_cluster_size", int, 256, "DenseClusterSize"),
            # 0 = dense-only build; see the BKT spec of the same name
            _spec("build_graph", int, 1, "BuildGraph"),
            _spec("dense_replicas", int, 1, "DenseReplicas"),
            _spec("dense_query_group", int, 0, "DenseQueryGroup"),
            _spec("dense_union_factor", int, 2, "DenseUnionFactor"),
            # builds refine ~15x faster through the dense engine at equal
            # quality (a CPU sweep; report removed in PR 29); "beam" restores the
            # reference's RefineGraph-by-walk semantics
            _spec("refine_search_mode", str, "dense", "RefineSearchMode"),
            # final-pass engine guardrail; see the BKT spec of the same name
            _spec("final_refine_search_mode", str, "beam",
                  "FinalRefineSearchMode"),
            # query-grouped probing for the REFINE searches specifically
            # (queries are corpus rows, maximally probe-local after the
            # partition sort — measured round 2: grouped refine at budget
            # 2048 lifted 100k beam recall 0.855 -> 0.992 at a fraction of
            # beam-refine's cost).  0 = ungrouped
            _spec("refine_query_group", int, 0, "RefineQueryGroup"),
            _spec("refine_union_factor", int, 4, "RefineUnionFactor"),
        ]
        + _GRAPH_SPECS[:2]
        + [_spec("tpt_top_dims", int, 5, "NumTopDimensionTPTSplit")]
        + _GRAPH_SPECS[2:]
        + _COMMON_TAIL_SPECS
    )


class FlatParams(ParamSet):
    """Params for the TPU-only exact FLAT index (no reference counterpart;
    kept registry-compatible so the wrapper SetBuildParam surface works)."""

    SPECS = [
        _spec("vector_file", str, "vectors.bin", "VectorFilePath"),
        _spec("delete_file", str, "deletes.bin", "DeleteVectorFilePath"),
        _spec("dist_calc_method", DistCalcMethod, DistCalcMethod.Cosine,
              "DistCalcMethod"),
        _spec("number_of_threads", int, 1, "NumberOfThreads"),
        _spec("delete_percentage_for_refine", float, 0.4,
              "DeletePercentageForRefine"),
        _spec("max_check", int, 8192, "MaxCheck"),
        _spec("batch_size", int, 256, "BatchSize"),
        # TPU-only, opt-in: hardware-accelerated approximate top-k
        # (lax.approx_max_k at ApproxRecallTarget per op — the
        # peak-FLOP/s KNN recipe, arXiv:2206.14286) instead of the exact
        # sort-based selection.  Trades the index's exactness guarantee
        # for selection speed at large N; distances of returned ids stay
        # exact
        _spec("approx_topk", bool, False, "ApproxTopK"),
        # bin-reduction top-k over the (Q, N) scan rows (ops/topk_bins
        # .py): off/on/auto, same semantics as the graph indexes' spec
        # of this name.  Works on every backend (approx_max_k is
        # TPU-accelerated only); composable with ApproxTopK — binned
        # wins where approx_max_k is unavailable or falls back to sort
        _spec("binned_topk", str, "off", "BinnedTopK"),
        # recall target shared by ApproxTopK (per-op recall_target,
        # previously hard-coded 0.99) and BinnedTopK's bin-count math;
        # (0, 1], 1.0 = exact.  Swept by bench's Pareto stage
        _spec("approx_recall_target", float, 0.99, "ApproxRecallTarget"),
        # TPU-only, opt-in: 1-bit sign-sketch pre-filter (XOR-friendly
        # binary quantization, arXiv:2008.02002 PAPERS.md).  The scan
        # reads packed (N, ceil(D/32)) int32 sketches — 1/32 of the f32
        # corpus bytes — Hamming-shortlists SketchRerank candidates via
        # XOR+popcount on the VPU, and exact-scores only those on the MXU.
        # Approximate like ApproxTopK; returned distances stay exact.
        _spec("sketch_prefilter", bool, False, "SketchPrefilter"),
        # shortlist size; 0 = auto, CALIBRATED per corpus snapshot: the
        # index samples rows as self-queries, measures the sketch rank
        # their exact top-10 land at, and uses the 95th percentile
        # (floored at max(128, 16k), capped at 8192).  Clustered corpora
        # calibrate small (~N/48); uniform or low-D data calibrates large
        # (sign sketches separate poorly there) — when the calibration
        # would exceed the 8192 cap, recall suffers and the remedy is an
        # explicit SketchRerank or disabling the prefilter
        _spec("sketch_rerank", int, 0, "SketchRerank"),
        # tiered corpus cascade (ops/cascade.py, ISSUE 14): the composed
        # sketch -> int8 -> fp device pipeline with per-tier budgets;
        # see _COMMON_TAIL_SPECS for the shared semantics.  On FLAT the
        # cascade replaces the whole scan (SketchPrefilter is the
        # sketch tier's standalone ancestor and is superseded when
        # CascadeSearch=1); CorpusTier=host/host_all moves the fp (and
        # int8) corpus to host RAM with zero full-corpus HBM residency
        _spec("cascade_search", int, 0, "CascadeSearch"),
        _spec("tier_budget_sketch", int, 0, "TierBudgetSketch"),
        _spec("tier_budget_int8", int, 0, "TierBudgetInt8"),
        _spec("corpus_tier", str, "device", "CorpusTier"),
        # memory/quality observability knobs; see _COMMON_TAIL_SPECS
        _spec("device_bytes_ledger", int, 1, "DeviceBytesLedger"),
        _spec("quality_sample_rate", float, 0.0, "QualitySampleRate"),
        _spec("quality_recall_floor", float, 0.0, "QualityRecallFloor"),
        _spec("quality_shadow_budget", float, 0.0, "QualityShadowBudget"),
        _spec("quality_window", int, 0, "QualityWindow"),
        # serving timeline; see _COMMON_TAIL_SPECS
        _spec("timeline_interval_ms", float, 0.0, "TimelineIntervalMs"),
        _spec("timeline_events", int, 0, "TimelineEvents"),
        # mutation durability + delta shard; see _COMMON_TAIL_SPECS
        _spec("wal_enabled", int, 0, "WalEnabled"),
        _spec("wal_fsync", int, 1, "WalFsync"),
        _spec("delta_shard_capacity", int, 0, "DeltaShardCapacity"),
        _spec("auto_refine_threshold", int, 0, "AutoRefineThreshold"),
    ]


# ---------------------------------------------------------------------------
# Live-actuation registry (ISSUE 17)
#
# `VectorIndex.set_parameter` will happily store any registered name at any
# value — that is the right contract for an operator at a REPL, but the
# online controller (serve/controller.py) changes knobs with nobody
# watching, so the set it may touch and the range it may use have to be
# declared somewhere AUDITABLE.  This registry is that declaration: every
# knob the control plane may live-apply, with hard bounds, whether the
# value must stay a power of two (budget-shaped kernels — a non-pow2
# MaxCheck would mint a fresh XLA compile per actuation, turning a latency
# page into a compile storm), and whether the knob lives on the index
# (applied through set_parameter) or on the serving tier (applied through
# an owner-provided setter, bounds still enforced here).  Actuating a name
# absent from the registry RAISES instead of silently no-opping: a silent
# no-op would leave the controller believing it relieved pressure while
# the index ignored it.


class UnknownActuationError(KeyError):
    """A live actuation targeted a knob that is not in the registry."""


@dataclasses.dataclass(frozen=True)
class ActuationSpec:
    name: str            # canonical RepresentStr
    lo: float            # inclusive lower bound
    hi: float            # inclusive upper bound
    pow2: bool = False   # quantize to a power of two (static kernel shapes)
    scope: str = "index"  # "index": via set_parameter; "tier": owner setter


LIVE_ACTUATIONS: Dict[str, ActuationSpec] = {
    s.name.lower(): s
    for s in [
        # candidate budget: the primary latency<->recall lever; pow2 so
        # every actuated value hits an existing compiled program shape
        ActuationSpec("MaxCheck", 64, 1 << 20, pow2=True),
        # cascade per-tier shortlists (0 = auto stays reachable: lo=0,
        # and pow2 quantization only applies above 1)
        ActuationSpec("TierBudgetSketch", 0, 1 << 20, pow2=True),
        ActuationSpec("TierBudgetInt8", 0, 1 << 20, pow2=True),
        # binned-TopK guarantee level — cheaper selection at lower target
        ActuationSpec("ApproxRecallTarget", 0.5, 1.0),
        # tier-scoped: admission's degraded-mode MaxCheck clamp
        ActuationSpec("DegradeMaxCheckFloor", 64, 1 << 20, pow2=True,
                      scope="tier"),
        # tier-scoped: aggregator hedge trigger percentile (lower =
        # hedge sooner = more duplicate work for a shorter tail)
        ActuationSpec("HedgePercentile", 50.0, 99.9, scope="tier"),
    ]
}


def actuation_spec(name: str) -> ActuationSpec:
    spec = LIVE_ACTUATIONS.get(name.lower())
    if spec is None:
        raise UnknownActuationError(name)
    return spec


def clamp_actuation(name: str, value) -> float:
    """Bound `value` to the registry range for `name`, quantizing to a
    power of two (rounding DOWN — never exceed the requested cost) for
    pow2 knobs.  Raises UnknownActuationError for unregistered names."""
    spec = actuation_spec(name)
    v = min(float(value), spec.hi)
    if spec.pow2 and v >= 1.0:
        v = float(1 << (int(v).bit_length() - 1))
    return max(v, spec.lo)


def actuate_index(index, name: str, value) -> float:
    """Live-apply a registered INDEX-scoped knob through the index's
    `set_parameter`, clamped per the registry; returns the value
    actually applied.  Raises UnknownActuationError for unregistered
    names, ValueError for tier-scoped ones, and RuntimeError when the
    index rejects a registered name — all three are control-plane bugs,
    not steady-state conditions, and must surface."""
    spec = actuation_spec(name)
    if spec.scope != "index":
        raise ValueError(
            "knob %s is tier-scoped; apply it through the owning tier's "
            "setter, not index.set_parameter" % spec.name)
    applied = clamp_actuation(name, value)
    out = int(applied) if float(applied).is_integer() else applied
    if not index.set_parameter(spec.name, str(out)):
        raise RuntimeError("index rejected registered live knob %s"
                           % spec.name)
    return float(out)
