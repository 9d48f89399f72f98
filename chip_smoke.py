#!/usr/bin/env python3
"""chip_smoke.py — the served paths no benchmark cell covers yet, once, on
the real chip.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --chips 4     # the sharded BKT path only, four chips
    python chip_smoke.py --rehearse    # tiny, any backend, never "ok": true

The repo is measured by `python3 -m benchmark.run` (BENCHMARK.json); its
cells check FLAT over the socket, the BKT dense scan and the sharded FLAT
mesh more strictly than this script did, so those phases are gone.  What
is left has no cell yet (ROADMAP B1 / B3 / B2): the BKT beam walk (200k x
128 f32 L2), BKT int8 cosine (d=384) and the sharded BKT mesh, each
through build -> save -> load -> SearchServer over a socket -> AnnClient
and checked against an exact numpy scan that imports nothing of
sptag_tpu.  A phase goes when its cell lands.  Serving, the f32 data and
the L2 reference are the benchmark's own (benchmark/harness/,
benchmark/datasets/); only what the harness lacks is written here.  Data
comes from --seed and nothing on disk.

ONE process owns the chip from start to end: the script builds, serves
(server on its own thread + event loop) and queries (clients on other
threads; serve/client.py imports no JAX) in this process and starts no
child.  Any failed check raises; nothing on this path turns a failure
into a printed field.  Output: one JSON object per phase, then — last
line — {"ok": ..., "device": {"platform", "kind", "count"}}; exit 0 only
with "ok": true, which needs a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

# numpy only: the harness imports the program inside its functions
from benchmark.datasets import clustered_f32, clustered_int8
from benchmark.harness import reference, reference_int8_cosine, serving
from benchmark.harness.serving import require

K = 10
MAX_CHECK = 2048
#: the knobs the builders' chip runs of BKT were taken with (the same as
#: benchmark/configs/bkt_100k_f32_l2_dense.json's, less its SearchMode)
BKT_PARAMS = {"BKTNumber": "1", "BKTKmeansK": "32", "TPTNumber": "8",
              "TPTLeafSize": "1000", "NeighborhoodSize": "32",
              "CEF": "256", "MaxCheckForRefineGraph": "512",
              "RefineIterations": "2", "MaxCheck": str(MAX_CHECK),
              "RefineQueryGroup": "32", "FinalRefineSearchMode": "same"}
#: configurations in the shape benchmark/harness/serving.py takes
BKT_F32 = {"algo": "BKT", "value_type": "Float", "metric": "L2", "k": K,
           "index_params": BKT_PARAMS}
BKT_INT8 = {**BKT_F32, "value_type": "Int8", "metric": "Cosine"}
#: rows ISSUE 22 asks of both BKT phases; a smaller `REAL` value is a cut
#: and is printed as one
ASKED_BKT_N = 200_000
#: the sizes of a real run, and of a rehearsal (widths, metric and k are
#: never cut — only row counts)
#: int8_n is CUT to 100k: at 200k the whole script took 1084 s of its
#: 1200 s on the chip (int8 build alone 566 s; my chip run C, PR 22)
REAL = dict(bkt_n=200_000, int8_n=100_000, fresh=1000, selfq=256)
#: TINY's 256 BKT rows are one dense block: from 512 rows up the CPU build
#: with BKT_PARAMS takes a minute and more (tier-1 runs this rehearsal)
TINY = dict(bkt_n=256, int8_n=4_000, fresh=64, selfq=32)
RECALL_BAR = {"dense": 0.90, "beam": 0.80, "int8": 0.90}
ALL_PHASES = "bkt,int8"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# clients and counters (the harness's generator is a child process; here
# every client is a thread of the process that owns the chip)
# ---------------------------------------------------------------------------

def _ids_of(res, name: str) -> list:
    from sptag_tpu.serve import wire

    require(res.status == wire.ResultStatus.Success,
            f"request on {name!r} came back with status {res.status}")
    require(len(res.results) == 1 and len(res.results[0].ids) == K,
            f"request on {name!r} returned a malformed result")
    return list(res.results[0].ids)


def ask_burst(addr, name: str, queries: np.ndarray) -> np.ndarray:
    """Every query in flight at once (pipelined connections), so the
    server's batcher forms real batches."""
    from sptag_tpu.serve.client import AnnClientPool

    texts = [serving.query_text(name, K, q) for q in queries]
    with AnnClientPool(addr[0], addr[1], connections=4, timeout_s=900.0,
                       max_workers=min(len(texts), 256)) as pool:
        futs = [pool.search_async(t) for t in texts]
        return np.asarray([_ids_of(f.result(), name) for f in futs],
                          np.int64)


def build_index(workdir: str, name: str, data: np.ndarray, config: dict,
                compile_log) -> tuple:
    """The harness's build (BIN file -> the builder CLI's main() -> saved
    folder).  Returns (folder, build+save seconds), and prints a line of
    its own first, so that a later failed check cannot lose the build's
    numbers."""
    folder = os.path.join(workdir, f"{name}_index")
    seconds = serving.build_index(workdir, folder, data, config)
    emit({"phase": f"{name}.build", "n": len(data), "build_seconds": seconds,
          "compiles": compile_log.count,
          "compile_seconds": compile_log.total_s})
    return folder, seconds


class Counters:
    """XLA compiles and persistent-cache traffic, read per phase."""

    def __init__(self):
        import jax.monitoring

        self.events = {"cache_requests": 0, "cache_hits": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.events["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.events["cache_hits"] += 1

    @contextlib.contextmanager
    def phase(self, out: dict):
        """Fill `out` with the phase's wall seconds, XLA compile count and
        seconds (utils/recompile_guard.py) and cache hits."""
        from sptag_tpu.utils import recompile_guard

        before = dict(self.events)
        t0 = time.perf_counter()
        with recompile_guard.track_compiles(out["phase"]) as log:
            yield log
        out.update(
            seconds=time.perf_counter() - t0, compiles=log.count,
            compile_seconds=log.total_s,
            cache_requests=self.events["cache_requests"]
            - before["cache_requests"],
            cache_hits=self.events["cache_hits"] - before["cache_hits"])


def no_serve_errors(where: str) -> None:
    """The server answers a failed search with a status, and counts it;
    both must be clean."""
    bad = {n: v for n, v in serving.serve_error_counts().items() if v}
    require(not bad, f"{bad} after {where}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_peaks(kind: str) -> dict:
    """The three peaks the benchmark's rooflines divide by, from its own
    table (benchmark/harness/peaks.json); a kind it lacks is refused."""
    peaks = serving.peaks_for(kind)
    return {name: peaks[name] for name in (
        "bf16_flops_per_s", "int8_ops_per_s", "hbm_bytes_per_s")}


def phase_device(rehearse: bool, chips: int) -> dict:
    import jax

    if rehearse:                 # the harness's check refuses a CPU
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        require(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
        emit({"phase": "device", **device})
        return device
    device = serving.check_device(chips)
    emit({"phase": "device", **device,
          "peaks": device_peaks(device["kind"])})
    return device


def _dense_route(index) -> dict:
    """Which route the index's LAST dense search took, as the searcher
    itself recorded it (the route is decided before the call and a failed
    kernel raises — algo/dense.py)."""
    from sptag_tpu.ops import pallas_kernels

    dense = index._get_dense()
    return {"pallas_supported": bool(pallas_kernels.supported(
                dense.data_perm)),
            "pallas_ran": bool(dense.last_use_pallas),
            "blocks": list(dense.data_perm.shape)}


def _beam_miss_diagnosis(index, addr, data, missed) -> dict:
    """For corpus rows the beam walk did not find as themselves: are they
    hard to reach in the graph (in-degree against the corpus median), and
    does exact f32 in-loop scoring find them (BeamScoreDtype=f32 — on a
    TPU the walk scores a bf16 shadow of the corpus)?  Printed, not judged:
    the walk stops after NoBetterPropagationLimit iterations without
    improvement, so a row with few in-edges can stay unvisited under
    either scoring."""
    graph = np.asarray(index._graph.graph)
    indeg = np.bincount(graph[graph >= 0].ravel(), minlength=len(data))
    require(index.set_parameter("BeamScoreDtype", "f32"),
            "BeamScoreDtype=f32 was refused")
    again = ask_burst(addr, "bkt", data[missed])
    require(index.set_parameter("BeamScoreDtype", "auto"),
            "BeamScoreDtype=auto was refused")
    return {"beam_self_miss_in_degree_median": float(np.median(
                indeg[missed])),
            "corpus_in_degree_median": float(np.median(indeg)),
            "beam_self_miss_found_with_f32_scoring": int(
                (again[:, 0] == missed).sum())}


def check_self_queries(mode: str, asked: int, missed: int, bar: float
                       ) -> None:
    """A corpus row queried as itself comes back first — as often as the
    mode's recall bar says, not always.  Both modes are approximate: dense
    scores only the MaxCheck/256 blocks with the nearest mean centroids;
    the beam walk stops after a few iterations without improvement and
    never visits a row few edges lead to (chip runs of PR 22: dense 252
    of 256, beam 233 of 256)."""
    require(asked - missed >= bar * asked,
            f"{mode}: {missed} of {asked} corpus rows did not find "
            "themselves first")


def phase_bkt(workdir, seed, size, counters) -> None:
    """The beam walk (the dense scan of the same index is the cell
    `bkt_100k.saturate`)."""
    n = size["bkt_n"]
    out = {"phase": "bkt_200k", "n": n, "d": 128, "dtype": "float32",
           "metric": "L2", "k": K, "max_check": MAX_CHECK}
    if n != ASKED_BKT_N:
        out["cut"] = f"n {ASKED_BKT_N} -> {n}"
    with counters.phase(out) as compile_log:
        data, fresh = clustered_f32.make(seed + 1, n, 128, size["fresh"])
        folder, out["build_seconds"] = build_index(
            workdir, "bkt", data, BKT_F32, compile_log)
        self_rows = np.random.default_rng(seed).choice(
            n, size["selfq"], replace=False)
        ref_ids, _ = reference.exact_topk(data, fresh, K)
        with serving.served(workdir, "bkt", folder, BKT_F32) as (server,
                                                                 addr):
            index = server.context.indexes["bkt"]
            # the mode is a parameter of the loaded index: no rebuild
            require(index.set_parameter("SearchMode", "beam"),
                    "SearchMode=beam was refused")
            t0 = time.perf_counter()
            own = ask_burst(addr, "bkt", data[self_rows])
            missed = self_rows[own[:, 0] != self_rows]
            out["beam_self_first"] = len(self_rows) - len(missed)
            if len(missed):
                out.update(_beam_miss_diagnosis(index, addr, data, missed))
            check_self_queries("beam", len(self_rows), len(missed),
                               RECALL_BAR["beam"])
            got = ask_burst(addr, "bkt", fresh)
            out["beam_seconds"] = time.perf_counter() - t0
            out["beam_recall_at_10"] = reference.recall_at_k(got, ref_ids, K)
            require(out["beam_recall_at_10"] >= RECALL_BAR["beam"],
                    f"beam recall@10 {out['beam_recall_at_10']:.4f}"
                    f" < {RECALL_BAR['beam']}")
            no_serve_errors("bkt_200k beam")
        out["self_queries"], out["fresh_queries"] = len(self_rows), len(fresh)
    emit(out)


def phase_int8(workdir, seed, size, counters, need_pallas: bool) -> None:
    n = size["int8_n"]
    out = {"phase": "int8_200k", "n": n, "d": 384, "dtype": "int8",
           "metric": "Cosine", "k": K, "max_check": MAX_CHECK}
    if n != ASKED_BKT_N:
        out["cut"] = f"n {ASKED_BKT_N} -> {n}"
    with counters.phase(out) as compile_log:
        data, fresh = clustered_int8.make(seed + 2, n, 384, size["fresh"])
        folder, out["build_seconds"] = build_index(
            workdir, "int8", data, BKT_INT8, compile_log)
        ref_ids, _ = reference_int8_cosine.exact_topk_int8_cosine(
            data, fresh, K)
        with serving.served(workdir, "int8", folder, BKT_INT8) as (server,
                                                                   addr):
            t0 = time.perf_counter()
            got = ask_burst(addr, "int8", fresh)
            out["dense_seconds"] = time.perf_counter() - t0
            out.update(_dense_route(server.context.indexes["int8"]))
        no_serve_errors("int8_200k")
        require(out["pallas_ran"] or not need_pallas,
                "int8 dense search did not take the Pallas route")
        out["dense_recall_at_10"] = reference.recall_at_k(got, ref_ids, K)
        require(out["dense_recall_at_10"] >= RECALL_BAR["int8"],
                f"int8 recall@10 {out['dense_recall_at_10']:.4f} < "
                f"{RECALL_BAR['int8']}")
        out["fresh_queries"] = len(fresh)
    emit(out)


def _spread(name: str, array, n_dev: int) -> dict:
    """Every one of `n_dev` distinct devices holds an equal share of
    `array`'s leading axis."""
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    rows = {s.data.shape[0] for s in shards}
    require(len(shards) == n_dev and len(devices) == n_dev,
            f"{name}: {len(shards)} shards on {len(devices)} devices, "
            f"expected {n_dev} on {n_dev}")
    require(rows == {array.shape[0] // n_dev},
            f"{name}: shard rows {sorted(rows)}, expected "
            f"{array.shape[0] // n_dev} on every device")
    return {"devices": sorted(d.id for d in devices),
            "shape_per_device": list(shards[0].data.shape)}


def phase_four_chips(seed, size, counters) -> None:
    """The sharded BKT mesh and what it is compared with (the exact
    reference) — nothing else runs under --chips 4 (sharded FLAT is the
    cell `sharded_deep10m.saturate`)."""
    import jax

    from sptag_tpu.core.types import DistCalcMethod
    from sptag_tpu.parallel.sharded import ShardedBKTIndex, make_mesh

    mesh = make_mesh(jax.devices()[:4])
    n = size["bkt_n"]
    out = {"phase": "sharded_bkt_200k", "n": n, "d": 128, "k": K,
           "max_check": MAX_CHECK}
    with counters.phase(out):
        data, fresh = clustered_f32.make(seed + 1, n, 128, size["fresh"])
        t0 = time.perf_counter()
        index = ShardedBKTIndex.build(data, DistCalcMethod.L2, mesh=mesh,
                                      params=BKT_PARAMS, dense=True)
        out["build_seconds"] = time.perf_counter() - t0
        for name in ("data", "graph", "dense_perm"):
            out[name] = _spread(name, getattr(index, name), 4)
        self_rows = np.random.default_rng(seed).choice(
            n, size["selfq"], replace=False)
        ref_ids, _ = reference.exact_topk(data, fresh, K)
        for mode, search in (("beam", index.search),
                             ("dense", index.search_dense)):
            _, own = search(data[self_rows], K, max_check=MAX_CHECK)
            missed = int((own[:, 0] != self_rows).sum())
            out[f"{mode}_self_first"] = len(self_rows) - missed
            check_self_queries(f"sharded {mode}", len(self_rows), missed,
                               RECALL_BAR[mode])
            _, got = search(fresh, K, max_check=MAX_CHECK)
            out[f"{mode}_recall_at_10"] = reference.recall_at_k(got, ref_ids,
                                                                K)
            require(out[f"{mode}_recall_at_10"] >= RECALL_BAR[mode],
                    f"sharded {mode} recall@10 "
                    f"{out[f'{mode}_recall_at_10']:.4f} < {RECALL_BAR[mode]}")
    emit(out)


# ---------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the sharded BKT path only (needs four chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend, Pallas in interpret "
                    "mode off the TPU; always ends \"ok\": false")
    ap.add_argument("--phases", default=ALL_PHASES,
                    help="one-chip phases to run (a partial run never "
                    "ends \"ok\": true)")
    return ap.parse_args(argv)


def run(args) -> dict:
    """All phases; returns the device dict.  Raises on the first failure."""
    import jax

    from sptag_tpu.ops import pallas_kernels
    from sptag_tpu.utils import enable_compile_cache

    # the library turns the persistent compile cache on when the first
    # index is made; do it first so that the run can print where it is
    enable_compile_cache()
    size = TINY if args.rehearse else REAL
    device = phase_device(args.rehearse, args.chips)
    on_tpu = device["platform"] == "tpu"
    if args.rehearse and not on_tpu:
        pallas_kernels.set_interpret(True)
    counters = Counters()
    emit({"phase": "compile_cache",
          "dir": jax.config.jax_compilation_cache_dir,
          "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))})
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase_four_chips(args.seed, size, counters)
        else:
            phases = args.phases.split(",")
            if "bkt" in phases:
                phase_bkt(workdir, args.seed, size, counters)
            if "int8" in phases:
                phase_int8(workdir, args.seed, size, counters, on_tpu)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return device


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.perf_counter()
    device, error = None, None
    try:
        device = run(args)
    except Exception as e:                               # noqa: BLE001
        # the ONE handler, at the process boundary: report the traceback,
        # print the last line with "ok": false, exit non-zero
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    complete = args.chips == 4 or args.phases == ALL_PHASES
    ok = (error is None and not args.rehearse and complete
          and device is not None and device["platform"] == "tpu")
    last = {"ok": ok, "device": device,
            "seconds": time.perf_counter() - t0}
    if error is not None:
        last["error"] = error
    if args.rehearse:
        last["rehearsal"] = True
    emit(last)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
