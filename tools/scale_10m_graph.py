"""10M-row GRAPH-mode sharded build proof (VERDICT r3 item 9).

Config 3's earlier 10M evidence was dense-only (BuildGraph=0); this drives
the resumable sharded *graph* build path (BuildGraph=1) at 10M x d96 on
the 8-device virtual CPU mesh with stage checkpoints, then smoke-checks
beam recall on a query sample and appends a SCALE.md row.

Resumability is part of the proof: run with --kill-after S to SIGKILL the
build mid-flight; re-running serves every FINISHED shard's stages from
its retained checkpoint (the sharded build keeps per-shard checkpoints
until all shards succeed — parallel/sharded.py) and resumes the
interrupted shard at its first incomplete stage.  The driver for that
two-phase drive:

    python tools/scale_10m_graph.py --n 10000000 --kill-after 600
    python tools/scale_10m_graph.py --n 10000000        # resumes

Build knobs keep wall time bounded on CPU: dense-mode grouped refine for
EVERY pass (FinalRefineSearchMode=same — the walk-quality guardrail is a
reference-consumer concern, orthogonal to proving the build path at
scale), RefineIterations=1, small TPT fanout.
"""

import argparse
import json
import logging
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def corpus(n, d, seed=5):
    import numpy as np

    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((1024, d)).astype(np.float32) * 3.0
    out = np.empty((n, d), np.float32)
    step = 1_000_000
    for i in range(0, n, step):
        m = min(step, n - i)
        assign = rng.integers(0, 1024, m)
        out[i:i + m] = (centers[assign]
                        + rng.standard_normal((m, d)).astype(np.float32))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--d", type=int, default=96)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--kill-after", type=float, default=0,
                    help="SIGKILL this process after S seconds (resume "
                         "drive phase 1)")
    ap.add_argument("--ckpt", default=os.path.join(REPO, ".bench_cache",
                                                   "scale10m_ckpt"))
    args = ap.parse_args()

    # INFO: the per-pass sampled graph-accuracy lines (graph/rng.py
    # "RNG refine pass i/n width=w acc=a") are the build-quality log the
    # refined run exists to produce — without this they are dropped
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(message)s")

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
                    f"{args.devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["SPTAG_TPU_BUILD_CKPT"] = args.ckpt
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from sptag_tpu.core.types import DistCalcMethod
    from sptag_tpu.parallel.sharded import ShardedBKTIndex, make_mesh

    if args.kill_after > 0:
        # watchdog THREAD, not a SIGALRM handler: Python signal handlers
        # only run between bytecodes on the main thread, and a single
        # jitted refine call can sit in native XLA for many minutes — a
        # deferred kill would silently degenerate the two-phase resume
        # drive into one complete build.  A thread delivers SIGKILL (no
        # cleanup, exactly what the drive wants) on time regardless.
        import threading

        pid = os.getpid()

        def _kill():
            print(f"[scale10m] SIGKILL after {args.kill_after}s "
                  "(resume drive)", flush=True)
            os.kill(pid, signal.SIGKILL)
        t = threading.Timer(args.kill_after, _kill)
        t.daemon = True
        t.start()

    t0 = time.time()
    data = corpus(args.n, args.d)
    t_data = time.time() - t0
    print(f"[scale10m] corpus {args.n}x{args.d} in {t_data:.0f}s",
          flush=True)

    params = {
        "BKTNumber": 1, "BKTKmeansK": 32,
        # round-5 measured: at 10M the refined run with speed knobs
        # (TPT 4, CEF 64, refine budget 256) came out WORSE than
        # candidates-only (0.469 vs 0.589 @2048) — the starved refine
        # (nprobe=1 per search) replaces TPT candidate edges with
        # near-random results.  Candidate-graph quality (TPT count, CEF)
        # is the honest lever at this scale; both overridable.
        "TPTNumber": int(os.environ.get("SCALE10M_TPT", "4")),
        "TPTLeafSize": 1000, "NeighborhoodSize": 32,
        "CEF": int(os.environ.get("SCALE10M_CEF", "64")),
        # SCALE10M_REFINE=0 selects the candidates-only graph (TPT
        # all-pairs + RNG prune + connectivity repair, no re-search
        # passes) — the wall-time-bounded configuration for the 10M CPU
        # proof; 1 (default) adds one grouped dense refine pass (the
        # 500k kill/resume drive's quality point)
        "MaxCheckForRefineGraph": 256,
        "RefineIterations": int(os.environ.get("SCALE10M_REFINE", "1")),
        "MaxCheck": 2048, "RefineQueryGroup": 32,
        "RefineSearchMode": "dense", "FinalRefineSearchMode": "same",
        "BuildGraph": 1,
    }
    t1 = time.time()
    # SCALE10M_DENSE=1 (default) packs the per-shard MXU tree-partition
    # layout too, so the quality ladder below can measure BOTH modes:
    # beam (the reference-parity walk) and dense (the TPU flagship —
    # measured at 250k it responds to budget all the way up where the
    # walk's recall is seed-coverage-bound; round 5).
    # RSS caveat: the dense pack allocates a padded second corpus copy
    # AFTER the build's resume checkpoints retire (~4 GB host-side at
    # 10M x d96) — on a memory-tight box set SCALE10M_DENSE=0 or a
    # mid-pack OOM costs the whole unresumable build.
    want_dense = os.environ.get("SCALE10M_DENSE", "1") == "1"
    index = ShardedBKTIndex.build(data, DistCalcMethod.L2,
                                  mesh=make_mesh(), params=params,
                                  dense=want_dense)
    build_s = time.time() - t1
    print(f"[scale10m] sharded graph build {build_s:.0f}s", flush=True)

    # beam recall smoke on a sample vs exact truth over the full corpus
    rng = np.random.default_rng(99)
    qidx = rng.integers(0, args.n, 64)
    queries = data[qidx] + 0.05 * rng.standard_normal(
        (64, args.d)).astype(np.float32)
    t2 = time.time()
    _, ids = index.search(queries, 10)
    search_s = time.time() - t2
    # budget ladder: recall at fixed MaxCheck decays with corpus size
    # (2048 candidates is ~0.02% coverage at 10M) — measure the graph's
    # quality envelope, not one rung (VERDICT r4 item 4)
    ladder_ids = {}
    for mc in (8192, 16384, 32768):
        tl = time.time()
        _, ids_mc = index.search(queries, 10, max_check=mc)
        ladder_ids[mc] = (ids_mc, round(time.time() - tl, 2))
    dense_ladder_ids = {}
    if want_dense:
        for mc in (8192, 16384, 32768):
            tl = time.time()
            _, ids_mc = index.search_dense(queries, 10, max_check=mc)
            dense_ladder_ids[mc] = (ids_mc, round(time.time() - tl, 2))
    # exact truth in 1M-row blocks
    best_d = np.full((64, 10), np.inf, np.float64)
    best_i = np.full((64, 10), -1, np.int64)
    qn = (queries.astype(np.float64) ** 2).sum(1)[:, None]
    for i in range(0, args.n, 1_000_000):
        blk = data[i:i + 1_000_000].astype(np.float64)
        dmat = qn + (blk ** 2).sum(1)[None, :] - 2.0 * (
            queries.astype(np.float64) @ blk.T)
        cat_d = np.concatenate([best_d, dmat], axis=1)
        cat_i = np.concatenate(
            [best_i, np.arange(i, i + blk.shape[0])[None, :].repeat(
                64, axis=0)], axis=1)
        sel = np.argpartition(cat_d, 10, axis=1)[:, :10]
        best_d = np.take_along_axis(cat_d, sel, axis=1)
        best_i = np.take_along_axis(cat_i, sel, axis=1)
    def _recall(got):
        return float(np.mean([
            len(set(int(v) for v in got[q] if v >= 0)
                & set(int(v) for v in best_i[q])) / 10 for q in range(64)]))

    recall = _recall(ids)
    ladder = {str(mc): {"recall_at_10": round(_recall(v[0]), 4),
                        "search64_s": v[1]}
              for mc, v in ladder_ids.items()}
    dense_ladder = {str(mc): {"recall_at_10": round(_recall(v[0]), 4),
                              "search64_s": v[1]}
                    for mc, v in dense_ladder_ids.items()}
    result = {
        "n": args.n, "d": args.d, "devices": args.devices,
        "build_s": round(build_s, 1), "corpus_s": round(t_data, 1),
        "search64_s": round(search_s, 2), "recall_at_10": round(recall, 4),
        "ladder": ladder, "dense_ladder": dense_ladder,
        # the build's OWN signal (any shard resumed from checkpoints) —
        # a non-empty checkpoint dir alone can be stale foreign state
        "resumed": bool(getattr(index, "build_resumed", False)),
        "params": params,
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
