"""BASELINE config 3 (Deep1B-10M shape) on ONE chip via dense-only build.

The config's reference topology is 8 servers behind an Aggregator
(/root/reference/AnnService/src/Aggregator/AggregatorService.cpp:206-279);
the TPU framework's mesh equivalent is validated on the virtual 8-device
CPU mesh (tests/test_sharded_bkt.py, reports/MESH_SCALING.md).  What no
round has shown yet is the SCALE on real silicon.  This run puts the full
10M x d96 f32 corpus on a single v5e chip (3.84 GB of vectors in HBM —
the 8-shard system's aggregate, one chip's budget) using BuildGraph=0:
the k-means forest + partition layout build in minutes, and the MXU
partition scan serves the whole corpus with no graph in memory.

A second, smaller config measures the LAION-shape slice (config 5 is
400M x d768 over 16 shards = 25M rows/shard — beyond one chip's HBM at
f32; the measured 1M x d768 slice gives the per-chip d=768 cost model).

Usage: python tools/deep1b_single_chip.py [--configs deep1b,laion]
Appends to reports/BASELINE_CONFIGS.md and prints one JSON line each.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CACHE = os.path.join(REPO, ".bench_cache")


def _truth_cached(tag, data, queries, k=10, metric="l2"):
    path = os.path.join(CACHE, f"truth_{tag}.npy")
    if os.path.exists(path):
        return np.load(path)
    t = np.zeros((len(queries), k), np.int64)
    if metric == "l2":
        dn = (data.astype(np.float32) ** 2).sum(1)
    step = 64
    for i in range(0, len(queries), step):
        q = queries[i:i + step].astype(np.float32)
        if metric == "l2":
            d = dn[None, :] - 2.0 * (q @ data.T)
        else:
            d = -(q @ data.T)
        idx = np.argpartition(d, k, axis=1)[:, :k]
        row = np.take_along_axis(d, idx, axis=1)
        order = np.argsort(row, axis=1)
        t[i:i + step] = np.take_along_axis(idx, order, axis=1)
    os.makedirs(CACHE, exist_ok=True)
    np.save(path, t)
    return t


def _measure(index, queries, truth, k, mcs, out, prefix):
    import bench

    for mc in mcs:
        index.set_parameter("MaxCheck", str(mc))
        index.search_batch(queries[:1024], k)
        index.search_batch(queries, k)
        t0 = time.perf_counter()
        reps = 2
        ids = None
        for _ in range(reps):
            _, got = index.search_batch(queries, k)
            ids = got if ids is None else ids
        qps = reps * len(queries) / (time.perf_counter() - t0)
        lat = []
        for _ in range(5):
            tb = time.perf_counter()
            index.search_batch(queries[:1024], k)
            lat.append(time.perf_counter() - tb)
        out[f"{prefix}mc{mc}"] = {
            "qps": round(qps, 1),
            "recall_at_10": round(bench.recall_at_k(ids, truth, k), 4),
            "p50_batch1024_ms": round(
                float(np.percentile(lat, 50)) * 1000, 2)}
        print(json.dumps({prefix + "mc": mc, **out[f"{prefix}mc{mc}"]}),
              flush=True)


def run_deep1b(small=False):
    import jax

    import sptag_tpu as sp

    n, d, nq, k = 10_000_000, 96, 4096, 10
    if small:                     # CPU smoke run of the exact code path
        n, nq = 200_000, 256
    rng = np.random.default_rng(23)
    centers = rng.standard_normal((4096, d)).astype(np.float32) * 3.0
    assign = rng.integers(0, 4096, n)
    data = centers[assign] + rng.standard_normal((n, d)).astype(np.float32)
    queries = (centers[rng.integers(0, 4096, nq)]
               + rng.standard_normal((nq, d)).astype(np.float32))
    del assign

    out = {"config": "Deep1B-10M-shape 10M x d96 f32 L2, dense-only, "
                     "single chip", "platform": jax.devices()[0].platform}
    t0 = time.time()
    truth = _truth_cached("deep1b_10m" if not small else "deep1b_smoke",
                          data, queries, k)
    out["truth_s"] = round(time.time() - t0, 1)

    t0 = time.time()
    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, val in [("BuildGraph", "0"), ("BKTNumber", "1"),
                      ("BKTKmeansK", "32"), ("BKTLeafSize", "384"),
                      ("DenseClusterSize", "512"), ("MaxCheck", "8192")]:
        idx.set_parameter(name, val)
    idx.build(data)
    out["build_s"] = round(time.time() - t0, 1)
    print(json.dumps({"built": out["build_s"]}), flush=True)

    _measure(idx, queries, truth, k, [4096, 8192, 16384], out, "")
    return out


def run_laion_slice(small=False):
    import jax

    import sptag_tpu as sp
    from bench import cosine_truth

    n, d, nq, k = 1_000_000, 768, 2048, 10
    if small:
        n, nq = 100_000, 256
    rng = np.random.default_rng(29)
    centers = rng.standard_normal((1024, d)).astype(np.float32)
    data = (centers[rng.integers(0, 1024, n)] * 2.0
            + rng.standard_normal((n, d)).astype(np.float32))
    queries = (centers[rng.integers(0, 1024, nq)] * 2.0
               + rng.standard_normal((nq, d)).astype(np.float32))

    out = {"config": "LAION-shape slice 1M x d768 f32 cosine, dense-only, "
                     "single chip (per-shard cost model for config 5)",
           "platform": jax.devices()[0].platform}
    t0 = time.time()
    tag = "laion_1m_d768" if not small else "laion_smoke"
    path = os.path.join(CACHE, f"truth_{tag}.npy")
    if os.path.exists(path):
        truth = np.load(path)
    else:
        truth = cosine_truth(data, queries, k)
        os.makedirs(CACHE, exist_ok=True)
        np.save(path, truth)
    out["truth_s"] = round(time.time() - t0, 1)

    t0 = time.time()
    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "Cosine")
    for name, val in [("BuildGraph", "0"), ("BKTNumber", "1"),
                      ("BKTKmeansK", "32"), ("BKTLeafSize", "384"),
                      ("DenseClusterSize", "512"), ("MaxCheck", "8192")]:
        idx.set_parameter(name, val)
    idx.build(data)
    out["build_s"] = round(time.time() - t0, 1)
    print(json.dumps({"built": out["build_s"]}), flush=True)

    _measure(idx, queries, truth, k, [4096, 8192], out, "")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="deep1b,laion")
    ap.add_argument("--small", action="store_true",
                    help="CPU smoke run of the exact code paths")
    args = ap.parse_args()
    if args.small:
        import jax

        jax.config.update("jax_platforms", "cpu")
    # resumable builds: the 10M-row tree stage is the long pole here — a
    # process death mid-build resumes instead of restarting (build_ckpt.py)
    os.environ.setdefault("SPTAG_TPU_BUILD_CKPT",
                          os.path.join(CACHE, "build_ckpt"))
    results = []
    for name in args.configs.split(","):
        fn = {"deep1b": run_deep1b, "laion": run_laion_slice}[name]
        try:
            r = fn(small=args.small)
        except Exception as e:                           # noqa: BLE001
            r = {"config": name, "error": repr(e)[:300]}
        results.append(r)
        print(json.dumps(r), flush=True)

    with open(os.path.join(REPO, "reports", "BASELINE_CONFIGS.md"),
              "a") as f:
        f.write(f"\n## Single-chip scale rows ({time.strftime('%Y-%m-%d')},"
                " dense-only build%s)\n\n"
                % (" — SMOKE SHAPES, not the real config" if args.small
                   else ""))
        for r in results:
            if "error" in r:
                f.write(f"* {r['config']}: ERROR {r['error']}\n")
                continue
            f.write(f"* **{r['config']}** ({r['platform']}): build "
                    f"{r['build_s']}s; ")
            f.write("; ".join(
                f"MaxCheck {key.lstrip('mc')}: {v['qps']} QPS @ "
                f"recall {v['recall_at_10']} (p50 {v['p50_batch1024_ms']}ms"
                f"/1024q)"
                for key, v in r.items() if key.startswith("mc")) + "\n")


if __name__ == "__main__":
    main()
