"""One-off scale proof: 500k-row BKT build + search end-to-end on the CPU
backend (which executes the identical programs).  Not measured on the
chip on this code.

Run from the repo root: `python tools/_scale_proof.py`
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    if os.environ.get("SCALE_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import sptag_tpu as sp
    from sptag_tpu.utils import enable_compile_cache, trace

    enable_compile_cache()
    n, d, nq = int(os.environ.get("SCALE_N", "500000")), 128, 1024
    rng = np.random.default_rng(17)
    centers = rng.standard_normal((512, d)).astype(np.float32) * 4.0
    data = (centers[rng.integers(0, 512, n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    queries = (centers[rng.integers(0, 512, nq)]
               + rng.standard_normal((nq, d)).astype(np.float32))

    idx = sp.create_instance("BKT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    for name, value in [("TPTNumber", "8"), ("TPTLeafSize", "1000"),
                        ("NeighborhoodSize", "32"), ("CEF", "256"),
                        ("MaxCheckForRefineGraph", "512"),
                        ("RefineIterations", "2"), ("MaxCheck", "2048")]:
        idx.set_parameter(name, value)
    t0 = time.time()
    idx.build(data)
    build_s = time.time() - t0

    # exact truth in chunks (float64-free: f32 corpus, expanded form)
    dn = (data.astype(np.float64) ** 2).sum(1)
    truth = np.zeros((nq, 10), np.int64)
    for i in range(0, nq, 128):
        dd = dn[None, :] - 2.0 * (queries[i:i + 128].astype(np.float64)
                                  @ data.T.astype(np.float64))
        part = np.argpartition(dd, 10, axis=1)[:, :10]
        row = np.take_along_axis(dd, part, axis=1)
        truth[i:i + 128] = np.take_along_axis(part, np.argsort(row, axis=1),
                                              axis=1)

    idx.search_batch(queries[:64], 10)          # warm
    t0 = time.time()
    _, ids = idx.search_batch(queries, 10)
    dt = time.time() - t0
    rec = float(np.mean([len(set(ids[i]) & set(truth[i])) / 10
                         for i in range(nq)]))

    # persistence round trip at scale
    t0 = time.time()
    idx.save_index("/tmp/scale_idx")
    save_s = time.time() - t0
    t0 = time.time()
    idx2 = sp.load_index("/tmp/scale_idx")
    load_s = time.time() - t0
    _, ids2 = idx2.search_batch(queries[:64], 10)

    print(json.dumps({
        "n": n, "build_s": round(build_s, 1),
        "qps": round(nq / dt, 1), "recall_at_10": round(rec, 4),
        "save_s": round(save_s, 1), "load_s": round(load_s, 1),
        "loaded_matches": bool((ids2 == ids[:64]).all()),
        "trace": {k: round(v["total_s"], 1)
                  for k, v in trace.report().items()},
    }))


if __name__ == "__main__":
    main()
