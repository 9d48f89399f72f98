"""Hand tool: recall and time of a BKT dense configuration by MaxCheck, on
ONE saved index a seed — how `bkt_deep10m_f32_l2_denseonly`'s MaxCheck
was found (PERF.md section 6, PR 48).

    chiprun -- python3 -m benchmark.tools.sweep_maxcheck <config> \
        <seed>[,...] [<MaxCheck>[,...]]

A seed's rows are drawn, built through the builder CLI's main() and
loaded back (`sptag_tpu.load_index`), as a served folder is; then for
each MaxCheck (default 2,048 ... 131,072 by powers of two) the
configuration's `check.queries` queries are searched in batches of the
largest warm bucket, as the server's executor would, and held against
the plain reference.  One JSON line a seed with the stages' seconds (rows
drawn, build, the program's `build.*` spans, load, the first search) and
one a MaxCheck: recall@10, `dist_err_ulps_rms`, wall ms a batch after the
first (which compiles), rows and blocks a query scored.
"""

import json
import os
import resource
import shutil
import sys
import time

import numpy as np

from benchmark.harness import compare, reference, serving
from benchmark.loadgen import load_by_name

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = [1 << p for p in range(11, 18)]


def main(argv) -> int:
    import sptag_tpu as sp
    from sptag_tpu.utils import metrics, trace

    with open(os.path.join(HERE, "configs", argv[0] + ".json")) as f:
        config = json.load(f)
    checks = ([int(c) for c in argv[2].split(",")] if len(argv) > 2
              else DEFAULT)
    nq, k = config["check"]["queries"], config["k"]
    batch = max(config["warm_buckets"])
    make = load_by_name("datasets", config["dataset"]).make
    for seed in (int(s) for s in argv[1].split(",")):
        workdir = os.path.join(HERE, ".work", "sweep")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        t0 = time.perf_counter()
        data, queries = make(seed, config["rows"], config["dim"], nq)
        t_drawn = time.perf_counter()
        folder = os.path.join(workdir, "index")
        build_s = serving.build_index(workdir, folder, data, config)
        t1 = time.perf_counter()
        index = sp.load_index(folder)
        t_loaded = time.perf_counter()
        index.search_batch(queries[:1], k)
        t_first = time.perf_counter()
        ref_ids, _ = reference.exact_topk(data, queries, k)
        spans = trace.report()
        print(json.dumps({
            "config": argv[0], "seed": seed, "rows": len(data),
            "draw_s": t_drawn - t0, "build_s": build_s,
            "spans": {n: spans[n]["total_s"] for n in sorted(spans)
                      if n.startswith("build.")},
            "load_s": t_loaded - t1, "first_search_s": t_first - t_loaded,
            "reference_s": time.perf_counter() - t_first,
            "gauges": {n: metrics.gauge_value(n) for n in (
                "dense.blocks", "dense.block_rows", "dense.pad_share")},
            "folder_bytes": sum(
                os.path.getsize(os.path.join(folder, n))
                for n in os.listdir(folder)),
            "host_peak_rss_bytes": 1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss}), flush=True)
        for max_check in checks:
            index.set_parameter("MaxCheck", str(max_check))
            ids, dists, ms = [], [], []
            for lo in [0] + list(range(0, nq, batch)):
                t = time.perf_counter()
                d, i = index.search_batch(queries[lo:lo + batch], k)
                ms.append(1e3 * (time.perf_counter() - t))
                ids.append(i)
                dists.append(d)
            ids, dists = np.concatenate(ids[1:]), np.concatenate(dists[1:])
            err = compare.dist_err_ulps(data, queries, np.arange(nq), ids,
                                        dists)
            print(json.dumps({
                "seed": seed, "MaxCheck": max_check,
                "recall_at_10": reference.recall_at_k(ids, ref_ids, k),
                "dist_err_ulps_rms": float(np.sqrt(np.mean(err ** 2))),
                "invalid_lists": compare.invalid_lists(ids, len(data)),
                "first_batch_ms": ms[0],
                "batch_ms": float(np.median(ms[1:])),
                "rows_per_query": metrics.gauge_value(
                    "dense.rows_per_query")}), flush=True)
        del index, data
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
