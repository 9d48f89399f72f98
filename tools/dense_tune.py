"""On-chip dense-mode tuning sweep: grouped probing and batch depth.

The f32 headline has run UNGROUPED since round 2 (union_factor=2 lost
recall on the loose synthetic corpus: 0.824 vs 0.967).  Ungrouped, each
Pallas grid step contracts (1, D) x (D, P) — one MXU row busy.
`tools/grouped_f32_recall.py` measures (CPU, platform-independent)
whether union_factor=4 holds recall; THIS script measures the QPS half
on the chip, plus the other first-order lever: in-flight batch depth
(every synced host<->device round trip has a fixed cost, so QPS at fixed
device throughput rises with queries per call until device time
dominates; the size of that cost is not measured on this code).

Usage: python tools/dense_tune.py [n]
Appends measured rows to reports/GROUPED_F32.md and prints JSON lines.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    import bench
    from sptag_tpu.utils import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    k = 10
    data, queries = bench.make_dataset(n=n, nq=4096)
    truth = bench.l2_truth(data, queries, k)

    index, build_s, cached = bench.build_or_load(
        f"bkt_f32_n{n}", lambda: bench.build_headline_f32(n, data),
        budget_s=1e9)
    rows = []
    # (group, union_factor, nq_in_flight): grouped configs first at the
    # bench's 4096, then batch-depth on the best-known ungrouped config
    for group, uf, nq in [(0, 0, 4096), (16, 4, 4096), (32, 4, 4096),
                          (32, 6, 4096), (0, 0, 2048), (0, 0, 8192),
                          (0, 0, 16384)]:
        qs = queries if nq <= len(queries) else np.concatenate(
            [queries] * (nq // len(queries)))[:nq]
        tr = truth if nq <= len(truth) else np.concatenate(
            [truth] * (nq // len(truth)))[:nq]
        index.set_parameter("DenseQueryGroup", str(group))
        index.set_parameter("DenseUnionFactor", str(uf or 2))
        index.search_batch(qs[:1024], k)            # compile small shape
        index.search_batch(qs, k)                   # compile + warm full
        t0 = time.perf_counter()
        reps = 3
        ids = None
        for _ in range(reps):
            _, out = index.search_batch(qs, k)
            ids = out if ids is None else ids
        dt = time.perf_counter() - t0
        qps = reps * nq / dt
        rec = bench.recall_at_k(ids, tr, k)
        try:
            eff = index._get_dense().last_effective_group
        except Exception:                            # noqa: BLE001
            eff = None
        row = {"platform": platform, "group": group, "union_factor": uf,
               "nq": nq, "qps": round(qps, 1),
               "recall_at_10": round(rec, 4), "effective_group": eff}
        rows.append(row)
        print(json.dumps(row), flush=True)

    with open(os.path.join(REPO, "reports", "GROUPED_F32.md"), "a") as f:
        f.write(f"\n## On-chip sweep ({platform}, n={n}, "
                f"{time.strftime('%Y-%m-%d')})\n\n"
                "| group | union_factor | effective G | nq in flight | QPS |"
                " recall@10 |\n|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(f"| {r['group'] or 'off'} | {r['union_factor'] or '-'} "
                    f"| {r['effective_group']} | {r['nq']} | {r['qps']} | "
                    f"{r['recall_at_10']} |\n")


if __name__ == "__main__":
    main()
