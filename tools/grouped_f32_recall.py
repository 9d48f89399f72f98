"""Recall experiment: grouped probing on the f32 headline corpus.

Round-2 measured grouped probing at union_factor=2 losing recall on the
loose 256-center f32 corpus (0.824 vs 0.967 ungrouped) and the bench has
run the f32 headline UNGROUPED since.  Ungrouped, the dense kernel's MXU
contraction is a (1, D) x (D, P) matvec — one systolic row busy out of
128.  The grouped kernel runs (G, D) x (D, P) per union block: G rows
busy, (Q/G)*U grid steps instead of Q*nprobe.  Whether the f32 corpus can
KEEP recall under grouping is a pure ranking question — platform
independent — so this experiment answers it on the CPU backend while the
union_factor=4 hypothesis (each query sees U*P candidates >= 4x MaxCheck,
recovering what the shared-union cut loses) waits on the chip only for
the QPS half of the story.

Usage: python tools/grouped_f32_recall.py [n] [nq]
Prints one JSON line per (G, U) config; appends to reports/GROUPED_F32.md.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    nq = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    import jax

    if os.environ.get("BENCH_PLATFORM") == "cpu":
        # an explicit CPU run: pin the platform list
        jax.config.update("jax_platforms", "cpu")
    import bench
    from sptag_tpu.utils import enable_compile_cache

    enable_compile_cache()
    data, queries = bench.make_dataset(n=n, nq=nq)
    truth = bench.l2_truth(data, queries, 10)

    index, build_s, cached = bench.build_or_load(
        f"bkt_f32_n{n}", lambda: bench.build_headline_f32(n, data),
        budget_s=1e9)
    print(json.dumps({"n": n, "nq": nq, "build_s": round(build_s, 1),
                      "cached": cached}), flush=True)

    rows = []
    for group, uf in [(0, 0), (8, 4), (16, 4), (32, 4), (16, 6), (32, 6)]:
        index.set_parameter("DenseQueryGroup", str(group))
        index.set_parameter("DenseUnionFactor", str(uf or 2))
        t0 = time.perf_counter()
        _, ids = index.search_batch(queries, 10)
        dt = time.perf_counter() - t0
        rec = bench.recall_at_k(ids, truth, 10)
        eff = getattr(index, "last_group_effective", None)
        try:
            eff = index._get_dense().last_effective_group
        except Exception:                                # noqa: BLE001
            pass
        row = {"group": group, "union_factor": uf, "recall_at_10":
               round(rec, 4), "effective_group": eff,
               "cpu_wall_s": round(dt, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    path = os.path.join(REPO, "reports", "GROUPED_F32.md")
    newfile = not os.path.exists(path)
    with open(path, "a") as f:
        if newfile:
            f.write(
                "# Grouped probing on the f32 headline corpus\n\n"
                "Recall is platform-independent (measured CPU); QPS "
                "columns get filled by the on-chip sweep.  MaxCheck 2048, "
                "k=10, corpus `bench.make_dataset`.\n\n"
                "| n | group | union_factor | effective G | recall@10 |\n"
                "|---|---|---|---|---|\n")
        for r in rows:
            f.write(f"| {n} | {r['group'] or 'off'} | "
                    f"{r['union_factor'] or '-'} | "
                    f"{r['effective_group']} | {r['recall_at_10']} |\n")


if __name__ == "__main__":
    main()
