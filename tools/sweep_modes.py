"""MaxCheck sweep: beam vs dense recall/latency curves (VERDICT item 5).

Mirrors the reference IndexSearcher harness loop
(/root/reference/AnnService/src/IndexSearcher/main.cpp:131-190): one index,
a list of MaxCheck values, per-value recall@10 + latency percentiles — run
for BOTH search modes so the TPU-only dense mode's curve can be compared
against the reference-semantics beam walk's.

Writes a markdown table to reports/MAXCHECK_SWEEP.md and prints it.

Usage: python tools/sweep_modes.py [n] [out_path]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    out_path = sys.argv[2] if len(sys.argv) > 2 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "reports", "MAXCHECK_SWEEP.md")
    platform = os.environ.get("BENCH_PLATFORM")
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from sptag_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import sptag_tpu as sp
    from bench import (make_dataset, _bkt_params, l2_truth, build_or_load,
                       build_headline_f32,
                       recall_at_k)

    k = 10
    batch = 256
    # one generation serves both harnesses: the latency sweep uses the
    # first 512 queries, the throughput section the full 2048
    data, queries_t = make_dataset(n=n, nq=2048)
    queries = queries_t[:512]
    truth_t = l2_truth(data, queries_t, k)
    truth = truth_t[:512]

    # SWEEP_REFINE_BUDGET overrides MaxCheckForRefineGraph at build time
    # (own cache tag).  The bench's default 512 targets the <600 s cold
    # build; beam recall is capped by it (reports/MAXCHECK_SWEEP.md: 512
    # capped 100k beam at 0.855, 2048 reached 0.992) — a 2048-budget
    # index shows the walk's recall with a production-quality graph.
    refine = int(os.environ.get("SWEEP_REFINE_BUDGET", "0"))

    def build():
        # refine==0 writes the SHARED bkt_f32_n{n} tag — must be the
        # bench's own builder so the cache cannot drift (bench.py comment
        # above build_headline_f32); the refine override builds under its
        # own suffixed tag and layers the one extra param on top
        if not refine:
            return build_headline_f32(n, data)
        index = sp.create_instance("BKT", "Float")
        index.set_parameter("DistCalcMethod", "L2")
        _bkt_params(index, n)
        index.set_parameter("MaxCheckForRefineGraph", str(refine))
        index.build(data)
        return index

    tag = f"bkt_f32_n{n}" + (f"_refine{refine}" if refine else "")
    index, build_s, cached = build_or_load(tag, build, 1e9)
    dev = jax.devices()[0].platform

    lines = [
        (f"## Refine budget {refine} (graph quality run)" if refine
         else "# MaxCheck sweep — beam vs dense recall/latency"),
        "",
        f"Corpus: synthetic clustered SIFT-like, n={n}, d=128, L2; "
        f"{len(queries)} queries, recall@{k} vs exact ground truth; "
        f"platform={dev}; build_s={build_s:.1f} (cached={cached}).",
        "",
        "Harness parity: reference IndexSearcher MaxCheck sweep "
        "(src/IndexSearcher/main.cpp:131-190).",
        "",
        "| MaxCheck | mode | recall@10 | avg ms/query | p95 batch ms | "
        "p99 batch ms |",
        "|---|---|---|---|---|---|",
    ]
    for max_check in (512, 1024, 2048, 4096, 8192):
        index.set_parameter("MaxCheck", str(max_check))
        # "auto" (VERDICT r3 item 4): per-request crossover — the row must
        # never be worse than the WORSE of beam/dense at the same budget
        for mode in ("beam", "dense", "auto"):
            index.set_parameter("SearchMode", mode)
            index.search_batch(queries[:batch], k)      # compile/warm
            times = []
            ids_all = np.zeros((len(queries), k), np.int64)
            for i in range(0, len(queries), batch):
                t0 = time.perf_counter()
                _, ids = index.search_batch(queries[i:i + batch], k)
                times.append(time.perf_counter() - t0)
                ids_all[i:i + batch] = ids[:, :k]
            recall = recall_at_k(ids_all, truth, k)
            total = sum(times)
            lines.append(
                f"| {max_check} | {mode} | {recall:.4f} | "
                f"{total / len(queries) * 1000:.2f} | "
                f"{np.percentile(times, 95) * 1000:.1f} | "
                f"{np.percentile(times, 99) * 1000:.1f} |")
            print(lines[-1], flush=True)

    # Throughput at MaxCheck 2048 (VERDICT item 4's "beam >= 2,000 QPS at
    # recall >= 0.95" is a THROUGHPUT target): one large chunked batch —
    # `lax.map` folds the chunk loop into a single device program, so a
    # synced host round trip is paid twice per call instead of once per
    # 256-query batch.  The small-batch loop above remains the
    # latency harness (reference IndexSearcher reports per-query latency).
    nq_t = len(queries_t)
    index.set_parameter("MaxCheck", "2048")
    lines += ["", "### Throughput (2048-query chunked batch, MaxCheck=2048)",
              "", "| mode | recall@10 | QPS |", "|---|---|---|"]
    for mode in ("beam", "dense"):
        index.set_parameter("SearchMode", mode)
        index.search_batch(queries_t, k)            # compile + warm
        best = float("inf")
        ids = None
        for _ in range(3):
            t0 = time.perf_counter()
            _, ids = index.search_batch(queries_t, k)
            best = min(best, time.perf_counter() - t0)
        recall = recall_at_k(ids[:, :k], truth_t, k)
        lines.append(f"| {mode} | {recall:.4f} | {nq_t / best:,.0f} |")
        print(lines[-1], flush=True)

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "a" if refine else "w") as f:
        f.write(("\n" if refine else "") + "\n".join(lines) + "\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
