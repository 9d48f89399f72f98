"""What a trip of the beam walk pays to fetch its candidates' vectors, by
layout, measured alone on the chip (PR 45; PERF.md section 6 keeps the
readings).

Two fetches of the same bytes at the beam cell's shapes (100k x 128 bf16,
32 neighbours a row, 128 queries x 64 pops a trip), each inside a
`fori_loop` of 32 trips with ids that change from trip to trip:

* `rows`:   `bf16[N, D]` by `Q*B*m` = 262,144 ids (the row-gather layout);
* `rows_hot0`: the same gather by the ids as the walk's row layout hands
  them over: sorted, three of four replaced by 0 (its placeholder for a
  candidate that is not fresh);
* `blocks`: `bf16[N, m, D]` by `Q*B` = 8,192 ids (`BeamPackedNeighbors`:
  a node's m neighbour vectors side by side, one 8 KB block a pop);
(PR 45 also read the blocks through a Pallas copy whose BlockSpecs follow
the prefetched ids, 16 blocks a step, in case XLA lowered the block gather
as a loop of small copies: it does not - 0.42 / 0.48 ms a trip against
XLA's 0.26 / 0.58 - and the copy was taken out again.)

Each is timed bare (the block reduced to a (Q,) sum so nothing is hoisted
or dropped) and scored as the walk scores it (bf16 -> f32, the `qd,qcd->qc`
contraction with the square sum).  `--walk 1` also times
`GraphSearchEngine.search` at MaxCheck 2048 over a random graph with the
table off and on: the whole trip, not the fetch alone.

One JSON line per reading; needs a TPU (exits 2 without one unless
`--tiny 1`, the CPU rehearsal at small shapes).

  chiprun -- python3 tools/probe_block_gather.py --walk 1
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TRIPS = 32


def _ids_for(trip, base_ids, n):
    """Ids that differ from trip to trip (a fetch by loop-invariant ids
    would be hoisted out of the loop)."""
    return (base_ids + trip * 7919) % n


def _loops(n, m, d, q, b):
    """The jitted 32-trip loops by name -> fn(rows, table, graph, sel0,
    queries) -> (Q,) float32."""
    import jax
    import jax.numpy as jnp

    def fetch_rows(rows, table, graph, sel):
        flat = graph[sel].reshape(q, b * m)
        return rows[flat]                                   # (Q, X, D)

    def fetch_rows_hot(rows, table, graph, sel):
        # as the walk's row layout asks: sorted ids, and row 0 in the
        # place of every candidate that is not fresh (here 3 of 4)
        flat = jnp.sort(graph[sel].reshape(q, b * m), axis=1)
        cold = (jnp.arange(b * m) % 4 == 0)[None, :]
        return rows[jnp.where(cold, flat, 0)]

    def fetch_blocks(rows, table, graph, sel):
        return table[sel].reshape(q, b * m, d)

    def bare(cvecs, queries):
        return jnp.sum(cvecs.astype(jnp.float32), axis=(1, 2))

    def scored(cvecs, queries):
        c = cvecs.astype(jnp.float32)
        dots = jnp.einsum("qd,qcd->qc", queries, c)
        return jnp.sum(jnp.sum(c * c, axis=-1) - 2.0 * dots, axis=1)

    def loop(fetch, use):
        def run(rows, table, graph, sel0, queries):
            def body(t, acc):
                sel = _ids_for(t, sel0, n)
                return acc + use(fetch(rows, table, graph, sel), queries)
            return jax.lax.fori_loop(0, TRIPS, body,
                                     jnp.zeros((q,), jnp.float32))
        return jax.jit(run)

    return {f"{fname}.{uname}": loop(fetch, use)
            for fname, fetch in (("rows", fetch_rows),
                                 ("rows_hot0", fetch_rows_hot),
                                 ("blocks", fetch_blocks))
            for uname, use in (("bare", bare), ("scored", scored))}


def _time(fn, args, repeats):
    import jax

    jax.block_until_ready(fn(*args))                        # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _walk(rows_np, graph_np, queries_np, repeats):
    """`GraphSearchEngine.search` at MaxCheck 2048, table off and on, ms a
    batch (host clock around the blocking search; the best of `repeats`)."""
    import numpy as np

    from sptag_tpu.algo.engine import GraphSearchEngine
    from sptag_tpu.core.types import DistCalcMethod

    pivots = np.arange(0, rows_np.shape[0], 24, dtype=np.int32)
    out = {}
    for name, packed in (("walk.rows", False), ("walk.blocks", True)):
        eng = GraphSearchEngine(rows_np, graph_np, pivots, None,
                                DistCalcMethod.L2, 1,
                                packed_neighbors=packed)
        d0, i0 = eng.search(queries_np, 10, max_check=2048)  # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            eng.search(queries_np, 10, max_check=2048)
            best = min(best, time.perf_counter() - t0)
        out[name] = (best, d0, i0)
        del eng
    same = bool(np.array_equal(out["walk.rows"][2], out["walk.blocks"][2])
                and np.array_equal(out["walk.rows"][1],
                                   out["walk.blocks"][1]))
    for name, (best, _, _) in out.items():
        print(json.dumps({"probe": name, "ms_a_batch": best * 1e3,
                          "answers_equal": same}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", type=int, default=0)
    ap.add_argument("--walk", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print("probe_block_gather: no TPU (use --tiny 1 to rehearse)",
              file=sys.stderr)
        return 2
    n, m, d, q, b = ((2048, 8, 128, 8, 16) if args.tiny
                     else (100_000, 32, 128, 128, 64))
    rng = np.random.default_rng(45)
    rows_np = rng.standard_normal((n, d)).astype(np.float32)
    graph_np = rng.integers(0, n, (n, m)).astype(np.int32)
    queries_np = rng.standard_normal((q, d)).astype(np.float32)
    rows = jnp.asarray(rows_np).astype(jnp.bfloat16)
    graph = jnp.asarray(graph_np)
    table = rows[graph]                                     # (N, m, D)
    sel0 = jnp.asarray(rng.integers(0, n, (q, b)).astype(np.int32))
    queries = jnp.asarray(queries_np)
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "bytes_limit": (dev.memory_stats() or {}
                                                 ).get("bytes_limit")},
                      "n": n, "m": m, "d": d, "q": q, "b": b,
                      "trips": TRIPS,
                      "table_bytes": int(table.nbytes),
                      "bytes_a_trip": q * b * m * d * 2}), flush=True)
    sums = {}
    for name, fn in _loops(n, m, d, q, b).items():
        try:
            secs = _time(fn, (rows, table, graph, sel0, queries),
                         args.repeats)
            sums[name] = np.asarray(fn(rows, table, graph, sel0, queries))
        except Exception as exc:                            # noqa: BLE001
            # a layout the compiler refuses is a reading too
            print(json.dumps({"probe": name, "error": repr(exc)[:400]}),
                  flush=True)
            continue
        print(json.dumps({"probe": name,
                          "ms_a_trip": secs * 1e3 / TRIPS,
                          "fetches_a_trip": (q * b * m
                                             if name.startswith("rows")
                                             else q * b),
                          "gb_s": q * b * m * d * 2 * TRIPS / secs / 1e9}),
              flush=True)
    for use in ("bare", "scored"):
        got = [v for k, v in sums.items()
               if k.endswith(use) and not k.startswith("rows_hot0")]
        ok = all(np.allclose(got[0], g, rtol=1e-3, atol=1e-2) for g in got)
        print(json.dumps({"check": use, "fetches_agree": bool(ok),
                          "compared": len(got)}), flush=True)
    if args.walk:
        _walk(rows_np, graph_np, queries_np, args.repeats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
