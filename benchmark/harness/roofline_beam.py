"""What the beam walk has to do, from the program's own counts — the
numerator of `kernel.beam_walk_roofline`.

Per batch the algorithm reads, for every real query, each row it scores
once (`rows_scored_per_query`, the program's gauge
`beam.rows_scored_per_query`: pops x neighbours over the trips the query
was alive in, at the scoring itemsize — 2 bytes where the walk scores a
bfloat16 shadow) and its L pool rows at float32 for the exact re-rank;
and the pivot table once, at float32, for the seeding.  It computes one
dot product per query with every one of those rows.  Counted from what
the algorithm needs, so that a fused or re-laid-out walk is read against
the same work: the neighbour lists, the visited bitset's reads and
copies, the sorts, the gathered (Q, B x m, D) block's write and re-read,
and the pad rows a batch walks beside its real queries are the
implementation's and are not counted.
"""


def beam_walk_least_seconds(batches: float, queries_per_batch: float,
                            rows_scored_per_query: float, pool: float,
                            pivots: float, dim: int, score_itemsize: int,
                            peaks: dict) -> dict:
    """-> {"seconds", "bound", "flop_seconds", "hbm_seconds"} for
    `batches` batches of `queries_per_batch` real queries, each scoring
    `rows_scored_per_query` rows of `dim` x `score_itemsize` bytes and
    re-ranking `pool` float32 rows, after one read of `pivots` float32
    pivot rows a batch."""
    dots = queries_per_batch * (rows_scored_per_query + pool + pivots)
    flop_s = batches * 2.0 * dim * dots / peaks["bf16_flops_per_s"]
    read = (queries_per_batch * dim
            * (rows_scored_per_query * score_itemsize + pool * 4)
            + pivots * dim * 4)
    hbm_s = batches * read / peaks["hbm_bytes_per_s"]
    return {"seconds": max(flop_s, hbm_s),
            "bound": "flops" if flop_s > hbm_s else "hbm",
            "flop_seconds": flop_s, "hbm_seconds": hbm_s}
