"""What the dense (tree-partition) scan has to do, from the index's own
counts — the numerator of `kernel.dense_scan_roofline`.

Per batch the algorithm reads the centroid table once and, for every
query, the rows of the blocks that query probes; it computes one dot
product per query with every centroid and every probed row.  Counted from
what the algorithm needs: the (Q, nprobe * P) score rows, the member-id
and norm gathers and the deleted-mask gather are the implementation's and
are not counted.  `rows_per_query` comes from the program's gauge
`dense.rows_per_query` (probed blocks x block rows), not from MaxCheck.
"""


def dense_scan_least_seconds(batches: float, queries_per_batch: float,
                             rows_per_query: float, centroids: float,
                             dim: int, itemsize: int, peaks: dict) -> dict:
    """-> {"seconds", "bound", "flop_seconds", "hbm_seconds"} for
    `batches` batches of `queries_per_batch` queries, each scoring
    `rows_per_query` corpus rows of `dim` x `itemsize` bytes and
    `centroids` float32 centroids."""
    dots = queries_per_batch * (rows_per_query + centroids)
    flop_s = batches * 2.0 * dim * dots / peaks["bf16_flops_per_s"]
    read = (queries_per_batch * rows_per_query * dim * itemsize
            + centroids * dim * 4)
    hbm_s = batches * read / peaks["hbm_bytes_per_s"]
    return {"seconds": max(flop_s, hbm_s),
            "bound": "flops" if flop_s > hbm_s else "hbm",
            "flop_seconds": flop_s, "hbm_seconds": hbm_s}
