"""What a kernel has to do, from its shapes — the roofline's numerator.

Counted from what the algorithm needs, not from what an implementation
materialises (the (Q, N) score matrix of the flat scan is the
implementation's and is not counted).  The peaks come from peaks.json.
"""


def flat_scan_least_seconds(batches: float, queries_per_batch: float,
                            rows: int, dim: int, itemsize: int,
                            peaks: dict) -> dict:
    """An exact scan of `rows` x `dim` for `batches` batches of
    `queries_per_batch` queries: every query's dot products with every row
    (2 * rows * dim operations each) and ONE read of the corpus per batch.
    -> {"seconds", "bound", "flop_seconds", "hbm_seconds"}."""
    flop_s = (2.0 * rows * dim * queries_per_batch * batches
              / peaks["bf16_flops_per_s"])
    hbm_s = batches * rows * dim * itemsize / peaks["hbm_bytes_per_s"]
    return {"seconds": max(flop_s, hbm_s),
            "bound": "flops" if flop_s > hbm_s else "hbm",
            "flop_seconds": flop_s, "hbm_seconds": hbm_s}
