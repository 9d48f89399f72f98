"""What an exact scan of one-byte rows has to do, from its shapes — the
numerator of `kernel.int8_scan_roofline`.

Counted from what the algorithm needs, not from what an implementation
materialises (the (Q, N) score matrix is the implementation's and is not
counted: benchmark/harness/roofline.py's rule).  The peaks come from
peaks.json: the int8 peak of the MXU, not the bf16 one.
"""


def int8_scan_least_seconds(runs: float, queries_per_run: float, rows: int,
                            dim: int, peaks: dict) -> dict:
    """`runs` program runs, each an exact scan of `rows` x `dim` one-byte
    values for `queries_per_run` queries: every query's dot products with
    every row (2 * rows * dim integer operations each) and ONE read of the
    rows a run.  -> {"seconds", "bound", "op_seconds", "hbm_seconds"}."""
    op_s = (2.0 * rows * dim * queries_per_run * runs
            / peaks["int8_ops_per_s"])
    hbm_s = runs * rows * dim * 1 / peaks["hbm_bytes_per_s"]
    return {"seconds": max(op_s, hbm_s),
            "bound": "ops" if op_s > hbm_s else "hbm",
            "op_seconds": op_s, "hbm_seconds": hbm_s}
