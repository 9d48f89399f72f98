"""What the exact scan of a corpus that takes adds in place has to do,
from its shapes — the numerator of `kernel.live_scan_roofline`.

Counted from what the algorithm needs: ONE read a program run of the
OCCUPIED rows (`rows` x `dim` x `itemsize`; the slots a block reserves
ahead of its rows, the tombstoned rows' share and the (Q, slots) score
matrix are the implementation's and are not counted), or every query's
dot products with every occupied row at the bf16 peak
(benchmark/harness/roofline.py's rule and peak).  A search batch and a
delete's search-by-content are runs of the same program and both count.
"""


def live_scan_least_seconds(runs: float, queries_per_run: float, rows: int,
                            dim: int, itemsize: int, peaks: dict) -> dict:
    """`runs` program runs, each an exact scan of `rows` occupied rows of
    `dim` values of `itemsize` bytes for `queries_per_run` queries.
    -> {"seconds", "bound", "flop_seconds", "hbm_seconds"}."""
    flop_s = (2.0 * rows * dim * queries_per_run * runs
              / peaks["bf16_flops_per_s"])
    hbm_s = runs * rows * dim * itemsize / peaks["hbm_bytes_per_s"]
    return {"seconds": max(flop_s, hbm_s),
            "bound": "flops" if flop_s > hbm_s else "hbm",
            "flop_seconds": flop_s, "hbm_seconds": hbm_s}
