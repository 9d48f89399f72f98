"""What the mesh FLAT scan has to do, from its shapes — the numerator of
`kernel.sharded_scan_roofline`.

The chips work side by side, so the least time of a batch is one chip's:
ONE read of that chip's share of the corpus (`rows_per_shard` row slots
of `dim` x `itemsize` bytes, padding included: the program reads it) and
every query's dot products with that share.  Counted from what the
algorithm needs: the per-shard (Q, rows_per_shard) score matrix, the
candidates gathered over ICI and the re-rank are the implementation's
and are not counted (the merge has a metric of its own).
"""

from benchmark.harness import roofline


def sharded_scan_least_seconds(batches: float, queries_per_batch: float,
                               rows_per_shard: int, dim: int, itemsize: int,
                               peaks: dict) -> dict:
    """-> {"seconds", "bound", "flop_seconds", "hbm_seconds"} of ONE chip
    for `batches` batches of `queries_per_batch` queries over its
    `rows_per_shard` rows; `peaks` are one chip's."""
    return roofline.flat_scan_least_seconds(
        batches, queries_per_batch, rows_per_shard, dim, itemsize, peaks)
