"""`clustered_f32_dense`: `clustered_f32`'s rows, for a tree-partition
(dense-only BKT) index at its source's rows.

The rows and queries ARE `datasets/clustered_f32.py`'s (the same function
of `seed`).  What this adds is the refusal, at once and with an exit code
(HarnessError), of a program that cannot bring such an index up inside a
run, as `clustered_f32_live` refuses one that cannot take a mutation in
place: PR 48's parent, run in the cell at 10M x 96 on the one-chip machine
(builder's chip run, PR 48, call 1: PERF.md section 6), built the tree in
189.5 s (the root's final assignment as ONE (1, 2^24, 96) batch; the
host at 34.6 GB), saved, loaded, and was still inside the first warm-up
search when the trial's 480 s ended: `partition_from_tree`, after one
Python step for each of 10M nodes, joining each of ~34,000 center samples
above the cut to the smallest of ~600,000 subtrees by a `min` over all of
them (the traceback's line; it had run 265 s).  A run that cannot produce
a result ends here instead, before a row is drawn: above
`PYTHON_STEP_ROWS` the program has to say that it lays the partition out by distance
(`sptag_tpu.algo.dense.place_rows`, which came with the level-by-level cut
and the block-wise layout).
"""

from benchmark.harness.serving import require
from benchmark.loadgen import load_by_name

# up to here the node-by-node cut and the whole-corpus layout temporaries
# take seconds (2M x 96 on a CPU: 34.8 s): rehearsals and small
# deployments run on any program
PYTHON_STEP_ROWS = 2_000_000


def _partition_is_laid_out_in_bulk(rows: int) -> None:
    if rows <= PYTHON_STEP_ROWS:
        return
    from sptag_tpu.algo import dense

    require(hasattr(dense, "place_rows"),
            f"{rows} rows are a tree of as many nodes and ~{rows // 220} "
            f"blocks, and this program cuts the tree one Python step a "
            f"node, joins every center sample above the cut to the "
            f"smallest of all blocks by a scan of them all, and packs the "
            f"layout through whole-corpus temporaries (no "
            f"sptag_tpu.algo.dense.place_rows): it cannot bring a "
            f"dense-only index of this size up inside a run")


def make(seed: int, rows: int, dim: int, queries: int):
    """-> `clustered_f32.make(seed, rows, dim, queries)`."""
    _partition_is_laid_out_in_bulk(rows)
    return load_by_name("datasets", "clustered_f32").make(
        seed, rows, dim, queries)
