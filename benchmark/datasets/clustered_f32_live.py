"""`clustered_f32_live`: `clustered_f32`'s rows, for a corpus that changes
while it is searched.

The rows and queries ARE `datasets/clustered_f32.py`'s (the same function
of `seed`).  What this adds is the refusal of a program that cannot run
such a cell at a deployment's size, at once and with an exit code
(HarnessError), as `clustered_int8` refuses a program whose ingest cannot
fit the host: a FLAT index that re-places its whole device block at the
first search after every mutation, and compiles its scan programs anew
every 128 added rows, answers this traffic at 5M x 100 with 5 % of the
writer's steps done and searches of 4.8 s, and with the profiler on its
run did not end inside 900 s (builder's chip runs, PR 40: PERF.md section
6).  A run that cannot produce a result ends here instead: above
`WHOLE_BLOCK_BYTES` the program has to say that its block takes
mutations in place.
"""

from benchmark.harness.serving import require
from benchmark.loadgen import load_by_name

# a block this small is re-placed in a few milliseconds: rehearsals and
# small deployments run on any program
WHOLE_BLOCK_BYTES = 1 << 28


def _block_follows_mutations(rows: int, dim: int) -> None:
    if rows * dim * 4 <= WHOLE_BLOCK_BYTES:
        return
    from sptag_tpu.algo import flat

    require(hasattr(flat, "reserved_slots"),
            f"{rows} x {dim} float32 rows are a device block of "
            f"{rows * dim * 4 / 1e9:.1f} GB, and this program re-places the "
            f"whole block after every add and delete (no "
            f"sptag_tpu.algo.flat.reserved_slots: its FLAT block takes no "
            f"write in place): it cannot run a cell that mutates 8 times a "
            f"second")


def make(seed: int, rows: int, dim: int, queries: int):
    """-> `clustered_f32.make(seed, rows, dim, queries)`."""
    _block_follows_mutations(rows, dim)
    return load_by_name("datasets", "clustered_f32").make(
        seed, rows, dim, queries)
