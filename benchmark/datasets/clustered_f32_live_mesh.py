"""`clustered_f32_live_mesh`: `clustered_f32`'s rows, for a corpus that is
laid over the chips of one host and changes while it is searched.

The rows and queries ARE `datasets/clustered_f32.py`'s (the same function
of `seed`).  What this adds is the refusal, at once and with an exit code
(HarnessError), of a program that cannot run such a cell at all: one
whose mesh FLAT index (`parallel/sharded.py ShardedFlatIndex`, what a
folder built with `Index.MeshShardAxis` is served as) takes no add.  On
such a program every `$admin:add` is answered with an error, the writer's
warm steps end at the first one and the run, after minutes of set-up,
could only come out `correct: false` by `mutations_failed`; it ends here
instead, before a row is drawn.
"""

from benchmark.harness.serving import require
from benchmark.loadgen import load_by_name


def _mesh_takes_adds() -> None:
    from sptag_tpu.parallel import sharded

    require(hasattr(sharded.ShardedFlatIndex, "add"),
            "this program's mesh FLAT index is built once (no "
            "sptag_tpu.parallel.sharded.ShardedFlatIndex.add): it cannot "
            "run a cell that adds to and deletes from a corpus laid over "
            "four chips")


def make(seed: int, rows: int, dim: int, queries: int):
    """-> `clustered_f32.make(seed, rows, dim, queries)`."""
    _mesh_takes_adds()
    return load_by_name("datasets", "clustered_f32").make(
        seed, rows, dim, queries)
