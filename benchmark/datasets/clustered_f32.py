"""`clustered_f32`: Gaussian clusters from the seed, in bulk.

Copied from chip_smoke.py::make_clustered (PR 22).  256 clusters at every
real size (fewer only where a rehearsal's corpus would leave a cluster
smaller than 512 rows): SIFT-like structure rather than pure noise, so that
tree partitions and top-k have something to find.
"""

import numpy as np

MAX_CLUSTERS = 256


def make(seed: int, rows: int, dim: int, queries: int):
    """-> ((rows, dim) float32 corpus, (queries, dim) float32 fresh
    queries), both functions of `seed` alone."""
    rng = np.random.default_rng(seed)
    clusters = max(4, min(MAX_CLUSTERS, rows // 512))
    centers = rng.standard_normal((clusters, dim), dtype=np.float32) * 4.0

    def draw(n):
        x = rng.standard_normal((n, dim), dtype=np.float32)
        x += centers[rng.integers(0, clusters, n)]
        return x

    return draw(rows), draw(queries)
