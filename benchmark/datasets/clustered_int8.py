"""`clustered_int8`: int8 cosine embeddings as they ship — the Gaussian
clusters of `clustered_f32`, every row scaled to unit length, x 127,
rounded — made in row blocks.

The corpus is a function of `seed` alone: block `i` of `BLOCK_ROWS` rows
is drawn from the generator keyed (seed, i), so the blocks can be drawn on
the cores the process may run on and the host never holds the rows as
float32 (13.6 GB at 8.84M x 384): 3.4 GB of int8 and one block a core.
"""

import os

import numpy as np

from benchmark.harness.reference_int8_cosine import spans
from benchmark.harness.serving import require

MAX_CLUSTERS = 256
BLOCK_ROWS = 8_192          # 12 MB of float32: larger blocks spend their time in page faults
_CENTERS, _CORPUS, _QUERIES = 0, 1, 2       # the generators' key spaces


def _ingest_fits_the_host(rows: int, dim: int) -> None:
    """A program that normalises a cosine corpus in ONE float64 pass
    (a copy, its square and the scaled rows: 24 bytes an element, 81 GB at
    8.84M x 384) is ended by the host's out-of-memory killer half-way
    through the build.  A run that cannot produce a result ends here
    instead, at once and with an exit code (HarnessError): where that
    pass would take more than half the host's memory, the program has to
    say that its normalisation works in blocks."""
    host = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    if rows * dim * 24 <= host // 2:
        return
    from sptag_tpu.ops import distance

    require(hasattr(distance, "NORMALIZE_BLOCK_ELEMENTS"),
            f"{rows} x {dim} int8 cosine rows need {rows * dim * 24 / 1e9:.0f}"
            f" GB of float64 for an ingest normalisation in one pass "
            f"(host: {host / 1e9:.0f} GB) and this program has no blocked "
            f"one (sptag_tpu.ops.distance.NORMALIZE_BLOCK_ELEMENTS)")


def make(seed: int, rows: int, dim: int, queries: int):
    """-> ((rows, dim) int8 corpus, (queries, dim) int8 fresh queries),
    both functions of `seed` alone."""
    _ingest_fits_the_host(rows, dim)
    clusters = max(4, min(MAX_CLUSTERS, rows // 512))
    centers = np.random.default_rng([_CENTERS, 0, seed]).standard_normal(
        (clusters, dim), dtype=np.float32) * 4.0

    def draw(space: int, block: int, x: np.ndarray, mean: np.ndarray):
        """Block `block` of key space `space`, drawn into the scratch `x`
        (n, dim) float32 and quantised there: nothing block-sized is
        allocated per block."""
        rng = np.random.default_rng([space, block, seed])
        rng.standard_normal(out=x, dtype=np.float32)
        np.take(centers, rng.integers(0, clusters, len(x)), axis=0, out=mean)
        x += mean
        np.multiply(x, x, out=mean)
        norm = np.sqrt(mean.sum(axis=1, keepdims=True))
        x /= np.maximum(norm, 1e-9)
        x *= 127.0
        np.rint(x, out=x)
        return np.clip(x, -128, 127, out=x)

    corpus = np.empty((rows, dim), np.int8)

    def fill(lo: int, hi: int) -> None:
        x = np.empty((BLOCK_ROWS, dim), np.float32)
        mean = np.empty_like(x)
        for block in range(lo, hi):
            at = block * BLOCK_ROWS
            n = min(BLOCK_ROWS, rows - at)
            np.copyto(corpus[at:at + n], draw(_CORPUS, block, x[:n],
                                              mean[:n]), casting="unsafe")

    spans(-(-rows // BLOCK_ROWS), fill)
    x = np.empty((queries, dim), np.float32)
    return corpus, draw(_QUERIES, 0, x, np.empty_like(x)).astype(np.int8)
