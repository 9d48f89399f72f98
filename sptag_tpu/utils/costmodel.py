"""Per-kernel cost ledger — analytic FLOPs / HBM-bytes, XLA cross-checked.

Telemetry (utils/metrics.py) and the flight recorder (utils/flightrec.py)
answer *where time goes*; nothing in the system answered *how well the
chip is used* — VERDICT §4 flags chip utilization as unknown, and the
hardware-conscious ANN literature (TPU-KNN, arXiv:2206.14286; Zhang et
al., arXiv:1712.02912) treats achieved FLOP/s and GB/s against machine
peaks as the primary metric.  This module is the substrate: every device
kernel family registers an **analytic cost formula** keyed by its static
shape configuration, and the registered numbers are **cross-checked
against XLA's own `Compiled.cost_analysis()`** so a formula cannot
silently drift from the kernel it claims to describe.

Contract (DESIGN.md §12):

* `register(family, kernel, formula)` binds a dotted family name (e.g.
  ``"beam.segment"``) to the jitted kernel function and a
  ``formula(**shape) -> (flops, bytes)`` callable.  Family names are
  string literals at the call site (the GL6xx cardinality argument);
  graftlint GL605 enforces that every jit site under ``algo/``/``ops/``
  is either registered here or carries a justified baseline entry, so a
  new kernel cannot silently opt out of roofline accounting.
* ``flops`` counts every arithmetic op the kernel executes once per
  dispatch (matmul 2·M·N·K plus the non-trivial secondary terms: sorts,
  scans, top-k) — the same convention as XLA's HloCostAnalysis.  For
  kernels with an internal ``lax.while_loop`` the formula is the
  **one-iteration body cost** (XLA counts a loop body once; it cannot
  know the trip count) — callers scale by their own iteration counts for
  runtime accounting.
* ``bytes`` follows the "bytes accessed" convention of HloCostAnalysis:
  operand + result bytes of the non-fused ops, which counts materialized
  intermediates (a (Q, N) distance matrix is written and re-read).  This
  is an *upper bound* on true HBM traffic (TPU fusion keeps more in
  VMEM), which makes the derived ``achieved_gbps`` honest in the
  direction that matters — it can only under-report headroom, never
  fabricate utilization.
* `crosscheck(family, compiled, **shape)` compares the registered
  estimate to `cost_analysis()`; a relative delta beyond ``tol`` (15%)
  increments the ``costmodel.xla_mismatch`` counter and logs the delta.
  tools/ci_check.sh runs the cross-check standalone on the CPU backend
  (tests/test_costmodel.py); the tolerance is the acceptance bar, not a
  per-op identity — formulas carry the *dominant physics* (contraction
  FLOPs, corpus bytes, per-element sort constants), calibrated once
  against the pinned XLA version.

The module is import-light (no jax at import time) so backend-free
consumers (the scheduler, serve tiers, graftlint tests) can read the
registry without initializing a device.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Dict, Optional, Tuple

from sptag_tpu.utils import metrics

log = logging.getLogger(__name__)

#: relative tolerance of the ledger-vs-XLA cross-check (the acceptance
#: bar: flat / dense / beam-segment agree within 15% on the CPU backend)
DEFAULT_TOLERANCE = 0.15


@dataclasses.dataclass(frozen=True)
class CostEntry:
    """One registered kernel family."""

    family: str
    kernel_name: str                       # function name, for GL605
    formula: Callable[..., Tuple[float, float]]


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    family: str
    flops: float
    hbm_bytes: float

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per byte) — the roofline x-axis."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0


_lock = threading.Lock()
_entries: Dict[str, CostEntry] = {}


def register(family: str, kernel, formula) -> None:
    """Bind `family` to a jitted `kernel` (the function object — its
    ``__name__`` is what GL605 matches against) and a
    ``formula(**shape) -> (flops, bytes)``.  Re-registration replaces
    (module reload under tests)."""
    name = getattr(kernel, "__wrapped__", kernel)
    name = getattr(name, "__name__", str(kernel))
    with _lock:
        _entries[family] = CostEntry(family, name, formula)


def families() -> Tuple[str, ...]:
    with _lock:
        return tuple(sorted(_entries))


def entry(family: str) -> Optional[CostEntry]:
    with _lock:
        return _entries.get(family)


def registered_kernel_names() -> Tuple[str, ...]:
    """Function names with a ledger entry — the GL605 allow-set."""
    with _lock:
        return tuple(sorted({e.kernel_name for e in _entries.values()}))


def estimate(family: str, **shape) -> CostEstimate:
    """Evaluate the registered formula at a static shape configuration."""
    e = entry(family)
    if e is None:
        raise KeyError(f"no cost-ledger entry for kernel family {family!r}"
                       " (register one in the kernel's module)")
    flops, nbytes = e.formula(**shape)
    return CostEstimate(family, float(flops), float(nbytes))


# ---------------------------------------------------------------------------
# XLA cross-check
# ---------------------------------------------------------------------------

def xla_cost(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) from a `Compiled.cost_analysis()` result,
    tolerant of the two shapes jax has shipped (a dict, or a list with
    one dict per device)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)))


def crosscheck(family: str, compiled, tol: float = DEFAULT_TOLERANCE,
               **shape) -> Dict[str, float]:
    """Compare the ledger's estimate against XLA's cost analysis of a
    compiled executable of the same kernel at the same static shapes.

    Returns ``{"flops_rel": ..., "bytes_rel": ...}`` (signed relative
    deltas, ledger vs XLA).  A delta beyond `tol` on either axis bumps
    the ``costmodel.xla_mismatch`` counter and logs the numbers — the
    formula has drifted from the kernel and the roofline percentages it
    feeds are no longer trustworthy."""
    est = estimate(family, **shape)
    xf, xb = xla_cost(compiled)
    rel = {
        "flops_rel": (est.flops - xf) / xf if xf else 0.0,
        "bytes_rel": (est.hbm_bytes - xb) / xb if xb else 0.0,
    }
    if abs(rel["flops_rel"]) > tol or abs(rel["bytes_rel"]) > tol:
        metrics.inc("costmodel.xla_mismatch")
        log.warning(
            "cost-ledger mismatch for %s at %r: ledger flops=%.3g "
            "xla=%.3g (%+.1f%%), ledger bytes=%.3g xla=%.3g (%+.1f%%)",
            family, shape, est.flops, xf, 100.0 * rel["flops_rel"],
            est.hbm_bytes, xb, 100.0 * rel["bytes_rel"])
    return rel


# ---------------------------------------------------------------------------
# shared formula building blocks
# ---------------------------------------------------------------------------
#
# Calibration note: the elementwise / sort constants below were fitted
# once against this container's XLA (jax 0.4.x CPU HloCostAnalysis) and
# pinned by tests/test_costmodel.py at several shapes; the matmul and
# gather terms are exact physics and dominate at real sizes, so version
# drift lands on the small terms first and the 15% tolerance absorbs it.

#: cost-analysis traversals of a materialized (Q, N) score matrix in a
#: scan kernel (mask write+read, negation, top-k read) — fitted 3.1-3.3
SCAN_MATRIX_TRAFFIC = 3.2

#: per-element flops XLA attributes to the POSITIONAL sort/scan/top-k
#: ensemble of one beam-walk iteration (`engine._sorted_dedup`: argsort,
#: the mask carried back through the inverse permutation, two bitset-word
#: gathers, segmented OR scan, merges).  The exact body left it (ISSUE
#: 33 with the row-gather layout, ISSUE 45 with the packed-neighbour
#: one); the binned body prices its L-wide pool de-duplication with it
WALK_SORT_FLOPS = 290.0

#: per-element word traffic of the same ensemble (sorted copies,
#: scan intermediates), in 4-byte words
WALK_SORT_TRAFFIC = 130.0

#: the same two for the exact body's X-wide ensemble IN SORTED-ID ORDER
#: (`engine._sorted_fresh`, ISSUE 33: one one-operand sort, one word
#: gather, no inverse permutation), fitted like the pair above at six
#: shapes (flops 158-185 an element, words 93-112 where the graph's
#: gather operand is small beside Q*X); the packed-neighbour layout's
#: scores ride the sort as a second operand and read within 3 % of it
WALK_SORTED_FLOPS = 170.0
WALK_SORTED_TRAFFIC = 105.0

#: float32 words an element of the scored (Q, X, D) block that cost
#: analysis charges the candidates' own square sum (the walk's L2 norm
#: since ISSUE 44): XLA:CPU rewrites a reduce over D >= 64 into a tree
#: (reduce-window) that does not fuse with the squares, so the block is
#: read, its squares written and read again (fitted 3.0 at D = 64 and
#: 128; a narrower reduce fuses and reads 1.0, the chip's compiler makes
#: it one pass with the scoring contraction)
WALK_ROW_NORM_TRAFFIC = 3.0

#: per-merged-row-element flops of the BINNED walk body's selection
#: ensemble (bin min/argmin reductions + shortlist top-L + the
#: rank-select pop's cumsum/scatter), fitted like WALK_SORT_FLOPS
#: against this container's HloCostAnalysis (BinnedTopK, ISSUE 13;
#: measured 31.8-34.5 across three shapes)
WALK_BINNED_FLOPS = 33.0

#: per-merged-row-element word traffic of the same binned ensemble
#: (fitted 14-24 once the corpus gather-operand term is split out —
#: the binned bytes formula carries N*D explicitly, unlike the exact
#: body whose X-wide ensemble dwarfs it)
WALK_BINNED_TRAFFIC = 19.0


def matmul_flops(m: float, n: float, k: float) -> float:
    """Dense (m, k) x (k, n) contraction: 2·m·n·k."""
    return 2.0 * m * n * k


def topk_flops(rows: float, width: float) -> float:
    """lax.top_k over (rows, width): ~2 compare-ops per element under
    HloCostAnalysis (fitted; exact shape varies with the lowering)."""
    return 2.0 * rows * width
