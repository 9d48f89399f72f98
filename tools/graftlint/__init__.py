"""graftlint — TPU/JAX static-analysis suite for sptag_tpu.

Checker families, each its own module with documented rule ids:

* GL1xx  hostsync       host<->device syncs on the jitted paths
* GL2xx  retrace        recompile-per-value / per-shape hazards
* GL3xx  concurrency    unlocked shared mutation, late-binding captures
* GL4xx  errorpath      swallowed exceptions at the ErrorCode boundaries
* GL5xx  dtype_parity   integer distance paths upcasting before the dot
* GL6xx  obsnames       literal metric/span/stage names
* GL7xx  lockgraph      lock-order cycles, blocking under a held lock,
                        leaked thread/task handles (+ GL41x persistence
                        writes outside the atomic/WAL funnel)
* GL8xx  guardedby      guarded-by inference: unguarded/inconsistent
                        writes to shared state, epoch-repin,
                        escape-before-publish, plain locks invisible
                        to the locksan runtime

Run `python -m tools.graftlint sptag_tpu/` from the repo root; accepted
findings live in `baseline.toml` (every entry justified).  The runtime
complements are `sptag_tpu/utils/recompile_guard.py` (zero recompiles
after warmup) and `sptag_tpu/utils/locksan.py` (lock-order sanitizer,
contention ledger, Eraser-style race sanitizer).
"""

from tools.graftlint.core import Finding, Project  # noqa: F401

__all__ = ["Finding", "Project", "lint_project", "lint_sources",
           "ALL_RULES"]


def __getattr__(name):
    # runner imports the checker modules, which import this package —
    # lazy re-export avoids the cycle at import time
    if name in ("lint_project", "lint_sources", "ALL_RULES", "main"):
        from tools.graftlint import runner
        return getattr(runner, name)
    raise AttributeError(name)
