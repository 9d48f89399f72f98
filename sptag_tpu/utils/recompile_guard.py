"""Recompile guard — runtime counterpart of graftlint's retrace lint.

graftlint (tools/graftlint) catches recompile HAZARDS statically; this
module counts what XLA actually compiled, via `jax.monitoring`'s
`/jax/core/compile/backend_compile_duration` event — emitted once per
backend compilation, including cache-miss recompiles that jit's Python
layer never sees.  Tests wrap a warmed-up search in `track_compiles()`
and assert zero events: the exact "no recompilation in the query loop"
invariant TPU-KNN (arXiv:2206.14286) requires for peak-FLOP/s serving,
enforced in tier-1 (tests/test_recompile.py) instead of discovered as a
bench regression rounds later.

Every observed compile is also fed into utils/trace.py
(`trace.record("xla.backend_compile[<label>]", dt)`), so `trace.report()`
shows compile cost next to host spans with no extra wiring.

Listener registration is process-global and installed once, lazily (the
module import does NOT import jax — importing the library must never
initialize a backend).  Guards nest: each active guard counts every
compile in its window.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Dict, Iterator, List, Optional

from sptag_tpu.utils import metrics, trace

log = logging.getLogger("sptag_tpu.tracesan")

#: the monitoring event jax emits once per XLA backend compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: the trace-span family compile durations are recorded under
TRACE_SPAN = "xla.backend_compile"

_lock = threading.Lock()
_active: List["CompileLog"] = []
_installed = False


class RecompileError(AssertionError):
    """A guard observed more XLA compilations than its window allows."""


class CompileLog:
    """Counter for one `track_compiles` window."""

    def __init__(self, label: str):
        self.label = label
        self.count = 0
        self.total_s = 0.0
        self.durations: List[float] = []
        self._log_lock = threading.Lock()

    def _record(self, duration_s: float) -> None:
        with self._log_lock:
            self.count += 1
            self.total_s += duration_s
            self.durations.append(duration_s)

    def assert_compiles(self, at_most: int,
                        context: str = "") -> None:
        """Raise RecompileError if more than `at_most` compilations were
        observed in this window."""
        if self.count > at_most:
            where = f" during {context}" if context else ""
            raise RecompileError(
                f"[{self.label}] {self.count} XLA compilation(s){where}, "
                f"expected at most {at_most} — a shape/dtype/static-arg "
                "is varying per call (see graftlint GL2xx and "
                "serve.service._sanitize_max_check for the quantization "
                "pattern)")

    def __repr__(self) -> str:
        return (f"CompileLog({self.label!r}, count={self.count}, "
                f"total_s={round(self.total_s, 3)})")


def _on_event_duration(event: str, duration_s: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    with _lock:
        logs = list(_active)
    for clog in logs:
        clog._record(duration_s)
        trace.record(f"{TRACE_SPAN}[{clog.label}]", duration_s)
    if not logs:
        trace.record(TRACE_SPAN, duration_s)
    # trace sanitizer: attribute the compile to the innermost hot
    # section of the COMPILING thread (the dispatch call that traced)
    # and check that family's compile budget
    if tracesan_enabled():
        _tracesan_on_compile()


def _ensure_listener() -> None:
    """Install the process-global monitoring listener (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _installed = True


@contextlib.contextmanager
def track_compiles(label: str = "guard") -> Iterator[CompileLog]:
    """Count XLA backend compilations within the block.

        with track_compiles("beam.warm") as log:
            index.search_batch(queries, 10)
        log.assert_compiles(at_most=0)
    """
    _ensure_listener()
    log = CompileLog(label)
    with _lock:
        _active.append(log)
    try:
        yield log
    finally:
        with _lock:
            _active.remove(log)


@contextlib.contextmanager
def no_recompiles(label: str = "steady-state",
                  at_most: int = 0) -> Iterator[CompileLog]:
    """`track_compiles` that raises RecompileError on exit when the block
    compiled more than `at_most` programs — the assertion form for tests
    and for wrapping a production serve loop after warmup.  Raises only
    on clean exits: an exception inside the block propagates unmasked."""
    with track_compiles(label) as log:
        yield log
    log.assert_compiles(at_most)


def warmup_then_guard(fn, *args, label: str = "steady-state",
                      repeats: int = 1, **kwargs):
    """Convenience: run `fn` once (warmup — compiles are expected), then
    `repeats` more times under a zero-compile guard.  Returns the last
    result.  The pattern every steady-state test wants as one call."""
    result = fn(*args, **kwargs)
    with no_recompiles(label):
        for _ in range(repeats):
            result = fn(*args, **kwargs)
    return result


# ---------------------------------------------------------------------------
# trace/transfer sanitizer (SPTAG_TRACESAN / [Service] TraceSanitizer)
# ---------------------------------------------------------------------------
#
# Runtime complement of graftlint's GL901/GL902: the static pass names
# the transfer/recompile hazards it can see; the sentinel observes the
# ones that actually happen.  Engine/scheduler hot paths declare
# themselves with `hot_section("family")`; inside a section:
#
# * every IMPLICIT device->host readback is a violation — armed mode
#   installs Python shims over `ArrayImpl.{__array__, __float__,
#   __int__, __bool__, item}` (the CPU backend's zero-copy host views
#   make `jax.transfer_guard` inert there, so the shims are what bites
#   under tests; the jax guard is ALSO entered per section and bites on
#   real TPU/GPU).  `np.asarray(device_arr)` goes through the C buffer
#   protocol, bypassing `__array__` entirely — that path is a known
#   runtime blind spot on CPU, covered statically by GL902.
# * `device_get(x)` below is the sanctioned EXPLICIT readback: it
#   routes through `jax.device_get` with a thread-local blessing so the
#   shims stay quiet (and jax's guard always allows explicit gets).
# * every XLA compile is attributed to the innermost section name (its
#   "family") and checked against a per-family compile budget
#   (`set_compile_budget`) — steady-state serve families budget 0 after
#   warmup; a trip counts `tracesan.compile_budget_trips` (strict:
#   raises CompileBudgetError).
#
# Off is FREE: `hot_section` tests one flag and yields; no shims are
# installed, no listener registered, serve bytes are byte-identical
# (tests/test_tracesan.py::test_tracesan_off_parity proves it).

_MAX_VIOLATION_RECORDS = 200

_ts_cfg_lock = threading.Lock()
_ts_tls = threading.local()            # .sections: List[str]; .blessed: int
_tracesan_override: Optional[bool] = None
_tracesan_strict_override: Optional[bool] = None
_ts_shims_installed = False
_ts_originals: Dict[str, object] = {}
_ts_violations: List[dict] = []
_ts_transfers = 0
_ts_compiles: Dict[str, int] = {}
_ts_budgets: Dict[str, int] = {}
_ts_default_budget: Optional[int] = None
_ts_budget_trips = 0


class TransferSyncError(AssertionError):
    """An implicit device->host transfer fired inside a hot section."""


class CompileBudgetError(RecompileError):
    """A hot-section family exceeded its XLA compile budget."""


def _tracesan_env() -> str:
    return os.environ.get("SPTAG_TRACESAN", "").strip().lower()


def tracesan_enabled() -> bool:
    """The opt-in trace/transfer sentinel.  Env ``SPTAG_TRACESAN=1``
    (``strict``/``raise`` to make violations raise) or ini ``[Service]
    TraceSanitizer``."""
    if _tracesan_override is not None:
        return _tracesan_override
    return _tracesan_env() in ("1", "true", "on", "yes", "log",
                               "strict", "raise")


def tracesan_strict() -> bool:
    if _tracesan_strict_override is not None:
        return _tracesan_strict_override
    return _tracesan_env() in ("strict", "raise")


def enable_tracesan(strict: Optional[bool] = None,
                    compile_budget: Optional[int] = None) -> None:
    """Arm the sentinel for hot sections entered FROM NOW ON.
    `strict`/`compile_budget` override the env; None keeps the
    env-derived values (budget default: unlimited)."""
    global _tracesan_override, _tracesan_strict_override, \
        _ts_default_budget
    with _ts_cfg_lock:
        _tracesan_override = True
        if strict is not None:
            _tracesan_strict_override = strict
        if compile_budget is not None:
            _ts_default_budget = int(compile_budget)


def disable_tracesan() -> None:
    global _tracesan_override, _tracesan_strict_override
    with _ts_cfg_lock:
        _tracesan_override = False
        _tracesan_strict_override = None
    _uninstall_shims()


def reset_tracesan() -> None:
    """Back to env-derived config; drop all records, counts, budgets,
    and shims.  Test isolation hook (conftest calls it per test)."""
    global _tracesan_override, _tracesan_strict_override, \
        _ts_default_budget, _ts_transfers, _ts_budget_trips
    with _ts_cfg_lock:
        _tracesan_override = None
        _tracesan_strict_override = None
        _ts_default_budget = None
        _ts_transfers = 0
        _ts_budget_trips = 0
        _ts_violations.clear()
        _ts_compiles.clear()
        _ts_budgets.clear()
    _uninstall_shims()


def set_compile_budget(family: str, at_most: int) -> None:
    """Budget XLA compiles for one hot-section family (overrides the
    `enable_tracesan(compile_budget=...)` default for that family)."""
    with _ts_cfg_lock:
        _ts_budgets[family] = int(at_most)


def violations() -> List[dict]:
    with _ts_cfg_lock:
        return [dict(v) for v in _ts_violations]


def violation_count() -> int:
    with _ts_cfg_lock:
        return _ts_transfers


def compile_counts() -> Dict[str, int]:
    """{family: observed XLA compiles} while armed."""
    with _ts_cfg_lock:
        return dict(_ts_compiles)


def tracesan_counters() -> Dict[str, object]:
    with _ts_cfg_lock:
        return {"enabled": tracesan_enabled(),
                "transfers": _ts_transfers,
                "compiles": sum(_ts_compiles.values()),
                "budget_trips": _ts_budget_trips}


def _sections() -> List[str]:
    return getattr(_ts_tls, "sections", None) or []


def _blessed() -> bool:
    return getattr(_ts_tls, "blessed", 0) > 0


def _flag_transfer(kind: str) -> None:
    sections = _sections()
    if not sections or _blessed() or not tracesan_enabled():
        return
    global _ts_transfers
    with _ts_cfg_lock:
        _ts_transfers += 1
        if len(_ts_violations) < _MAX_VIOLATION_RECORDS:
            _ts_violations.append({"section": sections[-1],
                                   "kind": kind,
                                   "stack": list(sections)})
    metrics.inc("tracesan.transfers")
    msg = (f"implicit device->host transfer (`{kind}`) inside hot "
           f"section {sections[-1]!r} — read back explicitly with "
           "recompile_guard.device_get, or move the sync out of the "
           "loop (graftlint GL902)")
    if tracesan_strict():
        raise TransferSyncError(msg)
    log.warning(msg)


def _install_shims() -> None:
    """Wrap ArrayImpl's host-readback dunders (idempotent).  Only the
    methods present on the running jax are wrapped; each shim is one
    TLS read when no hot section is active on the thread."""
    global _ts_shims_installed
    with _ts_cfg_lock:
        if _ts_shims_installed:
            return
        from jax._src.array import ArrayImpl

        def make(kind, orig):
            def shim(self, *args, **kwargs):
                if _sections():
                    _flag_transfer(kind)
                return orig(self, *args, **kwargs)
            shim.__name__ = getattr(orig, "__name__", kind)
            shim._tracesan_orig = orig
            return shim

        for kind, attr in (("__array__", "__array__"),
                           ("float", "__float__"),
                           ("int", "__int__"),
                           ("bool", "__bool__"),
                           ("item", "item")):
            orig = ArrayImpl.__dict__.get(attr)
            if orig is None or hasattr(orig, "_tracesan_orig"):
                continue
            _ts_originals[attr] = orig
            setattr(ArrayImpl, attr, make(kind, orig))
        _ts_shims_installed = True


def _uninstall_shims() -> None:
    global _ts_shims_installed
    with _ts_cfg_lock:
        if not _ts_shims_installed:
            return
        from jax._src.array import ArrayImpl
        for attr, orig in _ts_originals.items():
            setattr(ArrayImpl, attr, orig)
        _ts_originals.clear()
        _ts_shims_installed = False


@contextlib.contextmanager
def hot_section(name: str) -> Iterator[None]:
    """Declare a device-dispatch hot region (the scheduler cycle, bucket
    seeding, segment dispatch).  Disarmed: one flag test, then yield —
    zero cost.  Armed: implicit d2h readbacks inside the block are
    violations, and XLA compiles are attributed to `name`'s budget."""
    if not tracesan_enabled():
        yield
        return
    _ensure_listener()
    _install_shims()
    import jax
    stack = getattr(_ts_tls, "sections", None)
    if stack is None:
        stack = _ts_tls.sections = []
    stack.append(name)
    try:
        # inert on the CPU backend (zero-copy host views) but bites on
        # real TPU/GPU, where the shims cannot see XLA-internal syncs
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        stack.pop()


def device_get(x):
    """The sanctioned explicit readback: `jax.device_get` under a
    thread-local blessing, so the sentinel's shims stay quiet.  Returns
    numpy (READ-ONLY views on CPU — `np.array(...)` the result when a
    writable buffer is needed).  Disarmed this is just jax.device_get."""
    import jax
    if not tracesan_enabled():
        return jax.device_get(x)
    _ts_tls.blessed = getattr(_ts_tls, "blessed", 0) + 1
    try:
        return jax.device_get(x)
    finally:
        _ts_tls.blessed -= 1


def _tracesan_on_compile() -> None:
    sections = _sections()
    if not sections:
        return
    family = sections[-1]
    global _ts_budget_trips
    with _ts_cfg_lock:
        _ts_compiles[family] = _ts_compiles.get(family, 0) + 1
        count = _ts_compiles[family]
        budget = _ts_budgets.get(family, _ts_default_budget)
    metrics.inc("tracesan.compiles")
    if budget is None or count <= budget:
        return
    _ts_budget_trips += 1
    metrics.inc("tracesan.compile_budget_trips")
    msg = (f"hot-section family {family!r} compiled {count} XLA "
           f"program(s), budget {budget} — a shape/dtype/static-arg "
           "varies per call in the steady state (graftlint GL901/GL2xx)")
    if tracesan_strict():
        raise CompileBudgetError(msg)
    log.warning(msg)
