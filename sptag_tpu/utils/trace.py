"""Tracing / profiling subsystem.

The reference has NO tracing (SURVEY.md §5: only per-query clock() math in
IndexSearcher, /root/reference/AnnService/src/IndexSearcher/main.cpp:109,
143-171); the survey assigns a first-class tracing subsystem to the new
framework.  Two cooperating layers:

* host spans — `span("name")` context managers record wall-time into a
  process-wide registry; `report()` aggregates count/total/mean/max per
  name plus p50/p90/p99 derived from the log-bucketed histogram every
  `record()` also feeds (utils/metrics.py — which is how the Prometheus
  endpoint exports span latencies with no extra wiring).  Cheap enough to
  leave on in production paths (a perf_counter pair, a dict update and a
  histogram bucket increment per span).
* device tracing — the same `span` emits a `jax.profiler.TraceAnnotation`
  when a jax profiler trace is active, so host spans line up with device
  timelines in TensorBoard/Perfetto; `start_trace(logdir)` / `stop_trace()`
  wrap `jax.profiler` for callers that should not import jax eagerly.
  `annotate("name")` is the annotation alone, for per-request sites where
  a registry record thousands of times a second would cost the event loop
  the time being measured.

Spans are per BATCH on the served path (serve/server.py, serve/service.py,
core/index.py, algo/flat.py, algo/dense.py); docs/TELEMETRY.md lists them.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional

from sptag_tpu.utils import metrics

_lock = threading.Lock()
_spans: Dict[str, list] = {}      # name -> [count, total_s, max_s]
# True while a profiler trace is live: span() and annotate() then emit
# TraceAnnotations.  start_trace/stop_trace set it; a caller that starts
# jax.profiler itself (benchmark/run.py) sets it by hand.
_trace_active = False
_NO_ANNOTATION = contextlib.nullcontext()


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Record one host span; annotate the device trace when one is live."""
    ann = None
    if _trace_active:
        import jax.profiler
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        record(name, dt)


def annotate(name: str):
    """A `TraceAnnotation` when a profiler trace is live, else one shared
    no-op context manager: nothing is recorded in the registry or the
    histograms, so the cost with no trace live is this call."""
    if _trace_active:
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)
    return _NO_ANNOTATION


def record(name: str, seconds: float) -> None:
    """Record one externally-measured duration into the span registry —
    the entry point for instrumentation that observes durations instead
    of wrapping code (utils/recompile_guard.py feeds XLA backend-compile
    times here so `report()` shows compile cost next to host spans)."""
    with _lock:
        rec = _spans.setdefault(name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += seconds
        rec[2] = max(rec[2], seconds)
    metrics.observe(name, seconds)


def record_sum(name: str, total_s: float, count: int) -> None:
    """Add `count` occurrences worth `total_s` seconds in all to an entry,
    in one locked update, and feed no histogram: for a per-batch site
    whose per-request values only their sum is wanted of (serve/server.py
    sums its requests' arrival and departure instants this way).
    `report()` hands out count, total and mean like a span's; max_s stays
    0 and there are no percentiles."""
    with _lock:
        rec = _spans.setdefault(name, [0, 0.0, 0.0])
        rec[0] += count
        rec[1] += total_s


def report() -> Dict[str, Dict[str, float]]:
    """Snapshot of all spans: {name: {count, total_s, mean_s, max_s,
    p50_s, p90_s, p99_s}} — the percentiles come from the log-bucketed
    metrics histogram each record() feeds (upper-bound estimates, within
    one ~1.3x bucket of the true quantile)."""
    with _lock:
        spans = {name: tuple(rec) for name, rec in _spans.items()}
    out: Dict[str, Dict[str, float]] = {}
    for name, (c, t, mx) in spans.items():
        entry = {"count": c, "total_s": round(t, 6),
                 "mean_s": round(t / c, 6) if c else 0.0,
                 "max_s": round(mx, 6)}
        h = metrics.histogram_or_none(name)
        if h is not None and h.count:
            entry.update({"p50_s": round(h.percentile(50), 6),
                          "p90_s": round(h.percentile(90), 6),
                          "p99_s": round(h.percentile(99), 6)})
        out[name] = entry
    return out


def reset() -> None:
    """Clear the span registry (the paired metrics histograms are cleared
    by metrics.reset(); tests/conftest.py resets both)."""
    with _lock:
        _spans.clear()


def start_trace(logdir: str, python_tracer: bool = False) -> None:
    """Begin a jax profiler trace (XLA device timeline + host annotations).
    View with TensorBoard's profile plugin or Perfetto.  The python tracer
    is off unless asked for: it slows the server's event loop, which a
    trace of a live server is there to see."""
    global _trace_active
    import jax.profiler
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    _trace_active = True


def stop_trace() -> None:
    global _trace_active
    import jax.profiler
    _trace_active = False
    jax.profiler.stop_trace()
