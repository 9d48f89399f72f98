"""From a profiler trace to device numbers.

`read_xplane` turns the .xplane.pb jax.profiler writes into plain lists;
everything after it works on those lists, so the arithmetic is checked on
a hand-made fixture (benchmark/tests/).  What the first chip trace showed
(PERF.md section 5 has the full note): one plane per chip named
`/device:TPU:<n>`, with a line `XLA Ops` (one event per executed HLO
operation) and a line `XLA Modules` (one event per executed program, named
`jit_<function>(<fingerprint>)`); host threads are lines of `/host:CPU`, and
every `jax.profiler.TraceAnnotation` is an event there.  All planes share
one clock (nanoseconds).
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "benchmark.trace_window"


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def read_xplane(path: str) -> dict:
    """-> {"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...], "lines": {plane: {line: events}}} with every event a
    (name, start_s, duration_s) tuple."""
    import jax.profiler

    space = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in space.planes:
        counts = out["lines"].setdefault(plane.name, {})
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            counts[line.name] = len(events)
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                dev = out["devices"].setdefault(
                    plane.name, {"ops": [], "modules": []})
                if line.name == OPS_LINE:
                    dev["ops"] = events
                elif line.name == MODULES_LINE:
                    dev["modules"] = events
            elif plane.name == HOST_PLANE:
                out["host"].extend(events)
    return out


def window_of(host_events) -> tuple:
    """(start_s, end_s) of the span the harness holds open while it
    traces; the window every number below is clipped to."""
    for name, start, dur in host_events:
        if name == WINDOW_SPAN:
            return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi]; what lies outside is dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merged(events) -> list:
    """Sorted, non-overlapping (start, end) intervals covering `events`."""
    out = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events) -> float:
    """Union of the intervals in which an operation ran."""
    return sum(e - s for s, e in merged(events))


def idle_gaps(events, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi] no event covers."""
    gaps, at = [], lo
    for s, e in merged(events):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def gaps_by_host_span(gaps, host_events, ignore=(WINDOW_SPAN,)) -> list:
    """Every instant of every gap named by the innermost host span over it
    — of the spans that cover the instant, the one that started last —
    and "(no span)" where none does -> [[name, total seconds], ...],
    longest first.  Host threads are not told apart: the span that started
    last is what the host took up last."""
    spans = sorted((s, s + d, n) for n, s, d in host_events
                   if n not in ignore and d > 0)
    total = {}
    for g0, g1 in gaps:
        over = [(s, e, n) for s, e, n in spans if s < g1 and e > g0]
        cuts = sorted({g0, g1, *(t for s, e, _ in over for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            name, started = "(no span)", None
            for s, e, n in over:
                if s <= a and e >= b and (started is None or s >= started):
                    name, started = n, s
            total[name] = total.get(name, 0.0) + (b - a)
    return sorted(([n, t] for n, t in total.items()),
                  key=lambda r: -r[1])


def top_by_name(events, n: int = 10) -> list:
    """[[name, total seconds], ...] of the `n` names with most time."""
    total = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda r: -r[1])[:n]


def program_name(module_event_name: str) -> str:
    """`jit__flat_search_kernel(1234567)` -> `jit__flat_search_kernel`."""
    return module_event_name.split("(", 1)[0]


def reduce_trace(raw: dict, chips: int) -> dict:
    """The numbers the per-layer readers and the result line take from one
    traced slice, averaged over the `chips` busiest device planes."""
    lo, hi = window_of(raw["host"])
    planes = []
    for name, dev in raw["devices"].items():
        ops = clip(dev["ops"], lo, hi)
        planes.append((busy_seconds(ops), name, ops,
                       clip(dev["modules"], lo, hi)))
    planes.sort(key=lambda p: -p[0])
    used = planes[:chips]
    if not used:
        raise ValueError("the trace holds no device plane")
    busiest = used[0]
    programs = {}
    for name, _, dur in busiest[3]:
        rec = programs.setdefault(program_name(name), [0, 0.0])
        rec[0] += 1
        rec[1] += dur
    host = clip(raw["host"], lo, hi)
    return {
        "window_s": hi - lo,
        "busy_s": sum(p[0] for p in used) / len(used),
        "device_planes": [p[1] for p in used],
        "programs": {k: {"runs": v[0], "seconds": v[1]}
                     for k, v in programs.items()},
        "device_ops": top_by_name(busiest[2]),
        "idle_gaps": gaps_by_host_span(idle_gaps(busiest[2], lo, hi),
                                       host)[:10],
        "host_span_counts": {n: sum(1 for e in host if e[0] == n)
                             for n in {e[0] for e in host}
                             if n.startswith("server.")},
    }
