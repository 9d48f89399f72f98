"""benchdiff — the noise-aware perf-regression sentinel (ISSUE 10).

Compares a CURRENT artifact against a PINNED BASELINE artifact and exits
nonzero with a readable table when a watched metric regressed:

    python -m tools.benchdiff baseline.json current.json
    python -m tools.benchdiff --json baseline.json current.json

The artifacts it was written for came from the pre-chip ``bench.py``,
removed in PR 29 (the repo is measured by ``python3 -m benchmark.run``);
what still produces one is ``tools/autotune.py --gate`` (ROADMAP C1b).
Its tests pin ``tests/fixtures/bench_artifact.json``.

Design decisions, in order of importance:

* **Noise-aware**: a metric regresses only when the relative change
  exceeds its threshold AND the absolute change exceeds its min-delta
  floor.  Bench numbers on a contended CI host jitter by several
  percent; the floors keep a 3-QPS wiggle on a 20-QPS beam stage from
  crying wolf, the relative thresholds keep a 500-QPS drop on a
  15k-QPS dense stage from hiding inside them.
* **Platform-gated**: an artifact measured on ``cpu`` is NOT comparable
  to one measured on ``tpu`` — throughput metrics are skipped with a
  visible note (recall and result-quality metrics still diff; the
  algorithm is platform-independent).
* **Schema-versioned**: artifacts stamp ``schema_version``;
  the sentinel diffs the INTERSECTION of watched keys present in both
  artifacts and prints both versions, so a baseline from an older
  schema degrades to fewer checks, never to a false alarm.
* **Direction-aware**: QPS/recall/%-of-peak regress DOWN, latency
  regresses UP; improvements are reported but never fail the gate.

Driver-wrapped artifacts (``{"parsed": {...}}``) unwrap automatically.
Exit codes: 0 pass, 1 regression, 2 usage/load error.  Wired into
tools/ci_check.sh as a self-test (identical artifacts must pass; a
doctored −20 % loadgen p99 must fail).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

#: artifact schema this sentinel was written against
SCHEMA_VERSION = 1

HIGHER = "higher"     # regression = value went DOWN
LOWER = "lower"       # regression = value went UP


class Metric:
    """One watched key: dotted path, direction, relative threshold and
    absolute min-delta floor (both must be exceeded to flag), and
    whether the number depends on the measuring platform."""

    __slots__ = ("path", "direction", "rel", "floor", "platform_bound")

    def __init__(self, path: str, direction: str, rel: float,
                 floor: float, platform_bound: bool = True):
        self.path = path
        self.direction = direction
        self.rel = rel
        self.floor = floor
        self.platform_bound = platform_bound


#: the watched surface — per-stage throughput, latency, recall and
#: roofline %-of-peak.  Thresholds are deliberately loose (the bench
#: harness is single-run, not a statistics engine); tighten per-metric
#: as history accumulates rather than globally.
METRICS: List[Metric] = [
    # headline + per-stage throughput
    Metric("value", HIGHER, 0.15, 50.0),
    Metric("flat_qps", HIGHER, 0.15, 25.0),
    Metric("int8_qps", HIGHER, 0.15, 25.0),
    Metric("kdt_cosine_qps", HIGHER, 0.20, 10.0),
    Metric("kdt_dense_qps", HIGHER, 0.20, 25.0),
    Metric("beam_qps", HIGHER, 0.20, 2.0),
    # ISSUE 13: the binned walk's margin over the exact-top-k reference
    # pass measured in the SAME run — the bin-reduction specialization's
    # reason to exist.  Ratio of two same-run numbers, so it holds even
    # across host-speed changes that shift every absolute QPS.
    Metric("beam_binned_speedup", HIGHER, 0.20, 0.3),
    Metric("beam_exact_qps", HIGHER, 0.20, 2.0),
    # latency (lower is better)
    Metric("p50_batch_ms", LOWER, 0.20, 20.0),
    Metric("p99_batch_ms", LOWER, 0.20, 30.0),
    # result quality (platform-independent: the algorithm answered
    # worse, whatever measured it)
    Metric("recall_at_10", HIGHER, 0.01, 0.005, platform_bound=False),
    Metric("int8_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    Metric("beam_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    Metric("beam_exact_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    Metric("kdt_cosine_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    # open-loop serving capacity + tail (ISSUE 8's loadgen stage)
    Metric("loadgen.qps_at_slo", HIGHER, 0.20, 16.0),
    Metric("loadgen.p50_ms", LOWER, 0.20, 5.0),
    Metric("loadgen.p99_ms", LOWER, 0.20, 10.0),
    # ground-truth canary lines (ISSUE 15): exact recall vs the pinned
    # oracle truth is platform-independent — the canary answering worse
    # is a correctness regression whatever host measured it; canary p99
    # is the full-serve-path latency at probe (near-idle) load
    Metric("loadgen.canary_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    Metric("loadgen.canary_p99_ms", LOWER, 0.25, 10.0),
    # offline-autotuner replay (ISSUE 17): the emitted config
    # artifact's operating point — QPS at the recall-SLO target and the
    # recall actually delivered there.  A worse chosen point means the
    # tuner (or the engine underneath it) regressed; recall is
    # platform-independent like every quality line.
    Metric("autotune.qps_at_slo", HIGHER, 0.20, 16.0),
    Metric("autotune.recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    # mutation-under-load stage (ISSUE 9).  GL1001: this pair was
    # silently dead from the day it landed — the stage emits
    # `steady_p99_ms` (this entry watched the transposed
    # `p99_steady_ms`) and emitted no read-throughput key at all
    # (the stage then gained `read_qps`)
    Metric("mutate.read_qps", HIGHER, 0.20, 25.0),
    Metric("mutate.steady_p99_ms", LOWER, 0.25, 10.0),
    # in-mesh sharded serving stage (ISSUE 11): the one-dispatch mesh
    # path's throughput/tail, its margin over the socket fan-out
    # baseline, and the merged-path recall (platform-independent).  The
    # speedup ratio is the stage's reason to exist — hold that line.
    Metric("mesh_serve.inmesh_qps", HIGHER, 0.20, 8.0),
    Metric("mesh_serve.fanout_qps", HIGHER, 0.25, 5.0),
    Metric("mesh_serve.inmesh_p99_ms", LOWER, 0.25, 20.0),
    Metric("mesh_serve.speedup", HIGHER, 0.20, 0.15),
    Metric("mesh_serve.recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    # beyond-HBM tiered capacity (ISSUE 14): servable vectors per GB of
    # HBM at the recall floor (ledger-measured array bytes — platform-
    # independent), the chosen cascade config's recall line, and its
    # density ratio over the fp-only path (the stage's reason to exist)
    Metric("capacity.vectors_per_gb", HIGHER, 0.10, 1000.0,
           platform_bound=False),
    Metric("capacity.cascade_recall_at_10", HIGHER, 0.01, 0.005,
           platform_bound=False),
    Metric("capacity.capacity_ratio_vs_fp", HIGHER, 0.10, 0.3,
           platform_bound=False),
    # roofline %-of-peak per kernel family (ISSUE 6's ledger rows):
    # regressing the fraction of peak is the canary that a "faster in
    # QPS" change actually left device efficiency on the floor
    Metric("roofline.rows.flat.pct_peak", HIGHER, 0.20, 2.0),
    Metric("roofline.rows.dense.pct_peak", HIGHER, 0.20, 2.0),
    Metric("roofline.rows.beam.pct_peak", HIGHER, 0.20, 2.0),
    Metric("roofline.rows.int8.pct_peak", HIGHER, 0.20, 2.0),
]


def load_artifact(path: str) -> Dict[str, Any]:
    """Load one bench artifact, unwrapping the driver envelope
    (``{"parsed": {...}}``)."""
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: artifact is not a JSON object")
    if isinstance(obj.get("parsed"), dict):
        obj = obj["parsed"]
    return obj


def resolve(obj: Dict[str, Any], dotted: str) -> Optional[float]:
    """Walk a dotted path; returns a float or None when any hop is
    missing/None/non-numeric (missing keys are SKIPPED, not failed —
    stages are budget-gated and may legitimately be absent)."""
    cur: Any = obj
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    if isinstance(cur, bool) or not isinstance(cur, (int, float)):
        return None
    return float(cur)


class Verdict:
    __slots__ = ("metric", "base", "cur", "delta_pct", "status", "note")

    def __init__(self, metric: Metric, base: float, cur: float,
                 status: str, note: str = ""):
        self.metric = metric
        self.base = base
        self.cur = cur
        self.delta_pct = ((cur - base) / abs(base) * 100.0
                          if base else float("inf") if cur else 0.0)
        self.status = status
        self.note = note


def judge(metric: Metric, base: float, cur: float) -> Verdict:
    delta = cur - base
    worse = -delta if metric.direction == HIGHER else delta
    rel = worse / abs(base) if base else (1.0 if worse > 0 else 0.0)
    # inclusive comparisons: a change AT the threshold counts — "a 20%
    # p99 regression fails a 20% gate" reads as operators expect
    if worse > 0 and rel >= metric.rel and worse >= metric.floor:
        return Verdict(metric, base, cur, "REGRESSED",
                       f"worse by {rel * 100.0:.1f}% "
                       f"(> {metric.rel * 100.0:.0f}% and "
                       f"> {metric.floor:g} abs)")
    if worse > 0:
        return Verdict(metric, base, cur, "ok",
                       "within noise thresholds")
    if worse < 0 and rel < -metric.rel and -worse > metric.floor:
        return Verdict(metric, base, cur, "improved", "")
    return Verdict(metric, base, cur, "ok", "")


#: per-stage compile-count lines (ISSUE 16): the producer brackets each
#: `trace.span("bench.X")` stage with a recompile_guard.track_compiles
#: window, so the artifact's trace dict carries
#: `xla.backend_compile[bench.X]` spans whose COUNT is the number of
#: fresh XLA programs that stage minted.  More compiles in the same
#: stage is a recompile regression (a shape/dtype/static-arg started
#: varying — the GL901 hazard observed live) even when wall-clock QPS
#: hides it behind a warm cache.
_COMPILE_SPAN_PREFIX = "xla.backend_compile["


def _compile_count_metrics(baseline: Dict[str, Any],
                           current: Dict[str, Any]) -> List[Metric]:
    """Synthesize `<stage>.backend_compiles` metrics for every compile
    span labeled in BOTH artifacts (the watched list can't enumerate
    them statically — stages are budget-gated and labels grow with the
    bench)."""
    out: List[Metric] = []
    bt, ct = baseline.get("trace"), current.get("trace")
    if not isinstance(bt, dict) or not isinstance(ct, dict):
        return out
    for key in sorted(bt.keys() & ct.keys()):
        if not (key.startswith(_COMPILE_SPAN_PREFIX)
                and key.endswith("]")):
            continue
        label = key[len(_COMPILE_SPAN_PREFIX):-1]
        # direction-adjusted: compiles regress UPWARD; loose rel + a
        # 2-program floor absorbs warmup jitter (an extra dtype probe),
        # platform_bound because compile counts track the backend's
        # executable partitioning
        out.append(Metric(f"{label}.backend_compiles", LOWER, 0.25,
                          2.0, platform_bound=True))
    return out


def _resolve_compile_count(obj: Dict[str, Any], metric_path: str
                           ) -> Optional[float]:
    label = metric_path[:-len(".backend_compiles")]
    tr = obj.get("trace")
    if not isinstance(tr, dict):
        return None
    span = tr.get(f"{_COMPILE_SPAN_PREFIX}{label}]")
    if not isinstance(span, dict):
        return None
    count = span.get("count")
    if isinstance(count, bool) or not isinstance(count, (int, float)):
        return None
    return float(count)


def diff(baseline: Dict[str, Any], current: Dict[str, Any]
         ) -> Tuple[List[Verdict], List[str]]:
    """Judge every watched metric present in BOTH artifacts; returns
    (verdicts, notes).  Platform-bound metrics are skipped with a note
    when the two artifacts were measured on different backends."""
    notes: List[str] = []
    base_platform = baseline.get("platform", "")
    cur_platform = current.get("platform", "")
    platforms_differ = (base_platform and cur_platform
                        and base_platform != cur_platform)
    if platforms_differ:
        notes.append(
            f"platform mismatch (baseline={base_platform!r}, "
            f"current={cur_platform!r}): throughput/latency/roofline "
            "metrics skipped, quality metrics still checked")
    sv_base = baseline.get("schema_version", 0)
    sv_cur = current.get("schema_version", 0)
    if sv_base != sv_cur:
        notes.append(f"schema_version differs (baseline={sv_base}, "
                     f"current={sv_cur}): diffing shared keys only")
    verdicts: List[Verdict] = []
    for m in METRICS:
        if platforms_differ and m.platform_bound:
            continue
        base_v = resolve(baseline, m.path)
        cur_v = resolve(current, m.path)
        if base_v is None or cur_v is None:
            continue
        verdicts.append(judge(m, base_v, cur_v))
    for m in _compile_count_metrics(baseline, current):
        if platforms_differ and m.platform_bound:
            continue
        base_v = _resolve_compile_count(baseline, m.path)
        cur_v = _resolve_compile_count(current, m.path)
        if base_v is None or cur_v is None:
            continue
        verdicts.append(judge(m, base_v, cur_v))
    if not verdicts:
        notes.append("no watched metric present in both artifacts — "
                     "nothing was checked")
    return verdicts, notes


def render_table(verdicts: List[Verdict], notes: List[str],
                 baseline_path: str, current_path: str,
                 show_all: bool = False) -> str:
    lines = [f"benchdiff: {current_path} vs baseline {baseline_path}"]
    for n in notes:
        lines.append(f"  note: {n}")
    rows = [v for v in verdicts
            if show_all or v.status in ("REGRESSED", "improved")]
    if not rows and verdicts:
        lines.append(f"  {len(verdicts)} metric(s) checked, all within "
                     "thresholds")
    if rows:
        header = (f"  {'metric':<34} {'baseline':>12} {'current':>12} "
                  f"{'Δ%':>8}  status")
        lines.append(header)
        lines.append("  " + "-" * (len(header) - 2))
        for v in rows:
            lines.append(
                f"  {v.metric.path:<34} {v.base:>12.3f} {v.cur:>12.3f} "
                f"{v.delta_pct:>+8.1f}  {v.status}"
                + (f" — {v.note}" if v.note and v.status == "REGRESSED"
                   else ""))
    regressed = [v for v in verdicts if v.status == "REGRESSED"]
    lines.append(
        f"  verdict: {'FAIL — ' + str(len(regressed)) + ' regression(s)' if regressed else 'PASS'}"
        f" ({len(verdicts)} checked)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.benchdiff",
        description="Compare a bench artifact against a pinned baseline "
                    "and fail on perf regressions.")
    parser.add_argument("baseline", help="pinned baseline artifact "
                        "(e.g. tests/fixtures/bench_artifact.json)")
    parser.add_argument("current", help="freshly produced artifact")
    parser.add_argument("--json", action="store_true",
                        help="emit machine-readable verdicts instead of "
                        "the table")
    parser.add_argument("--show-all", action="store_true",
                        help="print every checked metric, not only "
                        "regressions/improvements")
    args = parser.parse_args(argv)
    try:
        baseline = load_artifact(args.baseline)
        current = load_artifact(args.current)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"benchdiff: cannot load artifacts: {e}", file=sys.stderr)
        return 2
    verdicts, notes = diff(baseline, current)
    if args.json:
        print(json.dumps({
            "baseline": args.baseline, "current": args.current,
            "schema_version": SCHEMA_VERSION,
            "notes": notes,
            "verdicts": [
                {"metric": v.metric.path, "baseline": v.base,
                 "current": v.cur,
                 "delta_pct": round(v.delta_pct, 3),
                 "status": v.status, "note": v.note}
                for v in verdicts],
            "pass": not any(v.status == "REGRESSED" for v in verdicts),
        }, indent=2))
    else:
        print(render_table(verdicts, notes, args.baseline, args.current,
                           show_all=args.show_all))
    return 1 if any(v.status == "REGRESSED" for v in verdicts) else 0


if __name__ == "__main__":
    raise SystemExit(main())
